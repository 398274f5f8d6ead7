"""What the paged serving loop holds for each serving cell's model at the
tiny size: the single-kind models (Qwen3's GQA pools, GLM's latent pools,
Jamba's pools beside per-row state) keep the cache leaves, the page table
and the chunk's one ``[B, K]`` output they had before a window layer's
ring of pages existed (the numbers below were read from the parent of
PR 41; the GQA models' lowered chunks differ from the parent's by the cache
append alone, a scatter of rows into the pool seen flat, the latent
model's not at all); MiMo has the rings beside them."""

import jax
import jax.numpy as jnp
import pytest
from flax.traverse_util import flatten_dict

from benchmarks.harness import build, manifest

# cell -> (cache leaves, page table, leaf names) as on the parent
SINGLE_KIND = {
    "qwen3-30b-a3b-decode.serve-rollout-closed": (
        4, (4, 3), {"cache_index", "cached_key", "cached_value", "page_table"},
    ),
    "glm-4.7-flash-decode.serve-reason-closed": (
        8, (4, 4),
        {"cache_index", "cached_latent", "cached_rope_key", "page_table"},
    ),
    "jamba2-3b-decode.serve-reason-closed": (
        6, (4, 4),
        {"cache_index", "cached_key", "cached_value", "conv_tail",
         "page_table", "ssm_state"},
    ),
}
MIMO = "mimo-v2-flash-share16-decode.serve-reason-closed"


def tiny_batcher(cell_name: str):
    config = manifest.cell(cell_name).config
    cfg, _ = build.sizes(config, tiny=True)
    serving = config["tiny"]["serving"]
    model = build.decode_model(config, cfg, serving["decode_max_length"])
    params = build.seeded_weights(model, 0)
    return build.build_batcher(model, params, serving), serving


def chunk_output(batcher, slots: int, k: int = 8):
    """The abstract result of the fused chunk without admission."""
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    # the host-made argument: plan, admission and page table, packed
    packed = i32(slots, k + 5 + batcher._cache_mgr.pages_per_row)
    args = (
        batcher._params, batcher._cache, i32(slots), i32(slots),
        jnp.zeros((slots,), bool), i32(slots), jax.random.PRNGKey(0), packed,
    )
    return jax.eval_shape(batcher._build_fused(k, False).jitted, *args)


@pytest.mark.parametrize("cell", SINGLE_KIND)
def test_a_single_kind_model_keeps_its_cache_and_its_chunk(cell):
    leaves, table, names = SINGLE_KIND[cell]
    batcher, serving = tiny_batcher(cell)
    flat = flatten_dict(batcher._cache)
    assert len(flat) == leaves and {p[-1] for p in flat} == names
    assert {v.shape for p, v in flat.items() if p[-1] == "page_table"} \
        == {table}
    assert batcher._cache_mgr.window_cache_bytes == 0 and not batcher._cache_mgr.ring_windows
    assert not batcher._cache_mgr.counts_held_rows
    out = chunk_output(batcher, serving["slots"])
    assert out[-1].shape == (serving["slots"], 8)
    assert jax.tree.structure(out[0]) == jax.tree.structure(batcher._cache)
    batcher.close()


def test_a_model_with_window_layers_adds_rings_and_counts():
    batcher, serving = tiny_batcher(MIMO)
    slots = serving["slots"]
    flat = flatten_dict(batcher._cache)
    names = {p[-1] for p in flat}
    assert names == {"cache_index", "cached_key", "cached_value",
                     "page_table", "ring_key", "ring_value"}
    # 2 full layers: index, two pools, a table; 2 window layers: index, ring
    assert len(flat) == 2 * 4 + 2 * 3
    assert {v.shape for p, v in flat.items() if p[-1] == "page_table"} \
        == {(slots, 8)}
    assert dict(batcher._cache_mgr.ring_windows) == {6: 2}
    assert batcher._cache_mgr.allocator.prefix_cache_enabled is False
    # the chunk's one output carries the held-rows counts below the slots:
    # held, routed and (0 for a router without a skip) skipped
    out = chunk_output(batcher, slots)
    assert out[-1].shape == (slots + 3, 8)
    batcher.close()


# -- the arithmetic around a ring -----------------------------------------------


def test_a_ring_is_the_window_and_the_page_being_written():
    from d9d_tpu.nn.attention import _cache_row_pad
    from d9d_tpu.ops.attention.pallas_decode import (
        paged_decode_geometry,
        window_pages,
    )

    # positions (i - W, i]: W - 1 behind the query's own, in whole pages
    assert [window_pages(w, 64) for w in (1, 2, 64, 65, 66, 128, 129, 130)] \
        == [1, 2, 2, 2, 3, 3, 3, 4]
    assert window_pages(6, 4) == 3
    # for every position of a query the window's pages fit the ring
    for window, page in ((128, 64), (6, 4), (5, 4), (9, 4)):
        for i in range(4 * window):
            spanned = i // page - max(i - window + 1, 0) // page + 1
            assert spanned <= window_pages(window, page)
    # a cached row wider than a lane tile ends on a tile's edge
    assert [_cache_row_pad(d) for d in (16, 64, 128, 192, 256, 320)] \
        == [0, 0, 0, 64, 0, 64]
    # the kernel's block: a window's pages at most, the keys' and the
    # values' own widths in the buffers of the rows a grid step attends
    shapes = dict(batch=256, n_pages=18, page_size=64, head_dim=256,
                  kv_itemsize=2, v_head_dim=128)
    full = paged_decode_geometry(kv_heads=4, **shapes)
    ring = paged_decode_geometry(kv_heads=8, window=128, **shapes)
    assert (full.pages_per_step, ring.pages_per_step) == (8, 3)
    assert (full.rows_per_step, ring.rows_per_step) == (2, 2)
    assert full.vmem_bytes == 2 * 2 * 8 * 4 * 64 * (256 + 128) * 2
    assert ring.vmem_bytes == 2 * 2 * 3 * 8 * 64 * (256 + 128) * 2
    # one head width and no window: what it was
    old = paged_decode_geometry(
        batch=64, kv_heads=4, n_pages=9, page_size=64, head_dim=128,
        kv_itemsize=2)
    assert (old.pages_per_step, old.rows_per_step, old.vmem_bytes) == (
        8, 4, 4 * 2 * 8 * 2 * 4 * 64 * 128 * 2)


def test_window_positions_are_the_context_or_the_window():
    from d9d_tpu.loop.serve_accounting import positions_under

    spans = [(0, 8), (3, 8), (5, 1), (6, 8), (120, 8), (124, 8), (1100, 5)]
    for window in (6, 128):
        want = sum(
            min(pos + j, window)
            for pos, steps in spans for j in range(1, steps + 1)
        )
        assert positions_under(spans, window) == want
    assert positions_under([], 128) == 0
