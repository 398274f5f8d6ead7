"""Flash-decode kernel parity vs the eager slot-mask oracle.

The kernel (ops/attention/pallas_decode.py) must reproduce
``eager_sdpa(q, cache, cache, causal=False, mask=_decode_slot_mask(...))``
bit-for-bit in semantics (fp32 accumulation both sides) across start
positions, windows, sinks, GQA grouping, ragged key validity, and
non-lane-aligned cache lengths. Runs in Pallas interpret mode on CPU.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.nn.attention import (
    _decode_slot_mask,
    _paged_slot,
    _scatter_head_rows,
    latent_attend,
)
from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu.ops.attention import pallas_decode
from d9d_tpu.ops.attention.pallas_decode import (
    append_tile,
    flash_decode_attention,
    latent_decode_attention,
    paged_append,
    paged_decode_geometry,
)


def _mk(b, t, hq, hkv, d, s, seed=0):
    """q plus a HEADS-MAJOR [B, Hkv, S, D] slot cache (the kernel's —
    and the GQA decode cache's — native layout)."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, t, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, hkv, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, hkv, s, d), jnp.float32)
    return q, k, v


def _oracle(q, k, v, start, window, sinks, kv_valid):
    s_max = k.shape[2]
    t = q.shape[1]
    mask = None
    if kv_valid is not None:
        mask = kv_valid[:, None, None, :].astype(bool)
    dec = _decode_slot_mask(jnp.asarray(start), t, s_max, window, mask)
    return eager_sdpa(
        q,
        jnp.transpose(k, (0, 2, 1, 3)),
        jnp.transpose(v, (0, 2, 1, 3)),
        causal=False, sinks=sinks, mask=dec,
    )


@pytest.mark.parametrize("t", [1, 3])
@pytest.mark.parametrize("start", [0, 5, 60])
@pytest.mark.parametrize("window", [None, 7])
def test_parity_start_window(t, start, window):
    b, hq, hkv, d, s = 2, 4, 2, 16, 64
    if start + t > s:
        pytest.skip("overflows cache")
    q, k, v = _mk(b, t, hq, hkv, d, s)
    got = flash_decode_attention(
        q, k, v, start=jnp.asarray(start), window_size=window,
        interpret=True, block_kv=32,
    )
    want = _oracle(q, k, v, start, window, None, None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_parity_sinks_and_validity():
    b, t, hq, hkv, d, s = 2, 1, 8, 2, 32, 96  # g=4, s not %128
    q, k, v = _mk(b, t, hq, hkv, d, s, seed=3)
    rng = np.random.RandomState(7)
    sinks = jnp.asarray(rng.randn(hq), jnp.float32)
    start = 40
    # left-padded ragged: row 0 valid from slot 10, row 1 from slot 0
    valid = np.ones((b, s), np.int32)
    valid[0, :10] = 0
    kv_valid = jnp.asarray(valid)
    got = flash_decode_attention(
        q, k, v, start=jnp.asarray(start), sinks=sinks, kv_valid=kv_valid,
        interpret=True, block_kv=32,
    )
    want = _oracle(q, k, v, start, None, sinks, kv_valid)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_parity_per_row_start():
    """Continuous batching: each row carries its own write index; the
    kernel must mask slot-causally per row (oracle: per-row slot mask)."""
    b, t, hq, hkv, d, s = 3, 1, 4, 2, 16, 64
    q, k, v = _mk(b, t, hq, hkv, d, s, seed=9)
    starts = jnp.asarray([0, 17, 63], jnp.int32)
    got = flash_decode_attention(
        q, k, v, start=starts, interpret=True, block_kv=32
    )
    for i in range(b):
        want_i = _oracle(
            q[i : i + 1], k[i : i + 1], v[i : i + 1],
            int(starts[i]), None, None, None,
        )
        np.testing.assert_allclose(
            np.asarray(got[i : i + 1]), np.asarray(want_i),
            rtol=2e-5, atol=2e-5,
        )


def test_fully_masked_rows_emit_zeros():
    """ADVICE r5 #3: a row whose EVERY key is masked must produce exact
    zeros (guarded softmax), not the silent mean-of-V that an unclamped
    online softmax yields when m never leaves its sentinel. Partially
    masked rows in the same batch must stay oracle-exact."""
    b, t, hq, hkv, d, s = 2, 1, 4, 2, 16, 64
    q, k, v = _mk(b, t, hq, hkv, d, s, seed=11)
    start = 40
    valid = np.ones((b, s), np.int32)
    valid[0, :] = 0        # row 0: nothing visible at all
    valid[1, :10] = 0      # row 1: ordinary left-padded raggedness
    got = np.asarray(flash_decode_attention(
        q, k, v, start=jnp.asarray(start),
        kv_valid=jnp.asarray(valid), interpret=True, block_kv=32,
    ))
    np.testing.assert_array_equal(got[0], np.zeros_like(got[0]))
    want = _oracle(q, k, v, start, None, None, jnp.asarray(valid))
    np.testing.assert_allclose(
        got[1:], np.asarray(want)[1:], rtol=2e-5, atol=2e-5
    )


def _paginate(k, v, page_size, seed=0):
    """Scatter a contiguous [B, Hkv, S, D] cache into a permuted page
    pool + page table whose gathered view equals the original — the
    paged call must then match the contiguous call exactly."""
    rng = np.random.RandomState(seed)
    b, hkv, s, d = k.shape
    n_pages = s // page_size
    pool_n = b * n_pages + 1  # page 0 = reserved garbage
    pool_k = np.zeros((pool_n, hkv, page_size, d), np.float32)
    pool_v = np.zeros((pool_n, hkv, page_size, d), np.float32)
    perm = rng.permutation(np.arange(1, pool_n))
    pt = np.zeros((b, n_pages), np.int32)
    i = 0
    for bi in range(b):
        for pi in range(n_pages):
            page = perm[i]
            i += 1
            pt[bi, pi] = page
            sl = slice(pi * page_size, (pi + 1) * page_size)
            pool_k[page] = np.asarray(k)[bi, :, sl]
            pool_v[page] = np.asarray(v)[bi, :, sl]
    return jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(pt)


@pytest.mark.parametrize("window", [None, 7])
def test_paged_parity_per_row_start(window):
    """The paged block-index gather (scalar-prefetch index map) must
    reproduce the contiguous kernel bit-for-bit in semantics: same
    per-row starts, same windows, pages deliberately scattered through
    the pool in permuted order."""
    b, t, hq, hkv, d, s, ps = 3, 1, 4, 2, 16, 64, 16
    q, k, v = _mk(b, t, hq, hkv, d, s, seed=21)
    starts = jnp.asarray([0, 17, 63], jnp.int32)
    want = flash_decode_attention(
        q, k, v, start=starts, window_size=window, interpret=True,
        block_kv=ps,
    )
    pool_k, pool_v, pt = _paginate(k, v, ps, seed=4)
    got = flash_decode_attention(
        q, pool_k, pool_v, start=starts, window_size=window,
        page_table=pt, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_paged_parity_sinks_and_gqa():
    b, t, hq, hkv, d, s, ps = 2, 1, 8, 2, 32, 96, 16  # g=4
    q, k, v = _mk(b, t, hq, hkv, d, s, seed=23)
    rng = np.random.RandomState(5)
    sinks = jnp.asarray(rng.randn(hq), jnp.float32)
    starts = jnp.asarray([40, 95], jnp.int32)
    want = flash_decode_attention(
        q, k, v, start=starts, sinks=sinks, interpret=True, block_kv=ps
    )
    pool_k, pool_v, pt = _paginate(k, v, ps, seed=6)
    got = flash_decode_attention(
        q, pool_k, pool_v, start=starts, sinks=sinks, page_table=pt,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    with pytest.raises(NotImplementedError, match="kv_valid"):
        flash_decode_attention(
            q, pool_k, pool_v, start=starts, page_table=pt,
            kv_valid=jnp.ones((b, pt.shape[1] * ps), jnp.int32),
            interpret=True,
        )


# (page_size, n_pages, key positions a block) -> pages a block
_BLOCK_GEOMETRIES = {
    "even-4+4": ((8, 8, 32), 4),
    "tail-4+3": ((8, 7, 32), 4),        # n_pages no multiple of the block
    "tail-2+2+1": ((16, 5, 32), 2),     # 3 blocks, the last of one page
    "tail-6+3": ((8, 9, 48), 6),
    "one-block": ((16, 4, 512), 4),     # every page in one block
    "qwen3-9x64": ((64, 9, 512), 8),    # the Qwen3 serving cell: 8 + 1
    "jamba-18x64": ((64, 18, 512), 8),  # the Jamba serving cell: 8 + 8 + 2
    "groups-4+4+3": ((8, 11, 32), 4),   # rows of 1, 2 and 3 blocks a group
}
_GROUPS = {"g8-on-4": (32, 4), "g20-on-1": (20, 1),
           "g8-on-2": (8, 2), "g8-on-8": (8, 8)}


def _with_rows_per_step(cases, grouped):
    """The cases a test had, one row a grid step, and ``grouped``: cases
    of the geometry ``groups-4+4+3`` that end in the rows a grid step
    attends."""
    old = [g for g in _BLOCK_GEOMETRIES if g != "groups-4+4+3"]
    return [(g, *rest, 1) for g in old for rest in cases] + [
        ("groups-4+4+3", *case) for case in grouped
    ]


def _block_case(monkeypatch, geometry, heads, t, d=16, seed=0):
    """q, a contiguous cache and the rows' starts that meet every edge
    of the block geometry: position 0, a row that ends inside a page
    with fewer live pages than one block, the last position of block 0,
    the first of block 1 (a block's edge), and every page live. The
    geometry ``groups-4+4+3`` has seven rows (no multiple of a group)
    in an order that puts rows of one, two and three live blocks, and a
    row at position 0, into one grid step at 2, 4 and 8 rows a step;
    its row 3 is the one a caller makes a dead row."""
    (ps, n_pages, block), want = _BLOCK_GEOMETRIES[geometry]
    monkeypatch.setattr(pallas_decode, "PAGED_STEP_POSITIONS", block)
    hq, hkv = _GROUPS[heads]
    s = ps * n_pages
    pps = want
    edge = min(pps * ps, s - t)
    if geometry == "groups-4+4+3":
        starts = [0, edge + 5, 2 * edge + 3, 0, ps + 3, s - t, 2 * edge - t]
    else:
        starts = [0, ps + 3, max(edge - t, 0), edge, s - t]
    geo = paged_decode_geometry(
        batch=len(starts), kv_heads=hkv, n_pages=n_pages, page_size=ps,
        head_dim=d, kv_itemsize=4,
    )
    assert geo.pages_per_step == want
    assert geo.grid == (-(-len(starts) // geo.rows_per_step),)
    q, k, v = _mk(len(starts), t, hq, hkv, d, s, seed=seed)
    return q, k, v, jnp.asarray(starts, jnp.int32), ps


_DEAD_ROW = 3  # of the geometry "groups-4+4+3"


def _assert_paged_equals_contiguous(monkeypatch, rows_per_step, q, pools,
                                    want, starts, **kwargs):
    """``pools = (pool_k, pool_v, table)`` attended ``rows_per_step``
    rows a grid step against ``want``, the contiguous call's result on
    the gathered view; a group of several rows also bit for bit against
    one row a step, and with row ``_DEAD_ROW`` dead: at position 0 of a
    table row of zeros, the garbage page, which holds finite numbers no
    live row may see."""
    pool_k, pool_v, pt = pools
    live = np.ones(q.shape[0], bool)
    if rows_per_step > 1:
        live[_DEAD_ROW] = False
        pt = pt.at[_DEAD_ROW].set(0)
        pool_k = pool_k.at[0].set(jnp.asarray(3, pool_k.dtype))
        pool_v = pool_v.at[0].set(jnp.asarray(-5, pool_v.dtype))

    def paged(rows):
        monkeypatch.setattr(pallas_decode, "PAGED_STEP_ROWS", rows)
        return np.asarray(flash_decode_attention(
            q, pool_k, pool_v, start=starts, page_table=pt, interpret=True,
            **kwargs,
        ))

    got = paged(rows_per_step)
    np.testing.assert_allclose(
        got[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5
    )
    if rows_per_step > 1:
        np.testing.assert_array_equal(got, paged(1))


@pytest.mark.parametrize(
    "geometry,heads,window,t,rows_per_step",
    _with_rows_per_step(
        [(h, w, t) for h in ("g8-on-4", "g20-on-1") for w in (None, 7)
         for t in (1, 3)],
        [("g8-on-2", None, 1, 4), ("g8-on-2", 7, 3, 2),
         ("g20-on-1", None, 3, 8), ("g20-on-1", 7, 1, 4),
         ("g8-on-8", None, 1, 2), ("g8-on-8", 7, 3, 8)],
    ),
)
def test_paged_block_parity(monkeypatch, geometry, heads, window, t,
                            rows_per_step):
    """A grid step attends a block of pages of each row of a group: the
    paged result equals the contiguous call's on the gathered view, over
    the geometries the rule produces and the rows that meet their edges,
    and a group's rows read what they read alone."""
    q, k, v, starts, ps = _block_case(monkeypatch, geometry, heads, t)
    want = flash_decode_attention(
        q, k, v, start=starts, interpret=True, block_kv=ps,
        window_size=window,
    )
    _assert_paged_equals_contiguous(
        monkeypatch, rows_per_step, q, _paginate(k, v, ps, seed=4), want,
        starts, window_size=window,
    )


@pytest.mark.parametrize(
    "geometry,heads,rows_per_step",
    _with_rows_per_step(
        [("g8-on-4",), ("g20-on-1",)], [("g8-on-2", 4)],
    ),
)
def test_paged_block_parity_sinks(monkeypatch, geometry, heads, rows_per_step):
    q, k, v, starts, ps = _block_case(monkeypatch, geometry, heads, 1, seed=2)
    sinks = jnp.asarray(np.random.RandomState(5).randn(q.shape[2]), jnp.float32)
    want = flash_decode_attention(
        q, k, v, start=starts, interpret=True, block_kv=ps, sinks=sinks,
    )
    _assert_paged_equals_contiguous(
        monkeypatch, rows_per_step, q, _paginate(k, v, ps, seed=4), want,
        starts, sinks=sinks,
    )


@pytest.mark.parametrize(
    "geometry,window,rows_per_step",
    _with_rows_per_step([(None,), (7,)], [(None, 2), (7, 8)]),
)
def test_paged_block_parity_int8(monkeypatch, geometry, window, rows_per_step):
    """int8 pools with scale pages gathered by the same rule: the
    kernel's in-VMEM ``int8 * scale`` equals the contiguous call on the
    widened view."""
    from d9d_tpu.nn.attention import _quantize_rows

    q, k, v, starts, ps = _block_case(
        monkeypatch, geometry, "g8-on-4", 1, seed=3
    )
    (k8, ks), (v8, vs) = _quantize_rows(k), _quantize_rows(v)
    wide_k = k8.astype(jnp.float32) * ks[..., None]
    wide_v = v8.astype(jnp.float32) * vs[..., None]
    want = flash_decode_attention(
        q, wide_k, wide_v, start=starts, window_size=window,
        interpret=True, block_kv=ps,
    )
    pool_k, pool_v, pt = _paginate(k8, v8, ps, seed=8)
    # a scale pool is a pool of [Hkv, page, 1] pages under the same table
    pool_ks, pool_vs, _ = _paginate(ks[..., None], vs[..., None], ps, seed=8)
    _assert_paged_equals_contiguous(
        monkeypatch, rows_per_step, q,
        (pool_k.astype(jnp.int8), pool_v.astype(jnp.int8), pt), want, starts,
        window_size=window, k_scale=pool_ks[..., 0], v_scale=pool_vs[..., 0],
    )


def test_paged_dead_tail_on_the_garbage_page():
    """The serving loop maps a row's unallocated tail to page 0: those
    pages are skipped by position, whatever page 0 holds."""
    b, t, hq, hkv, d, ps, n_pages = 2, 1, 8, 2, 16, 64, 9
    q, k, v = _mk(b, t, hq, hkv, d, ps * n_pages, seed=31)
    starts = jnp.asarray([70, 200], jnp.int32)
    pool_k, pool_v, pt = _paginate(k, v, ps, seed=9)
    pool_k = pool_k.at[0].set(1e4)
    pool_v = pool_v.at[0].set(jnp.nan)
    pt = pt.at[0, 2:].set(0).at[1, 4:].set(0)
    want = flash_decode_attention(
        q, k, v, start=starts, interpret=True, block_kv=ps
    )
    got = flash_decode_attention(
        q, pool_k, pool_v, start=starts, page_table=pt, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("table", ["shared", "ring"])
@pytest.mark.parametrize("ps", [16, 64])
@pytest.mark.parametrize("dk,dv", [(128, 128), (256, 128)])
@pytest.mark.parametrize("heads", [1, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_paged_append_writes_the_scatters_bits(dtype, heads, dk, dv, ps, table):
    """``paged_append`` against ``_scatter_head_rows`` on both pools, bit
    for bit: rows at a tile's first, last and middle position and on a
    page's last row, through the allocator's table (two dead rows on the
    garbage page) and through a ring of pages (a wrapped row, a dead row
    in its own ring); every other position of every pool unchanged."""
    rng = np.random.RandomState(heads * ps + dk)
    tile = append_tile(ps, dtype)
    assert tile == (16 if dtype == jnp.bfloat16 else 8)
    per_row = 3
    starts = [0, tile - 1, tile + tile // 2, ps - 1, ps + tile, 0, 0]
    b = len(starts)
    if table == "shared":
        pages = 1 + b * per_row
        pt = 1 + rng.permutation(pages - 1).reshape(b, per_row)
        pt[-2:] = 0  # dead rows: table row and write index pinned to 0
    else:
        pages = b * per_row
        # nn/attention.py _ring_page_table's rule, five logical pages
        pt = np.arange(b)[:, None] * per_row + np.arange(5)[None, :] % per_row
        starts[4] = 4 * ps + 1  # logical page 4 lives in ring page 1
    page, off = _paged_slot(
        jnp.asarray(pt, jnp.int32), jnp.asarray(starts, jnp.int32), ps)

    def draw(*shape):  # bits, so that a NaN pattern is a pattern like another
        return jnp.asarray(rng.randn(*shape), dtype)

    k_pool, v_pool = draw(pages, heads, ps, dk), draw(pages, heads, ps, dv)
    k_rows, v_rows = draw(b, heads, dk), draw(b, heads, dv)
    got = jax.jit(
        lambda *a: paged_append(*a, interpret=True)
    )(k_pool, v_pool, page, off, k_rows, v_rows)
    for pool, rows, new in zip((k_pool, v_pool), (k_rows, v_rows), got):
        want = np.array(_scatter_head_rows(pool, page, off, rows))
        new = np.array(new)
        assert new.dtype == want.dtype and new.shape == want.shape
        if table == "shared":
            # position 0 of the garbage page holds one dead row's write
            # or the other's; the rest of that page is as it was
            new[0, :, 0], want[0, :, 0] = 0, 0
        np.testing.assert_array_equal(new, want)
        untouched = np.ones(pool.shape[:3], bool)
        untouched[np.asarray(page), :, np.asarray(off)] = False
        np.testing.assert_array_equal(
            new[untouched], np.asarray(pool)[untouched])


@pytest.mark.parametrize(
    "shapes,pages_per_step,rows_per_step",
    [
        # tiny pages still cover 512 positions a block
        (dict(batch=4, kv_heads=2, n_pages=6, page_size=8), 6, 8),
        (dict(batch=4, kv_heads=2, n_pages=100, page_size=16), 32, 8),
        # a long row; 8 kv heads are 4 MiB a row, two rows the budget
        (dict(batch=8, kv_heads=4, n_pages=512, page_size=64), 8, 4),
        (dict(batch=8, kv_heads=8, n_pages=512, page_size=64), 8, 2),
        # float32 pools: 8 kv heads are the budget, 32 cut the block
        (dict(batch=8, kv_heads=8, n_pages=64, page_size=64,
              kv_itemsize=4), 8, 1),
        (dict(batch=8, kv_heads=32, n_pages=64, page_size=64,
              kv_itemsize=4), 2, 1),
        # pages of 256: two a block
        (dict(batch=8, kv_heads=2, n_pages=16, page_size=256), 2, 8),
        # a batch the group does not divide: its last group has dead rows
        (dict(batch=13, kv_heads=2, n_pages=18, page_size=64), 8, 8),
        # the serving cells. Qwen3-30B-A3B: 32 query heads on 4
        (dict(batch=64, kv_heads=4, n_pages=9, page_size=64,
              query_rows=8), 8, 4),
        # Jamba2-3B: 20 on ONE kv head, 24 padded rows
        (dict(batch=256, kv_heads=1, n_pages=18, page_size=64,
              query_rows=20), 8, 8),
        # MiMo-V2-Flash: 64 on 4 full, 64 on 8 under a window of 128,
        # key rows of 256 and value rows of 128
        (dict(batch=256, kv_heads=4, n_pages=18, page_size=64, head_dim=256,
              v_head_dim=128, query_rows=16), 8, 2),
        (dict(batch=256, kv_heads=8, n_pages=18, page_size=64, head_dim=256,
              v_head_dim=128, window=128, query_rows=8), 3, 2),
        # granite-4.0-h-small: 32 on 8; Solar-Open2: 64 on 8
        (dict(batch=128, kv_heads=8, n_pages=18, page_size=64,
              query_rows=4), 8, 2),
        (dict(batch=256, kv_heads=8, n_pages=18, page_size=64,
              query_rows=8), 8, 2),
        # ZAYA1-8B's latent pool: 8 on 2
        (dict(batch=256, kv_heads=2, n_pages=18, page_size=64,
              query_rows=4), 8, 8),
        # GLM-4.7-Flash's absorbed latent decode: 20 query heads on the
        # latent rows of 512 and rotary key rows stored as 128, one "kv
        # head": a page is 80 KB, 4 rows a step fill 5 of the 8 MiB
        (dict(batch=64, kv_heads=1, n_pages=18, page_size=64, head_dim=512,
              v_head_dim=128, query_rows=20), 8, 4),
    ],
)
def test_paged_geometry_from_shapes(shapes, pages_per_step, rows_per_step):
    shapes = {"head_dim": 128, "kv_itemsize": 2, **shapes}
    geo = paged_decode_geometry(**shapes)
    assert geo.pages_per_step == pages_per_step
    assert geo.rows_per_step == rows_per_step
    # one grid step a group of rows, whatever their pages
    assert geo.grid == (-(-shapes["batch"] // rows_per_step),)
    assert geo.vmem_bytes <= pallas_decode.PAGED_VMEM_BUDGET
    # the update's width: score rows a grid step
    rows_pad = -(-shapes.get("query_rows", 1) // 8) * 8
    assert (rows_per_step == 1 or rows_per_step * shapes["kv_heads"] * rows_pad
            <= pallas_decode.PAGED_STEP_WIDTH)


# -- the absorbed latent decode through the page table ----------------------

_LATENT = dict(h=5, r=32, d_rope=8, ps=8, n_pages=6)
# name -> each row's write index (its query's position); None: the last
_LATENT_ROWS = {
    "one-position": [0, 0, 0],
    "ends-on-a-page-edge": [7, 15, 47],
    "one-past-a-page-edge": [8, 16, 40],
    "full-context": [None, None, 3],
    "idle-slot-on-the-garbage-page": [13, "idle", 30],
    "fully-masked-row": [20, -1, 0],
    "batch-the-group-does-not-divide": [0, 9, 17, 26, 33, 41, None],
    "shuffled-table": [5, 44, 23, 12],
}


def _latent_case(starts, *, h, r, d_rope, ps, n_pages, rope_width=None,
                 seed=0):
    """Float32 queries, bf16 pools whose pages lie shuffled through the
    pool, the rows' table and write indices, and ``latent_attend``'s
    result on the gathered view. An ``"idle"`` row is what the serving
    loop makes of a free slot: write index 0, its table row on page 0,
    the garbage page, which holds finite numbers."""
    rng = np.random.RandomState(seed)
    b = len(starts)
    pool_n = b * n_pages + 1
    latent = rng.randn(pool_n, ps, r)
    rope = np.zeros((pool_n, ps, rope_width or d_rope))
    rope[..., :d_rope] = rng.randn(pool_n, ps, d_rope)
    table = 1 + rng.permutation(pool_n - 1).reshape(b, n_pages)
    for i, start in enumerate(starts):
        if start == "idle":
            table[i] = 0
    start = jnp.asarray(
        [0 if s == "idle" else n_pages * ps - 1 if s is None else s
         for s in starts], jnp.int32)
    latent = jnp.asarray(latent, jnp.bfloat16)
    rope = jnp.asarray(rope, jnp.bfloat16)
    table = jnp.asarray(table, jnp.int32)
    q_abs = jnp.asarray(rng.randn(b, 1, h, r), jnp.float32)
    q_rope = jnp.asarray(rng.randn(b, 1, h, d_rope), jnp.float32)
    want = latent_attend(
        q_abs, q_rope,
        latent[table].reshape(b, n_pages * ps, r),
        rope[table].reshape(b, n_pages * ps, -1)[..., :d_rope],
        _decode_slot_mask(start, 1, n_pages * ps, None, None), 0.25,
    )
    return (q_abs, q_rope, latent, rope), dict(
        start=start, page_table=table, softmax_scale=0.25, interpret=True
    ), np.asarray(want)


@pytest.mark.parametrize("rows", list(_LATENT_ROWS))
def test_latent_paged_parity(rows):
    """The paged kernel's latent configuration against ``latent_attend``
    on the gathered view of every page of every row: float32 agreement,
    exact zeros for a row that sees no position."""
    args, kwargs, want = _latent_case(_LATENT_ROWS[rows], **_LATENT)
    got = np.asarray(latent_decode_attention(*args, **kwargs))
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for i, start in enumerate(_LATENT_ROWS[rows]):
        if start == -1:
            np.testing.assert_array_equal(want[i], 0)
            np.testing.assert_array_equal(got[i], 0)


@pytest.mark.parametrize("rows_per_step", [1, 2, 8])
def test_latent_paged_groups_read_what_rows_read_alone(
        monkeypatch, rows_per_step):
    """Blocks of two pages (three a row) and groups of 1, 2 and 8 rows a
    grid step, rows of one, two and three live blocks in one group: the
    reference's numbers, and bit for bit what one row a step gives."""
    monkeypatch.setattr(pallas_decode, "PAGED_STEP_POSITIONS", 16)
    starts = _LATENT_ROWS["batch-the-group-does-not-divide"]
    args, kwargs, want = _latent_case(starts, **_LATENT, seed=1)

    def call(rows):
        monkeypatch.setattr(pallas_decode, "PAGED_STEP_ROWS", rows)
        return np.asarray(latent_decode_attention(*args, **kwargs))

    got = call(rows_per_step)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got, call(1))


def test_latent_paged_parity_at_the_glm_cells_pages():
    """Pages of 64, 18 a row, 20 query heads and rotary key rows stored
    a whole lane tile wide, as the serving loop seeds them: the full
    1,152 positions, a page's edge and one past it, blocks of 8 pages."""
    shape = dict(h=20, r=128, d_rope=64, ps=64, n_pages=18)
    starts = [None, 63, 64, 511, 512, 0, 700]
    args, kwargs, want = _latent_case(starts, **shape, rope_width=128)
    got = np.asarray(latent_decode_attention(*args, **kwargs))
    # sums of 1,152 float32 terms in another order: 1e-5 of a value's size
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the zeros that fill a rotary key row meet zeros: the bits of the
    # rows as they are
    q_abs, q_rope, latent, rope = args
    np.testing.assert_array_equal(got, np.asarray(latent_decode_attention(
        q_abs, q_rope, latent, rope[..., :64], **kwargs)))


def test_latent_paged_call_refuses_several_tokens():
    args, kwargs, _ = _latent_case([3], **_LATENT)
    q_abs, q_rope, latent, rope = args
    with pytest.raises(NotImplementedError, match="one token"):
        latent_decode_attention(
            jnp.tile(q_abs, (1, 2, 1, 1)), jnp.tile(q_rope, (1, 2, 1, 1)),
            latent, rope, **kwargs)


def test_parity_under_jit_traced_start():
    """start is traced in real decode loops (lax.scan carry)."""
    b, t, hq, hkv, d, s = 1, 1, 4, 4, 16, 64
    q, k, v = _mk(b, t, hq, hkv, d, s, seed=5)

    @jax.jit
    def step(start):
        return flash_decode_attention(
            q, k, v, start=start, interpret=True, block_kv=32
        )

    for start in (0, 17, 63):
        got = step(jnp.asarray(start))
        want = _oracle(q, k, v, start, None, None, None)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )


@pytest.mark.e2e  # slow tier: whole-module prefill+decode loop ×2 backends
def test_gqa_module_routes_pallas(monkeypatch):
    """GroupedQueryAttention decode through the kernel (env-forced on
    CPU → interpret mode) must match the default eager routing."""
    from d9d_tpu.nn.attention import GroupedQueryAttention
    from d9d_tpu.ops.rope import (
        compute_rope_frequencies,
        make_rope_cos_sin,
    )

    blk = GroupedQueryAttention(
        hidden_size=32, num_heads=4, num_kv_heads=2, head_dim=8,
        sdpa=eager_sdpa, dtype=jnp.float32, decode_max_length=16,
        window_size=6, use_sinks=True,
    )
    b = 2
    inv, sc = compute_rope_frequencies(8, 10000.0)

    def rope(start, t):
        pos = jnp.broadcast_to(jnp.arange(start, start + t), (b, t))
        return make_rope_cos_sin(pos, inv, sc)

    x4 = jax.random.normal(jax.random.PRNGKey(0), (b, 4, 32))
    cos, sin = rope(0, 4)
    variables = jax.jit(blk.init)(jax.random.PRNGKey(1), x4, cos, sin)
    params = variables["params"]
    fresh = jax.tree.map(jnp.zeros_like, variables["cache"])

    def drive():
        _, st = blk.apply({"params": params, "cache": fresh},
                          x4, cos, sin, mutable=["cache"])
        outs = []
        for i in range(4, 7):
            c1, s1 = rope(i, 1)
            o, st = blk.apply(
                {"params": params, "cache": st["cache"]},
                x4[:, :1], c1, s1, mutable=["cache"],
            )
            outs.append(o)
        return jnp.concatenate(outs, axis=1)

    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "eager")
    want = drive()
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "pallas")
    got = drive()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
