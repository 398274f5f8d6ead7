"""Compile-only rehearsal for the TPU v5e, kept as tests.

The chip's compiler is installed on the CPU rig and compiles for a
described, unattached ``v5e:2x2`` topology
(/opt/skills/guides/on-chip-measurement/SKILL.md §2). A compile that
passes here is a compile, never a run: it shows that the kernel fits the
chip's tiling, VMEM and partitioning rules at the real widths
(Qwen3-30B-A3B: H32/4 d128, 128 experts x 768, h2048) and that the
kernel or collective is really in the program; ``chip_smoke.py`` is what
executes them. Kernels that had passed every interpret-mode test were
refused here first (``ops/moe_pallas.py``, PR 21).

``jax.default_backend`` is steered by ``monkeypatch`` in the tests that
need the TPU branch of the code; the program grows no option for it.
"""

import dataclasses
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep compiler logs out of /tmp

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from tests.conftest import load_repo_module

# one definition of "a Pallas kernel is in this HLO" for these compiles
# and for the chip run that executes them
_smoke_pallas_calls = load_repo_module("chip_smoke", "chip_smoke.py").pallas_calls

BF16 = jnp.bfloat16
# Qwen3-30B-A3B attention and expert geometry (models/qwen3/moe.py)
HQ, HKV, D, T = 32, 4, 128, 4096
E, H, INTER, TOP_K = 128, 2048, 768, 8
PAGE = 64  # chip_smoke.py's serving page size


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this rig
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """An entry compiled for a described chip is written to the
    persistent cache but cannot be read back without the chip (the next
    compile warns and recompiles): keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Code that asks ``jax.default_backend()`` takes its TPU branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _pallas_calls(compiled, scope: str = "") -> int:
    return _smoke_pallas_calls([compiled.as_text()], scope)


def _fill_selects_of_row_gathers(compiled) -> list[str]:
    """Result shapes of the ``select`` over a ``[rows, H]`` buffer that
    ``jnp.take``'s default ``mode="fill"`` writes after a row gather under
    ``moe/permute`` or ``moe/combine`` (a read and a write of every row, to
    put NaN where an index was out of range): none where the gather says
    its indices are rows. (An exchange's own backward names the scope
    ``transpose(jvp(moe/permute))``.)"""
    return re.findall(
        rf"= (\w+\[\d+,{H}\])\S* select\([^\n]*"
        r'op_name="[^"]*moe/(?:permute|combine)\)*/jit\(_take\)/select_n"',
        compiled.as_text(),
    )


def _on(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


# -- attention kernels -------------------------------------------------------


@pytest.mark.parametrize("heads,window", [(64, 512), (48, None)],
                         ids=["window-512-64-heads", "full-48-heads"])
def test_flash_fwd_bwd_at_the_window_layer_cells_kinds(
        topo, as_tpu, heads, window):
    """The two attention kinds of the window-layer training cell: 64
    query heads under a window of 512 and 48 causal ones, on 8 key/value
    heads of 128 at 4,096 positions (the windowed kernels had only run in
    interpret mode before PR 44)."""
    from d9d_tpu.ops.attention.pallas_flash import make_pallas_flash_sdpa

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    flash = make_pallas_flash_sdpa()

    def loss(q, k, v):
        out = flash(q, k, v, causal=True, window_size=window)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        sds((1, T, heads, 128), BF16), sds((1, T, 8, 128), BF16),
        sds((1, T, 8, 128), BF16),
    ).compile()
    assert _pallas_calls(compiled) == 3  # forward, dq, dk/dv


@pytest.mark.parametrize(
    "fused_bwd,t",
    [(False, T), (True, T), (True, 1024)],
    ids=["split-t4096", "fused-t4096", "fused-t1024"],
)
def test_flash_fwd_bwd(topo, as_tpu, fused_bwd, t):
    from d9d_tpu.ops.attention.pallas_flash import (
        fused_bwd_applies,
        make_pallas_flash_sdpa,
    )

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    flash = make_pallas_flash_sdpa(fused_bwd=fused_bwd)

    def loss(q, k, v):
        return flash(q, k, v, causal=True).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        sds((1, t, HQ, D), BF16), sds((1, t, HKV, D), BF16),
        sds((1, t, HKV, D), BF16),
    ).compile()
    # The one-pass backward keeps a [g, T, d] dq state in VMEM: at the
    # 30B-A3B group size (g = 8) it fits only up to T ~ 1700, so asking
    # for it at T 4096 still takes the split kernels.
    one_pass = fused_bwd and fused_bwd_applies(
        t=t, num_heads=HQ, num_kv_heads=HKV, head_dim=D, itemsize=2
    )
    assert one_pass == (fused_bwd and t == 1024)
    # forward + one-pass backward, or forward + (dq, dk/dv)
    assert _pallas_calls(compiled) == (2 if one_pass else 3)


def test_flash_shards_itself_over_a_mesh(topo, as_tpu):
    """Mosaic kernels cannot be auto-partitioned: on a multi-device mesh
    the SDPA factory's flash backend has to shard_map itself (PR 21: the
    FSDP x EP train step failed to lower without it)."""
    from d9d_tpu.nn.sdpa import SdpaPallasFlashConfig, build_sdpa_backend

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("dp_s", "tp"))
    sds = _on(NamedSharding(mesh, P("dp_s")))
    flash = build_sdpa_backend(SdpaPallasFlashConfig())

    def loss(q, k, v):
        return flash(q, k, v, causal=True).astype(jnp.float32).sum()

    with jax.set_mesh(mesh):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            sds((4, 1024, HQ, D), BF16), sds((4, 1024, HKV, D), BF16),
            sds((4, 1024, HKV, D), BF16),
        ).compile()
    assert _pallas_calls(compiled) == 3


@pytest.mark.parametrize("layout", ["contiguous", "paged_bf16", "paged_int8"])
def test_flash_decode(topo, layout):
    from d9d_tpu.ops.attention.pallas_decode import flash_decode_attention

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    b, s = 4, 128
    q, start = sds((b, 1, HQ, D), BF16), sds((b,), jnp.int32)
    if layout == "contiguous":
        kv = sds((b, HKV, s, D), BF16)
        args, kwargs = (q, kv, kv), {}
    else:
        quant = layout == "paged_int8"
        n_pages = b * (s // PAGE) + 1
        pool = sds((n_pages, HKV, PAGE, D), jnp.int8 if quant else BF16)
        kwargs = {"page_table": sds((b, s // PAGE), jnp.int32)}
        if quant:
            scale = sds((n_pages, HKV, PAGE), jnp.float32)
            kwargs |= {"k_scale": scale, "v_scale": scale}
        args = (q, pool, pool)
    compiled = jax.jit(
        lambda q, k, v, start, **kw: flash_decode_attention(
            q, k, v, start=start, interpret=False, **kw
        )
    ).lower(*args, start, **kwargs).compile()
    assert _pallas_calls(compiled) == 1


@pytest.mark.parametrize("phase", ["prefill", "absorbed_step"])
def test_mla_at_glm_4_7_flash_widths(topo, as_tpu, phase):
    """``MultiHeadLatentAttention`` at the published GLM-4.7-Flash
    geometry (q compression 768, rank 512, 192 + 64 query/key and 256
    value dims, 20 heads): the decode-mode prefill takes d_qk = d_v = 256
    through the Pallas flash kernel, and the absorbed single-token step
    compiles against paged latent and rope-key pools of 64 slots x 1,152
    positions (the benchmark's ``glm-4.7-flash-decode`` cell): since
    PR 60 the append and the attend through the page table, two calls,
    the rotary key rows seeded a whole lane tile wide (Mosaic refuses to
    cut a page out of a pool of 64-number rows)."""
    import flax.linen as nn

    from d9d_tpu.nn.attention import MultiHeadLatentAttention
    from d9d_tpu.nn.decode_flags import PAGE_TABLE_LEAF, ring_caches
    from d9d_tpu.nn.sdpa import build_sdpa_backend

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    s_max, rank, d_rope = 1152, 512, 64
    module = MultiHeadLatentAttention(
        hidden_size=H, num_heads=20, qk_nope_head_dim=192,
        qk_rope_head_dim=d_rope, v_head_dim=256, kv_lora_rank=rank,
        q_lora_rank=768, sdpa=build_sdpa_backend(), norm_eps=1e-5,
        decode_max_length=s_max, dtype=BF16, param_dtype=BF16,
    )
    b, t = (4, 128) if phase == "prefill" else (64, 1)
    x = sds((b, t, H), BF16)
    rope = sds((b, t, d_rope // 2), jnp.float32)
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        nn.unbox(jax.eval_shape(
            lambda: module.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 1, H), BF16),
                jnp.zeros((1, 1, d_rope // 2)), jnp.zeros((1, 1, d_rope // 2)),
            )["params"]
        )),
    )
    if phase == "prefill":
        cache = {
            "cache_index": sds((), jnp.int32),
            "cached_latent": sds((b, s_max, rank), BF16),
            "cached_rope_key": sds((b, s_max, d_rope), BF16),
        }
    else:
        # the leaves as the serving loop's paged init has them declared
        with ring_caches(PAGE):
            declared = jax.eval_shape(
                lambda: module.init(
                    jax.random.PRNGKey(0), jnp.zeros((b, 1, H), BF16),
                    jnp.zeros((b, 1, d_rope // 2)),
                    jnp.zeros((b, 1, d_rope // 2)),
                )["cache"]
            )
        assert declared["cached_rope_key"].shape == (b, s_max, 128)
        pages = b * (s_max // PAGE) + 1
        cache = {
            "cache_index": sds((b,), jnp.int32),
            "cached_latent": sds((pages, PAGE, rank), BF16),
            "cached_rope_key": sds((pages, PAGE, 128), BF16),
            PAGE_TABLE_LEAF: sds((b, s_max // PAGE), jnp.int32),
        }
    compiled = jax.jit(
        lambda p, c, x, cos, sin: module.apply(
            {"params": p, "cache": c}, x, cos, sin, mutable=["cache"]
        ),
        donate_argnums=1,
    ).lower(params, cache, x, rope, rope).compile()
    if phase == "prefill":
        assert _pallas_calls(compiled) == 1
        return
    assert _pallas_calls(compiled, "mla/cache_append/paged_append") == 1
    assert _pallas_calls(compiled, "latent_decode_r4/latent_decode_p8") == 1
    # no view of every page of every row (151 MB with its float32 copy):
    # the queries, the result and their relayouts
    assert compiled.memory_analysis().temp_size_in_bytes < 20e6


def _assert_grouped_paged_call(compiled, geo, pool: str):
    """The one paged decode call of ``compiled``: under the scope that
    holds the rows a grid step attends and the name that holds the pages
    a block (which ``kernel.gqa_decode_roofline`` reads it by), within
    the VMEM a kernel has without asking, and with no copy of a ``pool``
    (an HLO shape pattern) anywhere in the program."""
    assert _pallas_calls(compiled) == 1
    text = compiled.as_text()
    (call,) = [
        line for line in text.splitlines()
        if "custom-call(" in line and "pallas_call" in line
    ]
    assert (f"paged_decode_r{geo.rows_per_step}/"
            f"paged_decode_p{geo.pages_per_step}/pallas_call") in call
    assert '"scoped_memory_configs":[]' in call  # no vmem_limit_bytes set
    (used,) = re.findall(
        r'"used_scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"', call)
    # the two buffers of the K and V blocks, the group's queries, results
    # and softmax state (the float32 casts stay in registers), within the
    # 16 MiB a kernel on this chip has unasked
    assert geo.vmem_bytes <= int(used) <= 1.1 * geo.vmem_bytes + 2**20
    assert int(used) <= 16 * 2**20
    assert not re.findall(rf"= {pool}[^=\n]*\bcopy(?:-start)?\(", text)


@pytest.mark.parametrize("pools", ["bf16", "int8"])
@pytest.mark.parametrize(
    "slots,hq,hkv,s,rows_per_step",
    # rows a grid step: with bf16 pools, with int8 pools (half the bytes)
    [(64, HQ, HKV, 576, (4, 8)), (256, 20, 1, 1152, (8, 8)),
     (128, 32, 8, 1152, (2, 4)), (256, 64, 8, 1152, (2, 4)),
     (256, 8, 2, 1152, (8, 8))],
    ids=["qwen3-64x4x9-group8", "jamba-256x1x18-group20",
         "granite-128x8x18-group4", "solar-256x8x18-group8",
         "zaya1-256x2x18-group4"],
)
def test_paged_decode_at_the_serving_cells(
        topo, slots, hq, hkv, s, rows_per_step, pools):
    """The paged decode kernel at the serving cells' geometries:
    Qwen3-30B-A3B (64 slots, 32 query heads on 4, 9 pages of 64 a row),
    Jamba2-3B's attention layers (256 slots, 20 query heads on ONE
    key/value head, 24 padded rows, 18 pages a row),
    granite-4.0-h-small's (128 slots, 32 on 8), Solar-Open2's (256
    slots, 64 on 8) and ZAYA1-8B's latent pool (256 slots, 8 on 2). One
    grid step a group of rows attends blocks of several pages of each,
    all kv heads of a page in one copy."""
    from d9d_tpu.ops.attention.pallas_decode import (
        flash_decode_attention,
        paged_decode_geometry,
    )

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    quant = pools == "int8"
    n_pages = s // PAGE
    geo = paged_decode_geometry(
        batch=slots, kv_heads=hkv, n_pages=n_pages, page_size=PAGE,
        head_dim=D, kv_itemsize=1 if quant else 2, query_rows=hq // hkv,
    )
    assert geo.pages_per_step > 1
    assert geo.rows_per_step == rows_per_step[quant]
    assert geo.grid == (slots // geo.rows_per_step,)
    dtype = jnp.int8 if quant else BF16
    pool = sds((slots * n_pages + 1, hkv, PAGE, D), dtype)
    kwargs = {"page_table": sds((slots, n_pages), jnp.int32)}
    if quant:
        scale = sds((slots * n_pages + 1, hkv, PAGE), jnp.float32)
        kwargs |= {"k_scale": scale, "v_scale": scale}
    compiled = jax.jit(
        lambda q, k, v, start, **kw: flash_decode_attention(
            q, k, v, start=start, interpret=False, **kw
        )
    ).lower(
        sds((slots, 1, hq, D), BF16), pool, pool, sds((slots,), jnp.int32),
        **kwargs,
    ).compile()
    _assert_grouped_paged_call(
        compiled, geo,
        rf"{'s8' if quant else 'bf16'}\[{slots * n_pages + 1},{hkv},{PAGE},{D}\]",
    )
    # the queries' and the result's relayouts; the int8 row scales are
    # gathered, not the whole scale pool relaid
    assert compiled.memory_analysis().temp_size_in_bytes < (
        2 * slots * hq * D * 2 + 4e6)


@pytest.mark.parametrize(
    "hkv,window,rows_per_step", [(4, None, 2), (8, 128, 2)],
    ids=["full-4x192", "window-8x192"],
)
def test_paged_decode_at_mimo_v2_flash_widths(topo, hkv, window, rows_per_step):
    """The paged decode kernel at the MiMo-V2-Flash cell's two kinds of
    layer (256 slots, 64 query heads, pages of 64): key rows of 192
    cached as 256 (whole lane tiles, so the kernel's own page copies end
    on a tile's edge) against value rows of 128. A full layer reads a
    pool through the allocator's table, 18 pages a row, blocks of 8; a
    window layer its row's ring of 3 pages, one block a row."""
    from d9d_tpu.nn.attention import _cache_row_pad
    from d9d_tpu.ops.attention.pallas_decode import (
        flash_decode_attention,
        paged_decode_geometry,
        window_pages,
    )

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    slots, hq, d, dv, n_pages = 256, 64, 192, 128, 18
    dk = d + _cache_row_pad(d)
    assert dk == 256
    geo = paged_decode_geometry(
        batch=slots, kv_heads=hkv, n_pages=n_pages, page_size=PAGE,
        head_dim=dk, kv_itemsize=2, v_head_dim=dv, window=window,
        query_rows=hq // hkv,
    )
    pages = slots * (window_pages(window, PAGE) if window else n_pages) + 1
    assert geo.pages_per_step == (3 if window else 8)
    assert geo.rows_per_step == rows_per_step
    compiled = jax.jit(
        lambda q, k, v, start, table: flash_decode_attention(
            q, k, v, start=start, page_table=table, window_size=window,
            softmax_scale=d ** -0.5, sinks=None, interpret=False,
        )
    ).lower(
        sds((slots, 1, hq, dk), BF16), sds((pages, hkv, PAGE, dk), BF16),
        sds((pages, hkv, PAGE, dv), BF16), sds((slots,), jnp.int32),
        sds((slots, n_pages), jnp.int32),
    ).compile()
    _assert_grouped_paged_call(
        compiled, geo, rf"bf16\[{pages},{hkv},{PAGE},(?:{dk}|{dv})\]")
    # the queries' relayout to [B, Hkv, g, 256] (8.4 MB) and the output's:
    # no pool is copied or relaid
    assert compiled.memory_analysis().temp_size_in_bytes < 20e6


def test_a_scanned_cache_append_copies_no_pool(topo):
    """A decode step in a ``lax.scan`` (the serving chunk's shape): the
    new token's rows scattered into a heads-major pool, then the paged
    kernel on it. Written as ``pool.at[page, :, off, :].set`` the compiler
    gives the scatter another layout and copies the whole pool there and
    back each step (3.9 ms a layer a step at these shapes on the chip,
    PERF.md, PR 41); as a scatter of rows into the flat pool the compiled
    program holds no copy of a pool."""
    from d9d_tpu.nn.attention import _scatter_head_rows
    from d9d_tpu.ops.attention.pallas_decode import flash_decode_attention

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    slots, hq, hkv, dk, dv, n_pages = 256, 64, 4, 256, 128, 18
    pages = slots * n_pages + 1

    def chunk(kpool, vpool, table, start, q, knew, vnew):
        def step(carry, _):
            kpool, vpool, start = carry
            page = jnp.take_along_axis(
                table, (start // PAGE)[:, None], axis=1)[:, 0]
            kpool = _scatter_head_rows(kpool, page, start % PAGE, knew)
            vpool = _scatter_head_rows(vpool, page, start % PAGE, vnew)
            out = flash_decode_attention(
                q, kpool, vpool, start=start, page_table=table,
                softmax_scale=192 ** -0.5, interpret=False,
            )
            return (kpool, vpool, start + 1), out.astype(jnp.float32).sum()

        (kpool, vpool, _), outs = jax.lax.scan(
            step, (kpool, vpool, start), None, length=8)
        return kpool, vpool, outs

    text = jax.jit(chunk, donate_argnums=(0, 1)).lower(
        sds((pages, hkv, PAGE, dk), BF16), sds((pages, hkv, PAGE, dv), BF16),
        sds((slots, n_pages), jnp.int32), sds((slots,), jnp.int32),
        sds((slots, 1, hq, dk), BF16), sds((slots, hkv, dk), BF16),
        sds((slots, hkv, dv), BF16),
    ).compile().as_text()
    pool_copies = re.findall(
        rf"= bf16\[{pages},{hkv},{PAGE},(?:{dk}|{dv})\][^=\n]*\bcopy\(", text)
    assert not pool_copies


@pytest.mark.parametrize(
    "slots,hkv,dk,dv,row_pages,window",
    [(256, 8, 256, 128, 3, 128), (64, 4, 128, 128, 9, None)],
    ids=["mimo-window-ring-256x8", "qwen3-64x4"],
)
def test_a_scanned_paged_append_holds_the_pools_in_place(
        topo, slots, hkv, dk, dv, row_pages, window):
    """The same eight-step scan with ``paged_append`` in the scatter's
    place, at the MiMo cell's window layers (256 slots, 8 kv heads, rings
    of 3 pages, 256 / 128) and at the Qwen3 cell's (64 slots, 4 heads,
    128 / 128): Mosaic takes the tile transfers for the described v5e
    (a refused transfer shape shows here, before chip time is spent),
    the compiled program holds no scatter and no copy of a pool, and
    both pools go through the call aliased."""
    from d9d_tpu.ops.attention.pallas_decode import (
        flash_decode_attention,
        paged_append,
    )

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    hq, pages = 8 * hkv, slots * row_pages + 1

    def chunk(kpool, vpool, table, start, q, knew, vnew):
        def step(carry, _):
            kpool, vpool, start = carry
            page = jnp.take_along_axis(
                table, (start // PAGE)[:, None], axis=1)[:, 0]
            kpool, vpool = paged_append(
                kpool, vpool, page, start % PAGE, knew, vnew,
                interpret=False)
            out = flash_decode_attention(
                q, kpool, vpool, start=start, page_table=table,
                window_size=window, interpret=False,
            )
            return (kpool, vpool, start + 1), out.astype(jnp.float32).sum()

        (kpool, vpool, _), outs = jax.lax.scan(
            step, (kpool, vpool, start), None, length=8)
        return kpool, vpool, outs

    text = jax.jit(chunk, donate_argnums=(0, 1)).lower(
        sds((pages, hkv, PAGE, dk), BF16), sds((pages, hkv, PAGE, dv), BF16),
        sds((slots, 18), jnp.int32), sds((slots,), jnp.int32),
        sds((slots, 1, hq, dk), BF16), sds((slots, hkv, dk), BF16),
        sds((slots, hkv, dv), BF16),
    ).compile().as_text()
    assert "scatter(" not in text
    pool = rf"bf16\[{pages},{hkv},{PAGE},(?:{dk}|{dv})\]"
    assert not re.findall(rf"= {pool}[^=\n]*\bcopy(?:-start)?\(", text)
    (append,) = [
        line for line in text.splitlines()
        if "paged_append/pallas_call" in line and "custom-call(" in line
    ]
    # operands: page, off, the new rows, then the two pools
    assert "output_to_operand_aliasing={{0}: (4, {}), {1}: (5, {})}" in append
    # held in HBM by name: a pool that fits the compiler's fast memory is
    # otherwise staged there whole around the call (the Qwen3 cell's body
    # of six layers: 13 copies of a pool a step, none with this)
    assert '"output_memory_colors":["0","0"]' in append


def test_a_donated_paged_append_compiles_outside_a_loop(topo):
    """The pools as a program's own donated arguments and results, no
    loop around the call (a serving step that is not a fused chunk): the
    verifier refused the call's HBM-named outputs as aliases of the
    unnamed parameters (`Different aliasing shapes`) until
    ``paged_append`` returned them behind a barrier."""
    from d9d_tpu.ops.attention.pallas_decode import paged_append

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    slots, pages = 64, 64 * 9 + 1
    text = jax.jit(
        lambda *a: paged_append(*a, interpret=False), donate_argnums=(0, 1),
    ).lower(
        sds((pages, HKV, PAGE, D), BF16), sds((pages, HKV, PAGE, D), BF16),
        sds((slots,), jnp.int32), sds((slots,), jnp.int32),
        sds((slots, HKV, D), BF16), sds((slots, HKV, D), BF16),
    ).compile().as_text()
    assert "paged_append/pallas_call" in text
    assert not re.findall(r"\bcopy(?:-start)?\(", text)  # in place


def test_mamba_step_at_jamba2_3b_widths(topo):
    """The Mamba-1 mixer's one-token step for 256 rows at the published
    widths (d_inner 5,120, d_state 16, dt_rank 160): the float32 state
    (84 MB) and the conv tail are updated in place when the cache is
    donated, and the step's temporaries stay a fraction of the state."""
    import flax.linen as nn

    from d9d_tpu.nn.mamba import MambaMixer

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    mixer = MambaMixer(
        hidden_size=2560, dt_rank=160, decode=True, dtype=BF16,
        param_dtype=BF16,
    )
    abstract = nn.unbox(jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros((256, 1, 2560), BF16))
    ))
    variables = jax.tree.map(lambda a: sds(a.shape, a.dtype), abstract)
    assert variables["cache"]["ssm_state"].shape == (256, 16, 5120)
    state_bytes = 256 * 16 * 5120 * 4
    compiled = jax.jit(
        lambda cache, params, u: mixer.apply(
            {"params": params, "cache": cache}, u, mutable=["cache"]
        ),
        donate_argnums=0,
    ).lower(
        variables["cache"], variables["params"], sds((256, 1, 2560), BF16)
    ).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= state_bytes  # updated in place
    assert ma.temp_size_in_bytes < state_bytes / 2


def test_mamba2_step_at_granite_4_0_h_small_widths(topo):
    """The Mamba-2 mixer's one-token step for 128 rows at the published
    widths (128 heads of 64, state 128, one group): the float32 state (a
    matrix a head, 537 MB) and the conv tail are updated in place when
    the cache is donated, and the step's temporaries stay a fraction of
    the state."""
    import flax.linen as nn

    from d9d_tpu.nn.mamba import Mamba2Mixer

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    mixer = Mamba2Mixer(
        hidden_size=4096, num_heads=128, head_dim=64, d_state=128,
        decode=True, dtype=BF16, param_dtype=BF16,
    )
    abstract = nn.unbox(jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros((128, 1, 4096), BF16))
    ))
    variables = jax.tree.map(lambda a: sds(a.shape, a.dtype), abstract)
    assert variables["cache"]["ssm_state"].shape == (128, 128, 64, 128)
    assert variables["cache"]["conv_tail"].shape == (128, 3, 8448)
    state_bytes = 128 * 128 * 64 * 128 * 4
    compiled = jax.jit(
        lambda cache, params, u: mixer.apply(
            {"params": params, "cache": cache}, u, mutable=["cache"]
        ),
        donate_argnums=0,
    ).lower(
        variables["cache"], variables["params"], sds((128, 1, 4096), BF16)
    ).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= state_bytes  # updated in place
    assert ma.temp_size_in_bytes < state_bytes / 2


def test_an_admission_clears_its_rows_in_place_at_granite_widths(topo):
    """The serving loop's reset (``decode_flags.zero_rows``) ahead of a
    scan of steps, on three Mamba-2 layers' per-row leaves for the
    Granite cell's 128 rows, the cache donated: the reset is a loop of
    in-place row writes, no ``select`` or copy of a state's whole shape
    stands between the argument and the scan, and the program holds no
    second copy of the state (the 6.5 MB tails the compiler is free to
    stage in fast memory, as it does in the cell's own step)."""
    from d9d_tpu.nn.decode_flags import zero_rows

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    state, tail = (128, 128, 64, 128), (128, 3, 8448)
    cache = {
        f"layers_{i}": {"mamba": {
            "ssm_state": sds(state, jnp.float32), "conv_tail": sds(tail, BF16),
        }} for i in range(3)
    }
    state_bytes = 3 * int(np.prod(state)) * 4

    def admit_then_step(cache, admit_mask):
        cache = zero_rows(cache, admit_mask)
        halve = lambda x: (x * 0.5).astype(x.dtype)  # noqa: E731
        cache, _ = jax.lax.scan(
            lambda c, _: (jax.tree.map(halve, c), None), cache, None, length=8)
        return cache

    compiled = jax.jit(admit_then_step, donate_argnums=0).lower(
        cache, sds((128,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert not re.search(
        r"= f32\[128,128,64,128\]\S* (select|copy|copy-start|copy-done)\(",
        text)
    assert "serve/reset_rows/while" in text
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= state_bytes  # cleared in place
    assert ma.temp_size_in_bytes < state_bytes / 3


def _decode_chunk(model, steps: int = 8):
    """``steps`` scanned greedy decode steps of ``model`` on a carried
    cache: the body of the serving loop's fused chunk."""
    def chunk(cache, params, tok, pos):
        def body(carry, _):
            cache, tok, pos = carry
            logits, new = model.apply(
                {"params": params, "cache": cache}, tok, pos,
                method="logits", mutable=["cache"])
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (new["cache"], tok[:, None], pos + 1), tok

        (cache, _, _), toks = jax.lax.scan(
            body, (cache, tok, pos), None, length=steps)
        return cache, toks

    return chunk


def test_fused_decode_steps_at_the_solar_open2_share8_cell(topo, as_tpu):
    """Eight scanned decode steps of the whole share (one period: gated
    NoPE GQA and three Kimi delta attention mixers at the published
    widths, 40 held experts and a shared one a layer) for the cell's 256
    rows, the cache donated: the three ``kda_step`` calls are in the
    program, each mixer's 1.07 GB float32 state is updated in place (no
    copy of it is made), and weights, cache and temporaries fit the chip
    with the room the cell's 71 % states. The body of the serving loop's
    fused chunk with an unpaged cache, not the chunk itself."""
    from d9d_tpu.models.solar import SolarCausalLM, solar_open2_250b_share8
    from d9d_tpu.nn.sdpa import build_sdpa_backend

    slots, state_bytes = 256, 256 * 64 * 128 * 128 * 4
    sds = _on(SingleDeviceSharding(topo.devices[0]))
    model = SolarCausalLM(
        config=solar_open2_250b_share8(), sdpa=build_sdpa_backend(),
        dtype=BF16, param_dtype=BF16, decode_max_length=1152,
    )
    z = jnp.zeros((slots, 1), jnp.int32)
    abstract = nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), z, z, z)))
    variables = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        {k: abstract[k] for k in ("params", "cache")})

    compiled = jax.jit(_decode_chunk(model), donate_argnums=0).lower(
        variables["cache"], variables["params"],
        sds(z.shape, z.dtype), sds(z.shape, z.dtype),
    ).compile()
    assert _pallas_calls(compiled, "kda_step") == 3
    text = compiled.as_text()
    assert not re.search(r"= f32\[256,64,128,128\]\S* copy\(", text)
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 3 * state_bytes  # updated in place
    assert ma.temp_size_in_bytes < 2 * state_bytes
    claimed = (ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert claimed < 14e9  # of the chip's 15.75 GB


def test_fused_decode_steps_at_the_glm_4_7_flash_cell(topo, as_tpu):
    """Eight scanned decode steps of the GLM cell's six layers for its 64
    rows, the paged cache donated (the body of the serving loop's fused
    chunk): a layer holds the append and the latent call through the page
    table, nothing gathers a pool, nothing scatters into one, and no copy
    of a whole pool is made around the calls. Behind XLA's scatter the
    compiler staged the 75.6 MB latent pool in its fast memory and copied
    it back out for the kernel, in four of the six layers (PR 60)."""
    from flax.traverse_util import flatten_dict, unflatten_dict

    from d9d_tpu.models.deepseek import DeepseekCausalLM, glm_4_7_flash
    from d9d_tpu.nn.decode_flags import PAGE_TABLE_LEAF, ring_caches
    from d9d_tpu.nn.sdpa import build_sdpa_backend
    from d9d_tpu.ops.attention.pallas_decode import paged_decode_geometry

    slots, n_pages, layers = 64, 18, 6
    pages = slots * n_pages + 1
    sds = _on(SingleDeviceSharding(topo.devices[0]))
    model = DeepseekCausalLM(
        config=dataclasses.replace(glm_4_7_flash(), num_layers=layers),
        sdpa=build_sdpa_backend(), dtype=BF16, param_dtype=BF16,
        decode_max_length=n_pages * PAGE,
    )
    z = jnp.zeros((slots, 1), jnp.int32)
    with ring_caches(PAGE):  # the serving loop's paged shape-only init
        abstract = nn.unbox(jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), z, z, z)))
    cache = {}
    for path, leaf in flatten_dict(abstract["cache"]).items():
        if path[-1] == "cache_index":
            cache[path] = sds((slots,), jnp.int32)
        else:  # a pool, and the table the loop seeds beside it
            assert path[-1] in ("cached_latent", "cached_rope_key")
            cache[path] = sds((pages, PAGE, leaf.shape[-1]), leaf.dtype)
            cache[path[:-1] + (PAGE_TABLE_LEAF,)] = sds(
                (slots, n_pages), jnp.int32)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), abstract["params"])

    compiled = jax.jit(_decode_chunk(model), donate_argnums=0).lower(
        unflatten_dict(cache), params, sds(z.shape, z.dtype),
        sds(z.shape, z.dtype),
    ).compile()
    geo = paged_decode_geometry(
        batch=slots, kv_heads=1, n_pages=n_pages, page_size=PAGE,
        head_dim=512, v_head_dim=128, kv_itemsize=2, query_rows=20,
    )
    assert (geo.pages_per_step, geo.rows_per_step) == (8, 4)
    assert _pallas_calls(compiled, "mla/cache_append/paged_append") == layers
    assert _pallas_calls(
        compiled, "mla/latent_attend/jit(_paged_decode_call)/"
        "latent_decode_r4/latent_decode_p8/pallas_call") == layers
    text = compiled.as_text()
    pool = rf"bf16\[{pages},(?:1,)?{PAGE},(?:512|128)\]"
    assert not re.findall(rf"= {pool}[^=\n]*\bcopy(?:-start)?\(", text)
    # the parent gathered bf16[64,18,64,512] a layer and read it as
    # [64,1152,512]: no row's view of its pages, of either pool
    assert f"[{slots},{n_pages},{PAGE}," not in text
    assert f"[{slots},{n_pages * PAGE}," not in text
    assert "scatter(" not in text
    ma = compiled.memory_analysis()
    pool_bytes = layers * pages * PAGE * (512 + 128) * 2
    assert ma.alias_size_in_bytes >= pool_bytes  # the pools, in place
    assert ma.temp_size_in_bytes < 0.25 * pool_bytes  # no row's view


# -- the output head's fused cross-entropy -----------------------------------


@pytest.mark.parametrize("v", [151936, 102400], ids=["qwen3", "deepseek"])
def test_linear_ce_block_loop_at_a_step_of_16k_tokens(topo, v):
    """Loss and both gradients of the fused cross-entropy at the one-chip
    training cells' sizes (16,384 tokens x 2,048, Qwen3's vocabulary with
    its ragged rest and DeepSeek's 25 even blocks): the compiled loops
    hold a ``[blocks, 4096, 2048]`` stack of the weight and of its
    gradient and the hidden state's float32 gradient, never the weight's
    whole gradient as loop state beside them, and no ``[N, V]`` array."""
    from d9d_tpu.ops.linear_ce import linear_cross_entropy

    n, d = 16384, 2048
    sds = _on(SingleDeviceSharding(topo.devices[0]))

    def total(h, w, labels, cot):
        return (linear_cross_entropy(h, w, labels) * cot).sum()

    compiled = jax.jit(jax.value_and_grad(total, argnums=(0, 1))).lower(
        sds((n, d), BF16), sds((v, d), BF16), sds((n,), jnp.int32),
        sds((n,), jnp.float32),
    ).compile()
    text = compiled.as_text()
    whiles = [line for line in text.splitlines() if " while(" in line]
    assert len(whiles) == 2  # the forward scan and the backward scan
    blocks = v // 4096
    for line in whiles:
        assert f"bf16[{blocks},4096,{d}]" in line
        assert f"[{v},{d}]" not in line and f"[{d},{v}]" not in line
    assert f"f32[{n},{d}]" in whiles[1]  # the carry, rounded once after
    assert f"[{n},{v}]" not in text and f"[{v},{n}]" not in text
    # slab, stacked gradient, a copy of the blocks where V is ragged
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# -- the local expert path in a decode chunk -----------------------------------


def _standalone_results(text: str):
    """``(opcode, bytes)`` of every array-valued instruction of compiled
    text that runs as an op of its own: the entry computation and loop
    bodies, not the insides of a fusion (where a weight-sized ``bitcast``
    fusion is an operand's view and writes nothing)."""
    size = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "s8": 1}
    fused = set(re.findall(r" fusion\(.*calls=%?([\w.\-]+)", text))
    inside = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            inside = head[1]
            continue
        found = inside not in fused and re.match(
            r"^\s+(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(",
            line,
        )
        if found:
            dtype, dims, opcode = found.groups()
            count = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            yield opcode, count * size.get(dtype, 4)


@pytest.mark.parametrize(
    "e,inter,top_k,parent_temp",
    [(E, INTER, TOP_K, 768e6), (64, 1536, 4, 0.81e9)],
    ids=["qwen3-128x768-top8", "glm-64x1536-top4"],
)
def test_expert_block_of_a_decode_chunk(topo, e, inter, top_k, parent_temp):
    """``MoELayer`` at the two MoE serving cells' expert shapes for the 64
    rows of a decode step, inside a loop of a chunk's 8 steps: the call
    takes the all-expert products (``ops/moe.py
    few_rows_touch_all_experts``), so no ``ragged-dot`` call is in the
    program, nothing the size of a weight is copied, transposed or
    concatenated (a chunk hoists such a thing out of its loop and keeps
    it: the parent held a ``gate|up`` copy of 768 MB / 0.81 GB a layer),
    and the temporaries are a small part of that."""
    from d9d_tpu.nn.moe import MoELayer

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    layer = MoELayer(
        hidden_dim=H, intermediate_dim_grouped=inter, num_grouped_experts=e,
        top_k=top_k, dtype=BF16, param_dtype=BF16,
    )
    x = sds((64, 1, H), BF16)
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        nn.unbox(jax.eval_shape(
            lambda x: layer.init(jax.random.PRNGKey(0), x)["params"], x
        )),
    )

    def chunk(params, x):
        return jax.lax.fori_loop(
            0, 8, lambda _, x: x + layer.apply({"params": params}, x), x
        )

    compiled = jax.jit(chunk).lower(params, x).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert len(re.findall(r"moe/experts/(?:gate_up|down)/all_experts", text)) >= 3
    one_weight = e * H * inter * 2
    relaid = [
        (opcode, size) for opcode, size in _standalone_results(text)
        if size >= one_weight and opcode in (
            "copy", "transpose", "concatenate", "fusion", "pad", "reshape")
    ]
    assert relaid == []
    assert compiled.memory_analysis().temp_size_in_bytes < parent_temp / 10


# -- the model ---------------------------------------------------------------


def test_one_layer_30b_a3b_value_and_grad(topo, as_tpu):
    """The whole model at published widths, one layer deep, through the
    factory's default SDPA: the flash kernels are in the program."""
    import flax.linen as nn

    from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig
    from d9d_tpu.nn.sdpa import build_sdpa_backend

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    cfg = dataclasses.replace(Qwen3MoeConfig.qwen3_30b_a3b(), num_layers=1)
    model = Qwen3MoeCausalLM(
        config=cfg, sdpa=build_sdpa_backend(), dtype=BF16, param_dtype=BF16
    )
    ids = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    abstract = nn.unbox(jax.eval_shape(
        lambda z: model.init(jax.random.PRNGKey(0), z, z, z)["params"], ids
    ))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), abstract)
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n_params > 1_200_000_000

    def loss(params, tokens):
        return model.apply({"params": params}, tokens, tokens, tokens).sum()

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        params, sds((1, T), jnp.int32)
    ).compile()
    assert _pallas_calls(compiled, "self_attn") >= 3, (
        "flash forward/backward kernels not in the HLO"
    )
    # the local path's permute and combine, forward and transposed
    assert _fill_selects_of_row_gathers(compiled) == []
    ma = compiled.memory_analysis()
    # bf16 params in, bf16 grads out: 2.5 GB each; the step fits one chip
    assert ma.argument_size_in_bytes + ma.output_size_in_bytes < 6e9


@pytest.mark.parametrize("mode", ["forward", "grad"])
def test_ep_dispatch_combine_on_four_chips(topo, as_tpu, mode):
    """The EP flow at 30B-A3B expert shapes over four described chips:
    ``lax.ragged_all_to_all`` itself, not the CPU emulation under it, and
    row gathers with no fill pass behind them."""
    from d9d_tpu.nn.moe import grouped_swiglu_apply
    from d9d_tpu.ops.ep_dispatch import ep_dispatch_compute_combine

    world, n_loc = 4, 4096
    mesh = Mesh(np.array(topo.devices), ("ep",))
    rows = _on(NamedSharding(mesh, P("ep")))
    e_loc = E // world

    def expert_fn(rows, group_sizes, gate_w, up_w, down_w):
        return grouped_swiglu_apply(
            rows, jnp.ones((rows.shape[0],), jnp.float32), group_sizes,
            gate_w, up_w, down_w, BF16,
        )

    def body(x, ids, probs, *weights):
        return ep_dispatch_compute_combine(
            x, ids, probs, expert_fn, weights, ep_axes=("ep",), e_loc=e_loc,
            ep_world=world, capacity_factor=None,
        )[0]

    run = jax.shard_map(
        body, mesh=mesh, in_specs=(P("ep"),) * 6, out_specs=P("ep"),
        check_vma=False,
    )
    fn = run
    if mode == "grad":
        def fn(x, *rest):
            return jax.grad(
                lambda x, *r: run(x, *r).astype(jnp.float32).sum(),
                argnums=(0, 3, 4, 5),
            )(x, *rest)

    compiled = jax.jit(fn).lower(
        rows((world * n_loc, H), BF16),
        rows((world * n_loc, TOP_K), jnp.int32),
        rows((world * n_loc, TOP_K), jnp.float32),
        rows((E, H, INTER), BF16), rows((E, H, INTER), BF16),
        rows((E, INTER, H), BF16),
    ).compile()
    n = compiled.as_text().count(" ragged-all-to-all(")
    # dispatch + combine, and the transposes of both in the backward
    assert n >= (2 if mode == "forward" else 3), n
    # at every rung, forward, recomputed and transposed
    assert _fill_selects_of_row_gathers(compiled) == []


@pytest.mark.parametrize("n,k,held,rows,width,dtype", [
    (16384, 8, 32, 20480, 2048, BF16),  # the window-layer cell's first rung
    (8192, 4, 8, 5120, 3584, BF16),  # Xing4.0's
    (1, 8, 16, 8, 4096, BF16),  # a one-row generate step of MiMo's share
    (1, 10, 18, 10, 4096, BF16),  # and of Granite's
    (4096, 8, 32, 5120, 2048, jnp.float32),  # every pass of the MXU
], ids=["laguna", "xing4.0", "generate-top-8", "generate-top-10", "float32"])
def test_fold_held_at_the_share_cells_shapes(
        topo, as_tpu, n, k, held, rows, width, dtype):
    """A held range's fold (the backward's is the same call on the
    cotangent's buffer): one Pallas call whatever ``top_k``, the buffer
    and the dtype, inside Mosaic's default scoped VMEM, and among the
    program's temporaries the buffer once over in slot order, in its own
    dtype, never a float32 copy of it."""
    from d9d_tpu.ops.moe import fold_held, sort_held_pairs

    sds = _on(SingleDeviceSharding(topo.devices[0]))

    def fold(y, local):
        return fold_held(y, sort_held_pairs(local, held, rows), n, k)

    compiled = jax.jit(fold).lower(
        sds((rows, width), dtype), sds((n, k), jnp.int32)).compile()
    assert _pallas_calls(compiled) == 1
    row_bytes = width * jnp.dtype(dtype).itemsize
    in_slot_order = -(-rows // 256) * 256 * row_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < (
        in_slot_order + rows * row_bytes // 2)


@pytest.mark.parametrize("call", ["read", "write", "write_bwd", "read_bwd"])
def test_mhc_calls_at_the_xing4_0_cells_shapes(topo, as_tpu, call):
    """The n-stream path's four passes (``ops/mhc.py``) at the Xing4.0
    cell's shapes, 2 x 4,096 tokens of 4 x 3,584 in bf16: each one Pallas call
    that fits the VMEM it asks for, and the program around it holds no
    temporary as large as the stream (no float32 copy, no padded one)."""
    from d9d_tpu.ops import mhc

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    batch, tokens, n, c = 2, 4096, 4, 3584
    stream = sds((batch, n, tokens, c), BF16)
    row = sds((batch, tokens, c), BF16)
    lanes = sds((batch, tokens, mhc.LANES), jnp.float32)
    phi_t = sds((mhc.phi_rows(n), n * c), BF16)
    a, b = sds((), jnp.float32), sds((n,), jnp.float32)
    fn, args = {
        "read": (lambda x, p, a, b: mhc._read_call(
            x, p, a, b, norm_eps=1e-6), (stream, phi_t, a, b)),
        "write": (mhc._write_call, (stream, row, lanes)),
        "write_bwd": (mhc._write_bwd_call, (stream, stream, row, lanes)),
        "read_bwd": (mhc._read_bwd_call,
                     (stream, phi_t, a, b, lanes, row, lanes)),
    }[call]
    compiled = jax.jit(fn).lower(*args).compile()
    assert _pallas_calls(compiled) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < (
        batch * tokens * n * c)


def test_mhc_shards_itself_over_a_mesh(topo, as_tpu):
    """On a mesh of four chips ``HyperConnection`` runs its calls in a
    ``shard_map`` over the batch axis (a Mosaic kernel cannot be
    partitioned by the compiler): a read and a write with their
    gradient compile, the four calls in the program, the maps' gradient
    reduced over the axis."""
    import flax.linen as nn

    from d9d_tpu.nn.hyper_connections import HyperConnection

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("dp_s", "tp"))
    n, c = 4, 3584
    mod = HyperConnection(hidden_size=c, streams=n, dtype=BF16,
                          param_dtype=BF16)
    stream = jax.ShapeDtypeStruct(
        (4, 512, n, c), BF16, sharding=NamedSharding(mesh, P("dp_s")))

    def loss(params, x):
        u, mix = mod.apply({"params": params}, x, method="read")
        new = mod.apply({"params": params}, x, jnp.tanh(u), mix,
                        method="write")
        return new.astype(jnp.float32).sum()

    with jax.set_mesh(mesh):
        abstract = nn.unbox(jax.eval_shape(
            lambda: mod.init(jax.random.PRNGKey(0), stream, method="read")
        ))["params"]
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, P())),
            abstract)
        compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            params, stream).compile()
    assert _pallas_calls(compiled, "mhc/") == 4
    assert " all-reduce" in compiled.as_text()


# -- the env-selected fused expert FFN (default stays ``xla``) ---------------

# What the v5e compiler says to the gather variants once the ``unroll=8``
# it refused first ("Only unroll=num_steps and unroll=1 supported") is
# unrolled by hand: the in-kernel row gather reads one bf16 row at a
# dynamic sublane offset. Not a line or two to repair; see ROADMAP
# ``env-selected-kernels``.
_GATHER_REFUSAL = "cannot statically prove that index in dimension 0"


@pytest.mark.parametrize(
    "variant",
    [
        "pallas",
        pytest.param("pallas_gather", marks=pytest.mark.xfail(
            strict=True, raises=Exception, reason=_GATHER_REFUSAL)),
        pytest.param("pallas_gather_combine", marks=pytest.mark.xfail(
            strict=True, raises=Exception, reason=_GATHER_REFUSAL)),
    ],
)
def test_moe_pallas_ffn(topo, variant):
    from d9d_tpu.ops import moe_pallas

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    block_m, n = 128, 4096
    m = n * TOP_K
    m_pad = (-(-m // block_m) + E) * block_m
    weights = (
        sds((E, H, INTER), BF16), sds((E, H, INTER), BF16),
        sds((E, INTER, H), BF16),
    )
    gid = sds((m_pad // block_m,), jnp.int32)
    # the eligibility gates pass these shapes, so the kernels are taken
    assert moe_pallas._tpu_shapes_ok(H, INTER, block_m, 2)
    assert moe_pallas._combine_fits(n, m, H, INTER, block_m, 2, E)
    if variant == "pallas":
        lowered = moe_pallas._fused_ffn_call.lower(
            sds((m_pad, H), BF16), sds((m_pad, 1), jnp.float32), gid,
            *weights, block_m=block_m, interpret=False,
        )
    else:
        call = (
            moe_pallas._fused_gather_call if variant == "pallas_gather"
            else moe_pallas._fused_gather_combine_call
        )
        lowered = call.lower(
            sds((n, H), BF16), sds((m, 1), jnp.float32), gid,
            sds((m_pad,), jnp.int32), *weights,
            block_m=block_m, top_k=TOP_K, interpret=False,
        )
    try:
        compiled = lowered.compile()
    except Exception as e:
        assert _GATHER_REFUSAL in str(e), e  # another refusal is news
        raise
    assert _pallas_calls(compiled) == 1
