"""Fused expert-FFN Pallas kernel over a group-aligned tile layout.

Replaces the local MoE compute chain
``grouped_matmul(gate+up) -> silu_mul -> grouped_matmul(down) -> *probs``
(ops/moe.py + nn/moe.py grouped_swiglu_apply; reference analogue:
nv-grouped-gemm + Triton permute/silu kernels, d9d/kernel/gmm/function.py,
d9d/kernel/moe/) with ONE Pallas kernel per layer call.

Why: the XLA chain round-trips ``[M, 2*inter]`` gate+up activations and
``[M, inter]`` hidden through HBM between the grouped matmuls, and the
fused gate+up single-ragged_dot trick additionally materializes a runtime
``[E, in, 2*inter]`` weight concat every call. This kernel keeps those intermediates in VMEM: each grid step loads one
``[block_m, h]`` activation tile plus its expert's three weight blocks,
runs gate/up/down matmuls + silu + prob-scale on-chip, and writes only
the ``[block_m, h]`` output tile.

The enabling layout trick is GROUP ALIGNMENT: expert groups are padded to
``block_m`` multiples so every tile belongs to exactly one expert — no
boundary tiles spanning two experts, so the kernel needs no multi-pass
accumulation (the hard part of megablocks-style GMMs). The pad rows are
zeros and cost only their matmul FLOPs, which the roofline shows are not
the binding resource at MoE shapes (the step is HBM-bound). Consecutive
tiles of the same expert reuse the already-fetched weight blocks (Pallas
skips re-DMA when the mapped block index repeats, and tiles are
expert-sorted by construction).

Backward: ``fused_moe_ffn`` is a custom_vjp whose bwd re-runs the
reference XLA path under ``jax.vjp`` — exact gradients, same cost as
today's remat backward, zero extra residual memory (saved tensors are the
function's own inputs). The fused kernel accelerates the forward AND the
remat recompute (jax.checkpoint replays the custom fwd).

Enable via ``D9D_TPU_MOE_FFN=pallas`` (default ``xla``); falls back to
the XLA path when shapes don't meet the TPU tiling constraints or the
VMEM budget. ``D9D_TPU_MOE_FFN=pallas_gather`` additionally fuses the
permute gather into the kernel: the whole token matrix ``x [N, h]``
(and flat probs) sits resident in VMEM and each M-tile gathers its rows
in-kernel via the scalar-prefetched ``pair_src`` map, so the aligned
activation buffer never exists in HBM. The gather variant
auto-falls back to plain ``pallas`` when the residency or SMEM index
maps don't fit (:func:`_gather_fits`).

Under the gather backend the COMBINE side fuses too (default on,
``D9D_TPU_MOE_COMBINE=unfused`` for the A/B): the kernel holds the
token-major combined output ``[N, h]`` resident in VMEM (constant
output index map — flushed to HBM once) and scatter-accumulates each
tile's prob-weighted down-projection rows into their owning tokens, so
the expert-sorted ``y`` and its pair-gathered copy — the combine half
of the roofline's 79 ms/step permute+combine gather traffic — never
touch HBM. One ragged gather → grouped matmul → K-sum, all in-kernel
(:func:`_ffn_gather_combine_kernel`; fit gate :func:`_combine_fits`).

Scope: the LOCAL MoE path only. The EP flow's per-shard ``expert_fn``
receives rows the dispatch all-to-all already delivered in expert-sorted
(but unaligned) order; re-aligning them for this kernel would cost a
``[rows, h]`` scatter + gather pair (~2·M·h·2 B) that cancels what the
fusion saves (~M·(2·inter+inter)·2·2 B — equal at h = 3·inter, the
Qwen3-MoE ratio). The local path wins only because the aligned gather
REPLACES the permute gather it already had to do.
"""

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from d9d_tpu.core.types import Array
from d9d_tpu.ops.moe import TokenSort, grouped_matmul
from d9d_tpu.ops.swiglu import silu_mul

LANES = 128


class AlignedMeta(NamedTuple):
    """Group-aligned layout descriptors (all int32, traced).

    dest_aligned: [M] aligned row for each (token, k) pair i (the combine
        gather indices; ``dest_aligned[i] = aligned_pos[sort.dest[i]]``).
    pair_src: [m_pad] owning pair of each aligned row (-1 for pad rows) —
        the gather map that fills the aligned activation buffer.
    gid: [T] owning expert of each block_m tile (pad tiles clamp to E-1).
    m_pad: static aligned buffer length (upper bound, block_m multiple).
    """

    dest_aligned: Array
    pair_src: Array
    gid: Array
    m_pad: int


def aligned_metadata(
    sort: TokenSort, num_experts: int, block_m: int
) -> AlignedMeta:
    """Static-shape aligned layout from a TokenSort (all jnp, O(M + E))."""
    m = sort.sort_idx.shape[0]
    # every group pads by < block_m, so this static bound always fits
    m_pad = (-(-m // block_m) + num_experts) * block_m
    gs = sort.group_sizes
    padded = ((gs + block_m - 1) // block_m) * block_m
    ends = jnp.cumsum(gs)
    aligned_starts = jnp.concatenate(
        [jnp.zeros((1,), gs.dtype), jnp.cumsum(padded)[:-1]]
    )
    rows = jnp.arange(m, dtype=jnp.int32)
    expert_of_row = jnp.searchsorted(ends, rows, side="right").astype(
        jnp.int32
    )
    starts = ends - gs
    rank = rows - starts[expert_of_row].astype(jnp.int32)
    aligned_pos = aligned_starts[expert_of_row].astype(jnp.int32) + rank
    dest_aligned = jnp.take(aligned_pos, sort.dest, axis=0)
    pair_src = (
        jnp.full((m_pad,), -1, jnp.int32)
        .at[dest_aligned]
        .set(jnp.arange(m, dtype=jnp.int32), unique_indices=True,
             mode="drop")
    )
    n_tiles = m_pad // block_m
    tile_starts = jnp.arange(n_tiles, dtype=jnp.int32) * block_m
    gid = jnp.minimum(
        jnp.searchsorted(jnp.cumsum(padded), tile_starts, side="right"),
        num_experts - 1,
    ).astype(jnp.int32)
    return AlignedMeta(
        dest_aligned=dest_aligned,
        pair_src=pair_src,
        gid=gid,
        m_pad=m_pad,
    )


def _ffn_kernel(gid_ref, a_ref, probs_ref, wg_ref, wu_ref, wd_ref, out_ref):
    """One aligned tile: out = (silu(A Wg) * (A Wu)) Wd * probs."""
    a = a_ref[...]
    g = jnp.dot(a, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(a, wu_ref[0], preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(g) * u).astype(a.dtype)
    y = jnp.dot(hidden, wd_ref[0], preferred_element_type=jnp.float32)
    out_ref[...] = (y * probs_ref[...]).astype(out_ref.dtype)


_GATHER_UNROLL = 8  # rows gathered per loop trip; block_m % 8 == 0 is gated


def _gather_rows(
    ps_ref, x_ref, probs_ref, a_scr, p_scr, *, block_m: int, top_k: int
):
    """Fill this tile's activation/prob scratch from the resident x/probs
    by the scalar-prefetched ``pair_src`` map; pad rows (pair_src < 0)
    load row 0 and are zeroed. Unrolled by hand: Mosaic lowers a
    ``fori_loop`` only rolled or fully unrolled ("Only unroll=num_steps
    and unroll=1 supported", the v5e compiler on ``unroll=8``)."""
    base = pl.program_id(0) * block_m

    def body(j, _):
        for u in range(_GATHER_UNROLL):
            i = j * _GATHER_UNROLL + u
            src = ps_ref[base + i]
            valid = src >= 0
            src0 = jnp.maximum(src, 0)
            row = x_ref[pl.ds(src0 // top_k, 1), :]
            a_scr[pl.ds(i, 1), :] = jnp.where(valid, row, 0)
            pr = probs_ref[pl.ds(src0, 1), :]
            p_scr[pl.ds(i, 1), :] = jnp.where(valid, pr, 0)
        return 0

    jax.lax.fori_loop(0, block_m // _GATHER_UNROLL, body, 0)


def _ffn_gather_kernel(
    gid_ref, ps_ref, x_ref, probs_ref, wg_ref, wu_ref, wd_ref, out_ref,
    a_scr, p_scr, *, block_m: int, top_k: int,
):
    """Gather-fused tile: rows stream VMEM→VMEM inside the kernel.

    The whole token matrix ``x [N, h]`` (and flat probs ``[M, 1]``) sits
    resident in VMEM (eligibility gates on the fit); each grid step
    gathers its tile's rows by the scalar-prefetched ``pair_src`` map —
    so the aligned activation buffer of the two-step path never exists
    in HBM (that buffer costs a full [m_pad, h] write + read per layer
    pass). Pad rows (pair_src < 0) load row 0 and are zeroed.
    """
    _gather_rows(
        ps_ref, x_ref, probs_ref, a_scr, p_scr, block_m=block_m, top_k=top_k
    )
    a = a_scr[...]
    g = jnp.dot(a, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(a, wu_ref[0], preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(g) * u).astype(a.dtype)
    y = jnp.dot(hidden, wd_ref[0], preferred_element_type=jnp.float32)
    out_ref[...] = (y * p_scr[...]).astype(out_ref.dtype)


def _ffn_gather_combine_kernel(
    gid_ref, ps_ref, x_ref, probs_ref, wg_ref, wu_ref, wd_ref, out_ref,
    a_scr, p_scr, y_scr, *, block_m: int, top_k: int,
):
    """Gather-fused FFN **with the combine folded in**: the kernel's
    output is the token-major combined [N, h] — one ragged gather →
    grouped matmul → K-sum, no expert-sorted y in HBM at all.

    Same VMEM-resident x/probs and in-kernel row gather as
    :func:`_ffn_gather_kernel`; the difference is on the way out. The
    output block is the whole [N, h] array with a constant index map, so
    it stays resident in VMEM across the (sequential) grid and is
    flushed to HBM once: each tile scatters its rows into
    ``out[pair_src[row] // top_k]`` with an in-VMEM read-modify-write —
    the K expert contributions of each token accumulate here instead of
    in an XLA reshape+sum over a pair-gathered copy. Pad rows
    (pair_src < 0) are skipped. The K-sum therefore runs in
    expert-sorted order rather than the XLA path's slot order — same
    numbers up to fp summation order (parity-tested at ulp tolerance).
    """
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    _gather_rows(
        ps_ref, x_ref, probs_ref, a_scr, p_scr, block_m=block_m, top_k=top_k
    )
    a = a_scr[...]
    g = jnp.dot(a, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(a, wu_ref[0], preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(g) * u).astype(a.dtype)
    y = jnp.dot(hidden, wd_ref[0], preferred_element_type=jnp.float32)
    y_scr[...] = (y * p_scr[...]).astype(out_ref.dtype)

    def combine(i, _):
        src = ps_ref[t * block_m + i]
        tok = jnp.maximum(src, 0) // top_k
        row = y_scr[pl.ds(i, 1), :]
        cur = out_ref[pl.ds(tok, 1), :]
        # pad rows write token 0's row back unchanged (+0): branchless
        out_ref[pl.ds(tok, 1), :] = cur + jnp.where(src >= 0, row, 0)
        return 0

    # NOT unrolled: consecutive rows may target the same token, so each
    # read-modify-write must retire before the next row's read
    jax.lax.fori_loop(0, block_m, combine, 0)


def _vmem_bytes_estimate(
    h: int, inter: int, block_m: int, itemsize: int
) -> int:
    """Per-grid-step VMEM bytes the fused kernel needs.

    Pallas double-buffers every streamed input block: three expert weight
    blocks (``2*h*inter`` gate+up plus ``inter*h`` down) dominate; the
    ``[block_m, h]`` activation/output tiles and ``[block_m, 1]`` probs
    ride along. The kernel body additionally holds fp32 gate/up products
    and the hidden tile (``3 * block_m * inter`` fp32, single-buffered).
    """
    weights = 3 * h * inter * itemsize * 2  # double-buffered DMA
    tiles = (2 * block_m * h + block_m) * itemsize * 2
    scratch = 3 * block_m * inter * 4
    return weights + tiles + scratch


def _vmem_budget() -> int:
    """Shared VMEM budget for both eligibility gates. Default: v5e/v4
    VMEM is 128 MiB/core; leave headroom for Mosaic's own staging. Read
    at call time like the file's other env knobs."""
    return int(
        os.environ.get("D9D_TPU_MOE_FFN_VMEM_BUDGET", 96 * 1024 * 1024)
    )


def _compiler_params(interpret: bool):
    """Grant the kernels the budget the eligibility gates promise them:
    Mosaic's default scoped-VMEM limit is 16 MiB, and the plain kernel at
    Qwen3-30B-A3B expert shapes (h2048, i768, block_m 128) needs 20 MiB
    ("Scoped allocation with size 20.00M and limit 16.00M exceeded scoped
    vmem limit", the v5e compiler without this)."""
    if interpret:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=_vmem_budget())


# scalar-prefetch budget for the gather variant's SMEM riders (gid +
# pair_src, int32). TPU scalar memory is far smaller than VMEM and its
# exact capacity is generation/toolchain-dependent — this conservative
# cap routes oversized maps to the two-step path instead of risking a
# Mosaic compile failure (same contract as the VMEM gate).
_SMEM_PREFETCH_BUDGET = 256 * 1024


def _gather_footprint(
    n: int, m: int, h: int, inter: int, block_m: int, itemsize: int
) -> int:
    """VMEM bytes of the gather variant: base kernel footprint + the
    resident x [n, h] / probs [m, 1] blocks (counted double-buffered
    like every other pipelined input — their index map is constant, but
    Pallas still allocates pipeline buffers) + the a/p gather scratch.
    Single source of truth for BOTH eligibility gates."""
    resident = (n * h * itemsize + m * 4) * 2  # double-buffered
    scratch = block_m * h * itemsize + block_m * 4
    return (
        _vmem_bytes_estimate(h, inter, block_m, itemsize)
        + resident + scratch
    )


def _gather_fits(
    n: int, m: int, h: int, inter: int, block_m: int, itemsize: int,
    num_experts: int,
) -> bool:
    """Can the gather variant hold x [n, h] + probs [m, 1] resident in
    VMEM on top of the base kernel footprint (plus its gather scratch),
    and its index maps in scalar memory? Also requires n and m
    sublane-aligned (full-array blocks)."""
    if n % 8 != 0 or m % 8 != 0:
        return False
    # SMEM riders: pair_src [m_pad] + gid [m_pad / block_m], int32
    # (m_pad bound per aligned_metadata: every group pads by < block_m)
    m_pad = (-(-m // block_m) + num_experts) * block_m
    if 4 * (m_pad + m_pad // block_m) > _SMEM_PREFETCH_BUDGET:
        return False
    return _gather_footprint(n, m, h, inter, block_m, itemsize) <= _vmem_budget()


def _combine_fits(
    n: int, m: int, h: int, inter: int, block_m: int, itemsize: int,
    num_experts: int,
) -> bool:
    """Gather-variant residency plus the combine's extra VMEM: the
    whole token-major output [n, h] resident across the grid (counted
    double-buffered like the other full-array blocks) and the
    [block_m, h] y scratch the scatter loop reads back from."""
    if not _gather_fits(n, m, h, inter, block_m, itemsize, num_experts):
        return False
    out_resident = n * h * itemsize * 2
    y_scratch = block_m * h * itemsize
    return (
        _gather_footprint(n, m, h, inter, block_m, itemsize)
        + out_resident + y_scratch
    ) <= _vmem_budget()


def _tpu_shapes_ok(
    h: int, inter: int, block_m: int, itemsize: int = 2
) -> bool:
    """Lane alignment AND VMEM fit — large h/inter geometries would fail
    at Mosaic compile instead of falling back, so estimate
    the footprint and route oversized shapes to the XLA chain
    (budget: :func:`_vmem_budget`)."""
    if not (h % LANES == 0 and inter % LANES == 0 and block_m % 8 == 0):
        return False
    return _vmem_bytes_estimate(h, inter, block_m, itemsize) <= _vmem_budget()


# d9d-lint: disable=D9D001 — standalone-use decorator; MoE layers trace this inside the tracked step programs
@functools.partial(
    jax.jit, static_argnames=("block_m", "interpret")
)
def _fused_ffn_call(
    aligned_x: Array,
    aligned_probs: Array,
    gid: Array,
    gate_w: Array,
    up_w: Array,
    down_w: Array,
    *,
    block_m: int,
    interpret: bool,
) -> Array:
    m_pad, h = aligned_x.shape
    inter = gate_w.shape[-1]
    n_tiles = m_pad // block_m
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # gid rides SMEM, available to index maps
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((block_m, h), lambda t, gid_ref: (t, 0)),
            pl.BlockSpec((block_m, 1), lambda t, gid_ref: (t, 0)),
            pl.BlockSpec((1, h, inter), lambda t, gid_ref: (gid_ref[t], 0, 0)),
            pl.BlockSpec((1, h, inter), lambda t, gid_ref: (gid_ref[t], 0, 0)),
            pl.BlockSpec((1, inter, h), lambda t, gid_ref: (gid_ref[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, h), lambda t, gid_ref: (t, 0)),
    )
    return pl.pallas_call(
        _ffn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, h), aligned_x.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(gid, aligned_x, aligned_probs, gate_w, up_w, down_w)


def _gather_grid_spec(
    x: Array, probs_flat: Array, pair_src: Array, gate_w: Array,
    block_m: int, out_spec: "pl.BlockSpec", extra_scratch: tuple = (),
) -> "pltpu.PrefetchScalarGridSpec":
    """Shared grid/in_specs/scratch of the two gather-variant kernels
    (resident x + probs, per-tile expert weight blocks via the gid SMEM
    rider); only the output spec and any extra scratch differ."""
    n, h = x.shape
    m = probs_flat.shape[0]
    inter = gate_w.shape[-1]
    m_pad = pair_src.shape[0]
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # gid + pair_src ride SMEM
        grid=(m_pad // block_m,),
        in_specs=[
            pl.BlockSpec((n, h), lambda t, gid_ref, ps_ref: (0, 0)),
            pl.BlockSpec((m, 1), lambda t, gid_ref, ps_ref: (0, 0)),
            pl.BlockSpec((1, h, inter),
                         lambda t, gid_ref, ps_ref: (gid_ref[t], 0, 0)),
            pl.BlockSpec((1, h, inter),
                         lambda t, gid_ref, ps_ref: (gid_ref[t], 0, 0)),
            pl.BlockSpec((1, inter, h),
                         lambda t, gid_ref, ps_ref: (gid_ref[t], 0, 0)),
        ],
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((block_m, h), x.dtype),
            pltpu.VMEM((block_m, 1), jnp.float32),
            *extra_scratch,
        ],
    )


# d9d-lint: disable=D9D001 — standalone-use decorator; MoE layers trace this inside the tracked step programs
@functools.partial(
    jax.jit, static_argnames=("block_m", "top_k", "interpret")
)
def _fused_gather_call(
    x: Array,
    probs_flat: Array,
    gid: Array,
    pair_src: Array,
    gate_w: Array,
    up_w: Array,
    down_w: Array,
    *,
    block_m: int,
    top_k: int,
    interpret: bool,
) -> Array:
    """``x [N, h]`` resident + in-kernel row gather → aligned ``[m_pad, h]``
    outputs (same aligned layout as :func:`_fused_ffn_call`)."""
    h = x.shape[1]
    m_pad = pair_src.shape[0]
    grid_spec = _gather_grid_spec(
        x, probs_flat, pair_src, gate_w, block_m,
        out_spec=pl.BlockSpec((block_m, h),
                              lambda t, gid_ref, ps_ref: (t, 0)),
    )
    return pl.pallas_call(
        functools.partial(
            _ffn_gather_kernel, block_m=block_m, top_k=top_k
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, h), x.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(gid, pair_src, x, probs_flat, gate_w, up_w, down_w)


# d9d-lint: disable=D9D001 — standalone-use decorator; MoE layers trace this inside the tracked step programs
@functools.partial(
    jax.jit, static_argnames=("block_m", "top_k", "interpret")
)
def _fused_gather_combine_call(
    x: Array,
    probs_flat: Array,
    gid: Array,
    pair_src: Array,
    gate_w: Array,
    up_w: Array,
    down_w: Array,
    *,
    block_m: int,
    top_k: int,
    interpret: bool,
) -> Array:
    """Gather + FFN + in-kernel combine → token-major ``[N, h]``
    directly (no aligned y buffer, no XLA pair gather / K-sum)."""
    n, h = x.shape
    grid_spec = _gather_grid_spec(
        x, probs_flat, pair_src, gate_w, block_m,
        # constant index map: the [N, h] accumulator stays resident in
        # VMEM across the sequential grid and flushes to HBM once
        out_spec=pl.BlockSpec((n, h), lambda t, gid_ref, ps_ref: (0, 0)),
        extra_scratch=(pltpu.VMEM((block_m, h), x.dtype),),
    )
    return pl.pallas_call(
        functools.partial(
            _ffn_gather_combine_kernel, block_m=block_m, top_k=top_k
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h), x.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(gid, pair_src, x, probs_flat, gate_w, up_w, down_w)


def _reference_apply(x, probs, sort, gate_w, up_w, down_w, dtype):
    """The existing XLA path (permute -> grouped matmuls -> combine);
    single source of truth for the custom_vjp backward AND the fallback.
    Uses the shared env-switched gate+up helper so the
    ``D9D_TPU_MOE_FUSED_GATE_UP`` A/B also covers the fallback and the
    custom_vjp backward under this backend."""
    from d9d_tpu.ops.moe import (
        gate_up_grouped_matmul, permute_tokens, unpermute_combine,
    )

    permuted_x, permuted_probs = permute_tokens(x, probs, sort)
    xx = permuted_x.astype(dtype)
    g, u = gate_up_grouped_matmul(
        xx, gate_w.astype(dtype), up_w.astype(dtype), sort.group_sizes
    )
    hidden = silu_mul(g, u)
    y = grouped_matmul(hidden, down_w.astype(dtype), sort.group_sizes)
    y = y * permuted_probs[:, None].astype(dtype)
    return unpermute_combine(y, sort, x.shape[0]).astype(x.dtype)


def _zero_cotangent(x):
    import numpy as np

    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13))
def fused_moe_ffn(
    x: Array,
    probs: Array,
    gate_w: Array,
    up_w: Array,
    down_w: Array,
    sort_idx: Array,
    dest: Array,
    token_idx: Array,
    group_sizes: Array,
    num_experts: int,
    block_m: int,
    interpret: bool,
    gather: bool,
    combine: bool,
) -> Array:
    """[N, D] tokens + routing -> combined [N, D] expert outputs.

    The TokenSort is passed as four flat arrays (custom_vjp cannot take a
    NamedTuple across the nondiff boundary); int arrays get float0
    cotangents like pallas_flash's segment ids. ``gather`` selects the
    in-kernel row-gather variant (x resident in VMEM; no HBM aligned
    activation buffer); ``combine`` additionally folds the down-side
    combine into the kernel (token-major [N, D] accumulated in VMEM —
    no expert-sorted y in HBM and no XLA pair gather / K-sum).
    """
    out, _ = _fused_fwd(
        x, probs, gate_w, up_w, down_w, sort_idx, dest, token_idx,
        group_sizes, num_experts, block_m, interpret, gather, combine,
    )
    return out


def _fused_fwd(
    x, probs, gate_w, up_w, down_w, sort_idx, dest, token_idx,
    group_sizes, num_experts, block_m, interpret, gather, combine,
):
    sort = TokenSort(sort_idx, dest, token_idx, group_sizes)
    meta = aligned_metadata(sort, num_experts, block_m)
    n, h = x.shape
    k = dest.shape[0] // n
    dtype = gate_w.dtype  # caller pre-casts weights to the compute dtype
    residuals = (x, probs, gate_w, up_w, down_w, sort_idx, dest,
                 token_idx, group_sizes)
    if gather and combine:
        # one kernel end to end: in-kernel row gather AND in-kernel
        # combine — the only HBM traffic for the whole expert FFN is
        # x/probs in (resident loads) and the combined [N, h] out
        out = _fused_gather_combine_call(
            x.astype(dtype),
            probs.reshape(-1, 1).astype(jnp.float32),
            meta.gid, meta.pair_src,
            gate_w, up_w, down_w,
            block_m=block_m, top_k=k, interpret=interpret,
        )
        return out.astype(x.dtype), residuals
    if gather:
        # the kernel gathers rows itself from a VMEM-resident x — no
        # [m_pad, h] aligned buffer in HBM at all (the buffer costs a
        # full write + read per layer pass on the two-step path)
        y_aligned = _fused_gather_call(
            x.astype(dtype),
            probs.reshape(-1, 1).astype(jnp.float32),
            meta.gid, meta.pair_src,
            gate_w, up_w, down_w,
            block_m=block_m, top_k=k, interpret=interpret,
        )
    else:
        # ONE gather fills the aligned activation buffer (pair i owns
        # token i // k); pad rows read token 0 and are zeroed by the
        # mask. Traffic = today's sorted-layout gather PLUS the pad rows
        # (m_pad - m zero rows written and re-read) — the static worst
        # case pads every group by block_m, so keep E*block_m small
        # against M (the block_m eligibility/sweep choices encode this).
        valid = (meta.pair_src >= 0)[:, None]
        token_src = jnp.maximum(meta.pair_src, 0) // k
        aligned_x = jnp.where(
            valid, jnp.take(x, token_src, axis=0), 0
        ).astype(dtype)
        aligned_probs = jnp.where(
            valid,
            jnp.take(
                probs.reshape(-1), jnp.maximum(meta.pair_src, 0)
            )[:, None],
            0,
        ).astype(jnp.float32)
        y_aligned = _fused_ffn_call(
            aligned_x, aligned_probs, meta.gid,
            gate_w, up_w, down_w,
            block_m=block_m, interpret=interpret,
        )
    # combine: collision-free gather by pair then K-sum (ops/moe.py
    # combine_pairs formulation, over the aligned layout)
    pair_y = jnp.take(y_aligned, meta.dest_aligned, axis=0)
    out = pair_y.reshape(n, k, h).sum(axis=1).astype(x.dtype)
    return out, residuals


def _fused_bwd(num_experts, block_m, interpret, gather, combine, residuals,
               d_out):
    (x, probs, gate_w, up_w, down_w, sort_idx, dest, token_idx,
     group_sizes) = residuals
    sort = TokenSort(sort_idx, dest, token_idx, group_sizes)
    dtype = gate_w.dtype

    def ref(x_, probs_, g_, u_, d_):
        return _reference_apply(x_, probs_, sort, g_, u_, d_, dtype)

    _, vjp = jax.vjp(ref, x, probs, gate_w, up_w, down_w)
    dx, dprobs, dg, du, dd = vjp(d_out)
    return (
        dx, dprobs, dg, du, dd,
        _zero_cotangent(sort_idx), _zero_cotangent(dest),
        _zero_cotangent(token_idx), _zero_cotangent(group_sizes),
    )


fused_moe_ffn.defvjp(_fused_fwd, _fused_bwd)


def moe_ffn_backend() -> str:
    """'pallas', 'pallas_gather' or 'xla' — env-selected like the SDPA
    backend family. ``pallas_gather`` additionally fuses the permute
    gather into the kernel (x resident in VMEM; falls back to plain
    ``pallas`` when the residency doesn't fit)."""
    return os.environ.get("D9D_TPU_MOE_FFN", "xla")


def fused_moe_ffn_apply(
    x: Array,
    probs: Array,
    sort: TokenSort,
    gate_w: Array,
    up_w: Array,
    down_w: Array,
    dtype,
    *,
    num_experts: int,
    block_m: int | None = None,
    interpret: bool | None = None,
    gather: bool | None = None,
    combine: bool | None = None,
) -> Array:
    """Entry point for nn/moe.py: fused kernel when eligible, else the
    reference XLA chain (identical math either way). ``gather`` forces
    the in-kernel row-gather variant on/off (None = env-selected via
    ``D9D_TPU_MOE_FFN=pallas_gather``); ``combine`` forces the
    in-kernel combine on/off (None = ``D9D_TPU_MOE_COMBINE``, default
    fused, gather variant only). Either way the VMEM-fit gates can
    veto per shape."""
    from d9d_tpu.ops.moe import fused_combine_enabled

    h = x.shape[-1]
    inter = gate_w.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_m is None:
        block_m = int(os.environ.get("D9D_TPU_MOE_FFN_BLOCK_M", "128"))
    itemsize = jnp.dtype(dtype).itemsize
    if not interpret and not _tpu_shapes_ok(h, inter, block_m, itemsize):
        return _reference_apply(x, probs, sort, gate_w, up_w, down_w, dtype)
    if gather is None:
        gather = moe_ffn_backend() == "pallas_gather"
    gather = gather and _gather_fits(
        x.shape[0], probs.size, h, inter, block_m, itemsize,
        num_experts=num_experts,
    )
    if combine is None:
        combine = fused_combine_enabled()
    combine = gather and combine and _combine_fits(
        x.shape[0], probs.size, h, inter, block_m, itemsize,
        num_experts=num_experts,
    )
    from jax.ad_checkpoint import checkpoint_name

    out = fused_moe_ffn(
        x, probs,
        gate_w.astype(dtype), up_w.astype(dtype), down_w.astype(dtype),
        sort.sort_idx, sort.dest, sort.token_idx, sort.group_sizes,
        num_experts, block_m, interpret, gather, combine,
    )
    # same checkpoint name the XLA chain's grouped dots carry, so the
    # save_expensive remat policy keeps its meaning under this backend
    # (saves the [N, h] layer output — smaller than the XLA chain's
    # [M, 2*inter] — and skips the fused-forward recompute in backward)
    return checkpoint_name(out, "moe_grouped_dot")
