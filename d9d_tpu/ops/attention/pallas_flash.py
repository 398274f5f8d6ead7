"""Pallas TPU flash attention (forward + backward), with GQA, causal,
sliding-window, learnable attention sinks, and packed-sequence segment ids.

TPU-native replacement for the reference's flash-attn wheel wrapper
(d9d/kernel/flash_attn/function.py:331,384 — FA4/CuTe with sinks, window,
varlen): an online-softmax forward and a two-kernel backward (dq; dk/dv)
with fp32 accumulation in VMEM scratch. Varlen batches map to segment ids
(packed layout), the TPU-friendly equivalent of cu_seqlens.

Public layout is flash-style ``[batch, seq, heads, head_dim]``; internally
tensors run as ``[batch, heads, seq, head_dim]`` so every block puts
(seq, head_dim) in the minor-two positions as the Mosaic tiling rules
require (second-minor %8, minor %128-or-full).

The kv-block grid dim is innermost, so per-(b, h, q-block) running max /
denominator / output accumulators persist in scratch across kv steps (TPU
grids execute sequentially). Block-skipping happens via ``pl.when``
(:func:`_skip_block`): a skipped block costs a grid step and the copy of
its K/V but no MXU work. Under a static window the grid is the window's
*band* (:class:`_Band`): the sequential dimension is as long as the most
blocks any outer block computes, the index maps start each row of it at
the first block in the window's reach, and a visit past the last block is
clamped to it and skipped, so only the bands' corners are visited in vain.
A call with no window visits every pair, the ones above the diagonal
included, and so does one with traced offsets (ring attention), whose
reach is not static. :func:`grid_visits` counts what each kernel's grid
visits and computes, and the wrapper records it.

The sink joins only the softmax denominator, so it is folded in *outside*
the kernel as an elementwise correction on (o, lse); the backward kernels
then see the corrected lse and need no sink plumbing. The analytic dsink
(reference function.py:34) is one XLA reduction over the saved lse.

Falls back to the eager XLA path for explicit boolean masks or
cross-length (decode) attention — those are not training hot paths.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from d9d_tpu.core.types import Array

NEG_BIG = -1e30
LANES = 128


@dataclasses.dataclass(frozen=True)
class _FlashConfig:
    causal: bool
    scale: float
    window: int | None
    has_sinks: bool
    has_segments: bool
    block_q: int
    block_kv: int
    seq_len: int  # real (unpadded) kv length
    interpret: bool
    # When True the kernel takes a leading SMEM int32[2] = [q_offset,
    # k_offset] input and causal/window masking runs on GLOBAL positions
    # (local + offset). This is how ring attention reuses the kernel: each
    # ring step attends a local q chunk against a rotating k/v chunk whose
    # global offsets are device-dependent (traced), so they cannot live in
    # this static config.
    has_positions: bool = False
    # One-pass backward (dq+dk+dv from a single logit recompute) instead
    # of the two-kernel split — see _bwd_fused_kernel. Applied when the
    # dq state fits VMEM (_fused_bwd_fits); sweep via D9D_TPU_FLASH_BWD.
    fused_bwd: bool = False


def _mask_block(s, cfg: _FlashConfig, iq, ik, q_seg, k_seg, qoff=None, koff=None):
    """Apply length / causal / window / segment masking to one [bq, bkv]
    logit block. ``qoff``/``koff`` are traced global-position offsets
    (SMEM scalars) when ``cfg.has_positions``."""
    bq, bkv = s.shape
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    k_loc = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = k_loc < cfg.seq_len  # padding is local regardless of offsets
    k_pos = k_loc
    if qoff is not None:
        q_pos = q_pos + qoff
        k_pos = k_pos + koff
    if cfg.causal:
        mask &= k_pos <= q_pos
    if cfg.window is not None:
        mask &= k_pos > q_pos - cfg.window
    if q_seg is not None:
        mask &= q_seg == k_seg
    return jnp.where(mask, s, NEG_BIG)


def _skip_block(cfg: _FlashConfig, iq, ik, qoff=None, koff=None, clamped=None):
    """True when the whole kv block is masked for the whole q block, or
    the visit is a band's ``clamped`` one (:func:`_band_visit`).

    Static (python bool arithmetic) without offsets; with traced offsets it
    becomes a scalar predicate — ``pl.when`` accepts both, and on TPU the
    dynamic form still skips the MXU work (e.g. every block of a ring step
    whose kv chunk is entirely in the causal future)."""
    q_lo = iq * cfg.block_q
    q_hi = q_lo + cfg.block_q - 1
    k_lo = ik * cfg.block_kv
    k_hi = k_lo + cfg.block_kv - 1
    if qoff is not None:
        q_lo, q_hi = q_lo + qoff, q_hi + qoff
        k_lo, k_hi = k_lo + koff, k_hi + koff
    skip = jnp.asarray(False)
    if cfg.causal:
        skip |= k_lo > q_hi
    if cfg.window is not None:
        skip |= k_hi <= q_lo - cfg.window
    if clamped is not None:
        skip |= clamped
    return skip


def _computes(cfg: _FlashConfig, iq: int, ik: int) -> bool:
    """:func:`_skip_block`'s rule, negated, on plain integers (no offsets)."""
    q_lo, q_hi = iq * cfg.block_q, (iq + 1) * cfg.block_q - 1
    k_lo, k_hi = ik * cfg.block_kv, (ik + 1) * cfg.block_kv - 1
    skip = cfg.causal and k_lo > q_hi
    if cfg.window is not None:
        skip = skip or k_hi <= q_lo - cfg.window
    return not skip


def _at_least_0(x):
    return max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)


@dataclasses.dataclass(frozen=True)
class _Band:
    """What a static window leaves of a kernel's sequential grid dimension:
    outer block ``o`` visits the inner blocks ``first(o) + j`` for ``j`` in
    ``range(reach)``, where ``first(o)`` is the first block that
    :func:`_skip_block` lets compute beside ``o`` and ``reach`` the most
    that compute beside any outer block (the grid stays rectangular). A
    visit at or past ``n``, the number of inner blocks there are, is
    *clamped*: its index map names block ``n - 1`` again, so nothing is
    copied, and the kernel skips it on the unclamped index."""

    step: int  # positions an outer block covers
    back: int  # positions before an outer block's first that it reaches
    block: int  # positions an inner block covers
    n: int
    reach: int

    def first(self, outer):
        """For a plain or a traced ``outer`` (index maps, kernels)."""
        return _at_least_0(outer * self.step - self.back) // self.block


def _band(cfg: _FlashConfig, n_q: int, n_kv: int, *, dkv: bool = False):
    """The band of the forward and dq grids (outer q block, inner kv
    blocks) or, with ``dkv``, of the dk/dv grid (outer kv block, inner q
    blocks); ``None`` where the whole grid is visited: no window, or
    traced offsets (ring attention), whose reach is not static."""
    if cfg.window is None or cfg.has_positions:
        return None
    computes = [
        [_computes(cfg, iq, ik) for ik in range(n_kv)] for iq in range(n_q)
    ]
    if dkv:
        # below the diagonal a kv block's first q block holds its first
        # key; without one every q block from the first may see it
        back = 0 if cfg.causal else n_kv * cfg.block_kv
        reach = max(sum(column) for column in zip(*computes))
        return _Band(cfg.block_kv, back, cfg.block_q, n_q, reach)
    reach = max(sum(row) for row in computes)
    return _Band(cfg.block_q, cfg.window - 1, cfg.block_kv, n_kv, reach)


def _band_visit(band: _Band | None, outer, j):
    """``(inner block, clamped)`` of the ``j``-th sequential step beside
    ``outer``: the step itself on a whole grid; in a band the unclamped
    index, for :func:`_skip_block` and :func:`_mask_block`, and whether the
    visit is a clamped one, which must add nothing."""
    if band is None:
        return j, None
    inner = band.first(outer) + j
    return inner, inner >= band.n


def _inner_block(band: _Band | None):
    """The index-map half of :func:`_band_visit`: ``(outer, j)`` to the
    block the ``j``-th sequential step beside ``outer`` brings in."""
    if band is None:
        return lambda outer, j: j
    return lambda outer, j: jnp.minimum(band.first(outer) + j, band.n - 1)


def grid_visits(
    cfg: _FlashConfig, t: int, s: int, kernel: str = "fwd"
) -> tuple[int, int]:
    """``(visited, computing)`` for one (batch, query head) of a kernel's
    grid over ``t`` queries and ``s`` keys: the (q block, kv block) pairs
    the grid visits and those that :func:`_skip_block` lets compute (its
    rule on plain integers, no offsets). ``kernel`` is ``"fwd"`` (dq has
    the same grid), ``"dkv"`` or ``"fused"`` (the one-pass backward, which
    keeps the whole grid under a window too). A visit that does not
    compute costs a grid step and no MXU work, and outside a band the
    copy of its K/V blocks."""
    n_q = -(-t // cfg.block_q)
    n_kv = -(-s // cfg.block_kv)
    dkv = kernel == "dkv"
    n_outer, n_inner = (n_kv, n_q) if dkv else (n_q, n_kv)
    band = None if kernel == "fused" else _band(cfg, n_q, n_kv, dkv=dkv)
    if band is None:
        visits = [(o, i) for o in range(n_outer) for i in range(n_inner)]
    else:
        visits = [
            (o, band.first(o) + j)
            for o in range(n_outer) for j in range(band.reach)
        ]
    computing = sum(
        i < n_inner and _computes(cfg, *((i, o) if dkv else (o, i)))
        for o, i in visits
    )
    return len(visits), computing


def _note_grid(cfg: _FlashConfig, q: Array, num_kv_heads: int) -> None:
    """Record what one call's grids visit and compute, by kind (``window``
    where the call has one, else ``full``) and pass, as gauges
    ``flash/<kind>/<pass>/blocks_{visited,computed}`` of the process's
    telemetry registry. It runs where the call is traced, on static
    shapes: the gauges hold the last traced call of a kind, and the
    Trainer puts them on a fetched step's span."""
    from d9d_tpu.telemetry import get_telemetry

    b, t, h, d = q.shape
    kind = "full" if cfg.window is None else "window"
    one_pass_bwd = cfg.fused_bwd and _fused_bwd_fits(
        h // num_kv_heads, t + _pad_len(t, cfg.block_q), d, q.dtype.itemsize
    )
    tele = get_telemetry()
    # each kernel's own grid: the forward's, and the backward's dq and
    # dk/dv (one kernel over the whole grid when fused)
    passes = {"fwd": ("fwd",),
              "bwd": ("fused",) if one_pass_bwd else ("fwd", "dkv")}
    for name, kernels in passes.items():
        visited, computing = (
            sum(n) for n in zip(*(grid_visits(cfg, t, t, k) for k in kernels))
        )
        tele.gauge(f"flash/{kind}/{name}/blocks_visited").set(b * h * visited)
        tele.gauge(f"flash/{kind}/{name}/blocks_computed").set(
            b * h * computing
        )


def _read_segs(cfg: _FlashConfig, qseg_ref, kseg_ref):
    if not cfg.has_segments:
        return None, None
    # q segs ride a [B, T, 1] column buffer; kv segs a [B, 1, T] row one —
    # singleton minor/second-minor dims are tiling-legal (block == array dim)
    q_seg = qseg_ref[0, :, :]  # [bq, 1]
    k_seg = kseg_ref[0, :, :]  # [1, bkv]
    return q_seg, k_seg


def _read_offsets(cfg: _FlashConfig, refs):
    """Split off the leading SMEM offsets ref when positions are in use."""
    if not cfg.has_positions:
        return None, None, refs
    offs_ref, *rest = refs
    return offs_ref[0], offs_ref[1], tuple(rest)


def _fwd_kernel(*refs, cfg: _FlashConfig, band: _Band | None):
    qoff, koff, refs = _read_offsets(cfg, refs)
    if cfg.has_segments:
        q_ref, k_ref, v_ref, qseg_ref, kseg_ref = refs[:5]
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[5:]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
        qseg_ref = kseg_ref = None
    iq, step = pl.program_id(2), pl.program_id(3)
    n_steps = pl.num_programs(3)
    ik, clamped = _band_visit(band, iq, step)

    @pl.when(step == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_not(_skip_block(cfg, iq, ik, qoff, koff, clamped)))
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        q_seg, k_seg = _read_segs(cfg, qseg_ref, kseg_ref)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * cfg.scale
        s = _mask_block(s, cfg, iq, ik, q_seg, k_seg, qoff, koff)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_ref[:, :1] + p.sum(axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(step == n_steps - 1)
    def _finalize():
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        o_ref[0, 0, :, :] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype
        )
        lse_ref[0, 0, :, :] = m + jnp.log(jnp.maximum(l, 1e-30))


def _bwd_dq_kernel(*refs, cfg: _FlashConfig, band: _Band | None):
    qoff, koff, refs = _read_offsets(cfg, refs)
    if cfg.has_segments:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref = refs[:8]
        dq_ref, dq_acc = refs[8:]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs
        qseg_ref = kseg_ref = None
    iq, step = pl.program_id(2), pl.program_id(3)
    n_steps = pl.num_programs(3)
    ik, clamped = _band_visit(band, iq, step)

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(jnp.logical_not(_skip_block(cfg, iq, ik, qoff, koff, clamped)))
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :, :]  # [bq, 1]
        delta = delta_ref[0, 0, :, :]  # [bq, 1]
        q_seg, k_seg = _read_segs(cfg, qseg_ref, kseg_ref)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * cfg.scale
        s = _mask_block(s, cfg, iq, ik, q_seg, k_seg, qoff, koff)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * cfg.scale
        dq_acc[:] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(step == n_steps - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    *refs, cfg: _FlashConfig, n_q_blocks: int, band: _Band | None
):
    """``n_q_blocks`` is the q blocks a head visits beside one kv block:
    all of them, or the band's reach."""
    qoff, koff, refs = _read_offsets(cfg, refs)
    if cfg.has_segments:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref = refs[:8]
        dk_ref, dv_ref, dk_acc, dv_acc = refs[8:]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        qseg_ref = kseg_ref = None
    ik, inner = pl.program_id(2), pl.program_id(3)
    n_inner = pl.num_programs(3)
    iq, clamped = _band_visit(band, ik, inner % n_q_blocks)

    @pl.when(inner == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(jnp.logical_not(_skip_block(cfg, iq, ik, qoff, koff, clamped)))
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :, :]
        delta = delta_ref[0, 0, :, :]
        q_seg, k_seg = _read_segs(cfg, qseg_ref, kseg_ref)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * cfg.scale
        s = _mask_block(s, cfg, iq, ik, q_seg, k_seg, qoff, koff)
        p = jnp.exp(s - lse)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * cfg.scale
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(inner == n_inner - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(*refs, cfg: _FlashConfig, n_q_blocks: int):
    """One-pass backward: dq, dk and dv from a single logit recompute.

    Same grid as the dkv kernel — (b, hkv, kv-block, g·q-block) — but the
    [bq, bkv] logit block, its mask and the ds term are computed ONCE per
    (q, kv) pair instead of once in each of the two split kernels (~20%
    of the backward's matmul work saved, plus q/k/v/do read once). The
    price: dq accumulates across the kv grid dim in a full-[g·Tq, d]
    fp32 VMEM scratch and the dq output block stays resident per
    (b, hkv), so this variant is gated on those fitting VMEM
    (_fused_bwd_fits)."""
    qoff, koff, refs = _read_offsets(cfg, refs)
    if cfg.has_segments:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref = refs[:8]
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs[8:]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc) = refs
        qseg_ref = kseg_ref = None
    ik, inner = pl.program_id(2), pl.program_id(3)
    n_kv = pl.num_programs(2)
    n_inner = pl.num_programs(3)
    iq = inner % n_q_blocks
    ig = inner // n_q_blocks

    @pl.when(jnp.logical_and(ik == 0, inner == 0))
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(inner == 0)
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(jnp.logical_not(_skip_block(cfg, iq, ik, qoff, koff)))
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :, :]
        delta = delta_ref[0, 0, :, :]
        q_seg, k_seg = _read_segs(cfg, qseg_ref, kseg_ref)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * cfg.scale
        s = _mask_block(s, cfg, iq, ik, q_seg, k_seg, qoff, koff)
        p = jnp.exp(s - lse)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * cfg.scale
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        row0 = (ig * n_q_blocks + iq) * cfg.block_q
        rows = pl.ds(row0, cfg.block_q)
        dq_acc[rows, :] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32
        )

    @pl.when(inner == n_inner - 1)
    def _finalize_kv():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(ik == n_kv - 1, inner == n_inner - 1))
    def _finalize_q():
        g = n_inner // n_q_blocks
        tq = n_q_blocks * cfg.block_q
        dq_ref[0, :, :, :] = (
            dq_acc[:].reshape(g, tq, dq_ref.shape[-1]).astype(dq_ref.dtype)
        )


def _pad_len(n: int, block: int) -> int:
    return (-n) % block


# VMEM budget for the fused backward's resident dq state (fp32 scratch +
# the revisited output block), leaving room for the streamed q/k/v/do
# blocks in a ~16 MB VMEM
_FUSED_BWD_VMEM_BUDGET = 10 * 1024 * 1024


def _fused_bwd_fits(g: int, tq: int, d: int, out_itemsize: int) -> bool:
    return g * tq * d * (4 + out_itemsize) <= _FUSED_BWD_VMEM_BUDGET


def fused_bwd_applies(
    *, t: int, num_heads: int, num_kv_heads: int, head_dim: int,
    itemsize: int, block_q: int = 1024,
) -> bool:
    """Would ``fused_bwd=True`` actually take the one-pass kernel for this
    shape? The SAME predicate _bwd_call gates on (padded sequence, real
    itemsize) — benches use it to mark rows where the silent fallback to
    the split kernels would otherwise fake an A/B datapoint."""
    block = _clamp_block(block_q, t)
    tq = t + _pad_len(t, block)
    return _fused_bwd_fits(num_heads // num_kv_heads, tq, head_dim, itemsize)


def _env_fused_bwd() -> bool:
    import os

    return os.environ.get("D9D_TPU_FLASH_BWD", "split") == "fused"


def _compiler_params(cfg: _FlashConfig, *, seq_kv: bool = False):
    if cfg.interpret:
        return None
    dims = ("parallel", "parallel",
            "arbitrary" if seq_kv else "parallel", "arbitrary")
    return pltpu.CompilerParams(dimension_semantics=dims)


def _seg_buffers(cfg, q_seg, kv_seg, pad_q, pad_k):
    """Column/row segment-id buffers (padded regions get sentinel ids that
    can never match a real segment or each other)."""
    if not cfg.has_segments:
        return ()
    qs = jnp.pad(q_seg, ((0, 0), (0, pad_q)), constant_values=-1)
    ks = jnp.pad(kv_seg, ((0, 0), (0, pad_k)), constant_values=-2)
    return qs[:, :, None], ks[:, None, :]


def _seg_specs(cfg, block_q_map, block_kv_map):
    if not cfg.has_segments:
        return ()
    return (
        pl.BlockSpec((1, cfg.block_q, 1), block_q_map),
        pl.BlockSpec((1, 1, cfg.block_kv), block_kv_map),
    )


def _to_bhtd(x, pad):
    """[B, T, H, D] → [B, H, T, D] (+ seq padding): blocks must keep
    (seq, head_dim) in the minor-two positions."""
    x = jnp.transpose(x, (0, 2, 1, 3))
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x


def _offs_args(cfg: _FlashConfig, offsets):
    """Leading SMEM input (spec, buffer) when positions are in use."""
    if not cfg.has_positions:
        return (), ()
    return (pl.BlockSpec(memory_space=pltpu.SMEM),), (offsets,)


def _fwd_call(cfg: _FlashConfig, q, k, v, offsets, q_seg, kv_seg):
    """Raw forward kernel invocation: ``(o [B,T,H,D], lse [B,H,T])``,
    no sink correction."""
    b, t, h, d = q.shape
    _, s, hkv, _ = k.shape
    g = h // hkv
    pad_q, pad_k = _pad_len(t, cfg.block_q), _pad_len(s, cfg.block_kv)
    tq, tk = t + pad_q, s + pad_k
    n_q, n_kv = tq // cfg.block_q, tk // cfg.block_kv

    qp, kp, vp = _to_bhtd(q, pad_q), _to_bhtd(k, pad_k), _to_bhtd(v, pad_k)
    offs_specs, offs_bufs = _offs_args(cfg, offsets)

    band = _band(cfg, n_q, n_kv)
    kv_block = _inner_block(band)
    kv_like = pl.BlockSpec(
        (1, 1, cfg.block_kv, d),
        lambda bi, hi, qi, ki, g=g: (bi, hi // g, kv_block(qi, ki), 0),
    )
    grid = (b, h, n_q, n_kv if band is None else band.reach)
    kernel = functools.partial(_fwd_kernel, cfg=cfg, band=band)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            *offs_specs,
            pl.BlockSpec((1, 1, cfg.block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            kv_like,
            kv_like,
            *_seg_specs(
                cfg,
                lambda bi, hi, qi, ki: (bi, qi, 0),
                lambda bi, hi, qi, ki: (bi, 0, kv_block(qi, ki)),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cfg.block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, cfg.block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.block_q, LANES), jnp.float32),
            pltpu.VMEM((cfg.block_q, LANES), jnp.float32),
            pltpu.VMEM((cfg.block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(cfg),
        interpret=cfg.interpret,
    )(*offs_bufs, qp, kp, vp,
      *_seg_buffers(cfg, q_seg, kv_seg, pad_q, pad_k))

    o = o[:, :, :t]
    lse = lse[:, :, :t, 0]  # [B, H, T]
    o = jnp.transpose(o, (0, 2, 1, 3))  # back to [B, T, H, D]
    return o, lse


def _name_kept(o, lse):
    """Name the two residuals only the forward kernel can make.

    Called inside the forward rules, so the variables the backward kernels
    read carry the names themselves: a ``jax.checkpoint`` policy that saves
    ``"sdpa_out"`` and ``"sdpa_lse"`` (every policy of
    ``models/qwen3/dense.py _remat_policy``) keeps them, and the
    rematerialised layer does not run the forward kernel again. A name on a
    copy of the output outside the ``custom_vjp`` saves the copy and the
    kernel runs all the same. Outside a ``jax.checkpoint`` a name is the
    identity."""
    return checkpoint_name(o, "sdpa_out"), checkpoint_name(lse, "sdpa_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _FlashConfig, q, k, v, sinks, q_seg, kv_seg):
    o, _ = _flash_fwd(cfg, q, k, v, sinks, q_seg, kv_seg)
    return o


def _flash_fwd(cfg: _FlashConfig, q, k, v, sinks, q_seg, kv_seg):
    o, lse = _fwd_call(cfg, q, k, v, None, q_seg, kv_seg)
    if cfg.has_sinks:
        # sink joins only the denominator: l' = l + exp(sink - m), so
        # o' = o / (1 + exp(sink - lse)) and lse' = lse + log1p(same).
        z = jnp.clip(sinks.astype(jnp.float32)[None, :, None] - lse, max=60.0)
        corr = jnp.exp(z)  # [B, H, T]
        inv = (1.0 / (1.0 + corr)).transpose(0, 2, 1)[..., None]  # [B,T,H,1]
        o = (o.astype(jnp.float32) * inv).astype(o.dtype)
        lse = lse + jnp.log1p(corr)
    o, lse = _name_kept(o, lse)
    return o, (q, k, v, sinks, q_seg, kv_seg, o, lse)


def _bwd_call(cfg: _FlashConfig, q, k, v, do, lse, delta, offsets, q_seg, kv_seg):
    """Raw backward kernel invocations: ``(dq, dk, dv)`` in [B,T,H,D].

    ``delta`` is the per-row correction the kernels subtract inside
    ``ds = p · (dp − delta) · scale`` — pass ``rowsum(dO⊙O)`` for a plain
    output cotangent, or ``rowsum(dO⊙O) − dlse`` when an lse cotangent is
    in play (∂lse/∂s = p, so it folds into the same term)."""
    b, t, h, d = q.shape
    _, s, hkv, _ = k.shape
    g = h // hkv
    pad_q, pad_k = _pad_len(t, cfg.block_q), _pad_len(s, cfg.block_kv)
    tq, tk = t + pad_q, s + pad_k
    n_q, n_kv = tq // cfg.block_q, tk // cfg.block_kv
    offs_specs, offs_bufs = _offs_args(cfg, offsets)

    def col(x, pad):  # [B, H, T] → padded [B, H, Tq, 1]
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad))) if pad else x
        return x[..., None]

    qp, kp, vp = _to_bhtd(q, pad_q), _to_bhtd(k, pad_k), _to_bhtd(v, pad_k)
    dop = _to_bhtd(do, pad_q)
    lsep, deltap = col(lse, pad_q), col(delta, pad_q)
    segs = _seg_buffers(cfg, q_seg, kv_seg, pad_q, pad_k)

    fused = cfg.fused_bwd and _fused_bwd_fits(g, tq, d, q.dtype.itemsize)
    # grid: (b, hkv, kv-block, g·q-block) — q heads and q blocks share the
    # inner sequential dim so dk/dv accumulate across both; under a band a
    # head's q blocks are the kv block's reach (the fused kernel's resident
    # dq state is laid out over the whole grid, which it keeps)
    q_band = None if fused else _band(cfg, n_q, n_kv, dkv=True)
    n_qv = n_q if q_band is None else q_band.reach
    q_block = _inner_block(q_band)

    def q_gather_map(bi, hi, ki, t_):
        return (bi, hi * g + t_ // n_qv, q_block(ki, t_ % n_qv), 0)

    q_gather = pl.BlockSpec((1, 1, cfg.block_q, d), q_gather_map)
    col_gather = pl.BlockSpec((1, 1, cfg.block_q, 1), q_gather_map)
    kv_self = pl.BlockSpec((1, 1, cfg.block_kv, d),
                           lambda bi, hi, ki, t_: (bi, hi, ki, 0))
    seg_specs_kv = _seg_specs(
        cfg,
        lambda bi, hi, ki, t_: (bi, q_block(ki, t_ % n_qv), 0),
        lambda bi, hi, ki, t_: (bi, 0, ki),
    )

    if fused:
        # dq block (1, g, tq, d) at a fixed index per (b, hkv): stays
        # resident across the whole kv×q sweep while the scratch
        # accumulates, written once at the last step
        dq_out = pl.BlockSpec(
            (1, g, tq, d), lambda bi, hi, ki, t_: (bi, hi, 0, 0)
        )
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, cfg=cfg, n_q_blocks=n_q),
            grid=(b, hkv, n_kv, g * n_q),
            in_specs=[
                *offs_specs,
                q_gather, kv_self, kv_self, q_gather, col_gather,
                col_gather, *seg_specs_kv,
            ],
            out_specs=[dq_out, kv_self, kv_self],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, tq, d), q.dtype),
                jax.ShapeDtypeStruct((b, hkv, tk, d), k.dtype),
                jax.ShapeDtypeStruct((b, hkv, tk, d), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((g * tq, d), jnp.float32),
                pltpu.VMEM((cfg.block_kv, d), jnp.float32),
                pltpu.VMEM((cfg.block_kv, d), jnp.float32),
            ],
            compiler_params=_compiler_params(cfg, seq_kv=True),
            interpret=cfg.interpret,
        )(*offs_bufs, qp, kp, vp, dop, lsep, deltap, *segs)
        dq = jnp.transpose(dq[:, :, :t], (0, 2, 1, 3))
        dk = jnp.transpose(dk[:, :, :s], (0, 2, 1, 3))
        dv = jnp.transpose(dv[:, :, :s], (0, 2, 1, 3))
        return dq, dk, dv


    kv_band = _band(cfg, n_q, n_kv)
    kv_block = _inner_block(kv_band)
    q_like = pl.BlockSpec((1, 1, cfg.block_q, d),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_like = pl.BlockSpec(
        (1, 1, cfg.block_kv, d),
        lambda bi, hi, qi, ki, g=g: (bi, hi // g, kv_block(qi, ki), 0),
    )
    col_like = pl.BlockSpec((1, 1, cfg.block_q, 1),
                            lambda bi, hi, qi, ki: (bi, hi, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, cfg=cfg, band=kv_band),
        grid=(b, h, n_q, n_kv if kv_band is None else kv_band.reach),
        in_specs=[
            *offs_specs,
            q_like, kv_like, kv_like, q_like, col_like, col_like,
            *_seg_specs(
                cfg,
                lambda bi, hi, qi, ki: (bi, qi, 0),
                lambda bi, hi, qi, ki: (bi, 0, kv_block(qi, ki)),
            ),
        ],
        out_specs=q_like,
        out_shape=jax.ShapeDtypeStruct((b, h, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((cfg.block_q, d), jnp.float32)],
        compiler_params=_compiler_params(cfg),
        interpret=cfg.interpret,
    )(*offs_bufs, qp, kp, vp, dop, lsep, deltap, *segs)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, cfg=cfg, n_q_blocks=n_qv, band=q_band
        ),
        grid=(b, hkv, n_kv, g * n_qv),
        in_specs=[
            *offs_specs,
            q_gather, kv_self, kv_self, q_gather, col_gather, col_gather,
            *seg_specs_kv,
        ],
        out_specs=[kv_self, kv_self],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.block_kv, d), jnp.float32),
            pltpu.VMEM((cfg.block_kv, d), jnp.float32),
        ],
        compiler_params=_compiler_params(cfg),
        interpret=cfg.interpret,
    )(*offs_bufs, qp, kp, vp, dop, lsep, deltap, *segs)

    dq = jnp.transpose(dq[:, :, :t], (0, 2, 1, 3))
    dk = jnp.transpose(dk[:, :, :s], (0, 2, 1, 3))
    dv = jnp.transpose(dv[:, :, :s], (0, 2, 1, 3))
    return dq, dk, dv


def _flash_bwd(cfg: _FlashConfig, residuals, do):
    q, k, v, sinks, q_seg, kv_seg, o, lse = residuals

    # Δ = rowsum(dO ⊙ O) per (b, h, t); O was saved by the forward.
    delta = jnp.einsum(
        "bthd,bthd->bht", do.astype(jnp.float32), o.astype(jnp.float32)
    )
    dq, dk, dv = _bwd_call(cfg, q, k, v, do, lse, delta, None, q_seg, kv_seg)

    if cfg.has_sinks:
        # p_sink[b,h,t] = exp(sink_h - lse); dsink = -Σ p_sink · Δ
        p_sink = jnp.exp(
            jnp.clip(sinks.astype(jnp.float32)[None, :, None] - lse, max=60.0)
        )
        dsinks = -(p_sink * delta).sum(axis=(0, 2)).astype(sinks.dtype)
    else:
        dsinks = jnp.zeros_like(sinks)
    return dq, dk, dv, dsinks, _zero_cotangent(q_seg), _zero_cotangent(kv_seg)


def _zero_cotangent(x):
    """Zero cotangent matching JAX's expectations: float0 for int arrays."""
    if x is None:
        return None
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros_like(x)
    import numpy as np

    return np.zeros(x.shape, dtype=jax.dtypes.float0)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_ol(cfg: _FlashConfig, q, k, v, offsets, q_seg, kv_seg):
    """Flash block returning ``(o, lse)`` — the composable form ring
    attention stitches across devices. Differentiable in BOTH outputs:
    the lse cotangent from the downstream combine folds into the existing
    backward kernels through the delta term (see :func:`_bwd_call`)."""
    return _fwd_call(cfg, q, k, v, offsets, q_seg, kv_seg)


def _flash_ol_fwd(cfg: _FlashConfig, q, k, v, offsets, q_seg, kv_seg):
    o, lse = _name_kept(*_fwd_call(cfg, q, k, v, offsets, q_seg, kv_seg))
    return (o, lse), (q, k, v, offsets, q_seg, kv_seg, o, lse)


def _flash_ol_bwd(cfg: _FlashConfig, residuals, cotangents):
    q, k, v, offsets, q_seg, kv_seg, o, lse = residuals
    do, dlse = cotangents
    delta = jnp.einsum(
        "bthd,bthd->bht", do.astype(jnp.float32), o.astype(jnp.float32)
    ) - dlse.astype(jnp.float32)
    dq, dk, dv = _bwd_call(cfg, q, k, v, do, lse, delta, offsets, q_seg, kv_seg)
    return (dq, dk, dv, _zero_cotangent(offsets),
            _zero_cotangent(q_seg), _zero_cotangent(kv_seg))


_flash_ol.defvjp(_flash_ol_fwd, _flash_ol_bwd)


def _clamp_block(block: int, n: int) -> int:
    return min(block, max(8, 2 ** math.ceil(math.log2(max(n, 1)))))


def _window_blocks(window: int) -> tuple[int, int]:
    """The largest ``(block_q, block_kv)`` a call under ``window`` takes.

    A block pair computes whole, so blocks longer than the window fill the
    band with masked-out pairs (2.93 times the window's own at 1,024 x 512
    under 512, 2.0 at 512 x 512); but a block's cost hardly falls with the
    pairs it holds under 512 x 512 (15 such blocks a head cost the same
    under windows of 128, 256 and 512, and 31 of 256 x 256 cost more), so
    the blocks follow the window down to 512 and no further: half the
    window rounded up to a power of two, at least 512. Swept on the chip
    at 4 x 4,096, 64 heads on 8 of 128, windows of 128 to 1,024 (PERF.md
    section 6, PR 45)."""
    block = max(512, 2 ** math.ceil(math.log2(window)) // 2)
    return block, block


def combine_attention_chunks(
    o: Array, lse: Array, o_new: Array, lse_new: Array
) -> tuple[Array, Array]:
    """Merge two normalized partial attention results ``(o [B,T,H,D],
    lse [B,H,T])`` over disjoint key sets into one — the logsumexp combine
    every :func:`flash_attention_block` consumer (ring steps, chunked
    simulations) must apply. Accumulates in fp32."""
    merged = jnp.logaddexp(lse, lse_new)
    w0 = jnp.exp(lse - merged).transpose(0, 2, 1)[..., None]
    w1 = jnp.exp(lse_new - merged).transpose(0, 2, 1)[..., None]
    out = o.astype(jnp.float32) * w0 + o_new.astype(jnp.float32) * w1
    return out, merged


def flash_attention_block(
    q: Array,
    k: Array,
    v: Array,
    *,
    q_offset: Array | int,
    k_offset: Array | int,
    causal: bool = True,
    softmax_scale: float | None = None,
    window_size: int | None = None,
    q_segments: Array | None = None,
    kv_segments: Array | None = None,
    block_q: int = 1024,
    block_kv: int = 512,
    interpret: bool | None = None,
    fused_bwd: bool | None = None,
) -> tuple[Array, Array]:
    """One flash-attention block at arbitrary global offsets → ``(o, lse)``.

    ``q [B,T,Hq,D]`` attends ``k/v [B,S,Hkv,D]`` as if the q rows sat at
    global positions ``q_offset + [0,T)`` and the keys at
    ``k_offset + [0,S)`` (offsets may be traced, e.g. derived from
    ``lax.axis_index`` inside shard_map). Causal/window masking uses those
    global positions; blocks wholly outside them are skipped on the fly.
    Rows with no visible key come back as ``o=garbage, lse≈-1e30`` — a
    downstream logsumexp-combine weighs them to zero.

    This is the per-ring-step primitive: combine partial results with
    ``new_lse = logaddexp(lse_a, lse_b)`` and
    ``o = exp(lse_a-new_lse)·o_a + exp(lse_b-new_lse)·o_b``. Both outputs
    are differentiable (reference treats attention as always-flash —
    d9d/kernel/flash_attn/function.py:331; this brings the CP ring to the
    same bar).
    """
    t, s, d = q.shape[1], k.shape[1], q.shape[-1]
    if (q_segments is None) != (kv_segments is None):
        raise ValueError("q_segments and kv_segments must be provided together")
    if fused_bwd is None:
        fused_bwd = _env_fused_bwd()
    cfg = _FlashConfig(
        causal=causal,
        scale=softmax_scale if softmax_scale is not None else d**-0.5,
        window=window_size,
        has_sinks=False,
        has_segments=q_segments is not None,
        block_q=_clamp_block(block_q, t),
        block_kv=_clamp_block(block_kv, s),
        seq_len=s,
        interpret=(jax.default_backend() != "tpu"
                   if interpret is None else interpret),
        has_positions=True,
        fused_bwd=fused_bwd,
    )
    offsets = jnp.stack(
        [jnp.asarray(q_offset, jnp.int32), jnp.asarray(k_offset, jnp.int32)]
    )
    return _flash_ol(cfg, q, k, v, offsets, q_segments, kv_segments)


def _shard_over_mesh(fn, mesh, batch_axes, head_axes, *, has_segments):
    """Run ``fn(q, k, v, sinks, q_seg, kv_seg)`` per shard of ``mesh``:
    batch over ``batch_axes``, heads over ``head_axes``, sequence whole.

    Mosaic kernels cannot be partitioned by the SPMD partitioner (the TPU
    lowering raises "Mosaic kernels cannot be automatically partitioned"
    as soon as the mesh has more than one device), so under FSDP/TP the
    kernel has to name its own partitioning. GQA grouping survives the
    head split because q and kv heads shard in the same contiguous order.
    """
    from jax.sharding import PartitionSpec as P

    qkv = P(batch_axes or None, None, head_axes or None, None)
    seg = P(batch_axes or None, None)
    in_specs = (qkv, qkv, qkv, P(head_axes or None))
    if has_segments:
        in_specs += (seg, seg)

    def per_shard(q, k, v, sinks, *segs):
        return fn(q, k, v, sinks, *(segs or (None, None)))

    run = jax.shard_map(
        per_shard, mesh=mesh, in_specs=in_specs, out_specs=qkv,
        check_vma=False,
    )
    return lambda q, k, v, sinks, q_seg, kv_seg: run(
        q, k, v, sinks, *((q_seg, kv_seg) if has_segments else ())
    )


def make_pallas_flash_sdpa(
    block_q: int = 1024,
    block_kv: int = 512,
    fused_bwd: bool | None = None,
    batch_axes: tuple[str, ...] = (),
    head_axes: tuple[str, ...] = (),
):
    """Build an SdpaBackend backed by the Pallas flash kernel.

    ``batch_axes`` / ``head_axes`` name the mesh axes the batch and head
    dims are split over. Where the ambient mesh gives any of them more
    than one device, the kernel runs inside a ``shard_map`` over them
    (:func:`_shard_over_mesh`); on a one-device mesh, or with no axes
    named, it is called directly.

    Default block sizes 1024x512 come from a toy-width sweep (t=2048/8192
    d=64, t=4096 d=128); no cell has re-swept them for a call with no
    window, and ``kernel.flash_train_roofline`` reads 26 to 34 % (ledger,
    PR 24; ROADMAP S9). Under a window a call takes at most
    :func:`_window_blocks` of it (swept on the chip at PR 45), and blocks
    are clamped to the padded sequence length below, so small inputs are
    unaffected.

    ``fused_bwd`` selects the one-pass backward (dq+dk+dv from a single
    logit recompute, ~20% fewer backward matmul FLOPs at the cost of a
    resident dq VMEM state — see :func:`_bwd_fused_kernel`). ``None``
    reads ``D9D_TPU_FLASH_BWD`` (``fused``/``split``); default split, the
    r3-measured configuration, until the fused variant is swept on chip.
    """
    if fused_bwd is None:
        fused_bwd = _env_fused_bwd()

    def sdpa(
        q: Array,
        k: Array,
        v: Array,
        *,
        causal: bool = True,
        softmax_scale: float | None = None,
        window_size: int | None = None,
        sinks: Array | None = None,
        mask: Array | None = None,
        q_segments: Array | None = None,
        kv_segments: Array | None = None,
    ) -> Array:
        if mask is not None or q.shape[1] != k.shape[1]:
            from d9d_tpu.ops.attention.eager import eager_sdpa

            return eager_sdpa(
                q, k, v, causal=causal, softmax_scale=softmax_scale,
                window_size=window_size, sinks=sinks, mask=mask,
                q_segments=q_segments, kv_segments=kv_segments,
            )
        if (q_segments is None) != (kv_segments is None):
            raise ValueError(
                "q_segments and kv_segments must be provided together"
            )
        t = q.shape[1]
        d = q.shape[-1]
        bq, bkv = block_q, block_kv
        if window_size is not None:
            cap_q, cap_kv = _window_blocks(window_size)
            bq, bkv = min(bq, cap_q), min(bkv, cap_kv)
        cfg = _FlashConfig(
            causal=causal,
            scale=softmax_scale if softmax_scale is not None else d**-0.5,
            window=window_size,
            has_sinks=sinks is not None,
            has_segments=q_segments is not None,
            block_q=_clamp_block(bq, t),
            block_kv=_clamp_block(bkv, t),
            seq_len=t,
            interpret=jax.default_backend() != "tpu",
            fused_bwd=fused_bwd,
        )
        _note_grid(cfg, q, k.shape[2])
        sinks_arr = (
            sinks if sinks is not None else jnp.zeros((q.shape[2],), jnp.float32)
        )
        call = functools.partial(_flash, cfg)
        mesh = jax.sharding.get_abstract_mesh()
        b_axes = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
        h_axes = tuple(a for a in head_axes if mesh.shape.get(a, 1) > 1)
        if b_axes or h_axes:
            call = _shard_over_mesh(
                call, mesh, b_axes, h_axes,
                has_segments=q_segments is not None,
            )
        return call(q, k, v, sinks_arr, q_segments, kv_segments)

    return sdpa
