"""Ring attention: context-parallel SDPA over a mesh axis.

Beyond-reference capability (SURVEY.md §2.9: the reference reserves
cp_shard/cp_replicate mesh dims but ships no CP implementation — every
model plan raises). Here CP is first-class: the sequence dim is sharded
over the ``cp_s`` mesh axis and attention runs as a ring
(arXiv 2310.01889 style): each device keeps its query block resident and
the K/V blocks rotate around the ring via ``ppermute`` over ICI, with
online-softmax accumulation — peak memory per device is O(T/cp · T/cp)
per block pair, and the rotation overlaps with the block matmuls under
XLA's async collectives.

Layout: contiguous sequence chunks — device ``i`` of the cp ring owns
positions ``[i·T_loc, (i+1)·T_loc)``. Causal masking across chunks falls
out of global position arithmetic (blocks strictly above the diagonal
contribute zero mass through -inf logits; compute is uniform across steps
so the program stays SPMD-static).

``ring_attention`` must be called *inside* ``shard_map`` (it uses
``axis_index``/``ppermute``); ``make_ring_sdpa`` wraps it into an SDPA
backend usable by the attention blocks under plain jit.
"""

import functools
import os
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from d9d_tpu.core import compat
from d9d_tpu.core.types import Array

_NEG_INF = float("-inf")
_NEG_BIG = -1e30  # finite stand-in: keeps lse arithmetic NaN-free


def _block_logits(q, k, scale):
    """q [B,T,Hkv,G,D] × k [B,S,Hkv,D] → logits [B,Hkv,G,T,S] (fp32)."""
    return jnp.einsum("bthgd,bshd->bhgts", q, k.astype(jnp.float32)) * scale


def _default_impl() -> str:
    return os.environ.get("D9D_TPU_RING_BLOCK", "flash")


def ring_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    axis_name: str,
    causal: bool = True,
    softmax_scale: float | None = None,
    window_size: int | None = None,
    sinks: Array | None = None,
    q_segments: Array | None = None,
    kv_segments: Array | None = None,
    impl: str | None = None,
) -> Array:
    """Per-shard attention: ``q/k/v [B, T_loc, H(q|kv), D]`` → ``[B, T_loc, Hq, D]``.

    Call inside ``shard_map`` with the sequence dim sharded over
    ``axis_name``. Semantics match :func:`eager_sdpa` on the gathered
    sequence (GQA broadcast, causal, sliding window, learnable sinks,
    packed segments). ``q_segments``/``kv_segments`` are this shard's
    ``[B, T_loc]`` slices of the global packed-sequence ids; the kv slice
    rotates around the ring alongside its K/V block and cross-segment
    pairs are masked out of the online softmax.

    ``impl`` selects the per-step block compute: ``"flash"`` (default; the
    Pallas kernel at the ring chunk's global offsets — never materializes
    the [T_loc, S_loc] logits, skips fully-future blocks) or ``"eager"``
    (fp32 einsum oracle, kept for cross-checks; env override
    ``D9D_TPU_RING_BLOCK``).
    """
    if (q_segments is None) != (kv_segments is None):
        raise ValueError("q_segments and kv_segments must be provided together")
    impl = impl or _default_impl()
    if impl == "flash":
        return _ring_flash(
            q, k, v, axis_name=axis_name, causal=causal,
            softmax_scale=softmax_scale, window_size=window_size, sinks=sinks,
            q_segments=q_segments, kv_segments=kv_segments,
        )
    if impl != "eager":
        raise ValueError(f"unknown ring block impl {impl!r}")
    return _ring_eager(
        q, k, v, axis_name=axis_name, causal=causal,
        softmax_scale=softmax_scale, window_size=window_size, sinks=sinks,
        q_segments=q_segments, kv_segments=kv_segments,
    )


def _ring_shape_checks(q, v):
    b, t_loc, hq, d = q.shape
    _, s_loc, hkv, dv = v.shape
    if hq % hkv != 0:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    if t_loc != s_loc:
        raise ValueError("ring attention requires equal q/kv shard lengths")
    return b, t_loc, hq, hkv, d, dv


def _ring_flash(
    q, k, v, *, axis_name, causal, softmax_scale, window_size, sinks,
    q_segments, kv_segments,
):
    """Ring steps through the Pallas flash kernel.

    Each step runs :func:`flash_attention_block` on the resident q chunk
    against the rotating k/v chunk at their true global offsets, then
    merges the normalized partials through a logsumexp combine. The
    [T_loc, S_loc] logit tensor never exists; causal future chunks cost
    only the rotation (the kernel's dynamic skip drops their MXU work).
    """
    from d9d_tpu.ops.attention.pallas_flash import (
        combine_attention_chunks,
        flash_attention_block,
    )

    b, t_loc, hq, hkv, d, dv = _ring_shape_checks(q, v)
    cp = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)

    # ring rotation: device r sends its current kv block to r+1, so after
    # step s device i holds the block originally owned by (i - s) % cp
    perm = [(r, (r + 1) % cp) for r in range(cp)]

    def step(carry, s):
        o, lse, k_blk, v_blk, kseg_blk = carry
        src = (my_idx - s) % cp

        o_blk, lse_blk = flash_attention_block(
            q, k_blk, v_blk,
            q_offset=my_idx * t_loc, k_offset=src * t_loc,
            causal=causal, softmax_scale=softmax_scale,
            window_size=window_size,
            q_segments=q_segments, kv_segments=kseg_blk,
        )
        o, new_lse = combine_attention_chunks(o, lse, o_blk, lse_blk)

        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        if kseg_blk is not None:
            kseg_blk = lax.ppermute(kseg_blk, axis_name, perm)
        return (o, new_lse, k_blk, v_blk, kseg_blk), None

    o0 = jnp.zeros((b, t_loc, hq, dv), jnp.float32)
    lse0 = jnp.full((b, hq, t_loc), _NEG_BIG, jnp.float32)
    (o, lse, _, _, _), _ = lax.scan(
        step, (o0, lse0, k, v, kv_segments), jnp.arange(cp)
    )

    if sinks is not None:
        # sink joins only the global softmax denominator (reference
        # kernel/flash_attn/function.py:34 — autodiff supplies dsink here):
        # o' = o / (1 + exp(sink - lse)).
        z = jnp.clip(sinks.astype(jnp.float32)[None, :, None] - lse, max=60.0)
        inv = (1.0 / (1.0 + jnp.exp(z))).transpose(0, 2, 1)[..., None]
        o = o * inv

    return o.astype(q.dtype)


def _ring_eager(
    q, k, v, *, axis_name, causal, softmax_scale, window_size, sinks,
    q_segments, kv_segments,
):
    """fp32 einsum oracle for the ring step (cross-check / fallback)."""
    b, t_loc, hq, hkv, d, dv = _ring_shape_checks(q, v)
    g = hq // hkv
    scale = softmax_scale if softmax_scale is not None else d**-0.5

    cp = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    q_pos = my_idx * t_loc + jnp.arange(t_loc)  # global positions [T_loc]

    qf = q.astype(jnp.float32).reshape(b, t_loc, hkv, g, d)

    perm = [(r, (r + 1) % cp) for r in range(cp)]

    def step(carry, s):
        o, m, l, k_blk, v_blk, kseg_blk = carry
        src = (my_idx - s) % cp
        k_pos = src * t_loc + jnp.arange(t_loc)

        logits = _block_logits(qf, k_blk, scale)  # [B,Hkv,G,T,S]
        neg = jnp.asarray(_NEG_INF, logits.dtype)
        qp = q_pos[:, None]
        kp = k_pos[None, :]
        if causal:
            logits = jnp.where(kp <= qp, logits, neg)
        if window_size is not None:
            logits = jnp.where(kp > qp - window_size, logits, neg)
        if kseg_blk is not None:
            same = (
                q_segments[:, None, None, :, None]
                == kseg_blk[:, None, None, None, :]
            )
            logits = jnp.where(same, logits, neg)

        blk_max = jnp.max(logits, axis=-1)  # [B,Hkv,G,T]
        new_m = jnp.maximum(m, blk_max)
        # guard fully-masked-so-far rows (m == new_m == -inf)
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - safe_m, _NEG_INF))
        p = jnp.exp(logits - safe_m[..., None])  # rows of -inf -> 0
        blk_o = jnp.einsum("bhgts,bshd->bthgd", p, v_blk.astype(jnp.float32))
        o = o * alpha.transpose(0, 3, 1, 2)[..., None] + blk_o
        l = l * alpha + jnp.sum(p, axis=-1)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        if kseg_blk is not None:
            kseg_blk = lax.ppermute(kseg_blk, axis_name, perm)
        return (o, new_m, l, k_blk, v_blk, kseg_blk), None

    o0 = jnp.zeros((b, t_loc, hkv, g, dv), jnp.float32)
    m0 = jnp.full((b, hkv, g, t_loc), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, t_loc), jnp.float32)
    (o, m, l, _, _, _), _ = lax.scan(
        step, (o0, m0, l0, k, v, kv_segments), jnp.arange(cp)
    )

    if sinks is not None:
        # sink logit joins the global softmax denominator (reference
        # kernel/flash_attn/function.py:34 — autodiff supplies dsink here)
        sink = sinks.astype(jnp.float32).reshape(1, hkv, g, 1)
        new_m = jnp.maximum(m, sink)
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - safe_m, _NEG_INF))
        l = l * alpha + jnp.exp(sink - safe_m)
        o = o * alpha.transpose(0, 3, 1, 2)[..., None]

    lT = l.transpose(0, 3, 1, 2)[..., None]  # [B,T,Hkv,G,1]
    out = o / jnp.maximum(lT, 1e-30)
    return out.reshape(b, t_loc, hq, dv).astype(q.dtype)


def make_ring_sdpa(
    mesh: Mesh,
    *,
    seq_axis: str = "cp_s",
    batch_axes: Sequence[str] = ("dp_r", "dp_s"),
    head_axes: Sequence[str] = ("tp",),
    impl: str | None = None,
):
    """Build an SDPA backend running ring attention over ``seq_axis``.

    The returned callable takes globally-sharded ``[B, T, H, D]`` arrays
    under jit and shard_maps them: batch over ``batch_axes``, sequence over
    ``seq_axis``, heads over ``head_axes`` (TP composes with CP — the ring
    only moves each device's head slice of K/V).
    """
    qkv_spec = P(tuple(batch_axes), seq_axis, tuple(head_axes), None)
    sink_spec = P(tuple(head_axes))
    seg_spec = P(tuple(batch_axes), seq_axis)

    def ring_sdpa(
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
        if mask is not None:
            raise NotImplementedError(
                "ring attention does not support arbitrary masks; use the "
                "eager/flash backends or express the mask as causal+window"
            )
        if (q_segments is None) != (kv_segments is None):
            raise ValueError(
                "q_segments and kv_segments must be provided together"
            )

        # Resolve the mesh at TRACE time: under the pipeline engine each
        # stage jits against its own pp-less submesh, and a shard_map
        # whose mesh disagrees with the context mesh is an error.
        from d9d_tpu.core.mesh import resolve_ambient_mesh

        m = resolve_ambient_mesh(
            (seq_axis, *batch_axes, *head_axes),
            fallback=mesh,
            what="ring attention",
        )

        # validate divisibility up front: without this, a mis-sized input
        # surfaces as an opaque shard_map in_specs error deep in the jit
        # (and the batch stager silently falls back to batch-only sharding
        # for indivisible sequences, guaranteeing the reshard fails here)
        def _size(axes):
            out = 1
            for a in axes:
                out *= m.shape[a]
            return out

        b, t, hq, _ = q.shape
        hkv = k.shape[2]
        cp = _size((seq_axis,))
        tp_h = _size(head_axes)
        dp = _size(batch_axes)
        if t % cp != 0:
            raise ValueError(
                f"ring attention: seq_len {t} not divisible by the "
                f"'{seq_axis}' axis size {cp}"
            )
        if hq % tp_h != 0 or hkv % tp_h != 0:
            raise ValueError(
                f"ring attention: heads (q={hq}, kv={hkv}) not divisible "
                f"by the head axes {tuple(head_axes)} size {tp_h}"
            )
        if b % dp != 0:
            raise ValueError(
                f"ring attention: batch {b} not divisible by the batch "
                f"axes {tuple(batch_axes)} size {dp}"
            )

        # align activations to the ring layout explicitly — otherwise the
        # partitioner resharding into shard_map's fixed in_specs can fall
        # back to replicate-then-repartition around every attention layer
        q, k, v = (lax.with_sharding_constraint(x, qkv_spec) for x in (q, k, v))

        has_sinks = sinks is not None
        has_segs = q_segments is not None
        in_specs = (qkv_spec,) * 3
        args = (q, k, v)
        if has_sinks:
            in_specs += (sink_spec,)
            args += (sinks,)
        if has_segs:
            in_specs += (seg_spec, seg_spec)
            args += (q_segments, kv_segments)

        @functools.partial(
            compat.shard_map,
            mesh=m,
            in_specs=in_specs,
            out_specs=qkv_spec,
            check_vma=False,
        )
        def run(q, k, v, *rest):
            rest = list(rest)
            s = rest.pop(0) if has_sinks else None
            qseg = rest.pop(0) if has_segs else None
            kseg = rest.pop(0) if has_segs else None
            return ring_attention(
                q, k, v, axis_name=seq_axis, causal=causal,
                softmax_scale=softmax_scale, window_size=window_size,
                sinks=s, q_segments=qseg, kv_segments=kseg, impl=impl,
            )

        return run(*args)

    return ring_sdpa
