"""Expert-parallel token dispatch/combine over ``jax.lax.ragged_all_to_all``.

TPU-native replacement for DeepEP's NVSHMEM all-to-all buffer (reference
d9d/module/block/moe/communications/deepep.py:55-150): tokens travel to the
shard that owns their expert, compute runs only on owned assignments, and
results ride a mirrored ragged all-to-all home. Per-shard grouped-GEMM row
count is the static receive buffer size: ``capacity_factor × N_global·k/ep``
with a capacity factor set (the compute scaling the all-gather flow lacked),
or the dropless worst case ``N_global·k`` with ``capacity_factor=None``
(exact results; only the communication is reduced to the ragged rows).

Flow inside one ``shard_map`` shard over the ep axes (W shards, each
owning ``e_loc = E/W`` experts):

1. sort this shard's ``m = n·k`` assignment rows by global expert id —
   rows become contiguous per destination shard;
2. all-gather the tiny per-expert count vector → the full [W, E] count
   matrix ``S``, from which *every* shard derives identical send/recv
   sizes, offsets, and (under capacity) identical deterministic clamping;
3. ragged all-to-all the hidden rows (only real rows move);
4. re-sort received rows by local expert (they arrive grouped by source),
   grouped-GEMM through this shard's experts;
5. inverse-permute and ragged all-to-all the results back;
6. owner side: weight by router probs and scatter-add per token.

Differentiable end to end: ``ragged_all_to_all`` carries JVP/transpose
rules, so the backward re-crosses the network exactly like DeepEP's
dispatch/combine backward pair (deepep.py:91-150). Capacity overflow drops
the tail rows of a (source, destination) slice deterministically; dropped
assignments contribute exactly zero (their return slot is never written),
matching capacity-style MoE semantics. ``capacity_factor=None`` is
dropless with a ``m·W``-row buffer.
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from d9d_tpu.core.types import Array
from d9d_tpu.ops.moe import combine_pairs, stable_expert_order

__all__ = ["ep_buffer_rows", "ep_dispatch_compute_combine"]


def _ragged_a2a(
    operand, output, in_off, send_sz, out_off, recv_sz, *, ep_axes, ep_world
):
    """``lax.ragged_all_to_all`` on TPU; exact-semantics emulation elsewhere.

    XLA:CPU has no ragged-all-to-all lowering, but the CPU mesh is the test
    rig — so emulate with an all-gather plus index reconstruction: for each
    output row, find the (sender, source-row) pair whose declared slice
    covers it. Slices are disjoint in this module's usage. Differentiable
    (gather-based), so backward tests exercise the same routing math.
    """
    if jax.default_backend() == "tpu":
        return lax.ragged_all_to_all(
            operand, output, in_off, send_sz, out_off, recv_sz,
            axis_name=ep_axes,
        )
    me = lax.axis_index(ep_axes)
    ops = lax.all_gather(operand, ep_axes, axis=0)  # [W, rows, D]
    in_offs = lax.all_gather(in_off, ep_axes, axis=0)  # [W, W]
    send_szs = lax.all_gather(send_sz, ep_axes, axis=0)
    out_offs = lax.all_gather(out_off, ep_axes, axis=0)

    p = jnp.arange(output.shape[0])
    starts = out_offs[:, me]  # where sender s's slice lands here
    sizes = send_szs[:, me]
    srcs_at = in_offs[:, me]
    hit = (p[:, None] >= starts[None, :]) & (
        p[:, None] < (starts + sizes)[None, :]
    )  # [rows_out, W]
    any_hit = hit.any(axis=1)
    s_of = jnp.argmax(hit, axis=1)
    row_of = jnp.take(srcs_at, s_of) + p - jnp.take(starts, s_of)
    row_of = jnp.clip(row_of, 0, operand.shape[0] - 1)
    picked = ops[s_of, row_of]
    return jnp.where(any_hit[:, None], picked, output)


def ep_buffer_rows(
    rows_per_shard: int, ep_world: int, capacity_factor: Optional[float]
) -> int:
    """Static receive-buffer row count (the per-shard grouped-GEMM size)."""
    if capacity_factor is None:
        return rows_per_shard * ep_world  # dropless worst case
    # round up to a sublane multiple for friendly tiling
    return ((math.ceil(rows_per_shard * capacity_factor) + 7) // 8) * 8


def _excl_cumsum(x: Array, axis: int = 0) -> Array:
    return jnp.cumsum(x, axis=axis) - x


def ep_dispatch_compute_combine(
    x_loc: Array,
    ids_loc: Array,
    probs_loc: Array,
    expert_fn,
    *,
    ep_axes: tuple[str, ...],
    e_loc: int,
    ep_world: int,
    capacity_factor: Optional[float],
) -> Array:
    """Inside-shard_map body: route rows to expert owners, compute, return.

    ``expert_fn(rows [M, D], group_sizes [e_loc]) -> [M, D]`` runs this
    shard's experts over expert-sorted rows (probabilities are applied on
    the owner side, after the results come home).
    """
    n, k = ids_loc.shape
    m = n * k
    d_model = x_loc.shape[-1]
    me = lax.axis_index(ep_axes)

    # 1. group assignment rows by global expert id (sort-free stable
    # permutation — see ops/moe.py stable_expert_order; TPU sorts are
    # bitonic and this runs per MoE layer per microbatch)
    ids_flat = ids_loc.reshape(-1)
    with jax.named_scope("moe/permute"):
        order, pair_dest, counts = stable_expert_order(
            ids_flat, e_loc * ep_world
        )
        token_of = order // k
        x_rows = jnp.take(x_loc, token_of, axis=0)  # [m, D]

    # 2. tiny count exchange: S[s, e] = rows shard s routes to expert e
    S = lax.all_gather(counts, ep_axes, axis=0)  # [W, E]
    # rows shard s sends to shard d
    R = S.reshape(ep_world, ep_world, e_loc).sum(axis=-1)  # [W(src), W(dst)]

    buf_rows = ep_buffer_rows(m, ep_world, capacity_factor)
    if capacity_factor is None:
        A = R
    else:
        # deterministic clamp, identical on every shard: earlier sources
        # keep their rows, the tail of a receiver's intake is cut
        room = jnp.maximum(buf_rows - _excl_cumsum(R, axis=0), 0)
        A = jnp.minimum(R, room)

    send_sizes = A[me]  # [W] rows I send to each dst
    input_offsets = _excl_cumsum(R[me])  # my sorted rows: blocks sized R[me]
    recv_sizes = A[:, me]  # [W] rows I receive from each src
    recv_offsets = _excl_cumsum(recv_sizes)
    output_offsets = _excl_cumsum(A, axis=0)[me]  # where my slice lands at dst

    # 3. dispatch hidden rows
    recv_buf = jnp.zeros((buf_rows, d_model), x_rows.dtype)
    with jax.named_scope("ep/dispatch_a2a"):
        recv = _ragged_a2a(
            x_rows,
            recv_buf,
            input_offsets.astype(jnp.int32),
            send_sizes.astype(jnp.int32),
            output_offsets.astype(jnp.int32),
            recv_sizes.astype(jnp.int32),
            ep_axes=ep_axes,
            ep_world=ep_world,
        )

    # 4. label received rows with their local expert. A source's slice is
    # expert-sorted; capacity cuts its tail. kcnt[s, e] = kept rows of
    # (src s, my local expert e).
    my_counts = lax.dynamic_slice_in_dim(
        S, me * e_loc, e_loc, axis=1
    )  # [W, e_loc]
    kcnt = jnp.clip(
        recv_sizes[:, None] - _excl_cumsum(my_counts, axis=1),
        0,
        my_counts,
    )
    row_pos = jnp.arange(buf_rows)
    src_of = jnp.searchsorted(
        jnp.cumsum(recv_sizes), row_pos, side="right"
    ).clip(0, ep_world - 1)
    q = row_pos - jnp.take(recv_offsets, src_of)
    incl = jnp.cumsum(kcnt, axis=1)  # [W, e_loc]
    labels = (q[:, None] >= jnp.take(incl, src_of, axis=0)).sum(axis=1)
    labels = jnp.clip(labels, 0, e_loc - 1)  # padding rows → last group

    with jax.named_scope("moe/permute"):
        by_expert, dest, group_sizes = stable_expert_order(labels, e_loc)
        rows_sorted = jnp.take(recv, by_expert, axis=0)

    with jax.named_scope("ep/expert_compute"):
        y_sorted = expert_fn(rows_sorted, group_sizes)
    # un-sort via the inverse permutation as a gather (dest[by_expert[r]]
    # == r) — cheaper than a zeros+scatter on TPU, same as ops/moe.py's
    # unpermute_combine
    with jax.named_scope("moe/combine"):
        y_buf = jnp.take(y_sorted, dest, axis=0)

    # 5. mirrored return trip (swap send/recv roles). My slice for source s
    # must land where s's sorted rows for me begin: s's own block layout.
    return_offsets = _excl_cumsum(R, axis=1)[:, me]
    with jax.named_scope("ep/combine_a2a"):
        home = _ragged_a2a(
            y_buf,
            jnp.zeros((m, d_model), y_buf.dtype),
            recv_offsets.astype(jnp.int32),
            recv_sizes.astype(jnp.int32),
            return_offsets.astype(jnp.int32),
            send_sizes.astype(jnp.int32),
            ep_axes=ep_axes,
            ep_world=ep_world,
        )

    # 6. weight by router probs, fold the k assignments per token
    # (collision-free gather form — see ops/moe.py combine_pairs)
    with jax.named_scope("moe/combine"):
        probs_rows = jnp.take(probs_loc.reshape(-1), order)
        weighted = home * probs_rows[:, None].astype(home.dtype)
        return combine_pairs(weighted, pair_dest, n)
