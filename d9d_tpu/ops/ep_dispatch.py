"""Expert-parallel token dispatch/combine over ``jax.lax.ragged_all_to_all``.

TPU-native replacement for DeepEP's NVSHMEM all-to-all buffer (reference
d9d/module/block/moe/communications/deepep.py:55-150): tokens travel to the
shard that owns their expert, compute runs only on owned assignments, and
results ride a mirrored ragged all-to-all home. Per-shard grouped-GEMM row
count is the static receive buffer size. With a capacity factor set it is
``capacity_factor × N_global·k/ep`` and overflow is dropped. Dropless
(``capacity_factor=None``) it is one rung of a short fixed ladder
(:func:`ep_buffer_ladder`: near ``1.25·m``, from eight shards one between,
``m·W``), chosen at run time per call from the counts the shards have
exchanged anyway: the smallest rung that holds the largest intake of any
shard. The worst case ``N_global·k`` is only the fallback, so exact results
cost the compute and traffic of the rows that arrive plus a quarter, until
routing is so uneven that one shard takes more than the snug rung holds;
memory is still claimed for the last rung.

Flow inside one ``shard_map`` shard over the ep axes (W shards, each
owning ``e_loc = E/W`` experts):

1. sort this shard's ``m = n·k`` assignment rows by global expert id —
   rows become contiguous per destination shard;
2. all-gather the tiny per-expert count vector → the full [W, E] count
   matrix ``S``, from which *every* shard derives identical send/recv
   sizes, offsets, the rung to run and (under capacity) identical
   deterministic clamping;
3. ragged all-to-all the hidden rows (only real rows move);
4. re-sort received rows by local expert (they arrive grouped by source),
   grouped-GEMM through this shard's experts;
5. inverse-permute and ragged all-to-all the results back, weight them
   by the router probs;
6. owner side: fold the k rows per token.

Steps 3 to 5 are :func:`_exchange`; dropless, they run under a
``lax.switch`` over the ladder (:func:`_laddered_exchange`). Every shard of
a group takes the same branch, so the collectives inside stay matched.

Differentiable end to end: ``ragged_all_to_all`` carries JVP/transpose
rules, so the backward re-crosses the network exactly like DeepEP's
dispatch/combine backward pair (deepep.py:91-150); the laddered exchange
has its own VJP that makes the same choice again in the backward. Rows
move only through ``ops/moe.py``'s ``permute_rows``, ``spread_to_pairs``
and ``combine_pairs``, whose transposes are gathers by the inverse
permutation ``stable_expert_order`` returns beside each index (over all
``buf_rows`` rows, padding included): the backward of steps 1, 4, 5 and 6
gathers as the forward does and holds no scatter-add of hidden-width rows.
Capacity overflow drops the tail rows of a (source, destination) slice
deterministically; dropped assignments contribute exactly zero (their
return slot is never written), matching capacity-style MoE semantics.
"""

import functools
import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from d9d_tpu.core.types import Array
from d9d_tpu.ops.moe import (
    combine_pairs,
    permute_rows,
    spread_to_pairs,
    stable_expert_order,
)

__all__ = [
    "EpBufferUse",
    "ep_buffer_ladder",
    "ep_buffer_rows",
    "ep_dispatch_compute_combine",
]


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
    """Static receive-buffer row count (the per-shard grouped-GEMM size).

    ``capacity_factor=None`` gives the dropless worst case ``m·W``: the
    last rung of :func:`ep_buffer_ladder`, the only one that always fits.
    """
    if capacity_factor is None:
        return rows_per_shard * ep_world  # dropless worst case
    # round up to a sublane multiple for friendly tiling
    return ((math.ceil(rows_per_shard * capacity_factor) + 7) // 8) * 8


# the snug rung's headroom over a perfectly even split of the group's rows
_SNUG_FACTOR = 1.25


def ep_buffer_ladder(rows_per_shard: int, ep_world: int) -> tuple[int, ...]:
    """Ascending receive-buffer sizes the dropless path chooses from.

    A snug rung near ``1.25·m`` and the worst case ``m·W``, which any
    routing fits; from ``W = 8``, where the two are more than six times
    apart, one rung between at their geometric mean. Every rung is traced,
    lowered and loaded, forward and backward, in every MoE layer (about
    a second of set-up a rung for four layers on the v5e host), so there
    are at most three. ``W = 1`` has one rung.
    """
    full = ep_buffer_rows(rows_per_shard, ep_world, None)
    factors = [_SNUG_FACTOR]
    if ep_world >= 8:
        factors.append(math.sqrt(_SNUG_FACTOR * ep_world))
    rungs = {ep_buffer_rows(rows_per_shard, ep_world, f) for f in factors}
    return (*sorted(r for r in rungs if r < full), full)


class EpBufferUse(NamedTuple):
    """What one dispatch took and needed; identical on every shard of the
    EP group (both derive from the all-gathered count matrix)."""

    rows_taken: Array  # int32 []: receive-buffer rows this call ran with
    rows_needed: Array  # int32 []: the largest intake of any shard
    fell_back: Array  # int32 []: 1 when a ladder's last rung had to run


def _excl_cumsum(x: Array, axis: int = 0) -> Array:
    return jnp.cumsum(x, axis=axis) - x


class _Route(NamedTuple):
    """The static half of one exchange (hashable: a ``custom_vjp``
    non-differentiable argument)."""

    expert_fn: Callable
    ep_axes: tuple[str, ...]
    e_loc: int
    ep_world: int


def _exchange(
    route: _Route, buf_rows: int, x_rows: Array, probs_rows: Array,
    expert_weights, S: Array,
) -> Array:
    """Steps 3 to 5 of the module docstring through a ``buf_rows``-row
    receive buffer: ``x_rows [m, D]`` (sorted by global expert) → the
    experts' outputs for the same rows, weighted by ``probs_rows [m]``.

    A receiver's intake beyond ``buf_rows`` is cut deterministically,
    identically on every shard: earlier sources keep their rows. With
    ``buf_rows`` at or above the group's largest intake nothing is cut.
    """
    expert_fn, ep_axes, e_loc, ep_world = route
    m, d_model = x_rows.shape
    me = lax.axis_index(ep_axes)
    # rows shard s sends to shard d
    R = S.reshape(ep_world, ep_world, e_loc).sum(axis=-1)  # [W(src), W(dst)]
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
        rows_sorted = permute_rows(recv, by_expert, dest)

    with jax.named_scope("ep/expert_compute"):
        y_sorted = expert_fn(rows_sorted, group_sizes, *expert_weights)
    # un-sort via the inverse permutation as a gather (dest[by_expert[r]]
    # == r) — cheaper than a zeros+scatter on TPU, same as ops/moe.py's
    # unpermute_combine
    with jax.named_scope("moe/combine"):
        y_buf = permute_rows(y_sorted, dest, by_expert)

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
    with jax.named_scope("moe/combine"):
        return home * probs_rows[:, None].astype(home.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _laddered_exchange(
    route: _Route, ladder: tuple[int, ...], rung: Array,
    x_rows: Array, probs_rows: Array, expert_weights, S: Array,
) -> Array:
    """:func:`_exchange` through rung ``rung`` of ``ladder``.

    ``rung`` is the same on every shard of the group, so the ragged
    all-to-alls inside the taken branch stay matched. The VJP is its own
    rule because differentiating through ``lax.switch`` makes every
    branch return the union of all branches' residuals, the others' as
    zeros: the snug branch would still allocate and zero worst-case-sized
    residuals. Here the forward keeps only its inputs and the backward
    makes the choice again, each branch differentiating its own exchange.
    Nothing after the exchange needs its output to differentiate (the
    probabilities are applied inside, the fold per token is linear), so
    under a layer's remat the recomputed forward is dead code and the
    backward's own recomputation is the one the remat would have done.
    """
    return lax.switch(
        rung,
        [functools.partial(_exchange, route, rows) for rows in ladder],
        x_rows, probs_rows, expert_weights, S,
    )


def _laddered_fwd(route, ladder, rung, *operands):
    out = _laddered_exchange(route, ladder, rung, *operands)
    return out, (rung, *operands)


def _laddered_bwd(route, ladder, residuals, g):
    rung, x_rows, probs_rows, expert_weights, S = residuals

    def pull_back(rows):
        def branch(x_rows, probs_rows, expert_weights, S, g):
            _, vjp = jax.vjp(
                lambda x, p, w: _exchange(route, rows, x, p, w, S),
                x_rows, probs_rows, expert_weights,
            )
            return vjp(g)

        return branch

    d_rows, d_probs, d_weights = lax.switch(
        rung, [pull_back(rows) for rows in ladder],
        x_rows, probs_rows, expert_weights, S, g,
    )
    return None, d_rows, d_probs, d_weights, None


_laddered_exchange.defvjp(_laddered_fwd, _laddered_bwd)


def ep_dispatch_compute_combine(
    x_loc: Array,
    ids_loc: Array,
    probs_loc: Array,
    expert_fn,
    expert_weights: tuple = (),
    *,
    ep_axes: tuple[str, ...],
    e_loc: int,
    ep_world: int,
    capacity_factor: Optional[float],
) -> tuple[Array, EpBufferUse]:
    """Inside-shard_map body: route rows to expert owners, compute, return.

    ``expert_fn(rows [M, D], group_sizes [e_loc], *expert_weights) ->
    [M, D]`` runs this shard's experts over expert-sorted rows
    (probabilities are applied on the owner side, after the results come
    home). Weights that take gradients are passed through
    ``expert_weights``, not closed over: the dropless exchange is a
    ``custom_vjp``. ``M`` is the receive buffer's row count: one static
    size under a capacity factor, one rung of :func:`ep_buffer_ladder`
    per call when dropless.

    Returns the combined rows ``[n, D]`` and the buffer's use.
    """
    n, k = ids_loc.shape
    m = n * k

    # 1. group assignment rows by global expert id (sort-free stable
    # permutation — see ops/moe.py stable_expert_order; TPU sorts are
    # bitonic and this runs per MoE layer per microbatch)
    ids_flat = ids_loc.reshape(-1)
    with jax.named_scope("moe/permute"):
        order, pair_dest, counts = stable_expert_order(
            ids_flat, e_loc * ep_world
        )
        token_of = order // k
        x_rows = spread_to_pairs(x_loc, token_of, pair_dest)  # [m, D]

    # 2. tiny count exchange: S[s, e] = rows shard s routes to expert e
    S = lax.all_gather(counts, ep_axes, axis=0)  # [W, E]
    # the largest intake of any shard: known everywhere before a row moves
    need = S.reshape(ep_world, ep_world, e_loc).sum(axis=(0, 2)).max()

    with jax.named_scope("moe/combine"):
        probs_rows = permute_rows(probs_loc.reshape(-1), order, pair_dest)

    route = _Route(expert_fn, tuple(ep_axes), e_loc, ep_world)
    if capacity_factor is None:
        ladder = ep_buffer_ladder(m, ep_world)
    else:
        ladder = (ep_buffer_rows(m, ep_world, capacity_factor),)
    if len(ladder) == 1:
        weighted = _exchange(
            route, ladder[0], x_rows, probs_rows, expert_weights, S
        )
        rung = 0
    else:
        # the smallest rung that holds the largest intake; the last always
        # does, so the path stays dropless whatever the routing
        rung = sum((need > rows).astype(jnp.int32) for rows in ladder[:-1])
        weighted = _laddered_exchange(
            route, ladder, rung, x_rows, probs_rows, tuple(expert_weights), S
        )

    # 6. fold the k assignments per token, already weighted by the router
    # probs (collision-free gather form — see ops/moe.py combine_pairs)
    with jax.named_scope("moe/combine"):
        out = combine_pairs(weighted, token_of, pair_dest, n)
    return out, EpBufferUse(
        rows_taken=jnp.take(jnp.asarray(ladder, jnp.int32), rung),
        rows_needed=need.astype(jnp.int32),
        fell_back=jnp.asarray(
            (rung == len(ladder) - 1) & (len(ladder) > 1), jnp.int32
        ),
    )
