"""MoE token routing/permutation ops + grouped matmul.

Replaces the reference kernel layer for MoE (SURVEY §2.2):
- nv-grouped-gemm wheel (d9d/kernel/gmm/function.py:10,51) →
  ``jax.lax.ragged_dot`` — XLA's native grouped GEMM, MXU-tiled on TPU,
  differentiable (dI and dW both flow; the reference's GradDirection split
  is owned by the pipelining layer's two-phase VJP instead).
- Triton permute/unpermute kernels (d9d/kernel/moe/permute_with_probs.py:711,
  indices_to_multihot.py:263) → a stable argsort over expert ids + gather;
  XLA fuses the gather into the surrounding computation, and every shape is
  static (N·K rows) as TPU compilation demands. Every row movement is one of
  three linear maps (:func:`permute_rows`, :func:`spread_to_pairs`,
  :func:`combine_pairs`) whose transposes are given as gathers, so the
  backward holds no scatter-add of hidden-width rows either.

- A call of few rows that reaches nearly every expert anyway (a decode
  step: 64 rows x top-8 over 128) skips all of that: plain products over
  ALL experts, the sum over experts inside the down product's contraction
  (:func:`all_experts_swiglu`, chosen from static shapes by
  :func:`few_rows_touch_all_experts`).

All functions operate on a flat token dim; callers reshape [B,T,D]→[N,D].
"""

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from d9d_tpu.core.types import Array
from d9d_tpu.ops.swiglu import silu_mul


class TokenSort(NamedTuple):
    """Result of sorting (token, choice) pairs by expert.

    sort_idx: [N*K] position in the flattened (token-major) pair array for
        each sorted row; row r of the permuted layout is pair sort_idx[r].
    dest: [N*K] inverse permutation — where pair i lands in the sorted
        layout (``dest[sort_idx[r]] == r``).
    token_idx: [N*K] owning token of each sorted row (= sort_idx // K).
    group_sizes: [E] rows per expert, in sorted order.
    """

    sort_idx: Array
    dest: Array
    token_idx: Array
    group_sizes: Array


# one-hot grouping wins below this M·E (int32 [M, E] ≈ 64 MB here); above,
# its HBM traffic inverts the r3 sweep's verdict and argsort takes over
_ONE_HOT_GROUPING_LIMIT = 16 * 1024 * 1024


def stable_expert_order(
    flat_ids: Array, num_experts: int
) -> tuple[Array, Array, Array]:
    """Stable grouping permutation over expert ids WITHOUT a sort.

    Returns ``(sort_idx [M], dest [M], group_sizes [E])`` where
    ``flat_ids[sort_idx]`` is grouped by expert with original order
    preserved within each group — exactly ``argsort(flat_ids, stable=True)``
    — and ``dest`` is the inverse permutation (where row i lands). Computed
    as one-hot → cumsum → scatter. TPU sorts lower to bitonic networks
    (log² passes); a log-depth cumsum over the [M, E] one-hot plus one
    scatter is much cheaper at MoE shapes, and the MoE layer runs this per
    layer per microbatch.

    The one-hot costs O(M·E) HBM traffic (recomputed again under remat):
    a win at swept shapes (M≤128k, E≤64: ≤33 MB) but inverting for very
    large M·E (E=256, M=131k → 134 MB ×2 per MoE layer per
    microbatch pressures HBM), so past a threshold this falls back to the
    stable argsort instead.
    """
    m = flat_ids.shape[0]
    if m * num_experts > _ONE_HOT_GROUPING_LIMIT:
        sort_idx = jnp.argsort(flat_ids, stable=True).astype(jnp.int32)
        dest = (
            jnp.zeros((m,), jnp.int32)
            .at[sort_idx]
            .set(jnp.arange(m, dtype=jnp.int32), unique_indices=True)
        )
        group_sizes = jnp.bincount(flat_ids, length=num_experts)
        return sort_idx, dest, group_sizes.astype(jnp.int32)
    one_hot = (
        flat_ids[:, None] == jnp.arange(num_experts, dtype=flat_ids.dtype)
    ).astype(jnp.int32)
    prefix = jnp.cumsum(one_hot, axis=0)  # inclusive per-expert counts
    group_sizes = prefix[-1]
    # rank of pair i among same-expert pairs, in original order
    rank = jnp.take_along_axis(prefix, flat_ids[:, None], axis=1)[:, 0] - 1
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes)[:-1]]
    )
    dest = offsets[flat_ids] + rank  # where pair i lands in sorted layout
    sort_idx = jnp.zeros((m,), jnp.int32).at[dest].set(
        jnp.arange(m, dtype=jnp.int32), mode="drop", unique_indices=True
    )
    return sort_idx, dest, group_sizes.astype(jnp.int32)


def sort_tokens_by_expert(topk_ids: Array, num_experts: int) -> TokenSort:
    """Stable-sort (token, k) pairs by their routed expert id.

    topk_ids: [N, K] int32 expert assignments.
    """
    n, k = topk_ids.shape
    flat_ids = topk_ids.reshape(n * k)
    sort_idx, dest, group_sizes = stable_expert_order(flat_ids, num_experts)
    return TokenSort(
        sort_idx=sort_idx,
        dest=dest,
        token_idx=sort_idx // k,
        group_sizes=group_sizes,
    )


@jax.custom_vjp
def permute_rows(x: Array, idx: Array, inv_idx: Array) -> Array:
    """``x[idx]`` for a permutation ``idx`` whose inverse is ``inv_idx``.

    The pair comes from one :func:`stable_expert_order` call (``sort_idx``
    and ``dest``, either way round). The transpose of a gather by a
    permutation is the gather by its inverse: no row collides and nothing
    is added. Autodiff cannot know ``idx`` is a permutation and would write
    a general scatter-add, several times slower than a gather on TPU, so
    the transpose is given here. Both are permutations of ``range(len(x))``,
    so every index is a row: ``mode="clip"`` leaves the gather alone where
    the default's fill writes a select over the gathered rows after it.
    """
    return jnp.take(x, idx, axis=0, mode="clip")


def _permute_rows_fwd(x, idx, inv_idx):
    return permute_rows(x, idx, inv_idx), (idx, inv_idx)


def _permute_rows_bwd(residuals, g):
    idx, inv_idx = residuals
    return permute_rows(g, inv_idx, idx), None, None


permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def spread_to_pairs(x: Array, token_idx: Array, dest: Array) -> Array:
    """A token's row to each of its K expert-sorted pair rows.

    x: [N, D] → [N*K, D], row r a copy of token ``token_idx[r]``;
    ``token_idx = sort_idx // K`` and ``dest`` the inverse of ``sort_idx``
    (:func:`stable_expert_order`). The mirror of :func:`combine_pairs`,
    which is its transpose: autodiff's own would be a scatter-add
    colliding K ways on every token. ``sort_idx`` permutes ``range(N*K)``,
    so ``sort_idx // K`` is a token's row: the gather clips, it never fills.
    """
    return _spread_to_pairs(x, token_idx, dest, x.shape[0])


# N is static beside the operands: the transpose needs it and sees only g
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _spread_to_pairs(x, token_idx, dest, num_tokens):
    return jnp.take(x, token_idx, axis=0, mode="clip")


def _spread_to_pairs_fwd(x, token_idx, dest, num_tokens):
    return _spread_to_pairs(x, token_idx, dest, num_tokens), (token_idx, dest)


def _spread_to_pairs_bwd(num_tokens, residuals, g):
    token_idx, dest = residuals
    return combine_pairs(g, token_idx, dest, num_tokens), None, None


_spread_to_pairs.defvjp(_spread_to_pairs_fwd, _spread_to_pairs_bwd)


def permute_tokens(
    x: Array, probs: Array, sort: TokenSort
) -> tuple[Array, Array]:
    """Gather tokens (and their routing probs) into expert-sorted layout.

    x: [N, D]; probs: [N, K] → ([N*K, D], [N*K]).
    """
    from jax.ad_checkpoint import checkpoint_name

    # named for the "save_expensive" remat policy: the grouped-matmul
    # backward needs these rows (dW), and recomputing them means redoing
    # the gather under remat
    permuted_x = checkpoint_name(
        spread_to_pairs(x, sort.token_idx, sort.dest), "moe_permuted_rows"
    )
    permuted_probs = permute_rows(probs.reshape(-1), sort.sort_idx, sort.dest)
    return permuted_x, permuted_probs


def fused_combine_enabled() -> bool:
    """``D9D_TPU_MOE_COMBINE`` A/B switch (default ON) for the
    gather-fused combine: under the ``pallas_gather`` FFN backend the
    down-projection's combine (ragged gather → grouped matmul → K-sum)
    runs INSIDE the fused kernel, accumulating token-major [N, D]
    outputs in VMEM — the expert-sorted y rows and the pair-gathered
    copy never exist in HBM. Read at call time
    like the file's other env knobs; ops/moe_pallas.py consults it and
    its VMEM-fit gate can still veto per shape."""
    return os.environ.get("D9D_TPU_MOE_COMBINE", "fused") != "unfused"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine_pairs(
    y: Array, token_idx: Array, dest: Array, num_tokens: int
) -> Array:
    """Fold expert-sorted pair rows back to their owning tokens.

    y: [N*K, D] expert-sorted rows (already prob-weighted); ``dest`` the
    inverse permutation from :func:`stable_expert_order` and ``token_idx``
    the owning token of each sorted row → [N, D].
    Formulated as a duplicate-free gather by ``dest`` followed by a K-row
    sum instead of ``zeros.at[token_idx].add(y)``: the scatter-add
    collides K ways on every token (each token owns K expert rows) while
    ``dest`` is a permutation, so the gather is collision-free on TPU. Its
    transpose is :func:`spread_to_pairs`, a gather by ``token_idx`` (the
    K-fold broadcast and the inverse permutation in one), where autodiff
    would scatter at ``dest``. ``dest`` permutes ``range(N*K)``, so every
    index is a row of ``y``: the gather clips, it never fills. Shared by
    the local MoE path and the EP shard_map combine.
    """
    k = dest.shape[0] // num_tokens
    pair_y = jnp.take(y, dest, axis=0, mode="clip")  # token-major pair rows
    return pair_y.reshape(num_tokens, k, y.shape[-1]).sum(axis=1)


def _combine_pairs_fwd(y, token_idx, dest, num_tokens):
    return combine_pairs(y, token_idx, dest, num_tokens), (token_idx, dest)


def _combine_pairs_bwd(num_tokens, residuals, g):
    token_idx, dest = residuals
    return spread_to_pairs(g, token_idx, dest), None, None


combine_pairs.defvjp(_combine_pairs_fwd, _combine_pairs_bwd)


def unpermute_combine(y: Array, sort: TokenSort, num_tokens: int) -> Array:
    """Combine expert outputs back to their owning tokens (local path).

    y: [N*K, D] (already prob-weighted) → [N, D]. The reverse of
    ``permute_tokens``; see :func:`combine_pairs` for the formulation.
    """
    return combine_pairs(y, sort.token_idx, sort.dest, num_tokens)


def gate_up_grouped_matmul(
    x: Array, gate_w: Array, up_w: Array, group_sizes: Array
) -> tuple[Array, Array]:
    """Gate and up projections as grouped matmuls → ``(g, u)``.

    Single owner of the ``D9D_TPU_MOE_FUSED_GATE_UP`` A/B (default on:
    ONE grouped matmul over a runtime ``[E, in, 2*inter]`` concat so the
    expert-sorted rows stream from HBM once; off: two grouped matmuls,
    no weight-concat materialization — see nn/moe.py grouped_swiglu_apply
    for the trade-off). Shared by the XLA MoE chain AND the Pallas
    backend's fallback/backward reference (the env switch must cover
    every path or the perf A/B is inconsistent). It covers the grouped
    matmuls only: :func:`all_experts_swiglu`, which a call of few rows
    takes, never concatenates and does not consult it. Weights must
    already be in the compute dtype.
    """
    if os.environ.get("D9D_TPU_MOE_FUSED_GATE_UP", "1") == "1":
        inter = gate_w.shape[-1]
        gate_up_w = jnp.concatenate([gate_w, up_w], axis=-1)
        h_gu = grouped_matmul(x, gate_up_w, group_sizes)  # [M, 2*inter]
        return h_gu[..., :inter], h_gu[..., inter:]
    return (
        grouped_matmul(x, gate_w, group_sizes),
        grouped_matmul(x, up_w, group_sizes),
    )


def grouped_matmul(x: Array, weight: Array, group_sizes: Array) -> Array:
    """Per-expert matmul on expert-sorted rows.

    x: [rows, in], weight: [E, in, out], group_sizes: [E] with
    sum(group_sizes) <= rows (trailing rows produce unspecified values —
    callers mask or pad with a zero expert). The output carries a
    checkpoint name: ``ragged_dot`` is a custom call the stock
    ``checkpoint_dots*`` policies don't match, so the "save_expensive"
    remat policy saves it by name instead of recomputing the experts'
    FLOPs in the backward pass.
    """
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(
        lax.ragged_dot(
            x,
            weight,
            group_sizes.astype(jnp.int32),
            preferred_element_type=x.dtype,
        ),
        "moe_grouped_dot",
    )


# --- few rows: every expert's weights once, through plain products -----------

# A call of N rows that multiplies every expert's weights does 2·N FLOP per
# bf16 weight, N FLOP per weight byte. The v5e's ridge is 197 TFLOP/s over
# 819 GB/s = 240 FLOP a byte, so with N well under it the products stay
# bound by the weights' bytes, which ``ragged_dot`` would stream too: it
# reads every expert some row chose, and N·K draws over E experts leave
# (1 - 1/E)^(N·K) ≈ exp(-N·K/E) of them untouched, 14 % at N·K = 2·E and
# 2 % at a decode step's 64 rows x top-8 over 128. What ``ragged_dot``
# pays beside the bytes is 7 to 9 us a group (PERF.md §6, PR 36), which
# a few rows a group cannot hide.
FEW_ROWS_LIMIT = 128
# A held range of a wider router's experts (``nn/moe.py _forward_held``)
# takes the same products up to the ridge itself. What it would take
# otherwise is a buffer chosen per call from the count of pairs that land
# here, and in passes over chunks of tokens where routing is uneven: each
# pass streams every held expert's weights again, so a decode step's time
# followed the seed's router (the MiMo cell's six runs spread by 10 %, one
# layer in some seeds at three times its even share; PERF.md §6, PR 41).
# The plain products cost the same whatever the routing.
HELD_FEW_ROWS_LIMIT = 256


def few_rows_touch_all_experts(
    num_rows: int, top_k: int, num_experts: int,
    row_limit: int = FEW_ROWS_LIMIT,
) -> bool:
    """Does a call of these static shapes take :func:`all_experts_swiglu`?

    Only where the all-expert products stay memory-bound (``num_rows`` at
    most ``row_limit``) and the routing reads nearly every expert
    anyway (``num_rows * top_k >= 2 * num_experts`` over the router's
    ``num_experts``: 86 % expected and more). A one-row ``generate`` step
    (8 draws over 128 experts) keeps ``ragged_dot`` and reads a sixteenth
    of the bytes; a training call has thousands of rows and keeps it too.
    """
    return num_rows <= row_limit and num_rows * top_k >= 2 * num_experts


def all_experts_swiglu(
    x: Array,
    topk_ids: Array,
    topk_probs: Array,
    gate_w: Array,
    up_w: Array,
    down_w: Array,
    dtype: jnp.dtype,
) -> Array:
    """The routed SwiGLU of a few rows as plain products over ALL experts.

    x: [N, D]; topk_ids, topk_probs: [N, K]; gate_w, up_w: [E, D, F] and
    down_w: [E, F, D] as stored → [N, D]. Every row meets every expert:
    gate and up are two products of the same [N, D] rows (256 KB at a
    decode step: read twice for nothing, so no ``[E, D, 2F]`` concatenation
    exists here), and the sum over experts is part of the down product's
    contraction, ``n (e f), (e f) d -> n d``, accumulated in float32 like
    any matmul. The router's probabilities enter as an ``[N, E]`` weight on
    the hidden rows; where a pair was not selected the hidden row is
    *selected* to zero, not multiplied by it, so what an expert makes of a
    row it was never routed contributes an exact zero whatever it is.

    No sort, no permute, no combine and no custom call: the compiler's own
    matmul streams the weights once (the output head's product at the same
    64 rows reads 85 % of the memory roofline where ``ragged_dot``'s 256
    groups a layer read 38 %). Plain ops, so autodiff is the backward.
    Weights are cast to ``dtype`` here as on the grouped path.
    """
    n, d = x.shape
    e, _, f = gate_w.shape
    x = x.astype(dtype)
    hit = topk_ids[:, :, None] == jnp.arange(e, dtype=topk_ids.dtype)
    selected = hit.any(axis=1)  # [N, E]
    # a token's K choices are distinct, so the sum has one term; drawn
    # twice, an expert weighs in with both probabilities as on the
    # grouped path
    weight = jnp.where(hit, topk_probs[:, :, None], 0).sum(axis=1)
    rows_by_all = (((1,), (1,)), ((), ()))  # n d, e d f -> n e f
    with jax.named_scope("moe/experts/gate_up/all_experts"):
        g = lax.dot_general(
            x, gate_w.astype(dtype), rows_by_all, preferred_element_type=dtype
        )
        u = lax.dot_general(
            x, up_w.astype(dtype), rows_by_all, preferred_element_type=dtype
        )
    with jax.named_scope("moe/experts/act"):
        hidden = silu_mul(g, u)
        hidden = jnp.where(
            selected[:, :, None],
            hidden * weight[:, :, None].astype(dtype),
            jnp.zeros((), dtype),
        )
    with jax.named_scope("moe/experts/down/all_experts"):
        return jnp.dot(
            hidden.reshape(n, e * f),
            down_w.astype(dtype).reshape(e * f, d),
            preferred_element_type=dtype,
        )


# --- a held range of a wider router's experts --------------------------------


class HeldSort(NamedTuple):
    """The (token, choice) pairs that land on the experts held here, laid
    into a buffer of ``M`` rows (:func:`sort_held_pairs`).

    Two orders over the same pairs: *rows* are expert-sorted (what the
    grouped matmul wants), *slots* are token-major (a token's pairs are
    neighbours and a block of tokens owns one run of slots, so folding
    them is one pass over the runs and no scatter: :func:`fold_held`).
    Rows and slots from ``rows_held`` on are padding.

    pair_of_row: [M] flattened (token-major) pair of each row.
    token_of_row: [M] owning token of each row.
    row_of_slot: [M] the row of each slot's pair.
    token_of_slot: [M] owning token of each slot.
    slot_start: [N] a token's first slot.
    slot_count: [N] pairs of a token that are held (at most K).
    rows_held: [] pairs held, at most M.
    group_sizes: [E] rows per held expert; the padding rows ride in the
        last group (as zero rows: :func:`spread_held`).
    """

    pair_of_row: Array
    token_of_row: Array
    row_of_slot: Array
    token_of_slot: Array
    slot_start: Array
    slot_count: Array
    rows_held: Array
    group_sizes: Array


def sort_held_pairs(
    local_ids: Array, num_held: int, buf_rows: int
) -> HeldSort:
    """Index vectors for the pairs routed to ``num_held`` local experts.

    local_ids: [N, K] int32 local expert of each pair, ``num_held`` for a
    pair routed to an expert held elsewhere. The caller picks ``buf_rows``
    at or above the number of held pairs. Only index vectors are made
    here, ``N*K`` integers at most: no row of hidden width moves. A
    slot's pair is one of the ``N*K`` (0 where the slot is empty), so the
    gather of ``dest`` clips, it never fills.
    """
    n, k = local_ids.shape
    flat = local_ids.reshape(n * k)
    # pairs routed elsewhere form one more group, sorted last
    sort_idx, dest, sizes = stable_expert_order(flat, num_held + 1)
    rows_held = n * k - sizes[num_held]
    pair_of_row = sort_idx[:buf_rows]

    held = flat < num_held
    seen = jnp.cumsum(held.astype(jnp.int32))
    pairs = jnp.arange(n * k, dtype=jnp.int32)
    # slot of a held pair: how many held pairs stand before it; the others
    # aim past the buffer, each at an index of its own, and are dropped
    slot = jnp.where(held, seen - 1, buf_rows + pairs - seen)
    pair_of_slot = jnp.zeros((buf_rows,), jnp.int32).at[slot].set(
        pairs, mode="drop", unique_indices=True
    )
    count = held.reshape(n, k).sum(axis=1).astype(jnp.int32)
    group_sizes = sizes[:num_held].at[num_held - 1].add(buf_rows - rows_held)
    return HeldSort(
        pair_of_row=pair_of_row,
        token_of_row=pair_of_row // k,
        row_of_slot=jnp.take(dest, pair_of_slot, mode="clip"),
        token_of_slot=pair_of_slot // k,
        slot_start=jnp.cumsum(count) - count,
        slot_count=count,
        rows_held=rows_held,
        group_sizes=group_sizes,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def spread_held(x: Array, held: HeldSort, top_k: int) -> Array:
    """A token's row to each of its held pairs' buffer rows.

    x: [N, D] → [M, D], row r a copy of token ``held.token_of_row[r]``,
    zeros from ``held.rows_held`` on. Its transpose is :func:`fold_held`.
    ``token_of_row`` is ``sort_idx[:M] // K``, a token's row, so the gather
    clips, it never fills: the one select over the rows is the live mask.
    """
    rows = jnp.take(x, held.token_of_row, axis=0, mode="clip")
    live = jnp.arange(rows.shape[0]) < held.rows_held
    return jnp.where(live[:, None], rows, jnp.zeros((), rows.dtype))


def _spread_held_fwd(x, held, top_k):
    return spread_held(x, held, top_k), (held, x.shape[0])


def _spread_held_bwd(top_k, residuals, g):
    held, num_tokens = residuals
    return fold_held(g, held, num_tokens, top_k), None


spread_held.defvjp(_spread_held_fwd, _spread_held_bwd)


# A fold's tile: this many tokens' sums accumulate over chunks of this many
# slots, this many bytes of a row at a time. At 256 x 256 a chunk's DMA
# (1 MB of bf16 at 2,048 columns) and its product on the MXU take about as
# long; the buffers (two chunks, two output blocks, the float32 sums and
# the product beside them) stay under Mosaic's default 16 MiB of scoped VMEM
# for bf16 and float32 rows alike
_FOLD_TOKENS = 256
_FOLD_SLOTS = 256
_FOLD_ROW_BYTES = 4096


def _round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def _fold_kernel(block_ref, chunk_ref, items_ref, token_ref, rows_ref,
                 out_ref, sum_ref):
    """Work item ``w``: add chunk ``chunk_ref[w]``'s rows to the sums of
    token block ``block_ref[w]``. A block's items are neighbours; items
    from ``items_ref[0]`` on are padding that repeats the last one."""
    w = pl.program_id(1)
    last = items_ref[0] - 1
    block = block_ref[w]
    tokens, slots = sum_ref.shape[0], rows_ref.shape[0]

    @pl.when((w == 0) | (block_ref[jnp.maximum(w - 1, 0)] != block))
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    @pl.when(w <= last)
    def _():
        token = block * tokens + lax.broadcasted_iota(
            jnp.int32, (tokens, slots), 0
        )
        # 0/1 times a row is the row: the products are exact, the sums
        # float32. A float32 row needs every pass of the MXU for that
        exact = rows_ref.dtype.itemsize >= 4
        sum_ref[...] += jnp.dot(
            (token_ref[...] == token).astype(rows_ref.dtype), rows_ref[...],
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST if exact else None,
        )

    following = block_ref[jnp.minimum(w + 1, pl.num_programs(1) - 1)]

    @pl.when((w == last) | (following != block))
    def _():
        out_ref[...] = sum_ref[...].astype(out_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fold_held(y: Array, held: HeldSort, num_tokens: int, top_k: int) -> Array:
    """Fold the held pairs' buffer rows back to their owning tokens.

    y: [M, D] expert-sorted rows → [N, D]: a token's float32 sum of its
    live rows, rounded once to ``y.dtype``; a token with no held pair gets
    zeros. One gather brings the rows into slot order, in ``y``'s own
    dtype, where a token's (at most K) pairs are neighbours and a block of
    tokens owns one contiguous run of slots. A kernel then reads each
    chunk of slots that a block's run touches and adds it to the block's
    sums as a 0/1 membership matrix ``[tokens, slots]`` times the chunk:
    every live row is read once (twice where a chunk straddles two blocks)
    and every token's row written once, so the cost follows the rows held.
    No scatter-add, no float32 copy of the buffer, and never ``N*K`` rows.
    A row that is not finite reaches its chunk's other tokens as NaN
    (``0 * inf``). Its transpose is :func:`spread_held`. Off the TPU the
    kernel runs interpreted.
    """
    m, d = y.shape
    tokens = min(_FOLD_TOKENS, _round_up(num_tokens, 16))
    slots = min(_FOLD_SLOTS, _round_up(m, 128))
    columns = max(
        (c for c in range(128, d + 1, 128)
         if d % c == 0 and c * y.dtype.itemsize <= _FOLD_ROW_BYTES),
        default=d,
    )
    num_blocks = -(-num_tokens // tokens)
    num_chunks = -(-m // slots)
    pad = num_chunks * slots - m

    live = jnp.minimum(held.rows_held, m)
    # every index is a row of the buffer: clipping them is free where the
    # default's fill of rows out of range doubles the gather's time
    by_slot = jnp.take(
        y, jnp.pad(held.row_of_slot, (0, pad)), axis=0, mode="clip"
    )
    token_of_slot = jnp.where(
        jnp.arange(m + pad) < live, jnp.pad(held.token_of_slot, (0, pad)), -1
    ).reshape(num_chunks, 1, slots)

    # the work items: for each block of tokens the chunks its run of slots
    # touches, one (of zeros to add) where it has none. Runs tile
    # [0, live), so two neighbours share at most one chunk and the items
    # number at most ``num_chunks - 1 + num_blocks``
    start = jnp.minimum(held.slot_start[::tokens], live)
    end = jnp.append(start[1:], live)
    first_chunk = jnp.minimum(start // slots, num_chunks - 1)
    last_chunk = jnp.clip((end - 1) // slots, first_chunk, num_chunks - 1)
    count = last_chunk - first_chunk + 1
    first_item = jnp.cumsum(count) - count
    item = jnp.arange(num_chunks - 1 + num_blocks, dtype=jnp.int32)
    block = jnp.sum(
        first_item[None, :] <= item[:, None], axis=1, dtype=jnp.int32
    ) - 1
    chunk = jnp.minimum(
        first_chunk[block] + item - first_item[block], last_chunk[block]
    )

    out = pl.pallas_call(
        _fold_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(d // columns, item.shape[0]),
            in_specs=[
                pl.BlockSpec(
                    (None, 1, slots), lambda c, w, blk, chk, n: (chk[w], 0, 0)
                ),
                pl.BlockSpec(
                    (slots, columns), lambda c, w, blk, chk, n: (chk[w], c)
                ),
            ],
            out_specs=pl.BlockSpec(
                (tokens, columns), lambda c, w, blk, chk, n: (blk[w], c)
            ),
            scratch_shapes=[pltpu.VMEM((tokens, columns), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_blocks * tokens, d), y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=jax.default_backend() != "tpu",
        name="fold_held",
    )(block, chunk, count.sum().reshape(1), token_of_slot, by_slot)
    return out[:num_tokens]


def _fold_held_fwd(y, held, num_tokens, top_k):
    return fold_held(y, held, num_tokens, top_k), held


def _fold_held_bwd(num_tokens, top_k, held, g):
    return spread_held(g, held, top_k), None


fold_held.defvjp(_fold_held_fwd, _fold_held_bwd)
