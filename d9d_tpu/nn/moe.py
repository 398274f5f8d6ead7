"""Mixture-of-Experts stack: router, grouped experts, shared expert, layer.

Parity: reference d9d/module/block/moe/* (router.py:23, grouped_linear.py:12,
grouped_experts.py:10, shared_expert.py:21, layer.py:16) and its
communication handlers (communications/{naive,deepep}.py).

TPU-native design:
- Grouped GEMM is ``lax.ragged_dot`` on expert-sorted rows (static N·K
  shape) instead of the nv-grouped-gemm wheel.
- The local (no-EP) path is the reference's NoCommunicationHandler: a
  stable argsort permute, expert compute, scatter-add combine.
- The EP path replaces DeepEP's NVSHMEM all-to-all with a
  ``ragged_all_to_all`` dispatch/compute/combine flow inside a
  ``shard_map`` over the expert mesh axes (ops/ep_dispatch.py): tokens
  travel only to their experts' owners and per-shard grouped-GEMM work is
  ``N·k/ep`` (+capacity padding), differentiable end to end with the
  backward re-crossing the network like DeepEP's dispatch/combine pair
  (deepep.py:91-150).
- Load stats are sown into the ``moe_stats`` collection instead of a
  mutable buffer (layer.py:16 tokens_per_expert).
"""

import dataclasses
import functools
from typing import NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from d9d_tpu.core import compat
from d9d_tpu.core.types import Array
from d9d_tpu.nn import logical_axes as la
from d9d_tpu.nn.cca import near
from d9d_tpu.nn.mlp import SwiGLU
from d9d_tpu.nn.norm import RMSNorm
from d9d_tpu.ops.ep_dispatch import (
    ep_buffer_rows,
    ep_dispatch_compute_combine,
)
from d9d_tpu.ops.moe import (
    HELD_FEW_ROWS_LIMIT,
    all_experts_swiglu,
    few_rows_touch_all_experts,
    fold_held,
    gate_up_grouped_matmul,
    grouped_matmul,
    permute_tokens,
    sort_held_pairs,
    sort_tokens_by_expert,
    spread_held,
    unpermute_combine,
)
from d9d_tpu.ops.moe_pallas import fused_moe_ffn_apply, moe_ffn_backend
from d9d_tpu.ops.swiglu import silu_mul

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SharedExpertParameters:
    """Config for the optional shared expert (reference shared_expert.py:8)."""

    intermediate_size: int
    enable_gate: bool = False


class TopKRouter(nn.Module):
    """Gate scores → optional selection bias → top-k → optional renorm.

    Reference router.py:23. ``score_function`` is the family's and is set
    by the model preset: ``"softmax"`` over all experts (Qwen3,
    DeepSeek-V2) or an independent ``"sigmoid"`` per expert (the
    DeepSeek-V3 line's ``noaux_tc``: GLM-4.7-Flash, Moonlight, Kimi-K2).
    The selection bias (loss-free load balancing, HF
    ``e_score_correction_bias``) joins the selection only, never the
    returned weights. It is a float32 leaf of the ``params`` collection,
    zero at init and behind ``stop_gradient``: whatever carries ``params``
    (``Trainer``, ``generate``, ``ContinuousBatcher``, a checkpoint)
    carries it, its gradient is exactly zero, and a balancing controller
    writes it outside the gradient path.

    ``mlp_hidden`` > 0 is the ZAYA form (arXiv:2511.17127): the gate is
    a small MLP, not one matrix. ``z = h W_D + b_D`` (``mlp_hidden``
    wide); with ``carry`` the state the previous layer's router handed
    on joins it, ``z += gamma * r_prev`` (a learned vector; the first
    layer is given none and has no ``gamma``), and ``z`` is what this
    layer hands on; the scores are ``W_3 gelu(W_2 gelu(W_1 RMSNorm(z) +
    b_1) + b_2)``. The call then returns ``z`` beside indices and
    weights. The down-projection takes its operands in the activation
    type and sums in float32; everything after it is float32 (a router
    of a few hundred numbers a token: top-1 selection is what its
    rounding would move). Scopes ``moe/router/{down, eda, mlp, score,
    select}``.
    """

    dim: int
    num_experts: int
    top_k: int
    renormalize_probabilities: bool = True
    enable_expert_bias: bool = False
    score_function: str = "softmax"
    # group-limited routing (DeepSeek ``group_limited_greedy``): experts
    # partition into ``n_group`` groups, each scored by its best expert;
    # only experts in the top ``topk_group`` groups are eligible for the
    # global top-k. n_group == 1 is plain top-k.
    n_group: int = 1
    topk_group: int = 1
    # the MLP form: its width (0 = one matrix), whether it carries state
    # from layer to layer, RMSNorm's eps, and noise on the init of its
    # biases and ``gamma`` (``nn/cca.py near``)
    mlp_hidden: int = 0
    carry: bool = False
    norm_eps: float = 1e-6
    init_jitter: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, hidden: Array, carried: Optional[Array] = None):
        """hidden [..., D] → (indices [..., K] int32, probs [..., K] fp32),
        and the router's state [..., mlp_hidden] as a third in the MLP
        form (``carried``: the previous layer's)."""
        if self.score_function not in ("softmax", "sigmoid"):
            raise ValueError(
                f"score_function {self.score_function!r}: softmax or sigmoid"
            )

        def dense(features, name, axes=(None, None), dtype=F32, **kw):
            return nn.Dense(
                features, name=name, dtype=dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), axes
                ),
                bias_init=nn.with_logical_partitioning(
                    near(0.0, self.init_jitter), (None,)
                ),
                **kw,
            )

        state = None
        if self.mlp_hidden:
            with jax.named_scope("moe/router/down"):
                state = dense(
                    self.mlp_hidden, "down", (la.EMBED, None), self.dtype,
                    dot_general=functools.partial(
                        lax.dot_general, preferred_element_type=F32
                    ),
                )(hidden).astype(F32)
            if self.carry and carried is not None:
                with jax.named_scope("moe/router/eda"):
                    gamma = self.param(
                        "carry_scale",
                        nn.with_logical_partitioning(
                            near(1.0, self.init_jitter), (None,)
                        ),
                        (self.mlp_hidden,), self.param_dtype,
                    )
                    state = state + gamma.astype(F32) * carried.astype(F32)
            with jax.named_scope("moe/router/mlp"):
                hidden = RMSNorm(
                    self.mlp_hidden, eps=self.norm_eps, name="norm",
                    param_dtype=self.param_dtype,
                )(state)
                for name in ("fc1", "fc2"):
                    hidden = jax.nn.gelu(
                        dense(self.mlp_hidden, name)(hidden),
                        approximate=False,
                    )
        with jax.named_scope("moe/router/score"):
            scores = dense(
                self.num_experts, "gate", (la.EMBED, None),
                F32 if self.mlp_hidden else self.dtype, use_bias=False,
            )(hidden)
            if self.score_function == "softmax":
                probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            else:
                probs = jax.nn.sigmoid(scores.astype(jnp.float32))

        with jax.named_scope("moe/router/select"):
            # selection scores may differ from the returned probs (bias
            # joins selection only; group-limited routing masks
            # ineligible groups)
            sel = probs
            if self.enable_expert_bias:
                bias = self.param(
                    "e_score_correction_bias",
                    nn.with_logical_partitioning(
                        nn.initializers.zeros, (None,)
                    ),
                    (self.num_experts,),
                    jnp.float32,
                )
                sel = sel + lax.stop_gradient(bias)
            if self.n_group > 1:
                if self.num_experts % self.n_group != 0:
                    raise ValueError(
                        f"num_experts {self.num_experts} not divisible by "
                        f"n_group {self.n_group}"
                    )
                per = self.num_experts // self.n_group
                group_score = sel.reshape(
                    *sel.shape[:-1], self.n_group, per
                ).max(axis=-1)
                _, top_g = lax.top_k(group_score, self.topk_group)
                gmask = (
                    jax.nn.one_hot(top_g, self.n_group, dtype=jnp.bool_)
                    .any(axis=-2)
                )
                emask = jnp.repeat(gmask, per, axis=-1)
                sel = jnp.where(emask, sel, -jnp.inf)
            _, selected_idx = lax.top_k(sel, self.top_k)
            selected_probs = jnp.take_along_axis(
                probs, selected_idx, axis=-1
            )

            if self.renormalize_probabilities:
                selected_probs = selected_probs / (
                    selected_probs.sum(axis=-1, keepdims=True) + 1e-20
                )
            routed = selected_idx.astype(jnp.int32), selected_probs
            return routed if state is None else (*routed, state)


class GroupedSwiGLU(nn.Module):
    """E parallel SwiGLU experts over grouped GEMM (reference
    grouped_experts.py:10 + grouped_linear.py:12). Weights are [E, in, out]
    with the ``expert`` logical axis on dim 0 so an EP plan shards experts
    across the expert mesh axes."""

    hidden_dim: int
    intermediate_dim: int
    num_experts: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        def weight(name, din, dout, ax_in, ax_out):
            init = nn.initializers.variance_scaling(
                1.0 / 3.0, "fan_in", "uniform", in_axis=1, out_axis=2
            )
            return self.param(
                name,
                nn.with_logical_partitioning(init, (la.EXPERT, ax_in, ax_out)),
                (self.num_experts, din, dout),
                self.param_dtype,
            )

        self.gate_weight = weight(
            "gate_proj",
            self.hidden_dim,
            self.intermediate_dim,
            la.EXPERT_EMBED,
            la.EXPERT_MLP,
        )
        self.up_weight = weight(
            "up_proj",
            self.hidden_dim,
            self.intermediate_dim,
            la.EXPERT_EMBED,
            la.EXPERT_MLP,
        )
        self.down_weight = weight(
            "down_proj",
            self.intermediate_dim,
            self.hidden_dim,
            la.EXPERT_MLP,
            la.EXPERT_EMBED,
        )

    def __call__(
        self, permuted_x: Array, permuted_probs: Array, group_sizes: Array
    ) -> Array:
        """Expert-sorted rows [M, D] + probs [M] → weighted outputs [M, D]."""
        return grouped_swiglu_apply(
            permuted_x,
            permuted_probs,
            group_sizes,
            self.gate_weight,
            self.up_weight,
            self.down_weight,
            self.dtype,
        )


def grouped_swiglu_apply(
    permuted_x: Array,
    permuted_probs: Array,
    group_sizes: Array,
    gate_w: Array,
    up_w: Array,
    down_w: Array,
    dtype: jnp.dtype,
) -> Array:
    """Functional core shared by the local path and the EP shard_map body.

    Gate and up projections run as ONE grouped matmul over a runtime
    concatenation ``[E, in, 2*inter]``: the expert-sorted activation rows
    stream from HBM once instead of twice and per-expert M-tiles are
    reused across both projections, while parameters (and therefore
    checkpoints, HF mappers, PEFT and sharding plans) stay separate
    gate/up tensors.

    Caveat: because ragged_dot is an opaque custom call, XLA
    materializes the concatenated weight copy each forward (again in the
    backward under remat) — one extra full-weight write+read per MoE layer
    per microbatch; ``D9D_TPU_MOE_FUSED_GATE_UP=0`` switches to two
    grouped matmuls (a training question: ROADMAP S6,
    ``env-selected-kernels``). A decode step's 64 rows do not come here
    since PR 36: the local path sends a call of few rows that reaches
    nearly every expert through ``ops/moe.py all_experts_swiglu``, which
    has no concatenation and no ``ragged_dot`` (a fused chunk used to
    hoist the copy, 768 MB a layer, and keep it), nor does a decode
    step through a held range since PR 41 (``_forward_held``). What
    still comes here: the training calls, the EP shard body, a held
    range's calls of more than ``HELD_FEW_ROWS_LIMIT`` rows or of too few
    to reach the router's width, and a one-row ``generate`` step, which
    reads 8 experts of 128.

    The ``moe/experts/{gate_up,act,down}`` scopes are HLO metadata only
    (the all-expert form carries the same, with ``all_experts`` under
    the two products'): in a trace they say which grouped matmul
    (forward or transposed) an op belongs to. The TPU compiler rewrites
    ``ragged_dot`` into a custom call named ``ragged-dot-none`` and drops
    its ``op_name``: the scopes stay on what stands around the call (the
    weight concatenation, the slices, the probability weighting) and in
    the lowered HLO, and a trace's reader takes the calls themselves by
    their name (``benchmarks/harness/layers.py``).
    """
    x = permuted_x.astype(dtype)
    with jax.named_scope("moe/experts/gate_up"):
        g, u = gate_up_grouped_matmul(
            x, gate_w.astype(dtype), up_w.astype(dtype), group_sizes
        )
    with jax.named_scope("moe/experts/act"):
        hidden = silu_mul(g, u)
    with jax.named_scope("moe/experts/down"):
        out = grouped_matmul(hidden, down_w.astype(dtype), group_sizes)
        return out * permuted_probs[:, None].astype(dtype)


# --- a held range of the router's experts -------------------------------------


class _HeldLadder(NamedTuple):
    """The static half of the held-range path (hashable: a ``custom_vjp``
    non-differentiable argument)."""

    dtype: object
    top_k: int
    buffers: tuple[int, ...]  # ascending one-pass buffer sizes, in rows
    passes: int  # token chunks of the fallback, each with room for all its pairs


# Each one-pass buffer is a quarter above the one below, from a quarter above
# an even router's share up to this multiple of it. A held range is a few
# experts and nothing balances the router here, so one layer's share wanders:
# the Xing4.0 cell's MTP block went from 4,340 rows (of 32,768; even: 4,096)
# to 5,904 and back within 40 steps (my chip run, PR 35). The EP path's two
# rungs below its worst case (``ep_buffer_ladder``: 1.25 and 3.16 at eight
# shards) would have run that layer at 12,952 rows; here its cost follows its
# rows within a quarter
_HELD_RUNG_RATIO = 1.25
_HELD_ONE_PASS_LIMIT = 3.2


def held_ladder(
    num_tokens: int, top_k: int, num_held: int, num_routed: int
) -> tuple[tuple[int, ...], int]:
    """``(buffers, passes)`` for ``num_held`` of ``num_routed`` experts.

    The one-pass buffers are chosen from the count of pairs that land
    here, the way the dropless EP path chooses its rung
    (``ops/ep_dispatch.py``), on a finer ladder: see ``_HELD_RUNG_RATIO``.
    Routing so uneven that the last of them overflows is computed in
    ``passes`` chunks of tokens, the fewest whose every pair fits that last
    buffer: dropless whatever the routing, and never ``num_tokens * top_k``
    rows in one buffer. A call of so few tokens (a decode step) that the
    smallest rung would hold every pair, or that one token's ``top_k``
    pairs alone overflow the last rung (a one-row ``generate`` step at
    top-10 of a four-way share), gets one buffer of all its pairs and
    ``passes`` 1.
    """
    share = num_routed // num_held
    pairs = num_tokens * top_k
    even = -(-pairs // share)
    buffers, factor = [], _HELD_RUNG_RATIO
    while factor <= _HELD_ONE_PASS_LIMIT:
        rows = ep_buffer_rows(even, share, factor)
        if rows >= pairs:
            break
        buffers.append(rows)
        factor *= _HELD_RUNG_RATIO
    if not buffers or top_k > buffers[-1]:
        return (pairs,), 1
    passes = next(
        p for p in range(2, num_tokens + 1)
        if num_tokens % p == 0 and num_tokens // p * top_k <= buffers[-1]
    )
    return tuple(buffers), passes


def _held_pass(dtype, top_k, buf_rows, x, local_ids, probs, weights):
    """The held experts over ``x [n, D]`` through one ``buf_rows``-row
    buffer: gather the rows of the pairs that land here, grouped SwiGLU,
    fold them back to their tokens. ``local_ids [n, K]`` is
    ``weights[0].shape[0]`` for a pair routed elsewhere."""
    with jax.named_scope("moe/permute"):
        held = sort_held_pairs(local_ids, weights[0].shape[0], buf_rows)
        rows = spread_held(x, held, top_k)
        live = jnp.arange(buf_rows) < held.rows_held
        row_probs = jnp.where(
            live, jnp.take(probs.reshape(-1), held.pair_of_row), 0
        )
    y = grouped_swiglu_apply(
        rows, row_probs, held.group_sizes, *weights, dtype
    )
    with jax.named_scope("moe/combine"):
        return fold_held(y, held, x.shape[0], top_k).astype(x.dtype)


def _held_branches(ladder: _HeldLadder):
    dtype, top_k, buffers, passes = ladder

    def in_chunks(x, local_ids, probs, weights):
        n, d = x.shape
        chunk = n // passes

        def one(_, part):
            return None, _held_pass(
                dtype, top_k, chunk * top_k, *part, weights
            )

        _, out = lax.scan(one, None, (
            x.reshape(passes, chunk, d),
            local_ids.reshape(passes, chunk, top_k),
            probs.reshape(passes, chunk, top_k),
        ))
        return out.reshape(n, d)

    return [
        *(functools.partial(_held_pass, dtype, top_k, rows)
          for rows in buffers),
        in_chunks,
    ]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _laddered_held(ladder: _HeldLadder, rung, x, local_ids, probs, weights):
    """The held experts through rung ``rung`` of ``ladder`` (the last is
    the chunked fallback). Its own VJP for the reason
    ``ops/ep_dispatch.py _laddered_exchange`` gives: differentiating
    through ``lax.switch`` would have the snug branch allocate the other
    branches' residuals as zeros. The forward keeps its inputs and the
    backward makes the choice again."""
    return lax.switch(rung, _held_branches(ladder), x, local_ids, probs, weights)


def _laddered_held_fwd(ladder, rung, x, local_ids, probs, weights):
    out = _laddered_held(ladder, rung, x, local_ids, probs, weights)
    return out, (rung, x, local_ids, probs, weights)


def _laddered_held_bwd(ladder, residuals, g):
    rung, x, local_ids, probs, weights = residuals

    def pull_back(branch):
        def pulled(x, local_ids, probs, weights, g):
            _, vjp = jax.vjp(
                lambda x, p, w: branch(x, local_ids, p, w), x, probs, weights
            )
            return vjp(g)

        return pulled

    d_x, d_probs, d_weights = lax.switch(
        rung, [pull_back(b) for b in _held_branches(ladder)],
        x, local_ids, probs, weights, g,
    )
    return None, d_x, None, d_probs, d_weights


_laddered_held.defvjp(_laddered_held_fwd, _laddered_held_bwd)


def held_experts_apply(
    x: Array, local_ids: Array, probs: Array, weights: tuple,
    *, num_routed: int, dtype: jnp.dtype,
) -> Array:
    """Routed output of the experts held here, for tokens routed over
    ``num_routed`` experts of which ``weights`` hold a contiguous range.

    x: [N, D]; local_ids: [N, K] the chosen expert less the first held
    one, ``E`` (the count held) where that falls outside ``[0, E)``;
    probs: [N, K]. Only the pairs that land here are gathered, multiplied
    and folded: in the smallest buffer of :func:`held_ladder` that holds
    them, chosen per call from their count. What the other experts would
    add is computed by the chips that hold them, and nowhere here.
    """
    n, k = local_ids.shape
    num_held = weights[0].shape[0]
    buffers, passes = held_ladder(n, k, num_held, num_routed)
    if passes == 1:
        return _held_pass(dtype, k, buffers[0], x, local_ids, probs, weights)
    rows_held = (local_ids < num_held).sum()
    rung = sum((rows_held > rows).astype(jnp.int32) for rows in buffers)
    return _laddered_held(
        _HeldLadder(dtype, k, buffers, passes), rung,
        x, local_ids, probs, tuple(weights),
    )



class SharedSwiGLU(nn.Module):
    """Always-on expert with optional sigmoid gate (reference
    shared_expert.py:21)."""

    hidden_size: int
    params_config: SharedExpertParameters
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        out = SwiGLU(
            hidden_size=self.hidden_size,
            intermediate_size=self.params_config.intermediate_size,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="expert",
        )(x)
        if self.params_config.enable_gate:
            gate = nn.Dense(
                1,
                use_bias=False,
                name="gate",
                dtype=self.dtype,
                param_dtype=self.param_dtype,
            )(x)
            out = out * nn.sigmoid(gate)
        return out


# what the EP path sows into ``moe_stats`` beside ``tokens_per_expert``, each
# a scalar that sums over layers and microbatches: receive-buffer rows taken,
# rows needed, fallbacks to the worst-case rung, and dispatches to share over
EP_BUFFER_STATS = (
    "ep_buffer_rows", "ep_rows_needed", "ep_fallbacks", "ep_dispatches",
)


class MoELayer(nn.Module):
    """Router + dispatch + grouped experts + combine (+ shared expert).

    ``ep_axes`` selects the communication handler, mirroring the
    reference's enable_distributed_communicator (layer.py:67):
    - None → local permute only (NoCommunicationHandler).
    - mesh axis tuple → shard_map EP flow over those axes. Expert weights
      must be sharded over ``ep_axes`` on the expert dim (the EP plan
      arranges this).

    Token layout for the EP flow:
    - ``token_axes=None`` (legacy) → tokens are flattened [B·T, D] and
      resharded over ``ep_axes`` at the layer boundary. Correct, but the
      boundary reshard is a real all-to-all the partitioner may implement
      as replicate+slice, and any ep axis that carries no tokens upstream
      (e.g. tp) replicates the dense compute.
    - ``token_axes=(batch_axes, seq_axes)`` → the shard_map rides the
      residual activation layout [B@batch_axes, T@seq_axes, D] directly
      (zero boundary reshard). ep axes that don't shard tokens upstream
      (tp / cp_replicate) subdivide each device's local tokens by their
      axis index — Megatron-sequence-parallel style — and an all-gather
      over those axes after combine restores the local block, so every
      device in the ep fiber owns a disjoint token set and no compute is
      duplicated.
    """

    hidden_dim: int
    intermediate_dim_grouped: int
    num_grouped_experts: int
    top_k: int
    router_renormalize_probabilities: bool = True
    router_enable_expert_bias: bool = False
    # the family's gate score: see TopKRouter.score_function
    router_score_function: str = "softmax"
    # group-limited routing (see TopKRouter.n_group / topk_group)
    router_n_group: int = 1
    router_topk_group: int = 1
    shared_expert: Optional[SharedExpertParameters] = None
    ep_axes: Optional[tuple[str, ...]] = None
    # (batch_axes, seq_axes) of the residual activation layout — see class
    # docstring; None keeps the legacy flatten+reshard EP flow
    token_axes: Optional[tuple[tuple[str, ...], tuple[str, ...]]] = None
    # receive-buffer rows per shard = capacity_factor × n_loc·k (rounded) —
    # this is also the per-shard grouped-GEMM row count, so a factor like
    # 2.0 gives the N·k/ep compute scaling; overflow drops assignment tails
    # deterministically, contributing exact zeros (DeepSeek capacity style).
    # None = dropless, exact results: the buffer is the smallest rung of a
    # fixed ladder (about 1.25 × n_loc·k, from ep = 8 one between,
    # n_loc·k·ep) that holds the largest intake of any shard, chosen per
    # call from the exchanged counts (ops/ep_dispatch.py ep_buffer_ladder).
    # Compute follows the rows that arrive; only routing that sends one
    # shard more than a quarter above its even share pays more, up to the
    # all-gather scale of the last rung, and moe_stats /
    # moe/ep_fallback_share say when. Memory is claimed for the last rung
    # either way: set a factor where that does not fit
    ep_capacity_factor: Optional[float] = None
    # DeepSeek routed_scaling_factor: multiplies the routed experts'
    # combined output (not the shared expert)
    routed_scaling: float = 1.0
    # A held range of a wider router's experts: one chip's share of a
    # layer that expert-parallel chips divide between them, seen from that
    # chip alone. ``num_routed_experts`` is the router's width (0 = the
    # count held: every expert is here) and ``first_held_expert`` the first
    # of the ``num_grouped_experts`` held. The router scores, biases,
    # selects and renormalises over all of them; the pairs routed outside
    # the range drop out before the permutation and only the rows that
    # land here are gathered, multiplied and combined
    # (``held_experts_apply``). What the other experts would add is theirs
    # to compute: nothing here stands in for them.
    num_routed_experts: int = 0
    first_held_expert: int = 0
    # the router as an MLP that may carry state from layer to layer
    # (``TopKRouter.mlp_hidden``, ``.carry``): the call then takes the
    # previous layer's state and returns this layer's beside its output
    router_mlp_hidden: int = 0
    router_carry: bool = False
    router_norm_eps: float = 1e-6
    router_init_jitter: float = 0.0
    # the router's last id is no expert but a skip (ZAYA's
    # mixture-of-depths): a token routed there gets nothing from this
    # layer. It is an id outside the held range like any other
    # (``num_routed_experts`` one more than the experts), so nothing
    # masks it; the flag has the layer count its rows, ``rows_skipped``
    router_skip: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @property
    def router_width(self) -> int:
        return self.num_routed_experts or self.num_grouped_experts

    def setup(self) -> None:
        held, routed = self.num_grouped_experts, self.router_width
        if routed != held:
            if not 0 <= self.first_held_expert <= routed - held:
                raise ValueError(
                    f"{held} experts from {self.first_held_expert} on do "
                    f"not lie inside {routed} routed experts"
                )
            if self.ep_axes is not None:
                raise ValueError(
                    "a held range is one chip's view of an expert-parallel "
                    "layer; with ep_axes the mesh holds every expert"
                )
        if self.router_skip and (
            self.first_held_expert + held >= routed
        ):
            raise ValueError(
                f"a skip is the router's last id, {routed - 1}, outside "
                f"the experts held ({held} from {self.first_held_expert})"
            )
        self.router = TopKRouter(
            dim=self.hidden_dim,
            num_experts=routed,
            top_k=self.top_k,
            renormalize_probabilities=self.router_renormalize_probabilities,
            enable_expert_bias=self.router_enable_expert_bias,
            score_function=self.router_score_function,
            n_group=self.router_n_group,
            topk_group=self.router_topk_group,
            mlp_hidden=self.router_mlp_hidden,
            carry=self.router_carry,
            norm_eps=self.router_norm_eps,
            init_jitter=self.router_init_jitter,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        self.grouped_experts = GroupedSwiGLU(
            hidden_dim=self.hidden_dim,
            intermediate_dim=self.intermediate_dim_grouped,
            num_experts=self.num_grouped_experts,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        if self.shared_expert is not None:
            self.shared_expert_module = SharedSwiGLU(
                hidden_size=self.hidden_dim,
                params_config=self.shared_expert,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
            )

    def __call__(self, hidden: Array, router_state: Optional[Array] = None):
        """[B, T, D] → [B, T, D]; with an MLP router ``(output, the
        router's state [B, T, router_mlp_hidden])``, ``router_state`` being
        the previous layer's."""
        orig_shape = hidden.shape

        # router + shared expert run on the 3D layout: flattening first
        # would detour [B@dp, T@cp] activations through a fused-token
        # sharding and back (replicate-reshard at scale)
        shared = None
        if self.shared_expert is not None:
            shared = self.shared_expert_module(hidden)

        # [B, T, K] twice, and the MLP form's state
        topk_ids, topk_probs, *state = self.router(hidden, router_state)

        # load-balancing stats (reference tokens_per_expert buffer):
        # collected when callers apply with mutable=["moe_stats"]
        per_expert = jnp.bincount(
            topk_ids.reshape(-1), length=self.router_width
        )
        self.sow(
            "moe_stats",
            "tokens_per_expert",
            per_expert,
            reduce_fn=lambda a, b: a + b,
            init_fn=lambda: jnp.zeros((self.router_width,), jnp.int32),
        )
        if self.router_skip:
            self._sow_count("rows_skipped", per_expert[-1])

        k = topk_ids.shape[-1]
        if self.router_width != self.num_grouped_experts:
            out = self._forward_held(
                hidden.reshape(-1, orig_shape[-1]),
                topk_ids.reshape(-1, k),
                topk_probs.reshape(-1, k),
            ).reshape(orig_shape)
        elif self.ep_axes is None:
            out = self._forward_local(
                hidden.reshape(-1, orig_shape[-1]),
                topk_ids.reshape(-1, k),
                topk_probs.reshape(-1, k),
            ).reshape(orig_shape)
        else:
            out = self._forward_ep(hidden, topk_ids, topk_probs)

        if self.routed_scaling != 1.0:
            # DeepSeek-style scale on the ROUTED output only (HF
            # DeepseekV2MoE: routed * factor + shared)
            out = out * jnp.asarray(self.routed_scaling, out.dtype)
        if shared is not None:
            out = out + shared
        return (out, *state) if state else out

    def _sow_count(self, name: str, value) -> None:
        """A scalar of ``moe_stats`` that sums over layers and calls."""
        self.sow(
            "moe_stats", name, jnp.asarray(value, jnp.float32),
            reduce_fn=lambda a, b: a + b,
            init_fn=lambda: jnp.zeros((), jnp.float32),
        )

    # --- local permute path (reference communications/naive.py) ----------

    def _forward_local(
        self, x: Array, topk_ids: Array, topk_probs: Array
    ) -> Array:
        if moe_ffn_backend() == "xla" and few_rows_touch_all_experts(
            *topk_ids.shape, self.num_grouped_experts
        ):
            # a decode step's few rows reach nearly every expert: plain
            # products over all of them, nothing sorted, moved or folded
            return self._all_experts(x, topk_ids, topk_probs)
        with jax.named_scope("moe/permute"):
            sort = sort_tokens_by_expert(topk_ids, self.num_grouped_experts)
        if moe_ffn_backend() in ("pallas", "pallas_gather"):
            # one fused Pallas kernel over the group-aligned layout: the
            # [M, 2*inter]/[M, inter] intermediates and the gate+up weight
            # concat never touch HBM (ops/moe_pallas.py; backward runs
            # the XLA chain below via custom_vjp — identical math).
            # pallas_gather additionally keeps x resident in VMEM and
            # gathers rows in-kernel (no HBM aligned activation buffer),
            # and by default folds the combine in too: the kernel
            # scatter-accumulates token-major [N, D] output in VMEM, so
            # the expert-sorted y rows never hit HBM either
            # (D9D_TPU_MOE_COMBINE=unfused for the A/B)
            with jax.named_scope("moe/experts/fused_ffn"):
                return fused_moe_ffn_apply(
                    x, topk_probs, sort,
                    self.grouped_experts.gate_weight,
                    self.grouped_experts.up_weight,
                    self.grouped_experts.down_weight,
                    self.dtype,
                    num_experts=self.num_grouped_experts,
                )
        with jax.named_scope("moe/permute"):
            permuted_x, permuted_probs = permute_tokens(x, topk_probs, sort)
        y = self.grouped_experts(permuted_x, permuted_probs, sort.group_sizes)
        with jax.named_scope("moe/combine"):
            return unpermute_combine(y, sort, x.shape[0]).astype(x.dtype)

    # --- a held range of the router's experts ----------------------------

    def _forward_held(
        self, x: Array, topk_ids: Array, topk_probs: Array
    ) -> Array:
        held = self.num_grouped_experts
        with jax.named_scope("moe/permute"):
            local = topk_ids - self.first_held_expert
            local = jnp.where((local >= 0) & (local < held), local, held)
        # routed pairs that landed here, and all of them: the share says
        # how far the rows computed are from an even router's
        self._sow_count("rows_held", (local < held).sum())
        self._sow_count("rows_routed", local.size)
        if moe_ffn_backend() == "xla" and few_rows_touch_all_experts(
            *local.shape, self.router_width, HELD_FEW_ROWS_LIMIT
        ):
            # a decode step: every held expert's weights once through
            # plain products, whatever the routing (an id of ``held``, a
            # pair routed elsewhere, matches no expert and adds nothing)
            return self._all_experts(x, local, topk_probs)
        return held_experts_apply(
            x, local, topk_probs, self._expert_weights(),
            num_routed=self.router_width, dtype=self.dtype,
        )

    def _expert_weights(self):
        experts = self.grouped_experts
        return experts.gate_weight, experts.up_weight, experts.down_weight

    def _all_experts(self, x: Array, ids: Array, probs: Array) -> Array:
        return all_experts_swiglu(
            x, ids, probs, *self._expert_weights(), self.dtype
        ).astype(x.dtype)

    # --- EP path (reference communications/deepep.py, re-designed) -------

    def _forward_ep(
        self, hidden: Array, topk_ids: Array, topk_probs: Array
    ) -> Array:
        """hidden [B, T, D], ids/probs [B, T, K] → [B, T, D]."""
        from d9d_tpu.core.mesh import resolve_ambient_mesh

        ep_axes = tuple(self.ep_axes)
        mesh = resolve_ambient_mesh(ep_axes, what="MoE EP path")
        ep_size = 1
        for a in ep_axes:
            ep_size *= mesh.shape[a]
        num_experts = self.num_grouped_experts
        if num_experts % ep_size != 0:
            raise ValueError(
                f"num_experts {num_experts} not divisible by ep size {ep_size}"
            )
        e_loc = num_experts // ep_size
        dtype = self.dtype
        capacity = self.ep_capacity_factor

        def expert_fn(rows, group_sizes, gate_w, up_w, down_w):
            return grouped_swiglu_apply(
                rows,
                jnp.ones((rows.shape[0],), jnp.float32),
                group_sizes,
                gate_w,
                up_w,
                down_w,
                dtype,
            )

        def dispatch_local(x_loc, ids_loc, probs_loc, gate_w, up_w, down_w):
            out, use = ep_dispatch_compute_combine(
                x_loc,
                ids_loc,
                probs_loc,
                expert_fn,
                (gate_w, up_w, down_w),
                ep_axes=ep_axes,
                e_loc=e_loc,
                ep_world=ep_size,
                capacity_factor=capacity,
            )
            # one row a device: what its EP group's buffer took and needed
            return out, jnp.stack(use).astype(jnp.float32)[None]

        if self.token_axes is None:
            # legacy flow: flatten tokens globally, reshard over ep_axes
            d = hidden.shape[-1]
            k = topk_ids.shape[-1]
            out, use = compat.shard_map(
                dispatch_local,
                mesh=mesh,
                in_specs=(P(ep_axes, None),) * 3
                + (P(ep_axes, None, None),) * 3,
                out_specs=(P(ep_axes, None), P(ep_axes, None)),
                axis_names=set(ep_axes),
            )(
                hidden.reshape(-1, d),
                topk_ids.reshape(-1, k),
                topk_probs.reshape(-1, k),
                *self._expert_weights(),
            )
            self._sow_ep_buffer_use(use)
            return out.reshape(hidden.shape).astype(hidden.dtype)

        # token-layout flow: ride the residual sharding, no boundary reshard
        batch_axes, seq_axes = (tuple(a) for a in self.token_axes)
        token_carrying = set(batch_axes) | set(seq_axes)
        dup_axes = tuple(a for a in ep_axes if a not in token_carrying)
        dup = 1
        for a in dup_axes:
            dup *= mesh.shape[a]
        tok_spec = P(batch_axes, seq_axes, None)

        def ep_body(x_loc, ids_loc, probs_loc, gate_w, up_w, down_w):
            b_loc, t_loc, d = x_loc.shape
            n_full = b_loc * t_loc
            x_flat = x_loc.reshape(n_full, d)
            ids_flat = ids_loc.reshape(n_full, -1)
            probs_flat = probs_loc.reshape(n_full, -1)

            if dup > 1:
                # ep axes that shard no tokens upstream see a replicated
                # local block: subdivide ownership by axis index so the ep
                # fiber's token sets stay disjoint (Megatron-SP style)
                if n_full % dup != 0:
                    raise ValueError(
                        f"local token count {n_full} not divisible by the "
                        f"non-token ep axes {dup_axes} (size {dup})"
                    )
                n_own = n_full // dup
                idx = lax.axis_index(dup_axes)
                start = idx * n_own
                x_flat = lax.dynamic_slice_in_dim(x_flat, start, n_own)
                ids_flat = lax.dynamic_slice_in_dim(ids_flat, start, n_own)
                probs_flat = lax.dynamic_slice_in_dim(probs_flat, start, n_own)

            out, use = dispatch_local(
                x_flat, ids_flat, probs_flat, gate_w, up_w, down_w
            )

            if dup > 1:
                # restore the full local block (and with it, replication
                # over the non-token ep axes the out_spec declares)
                out = lax.all_gather(out, dup_axes, axis=0, tiled=True)
            return out.reshape(b_loc, t_loc, d), use

        out, use = compat.shard_map(
            ep_body,
            mesh=mesh,
            in_specs=(tok_spec,) * 3 + (P(ep_axes, None, None),) * 3,
            # EP groups route their own tokens and may take different
            # rungs: every device of the mesh reports its group's
            out_specs=(tok_spec, P(tuple(mesh.axis_names), None)),
            # the tiled all_gather over dup_axes makes the output invariant
            # there, which vma inference cannot see statically
            check_vma=False,
        )(hidden, topk_ids, topk_probs, *self._expert_weights())
        self._sow_ep_buffer_use(use)
        return out.astype(hidden.dtype)

    def _sow_ep_buffer_use(self, use: Array) -> None:
        """``use [devices, 3]``: what each device's EP group took, needed
        and whether it fell back (``ops/ep_dispatch.py EpBufferUse``).
        Sown as means over the devices, beside ``tokens_per_expert``, with
        a count of dispatches to take shares over: a run whose routing has
        drifted out of the snug rung says so in its metrics."""
        values = (*use.mean(axis=0), jnp.ones((), jnp.float32))
        for name, value in zip(EP_BUFFER_STATS, values):
            self._sow_count(name, value)
