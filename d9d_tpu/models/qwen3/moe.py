"""Qwen3-MoE model family: stage-aware backbone + task heads.

Reference: d9d/module/model/qwen3_moe/model.py:29,221,322,425 and
params.py:4-93. Same structure as the dense family, with the FFN replaced
by an MoE layer (all layers sparse by default; ``mlp_only_layers`` keeps
specific layers dense, matching HF Qwen3MoE semantics).
"""

import contextlib
import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding

from d9d_tpu.core.types import Array
from d9d_tpu.nn.attention import GroupedQueryAttention
from d9d_tpu.nn.cca import near
from d9d_tpu.nn.embedding import TokenEmbedding
from d9d_tpu.nn.hyper_connections import expand_streams, sum_streams
from d9d_tpu.nn import logical_axes as la
from d9d_tpu.nn.heads import (
    LM_IGNORE_INDEX,
    ClassificationHead,
    EmbeddingHead,
    LanguageModellingHead,
)
from d9d_tpu.nn.mlp import SwiGLU
from d9d_tpu.nn.moe import MoELayer, SharedExpertParameters
from d9d_tpu.nn.norm import RMSNorm
from d9d_tpu.nn.vocab_ranges import concat_vocab_ranges
from d9d_tpu.nn.sdpa.protocol import SdpaBackend
from d9d_tpu.ops import (
    RopeScaling,
    RopeScalingNone,
    compute_rope_frequencies,
    make_rope_cos_sin,
)
from d9d_tpu.telemetry import numerics
from d9d_tpu.pipelining import (
    PipelineStageInfo,
    distribute_layers_for_pipeline_stage,
)


@dataclasses.dataclass(frozen=True)
class MLAParameters:
    """Multi-head-latent attention geometry (DeepSeek-V2 family;
    nn/attention.py MultiHeadLatentAttention). When set on a config,
    every attention layer runs MLA instead of GQA and rope frequencies
    are computed over ``qk_rope_head_dim``."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    q_lora_rank: Optional[int] = None
    # override the default d_qk**-0.5 (DeepSeek yarn mscale: the
    # checkpoint's softmax scale carries a yarn temperature factor)
    softmax_scale: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class Mamba2Parameters:
    """Geometry of the ``"mamba2"`` kind's mixer (``nn/mamba.py
    Mamba2Mixer``): ``num_heads`` heads of ``head_dim`` channels whose
    decay, skip and step bias are one number a head, a state of
    ``d_state`` numbers a channel, B and C shared by the heads of each of
    ``n_groups`` groups, ``d_conv`` taps, and the prefill scan's chunk."""

    num_heads: int
    head_dim: int
    d_state: int = 128
    n_groups: int = 1
    d_conv: int = 4
    chunk_size: int = 256


@dataclasses.dataclass(frozen=True)
class KdaParameters:
    """Geometry of the ``"kda"`` kind's mixer (``nn/linear_attention.py
    KimiDeltaAttention``): ``num_heads`` heads of ``head_dim`` key and
    value channels, a decay a key channel, ``conv_size`` taps, the decay
    and output gates' rank (0 = ``head_dim``), a write strength to 2
    under ``allow_neg_eigval``, and the chunked form's chunk."""

    num_heads: int
    head_dim: int
    conv_size: int = 4
    gate_rank: int = 0
    allow_neg_eigval: bool = False
    chunk_size: int = 64


@dataclasses.dataclass(frozen=True)
class CcaParameters:
    """The ``"cca"`` kind's block (``nn/cca.py CompressedConvAttention``,
    on the config's ``num_heads``, ``num_kv_heads`` and ``head_dim``):
    the taps of its two convolutions and the readings its published
    configuration leaves open, each a switch of the module."""

    time0: int = 2
    time1: int = 2
    conv1_grouped: bool = True
    qk_mean: bool = True
    value_shift: bool = True
    key_temperature: bool = True


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """Constants on the stack's path (the Granite family's four): on the
    embedding table's output, on both residual branches of every layer, a
    divisor on the head's logits (in the fused loss too), and the softmax
    scale of the GQA kinds where it is not ``head_dim ** -0.5``."""

    embedding: float = 1.0
    residual: float = 1.0
    logits_divisor: float = 1.0
    attention_softmax_scale: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """One more grouped-query attention kind of a stack that mixes them
    (``Qwen3MoeConfig.attention_kinds``), by what it changes from the
    config's plain fields, which stay the settings of the kind named
    ``"attention"``: ``None`` keeps the plain field. A window makes the
    kind's layers attend the last ``window_size`` positions only, and the
    serving loop then keeps no more of them (``nn/attention.py``, "ring of
    pages"); ``use_sinks`` gives each query head a learned logit that
    joins the softmax's denominator alone. ``num_heads`` is the kind's
    count of query heads (its ``q_proj``, gate and ``o_proj`` widths);
    ``rope_fraction`` and ``rope_scaling`` are the share of a head the
    kind rotates and the law its frequencies follow, so that one stack
    holds a half-rotated YaRN kind beside a plainly, wholly rotated one."""

    num_kv_heads: Optional[int] = None
    rope_theta: Optional[float] = None
    window_size: Optional[int] = None
    use_sinks: bool = False
    num_heads: Optional[int] = None
    rope_fraction: Optional[float] = None
    rope_scaling: Optional[RopeScaling] = None


# the token-mixer kinds a layer can be without an entry in
# ``attention_kinds``: grouped-query attention with the plain fields,
# latent attention (``mla``), a Mamba-1 mixer, a Mamba-2 mixer, a
# GatedDeltaNet block, a Kimi delta attention block, compressed
# convolutional attention
LAYER_KINDS = ("attention", "mla", "mamba", "mamba2", "gdn", "kda", "cca")
# a stack with a layer of one of these adds its residual stream in
# float32: the state-space mixers (Mamba's ``residual_in_fp32``) and Kimi
# delta attention, whose float32 state remembers a prompt's roundings for
# as long as it remembers the prompt (on the chip the share cell's bf16
# stream stood 0.0082 from the float32 reference and its served streams
# left ``generate``'s at reference gaps up to 0.012 of the 0.02 allowed;
# with this 0.0057 and 0.0036, for 0.5 % of the rate: PERF.md, PR 51),
# and compressed convolutional attention, whose unit queries and keys
# make a softmax at logits up to sqrt(head_dim) out of whatever the
# stream's rounding left (twelve layers' bf16 stream stood 0.0073 to
# 0.0080 from the reference with served streams leaving ``generate``'s at
# gaps up to 0.0185 of the 0.02; with this 0.0037 to 0.0047 and 0.0104,
# for 1.0 % of the rate: PERF.md, PR 56)
FLOAT32_STREAM_KINDS = ("mamba", "mamba2", "kda", "cca")


@dataclasses.dataclass(frozen=True)
class Qwen3MoeConfig:
    vocab_ranges: tuple[tuple[str, int], ...]
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    # dense FFN width for layers listed in mlp_only_layers
    intermediate_size: int = 0
    mlp_only_layers: tuple[int, ...] = ()
    shared_expert: Optional[SharedExpertParameters] = None
    norm_topk_prob: bool = True
    rope_theta: float = 1_000_000.0
    rope_scaling: RopeScaling = RopeScalingNone()
    qk_norm: bool = True
    norm_eps: float = 1e-6
    remat: bool = True
    # see Qwen3DenseConfig.remat_policy
    remat_policy: str = "full"
    # keep the compiler from merging a layer's rematerialised forward with
    # the forward it repeats (``jax.checkpoint(prevent_cse=...)``). Off,
    # the layers are unrolled and the compiler may keep what the remat
    # meant to drop: five Xing4.0 layers and the MTP block claimed 12.9 GB
    # of temporaries off and 8.1 GB on (described-v5e compile, PR 35). The
    # shallower presets leave it off and keep the programs they had. On,
    # every layer's backward truly runs its forward again, all of it but
    # the flash kernel (remat_policy keeps that call's output and
    # log-sum-exp)
    remat_prevent_cse: bool = False
    # Qwen3-Next attention features: sigmoid output gate on attention
    # layers, partial rotary (frequencies computed over the rotary dim),
    # zero-centered RMSNorm weights (scale = 1 + w) on every norm except
    # the GDN gated output norm
    use_output_gate: bool = False
    # the gate is one logit a query head, broadcast over the head's
    # numbers (``gate_proj`` of width ``h``), not one a number
    output_gate_per_head: bool = False
    # single matmul for q/k/v (see nn/attention.py fused_qkv)
    fused_qkv: bool = False
    rope_fraction: float = 1.0
    zero_centered_norms: bool = False
    # mesh axes carrying expert parallelism; None = local experts
    ep_axes: Optional[tuple[str, ...]] = None
    # (batch_axes, seq_axes) of the residual activation layout; when set,
    # the EP flow shard_maps over this layout directly (no boundary
    # reshard) — see MoELayer.token_axes
    moe_token_axes: Optional[tuple[tuple[str, ...], tuple[str, ...]]] = None
    # Hybrid linear-attention layers (beyond-reference; Qwen3-Next-style
    # 3:1 GDN:attention stacks): listed layer indices swap GQA for a
    # GatedDeltaNet block. Geometry defaults derive from the attention
    # dims when the gdn_* fields are 0.
    linear_attention_layers: tuple[int, ...] = ()
    gdn_qk_heads: int = 0
    gdn_v_heads: int = 0
    gdn_head_qk_dim: int = 0
    gdn_head_v_dim: int = 0
    gdn_conv_size: int = 4
    # EP dispatch buffer sizing (see MoELayer.ep_capacity_factor): a factor
    # like 2.0 gives N·k/ep per-shard compute with deterministic drops;
    # None = dropless and exact, the buffer a rung chosen per call from the
    # exchanged counts, the worst case N·k only as the fallback
    ep_capacity_factor: Optional[float] = None
    # MLA attention on every (non-GDN) layer when set — the DeepSeek-V2
    # family rides this backbone (models/deepseek/)
    mla: Optional[MLAParameters] = None
    # DeepSeek routed_scaling_factor (routed experts' output only)
    routed_scaling_factor: float = 1.0
    # group-limited routing (DeepSeek group_limited_greedy; see
    # TopKRouter.n_group / topk_group); 1 = plain top-k
    router_n_group: int = 1
    router_topk_group: int = 1
    # the gate's score function and its selection bias (DeepSeek-V3
    # ``noaux_tc``: sigmoid scores, ``e_score_correction_bias``); see
    # TopKRouter
    router_score_function: str = "softmax"
    router_expert_bias: bool = False
    # State-space layers (models/jamba/): listed layers swap attention for
    # a Mamba-1 mixer (nn/mamba.py); a stack with any adds its residual
    # stream in float32 (Mamba's residual_in_fp32). dt_rank 0 = hidden/16.
    mamba_layers: tuple[int, ...] = ()
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # the ``"mamba2"`` kind's block (models/granite/) and the stack's
    # constant multipliers: one block a mechanism, see the two classes
    mamba2: Optional[Mamba2Parameters] = None
    # the ``"kda"`` kind's block (models/solar/)
    kda: Optional[KdaParameters] = None
    multipliers: Multipliers = Multipliers()
    # the ``"cca"`` kind's block (models/zaya/)
    cca: Optional[CcaParameters] = None
    # the ZAYA block's other parts. The router is an MLP
    # ``router_hidden_size`` wide (0 = one matrix; nn/moe.py TopKRouter)
    # whose state, with ``router_carry``, goes from each layer to the
    # next: the layer loop then carries it beside the stream.
    # ``router_skip``: the router's last id is a skip
    # (``num_routed_experts`` is one more than the experts held).
    # ``residual_scaling``: both residual additions are ``s_r * (x + b_r)
    # + s_h * (branch + b_h)`` with four learned vectors each
    # (``ScaledResidual``). ``init_jitter``: noise on the ones and zeros
    # these vectors, the router's biases and the attention's temperature
    # start at, for seeded weights that are to exercise them
    router_hidden_size: int = 0
    router_carry: bool = False
    router_skip: bool = False
    residual_scaling: bool = False
    init_jitter: float = 0.0
    # the output head reads the embedding table (no head parameters);
    # the table is then drawn at embedding_init_std, the family's
    # initializer_range, so that logits are of order 1 at init
    tie_word_embeddings: bool = False
    embedding_init_std: float = 1.0
    # A chip's share of an expert-parallel layer (MoELayer's held range):
    # ``num_experts`` stays the count held, the weights' leading dimension;
    # the router is ``num_routed_experts`` wide (0 = the count held) and
    # the experts held are that many from ``first_held_expert`` on
    num_routed_experts: int = 0
    first_held_expert: int = 0
    # manifold-constrained hyper-connections (nn/hyper_connections.py):
    # the residual stream is ``hc_mult`` rows a token, mixed around every
    # sublayer by coefficients of the token; 1 = the plain residual path
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple[float, float] = (-30.0, 30.0)
    # trained multi-token-prediction modules (DeepSeek-V3 section 2.2;
    # MultiTokenPrediction below): 0 or 1. With labels the per-token loss
    # is next-token loss + ``mtp_loss_weight`` x the module's loss on the
    # token after; ``logits`` and decode run the stack alone
    num_mtp_modules: int = 0
    mtp_loss_weight: float = 0.3
    # The per-layer pattern of token-mixer kinds: ``layer_kinds[i]`` names
    # layer i's, one of ``LAYER_KINDS`` or a name ``attention_kinds``
    # defines. Empty, the pattern is what ``mamba_layers``,
    # ``linear_attention_layers`` and ``mla`` say, in that order (the
    # presets that predate it): ``layer_kind`` is the one reading of both
    layer_kinds: tuple[str, ...] = ()
    attention_kinds: tuple[tuple[str, AttentionKind], ...] = ()
    # a value head narrower than the query/key head (0 = ``head_dim``) and
    # a constant on the value projection's output, in every GQA kind
    v_head_dim: int = 0
    attention_value_scale: float = 1.0

    def __post_init__(self):
        named = dict(self.attention_kinds)
        if set(named) & set(LAYER_KINDS):
            raise ValueError(
                f"attention_kinds may not redefine {LAYER_KINDS}: {set(named)}"
            )
        unknown = set(self.layer_kinds) - set(LAYER_KINDS) - set(named)
        if unknown:
            raise ValueError(f"layer_kinds names no kind: {sorted(unknown)}")
        if self.layer_kinds and len(self.layer_kinds) != self.num_layers:
            raise ValueError(
                f"{len(self.layer_kinds)} layer_kinds for {self.num_layers} "
                "layers"
            )
        if self.router_carry and not self.router_hidden_size:
            raise ValueError("router_carry needs the router's MLP form")
        if self.residual_scaling and self.hc_mult > 1:
            raise ValueError(
                "residual_scaling and hc_mult both replace the residual "
                "addition"
            )

    @property
    def vocab_size(self) -> int:
        return sum(s for _, s in self.vocab_ranges)

    def layer_kind(self, layer_idx: int) -> str:
        """The token-mixer kind of layer ``layer_idx``. A layer past the
        stack's (the multi-token-prediction block) is of the kind a
        stack without a pattern would give it."""
        if layer_idx < len(self.layer_kinds):
            return self.layer_kinds[layer_idx]
        if layer_idx in self.mamba_layers:
            return "mamba"
        if layer_idx in self.linear_attention_layers:
            return "gdn"
        return "attention" if self.mla is None else "mla"

    @property
    def float32_stream(self) -> bool:
        return any(
            self.layer_kind(i) in FLOAT32_STREAM_KINDS
            for i in range(self.num_layers)
        )

    def attention_kind(self, kind: str) -> AttentionKind:
        """The settings of GQA kind ``kind``, every field filled in."""
        own = dict(self.attention_kinds).get(kind, AttentionKind())
        return AttentionKind(
            num_kv_heads=own.num_kv_heads or self.num_kv_heads,
            rope_theta=own.rope_theta or self.rope_theta,
            window_size=own.window_size,
            use_sinks=own.use_sinks,
            num_heads=own.num_heads or self.num_heads,
            rope_fraction=(
                self.rope_fraction if own.rope_fraction is None
                else own.rope_fraction
            ),
            rope_scaling=own.rope_scaling or self.rope_scaling,
        )

    @staticmethod
    def tiny(vocab_size: int = 256, ep_axes=None) -> "Qwen3MoeConfig":
        return Qwen3MoeConfig(
            vocab_ranges=(("default", vocab_size),),
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            moe_intermediate_size=64,
            num_experts=8,
            num_experts_per_tok=2,
            remat=False,
            ep_axes=ep_axes,
        )

    @staticmethod
    def hybrid_tiny(vocab_size: int = 256, ep_axes=None) -> "Qwen3MoeConfig":
        """CPU-runnable hybrid: GDN on 3 of 4 layers (Qwen3-Next 3:1 ratio)."""
        return Qwen3MoeConfig(
            vocab_ranges=(("default", vocab_size),),
            hidden_size=64,
            num_layers=4,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            moe_intermediate_size=64,
            num_experts=8,
            num_experts_per_tok=2,
            remat=False,
            ep_axes=ep_axes,
            linear_attention_layers=(0, 1, 2),
        )

    @staticmethod
    def qwen3_next_80b_a3b(vocab_size: int = 151_936, ep_axes=None) -> "Qwen3MoeConfig":
        """Qwen3-Next-80B-A3B geometry: 3:1 GDN:attention hybrid + MoE
        (beyond-reference flagship for the linear-attention family;
        BASELINE config 5). Matches HF transformers' Qwen3Next semantics:
        gated attention output, partial rotary (0.25, frequencies over the
        rotary dim), zero-centered norms, gated shared expert."""
        return Qwen3MoeConfig(
            vocab_ranges=(("default", vocab_size),),
            hidden_size=2048,
            num_layers=48,
            num_heads=16,
            num_kv_heads=2,
            head_dim=256,
            moe_intermediate_size=512,
            num_experts=512,
            num_experts_per_tok=10,
            shared_expert=SharedExpertParameters(
                intermediate_size=512, enable_gate=True
            ),
            ep_axes=ep_axes,
            linear_attention_layers=tuple(
                i for i in range(48) if i % 4 != 3
            ),
            gdn_qk_heads=16,
            gdn_v_heads=32,
            gdn_head_qk_dim=128,
            gdn_head_v_dim=128,
            use_output_gate=True,
            rope_fraction=0.25,
            zero_centered_norms=True,
            rope_theta=10_000_000.0,
        )

    @staticmethod
    def qwen3_30b_a3b(vocab_size: int = 151_936, ep_axes=None) -> "Qwen3MoeConfig":
        """Qwen3-30B-A3B geometry (flagship MoE, BASELINE config 3)."""
        return Qwen3MoeConfig(
            vocab_ranges=(("default", vocab_size),),
            hidden_size=2048,
            num_layers=48,
            num_heads=32,
            num_kv_heads=4,
            head_dim=128,
            moe_intermediate_size=768,
            num_experts=128,
            num_experts_per_tok=8,
            ep_axes=ep_axes,
        )


class ScaledResidual(nn.Module):
    """``s_r * (x + b_r) + s_h * (branch + b_h)``: a residual addition
    with a learned scale and bias a channel on each side (the ZAYA
    block's; ones and zeros at the published init). Float32 sums, the
    result in the stream's type."""

    hidden_size: int
    init_jitter: float = 0.0
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array, branch: Array) -> Array:
        def vector(name, value):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    near(value, self.init_jitter), (None,)
                ),
                (self.hidden_size,), self.param_dtype,
            ).astype(jnp.float32)

        with jax.named_scope("residual_scale"):
            out = vector("stream_scale", 1.0) * (
                x.astype(jnp.float32) + vector("stream_bias", 0.0)
            ) + vector("branch_scale", 1.0) * (
                branch.astype(jnp.float32) + vector("branch_bias", 0.0)
            )
            return out.astype(x.dtype)


class Qwen3MoeDecoderLayer(nn.Module):
    config: Qwen3MoeConfig
    sdpa: SdpaBackend
    layer_idx: int
    # KV-cache / GDN-state decode mode (loop/generate.py); 0 = training
    decode_max_length: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: Array,
        cos: Optional[Array],
        sin: Optional[Array],
        mask: Optional[Array] = None,
        padding_mask: Optional[Array] = None,
        router_state: Optional[Array] = None,
    ):
        """The stream after the layer; with ``router_carry`` ``(stream,
        the router's state)``, ``router_state`` being the layer before's
        (None for the first)."""
        cfg = self.config
        zc = cfg.zero_centered_norms
        # hc_mult > 1: ``x`` is the n-stream ``[B, T, n, C]`` and each
        # sublayer reads a mix of it and writes into all of it
        hc = self._hyper_connection if cfg.hc_mult > 1 else None
        if hc:
            attn_hc = hc("attn_mhc")
            attn_in, attn_mix = attn_hc.read(x)
        else:
            attn_in = x
        normed = RMSNorm(
            cfg.hidden_size, eps=cfg.norm_eps, zero_centered=zc,
            name="input_layernorm",
        )(attn_in)
        kind = cfg.layer_kind(self.layer_idx)
        if kind == "mamba":
            from d9d_tpu.nn.mamba import MambaMixer

            # like GDN below, the mixer zeroes padded positions itself
            # and takes the [B, T] ``padding_mask``
            attn_out = MambaMixer(
                hidden_size=cfg.hidden_size,
                d_state=cfg.mamba_d_state,
                d_conv=cfg.mamba_d_conv,
                expand=cfg.mamba_expand,
                dt_rank=cfg.mamba_dt_rank,
                norm_eps=cfg.norm_eps,
                decode=self.decode_max_length > 0,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="mamba",
            )(normed, padding_mask)
        elif kind == "mamba2":
            from d9d_tpu.nn.mamba import Mamba2Mixer

            attn_out = Mamba2Mixer(
                hidden_size=cfg.hidden_size,
                **dataclasses.asdict(cfg.mamba2),
                norm_eps=cfg.norm_eps,
                decode=self.decode_max_length > 0,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="mamba",
            )(normed, padding_mask)
        elif kind == "gdn":
            from d9d_tpu.nn.linear_attention import GatedDeltaNet

            # GDN zeroes padded positions before the conv/recurrence (HF
            # Qwen3Next's apply_mask_to_padding_states); the sdpa-style
            # ``mask`` cannot express this, so padded batches must pass the
            # [B, T] ``padding_mask`` alongside it
            attn_out = GatedDeltaNet(
                hidden_size=cfg.hidden_size,
                num_qk_heads=cfg.gdn_qk_heads or cfg.num_kv_heads,
                num_v_heads=cfg.gdn_v_heads or cfg.num_heads,
                head_qk_dim=cfg.gdn_head_qk_dim or cfg.head_dim,
                head_v_dim=cfg.gdn_head_v_dim or cfg.head_dim,
                conv_size=cfg.gdn_conv_size,
                norm_eps=cfg.norm_eps,
                decode=self.decode_max_length > 0,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="linear_attn",
            )(normed, padding_mask)
        elif kind == "kda":
            from d9d_tpu.nn.linear_attention import KimiDeltaAttention

            # named ``kda``: its ops fall under ``/kda/`` in a trace and
            # not under the attention layers' ``/self_attn/``
            attn_out = KimiDeltaAttention(
                hidden_size=cfg.hidden_size,
                **dataclasses.asdict(cfg.kda),
                norm_eps=cfg.norm_eps,
                decode=self.decode_max_length > 0,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="kda",
            )(normed, padding_mask)
        elif kind == "cca":
            from d9d_tpu.nn.cca import CompressedConvAttention

            # named ``self_attn``: a trace counts the whole sublayer with
            # the attention layers
            attn_out = CompressedConvAttention(
                hidden_size=cfg.hidden_size,
                num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim,
                **dataclasses.asdict(cfg.cca),
                rope_fraction=cfg.rope_fraction,
                init_jitter=cfg.init_jitter,
                softmax_scale=cfg.multipliers.attention_softmax_scale,
                sdpa=self.sdpa,
                decode_max_length=self.decode_max_length,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="self_attn",
            )(normed, cos, sin, mask, padding_mask)
        elif kind == "mla":
            from d9d_tpu.nn.attention import MultiHeadLatentAttention

            attn_out = MultiHeadLatentAttention(
                hidden_size=cfg.hidden_size,
                num_heads=cfg.num_heads,
                qk_nope_head_dim=cfg.mla.qk_nope_head_dim,
                qk_rope_head_dim=cfg.mla.qk_rope_head_dim,
                v_head_dim=cfg.mla.v_head_dim,
                kv_lora_rank=cfg.mla.kv_lora_rank,
                q_lora_rank=cfg.mla.q_lora_rank,
                softmax_scale=cfg.mla.softmax_scale,
                sdpa=self.sdpa,
                norm_eps=cfg.norm_eps,
                decode_max_length=self.decode_max_length,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="self_attn",
            )(normed, cos, sin, mask)
        else:
            own = cfg.attention_kind(kind)
            # a kind beside the plain one says so in its ops' scope
            # (``layers_3/attn_window/self_attn/...``): a trace tells a
            # window layer's attention from a full one's
            scope = (
                contextlib.nullcontext() if kind == "attention"
                else jax.named_scope(f"attn_{kind}")
            )
            with scope:
                attn_out = GroupedQueryAttention(
                    hidden_size=cfg.hidden_size,
                    num_heads=own.num_heads,
                    num_kv_heads=own.num_kv_heads,
                    head_dim=cfg.head_dim,
                    v_head_dim=cfg.v_head_dim,
                    value_scale=cfg.attention_value_scale,
                    sdpa=self.sdpa,
                    qk_norm=cfg.qk_norm,
                    qk_norm_zero_centered=zc,
                    use_output_gate=cfg.use_output_gate,
                    gate_per_head=cfg.output_gate_per_head,
                    fused_qkv=cfg.fused_qkv,
                    rope_fraction=own.rope_fraction,
                    window_size=own.window_size,
                    use_sinks=own.use_sinks,
                    softmax_scale=cfg.multipliers.attention_softmax_scale,
                    decode_max_length=self.decode_max_length,
                    dtype=self.dtype,
                    param_dtype=self.param_dtype,
                    name="self_attn",
                )(normed, cos, sin, mask)
        branch = cfg.multipliers.residual
        if branch != 1.0:
            # on the branch in the stream's type, before the addition
            attn_out = branch * attn_out.astype(x.dtype)
        if hc:
            x = attn_hc.write(x, attn_out, attn_mix)
            mlp_hc = hc("mlp_mhc")
            mlp_in, mlp_mix = mlp_hc.read(x)
        elif cfg.residual_scaling:
            x = mlp_in = self._scaled_residual("attn_residual")(x, attn_out)
        else:
            x = mlp_in = x + attn_out
        h = RMSNorm(
            cfg.hidden_size, eps=cfg.norm_eps, zero_centered=zc,
            name="post_attention_layernorm",
        )(mlp_in)
        if self.layer_idx in cfg.mlp_only_layers:
            mlp_out = SwiGLU(
                hidden_size=cfg.hidden_size,
                intermediate_size=cfg.intermediate_size,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="mlp",
            )(h)
        else:
            mlp_out = MoELayer(
                hidden_dim=cfg.hidden_size,
                intermediate_dim_grouped=cfg.moe_intermediate_size,
                num_grouped_experts=cfg.num_experts,
                top_k=cfg.num_experts_per_tok,
                router_renormalize_probabilities=cfg.norm_topk_prob,
                router_enable_expert_bias=cfg.router_expert_bias,
                router_score_function=cfg.router_score_function,
                shared_expert=cfg.shared_expert,
                ep_axes=cfg.ep_axes,
                token_axes=cfg.moe_token_axes,
                ep_capacity_factor=cfg.ep_capacity_factor,
                routed_scaling=cfg.routed_scaling_factor,
                router_n_group=cfg.router_n_group,
                router_topk_group=cfg.router_topk_group,
                num_routed_experts=cfg.num_routed_experts,
                first_held_expert=cfg.first_held_expert,
                router_mlp_hidden=cfg.router_hidden_size,
                router_carry=cfg.router_carry,
                router_norm_eps=cfg.norm_eps,
                router_init_jitter=cfg.init_jitter,
                router_skip=cfg.router_skip,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="mlp",
            )(h, router_state)
            if cfg.router_hidden_size:
                mlp_out, router_state = mlp_out
        if branch != 1.0:
            mlp_out = branch * mlp_out.astype(x.dtype)
        if hc:
            x = mlp_hc.write(x, mlp_out, mlp_mix)
        elif cfg.residual_scaling:
            x = self._scaled_residual("mlp_residual")(x, mlp_out)
        else:
            x = x + mlp_out
        return (x, router_state) if cfg.router_carry else x

    def _scaled_residual(self, name: str):
        return ScaledResidual(
            hidden_size=self.config.hidden_size,
            init_jitter=self.config.init_jitter,
            param_dtype=self.param_dtype,
            name=name,
        )

    def _hyper_connection(self, name: str):
        from d9d_tpu.nn.hyper_connections import HyperConnection

        cfg = self.config
        return HyperConnection(
            hidden_size=cfg.hidden_size,
            streams=cfg.hc_mult,
            sinkhorn_iters=cfg.hc_sinkhorn_iters,
            eps=cfg.hc_eps,
            res_clamp=cfg.hc_res_clamp,
            norm_eps=cfg.norm_eps,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name=name,
        )


def rope_cos_sin(
    cfg: Qwen3MoeConfig, positions: Array, kind: str = "attention"
):
    """``(cos, sin)`` at ``positions`` for the rotary geometry of the
    config's attention kind ``kind`` (its base, the share of a head it
    rotates, its scaling law), or ``(None, None)`` where nothing is
    rotated."""
    own = cfg.attention_kind(kind)
    # partial rotary (rope_fraction < 1): frequencies are computed over
    # the rotary dim, not head_dim (NeoX/Qwen3-Next semantics). MLA
    # (DeepSeek) rotates only its decoupled rope sub-vector.
    rotary_dim = (
        cfg.mla.qk_rope_head_dim if cfg.mla is not None
        else int(cfg.head_dim * own.rope_fraction)
    )
    # rope_fraction 0 (no positional encoding: the attention layers
    # of a state-space hybrid) rotates nothing: no frequencies
    if not rotary_dim:
        return None, None
    inv_freq, att_scale = compute_rope_frequencies(
        rotary_dim, own.rope_theta, own.rope_scaling
    )
    return make_rope_cos_sin(positions, inv_freq, att_scale)


def decoder_layer_class(cfg: Qwen3MoeConfig, decode_max_length: int):
    """The decoder layer, rematerialised where the config asks for it."""
    # remat is a backward-pass tool; decode is forward-only and its
    # mutable cache variables don't compose with nn.remat
    if cfg.remat and decode_max_length == 0:
        from d9d_tpu.models.qwen3.dense import _remat_policy

        return nn.remat(
            Qwen3MoeDecoderLayer,
            prevent_cse=cfg.remat_prevent_cse,
            policy=_remat_policy(cfg.remat_policy),
        )
    return Qwen3MoeDecoderLayer


class Qwen3MoeBackbone(nn.Module):
    config: Qwen3MoeConfig
    sdpa: SdpaBackend
    stage: PipelineStageInfo = PipelineStageInfo()
    # residual-stream [B, T, E] sharding pin — see Qwen3DenseBackbone
    act_sharding: Optional[NamedSharding] = None
    # KV-cache / GDN-state decode mode (loop/generate.py); 0 = training
    decode_max_length: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def _pin(self, x: Array) -> Array:
        if self.act_sharding is not None:
            return lax.with_sharding_constraint(x, self.act_sharding)
        return x

    @nn.compact
    def __call__(
        self,
        x: Array,
        positions: Array,
        mask: Optional[Array] = None,
        padding_mask: Optional[Array] = None,
        with_prenorm: bool = False,
    ) -> Array:
        """Between pipeline stages the carry is the residual stream:
        ``[B, T, C]``, or ``[B, T, n, C]`` under ``hc_mult`` n. On the last
        stage ``with_prenorm`` also returns what the final norm was given
        (the multi-token-prediction module's input)."""
        cfg = self.config
        stream = (
            jnp.float32 if cfg.float32_stream else self.dtype
        )
        if self.stage.is_first:
            x = TokenEmbedding(
                vocab_ranges=cfg.vocab_ranges,
                hidden_size=cfg.hidden_size,
                init_std=cfg.embedding_init_std,
                dtype=stream,
                param_dtype=self.param_dtype,
                name="embed_tokens",
            )(x)
            if cfg.multipliers.embedding != 1.0:
                x = cfg.multipliers.embedding * x
            if cfg.hc_mult > 1:
                x = expand_streams(x, cfg.hc_mult)
        else:
            x = x.astype(stream)
        x = self._pin(x)

        # one table a rotation (base, rotated share, scaling law): a
        # stack of one kind builds one
        rope: dict = {}
        layer_cls = decoder_layer_class(cfg, self.decode_max_length)
        if cfg.router_carry and self.stage.num_stages > 1:
            raise NotImplementedError(
                "router_carry: the router's state goes from layer to layer "
                "beside the stream, and a pipeline stage hands on the "
                "stream alone"
            )
        # the loop's second carry (router_carry): layer i's router state,
        # read by layer i + 1's
        router_state = None

        for gid in distribute_layers_for_pipeline_stage(cfg.num_layers, self.stage):
            kind = cfg.layer_kind(gid)
            own = cfg.attention_kind(kind)
            rotation = (own.rope_theta, own.rope_fraction, own.rope_scaling)
            if rotation not in rope:
                rope[rotation] = rope_cos_sin(cfg, positions, kind)
            cos, sin = rope[rotation]
            x = layer_cls(
                config=cfg,
                sdpa=self.sdpa,
                layer_idx=gid,
                decode_max_length=self.decode_max_length,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"layers_{gid}",
            )(x, cos, sin, mask, padding_mask, router_state)
            if cfg.router_carry:
                x, router_state = x
            x = self._pin(x)
            # numerics plane (telemetry/numerics.py): tap each layer's
            # residual-stream output HERE — outside the (possible)
            # nn.remat boundary — named by the layer's module path.
            # A no-op unless a numerics-enabled train step is tracing.
            numerics.tap(f"layers_{gid}", x)

        if self.stage.is_last:
            prenorm = sum_streams(x) if cfg.hc_mult > 1 else x
            x = RMSNorm(
                cfg.hidden_size, eps=cfg.norm_eps,
                zero_centered=cfg.zero_centered_norms, name="norm",
            )(prenorm).astype(self.dtype)
            numerics.tap("norm", x)
            if with_prenorm:
                return x, prenorm
        return x


class MultiTokenPrediction(nn.Module):
    """One trained multi-token-prediction module (DeepSeek-V3,
    arXiv:2412.19437 section 2.2, depth 1).

    ``h' = M [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))]`` with ``h_i`` the
    main stack's output before its final norm and ``M`` a ``2C x C``
    merge; one more decoder block of the expert kind (under ``hc_mult``
    n its own n streams, started as n copies of ``h'`` and read out by
    the sum); its own final norm. The embedding table and the head are
    the main model's: the caller looks up ``Emb(t_{i+1})`` and gives the
    result here to the shared head with the labels shifted once more.
    Trained only: ``logits`` and decode never build it.
    """

    config: Qwen3MoeConfig
    sdpa: SdpaBackend
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        hidden: Array,
        next_embedding: Array,
        positions: Array,
        mask: Optional[Array] = None,
        padding_mask: Optional[Array] = None,
    ) -> Array:
        """``hidden``, ``next_embedding`` ``[B, T, C]`` → ``[B, T, C]``,
        normed for the head."""
        cfg = self.config

        def norm(name: str):
            return RMSNorm(
                cfg.hidden_size, eps=cfg.norm_eps,
                zero_centered=cfg.zero_centered_norms, name=name,
            )

        pair = jnp.concatenate(
            [norm("hnorm")(hidden), norm("enorm")(next_embedding)], axis=-1
        ).astype(self.dtype)
        x = nn.Dense(
            cfg.hidden_size,
            use_bias=False,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), (la.EMBED, None)
            ),
            name="merge",
        )(pair)
        if cfg.hc_mult > 1:
            x = expand_streams(x, cfg.hc_mult)
        cos, sin = rope_cos_sin(cfg, positions)
        # a layer index past the stack's: of the kind that follows the
        # dense layers
        x = decoder_layer_class(cfg, 0)(
            config=cfg,
            sdpa=self.sdpa,
            layer_idx=cfg.num_layers,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="block",
        )(x, cos, sin, mask, padding_mask)
        if cfg.hc_mult > 1:
            x = sum_streams(x)
        return norm("norm")(x).astype(self.dtype)


class Qwen3MoeCausalLM(nn.Module):
    """Backbone + fused-CE LM head (reference model.py:221)."""

    config: Qwen3MoeConfig
    sdpa: SdpaBackend
    stage: PipelineStageInfo = PipelineStageInfo()
    ce_chunk_size: "int | str" = "auto"
    act_sharding: Optional[NamedSharding] = None
    # KV-cache / GDN-state decode mode (loop/generate.py); 0 = training
    decode_max_length: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        self.model = Qwen3MoeBackbone(
            config=self.config,
            sdpa=self.sdpa,
            stage=self.stage,
            act_sharding=self.act_sharding,
            decode_max_length=self.decode_max_length,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        tied = self.config.tie_word_embeddings
        if tied and not (self.stage.is_first and self.stage.is_last):
            raise ValueError(
                "tie_word_embeddings needs the embedding and the head on "
                "one pipeline stage"
            )
        mtp = self.config.num_mtp_modules
        if mtp not in (0, 1):
            raise ValueError(f"num_mtp_modules {mtp}: 0 or 1")
        if mtp and not (self.stage.is_first and self.stage.is_last):
            raise ValueError(
                "a multi-token-prediction module needs the embedding and "
                "the head on one pipeline stage"
            )
        if mtp:
            self.mtp = MultiTokenPrediction(
                config=self.config,
                sdpa=self.sdpa,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
            )
        if self.stage.is_last:
            self.lm_head = LanguageModellingHead(
                vocab_ranges=self.config.vocab_ranges,
                hidden_size=self.config.hidden_size,
                ce_chunk_size=self.ce_chunk_size,
                logits_divisor=self.config.multipliers.logits_divisor,
                tied=tied,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
            )

    def _embedding_table(self) -> Array:
        """The backbone's embedding table ``[V, C]``, once the backbone
        has run (its parameters exist then, at ``init`` too)."""
        tables = nn.meta.unbox(self.model.variables["params"]["embed_tokens"])
        return concat_vocab_ranges(
            [tables[f"embedding_{name}"] for name, _ in self.config.vocab_ranges]
        )

    def _head_table(self) -> Optional[Array]:
        """The embedding table a tied head reads; else None."""
        if not self.config.tie_word_embeddings:
            return None
        return self._embedding_table()

    def _next_embedding(self, tokens: Array) -> Array:
        """``Emb(t_{i+1})`` at position i. The last position is given the
        first token: causal, so only its own output sees it, and its label
        is ignored."""
        return jnp.take(
            self._embedding_table(), jnp.roll(tokens, -1, axis=1), axis=0
        ).astype(self.dtype)

    def _loss_with_mtp(
        self, tokens: Array, positions: Array, labels: Array,
        mask: Optional[Array], padding_mask: Optional[Array],
    ) -> Array:
        """Per-token next-token loss + ``mtp_loss_weight`` x the module's
        loss on the token after: position i merges ``h_i`` with the
        embedding of ``t_{i+1}`` and is held to ``t_{i+2}`` (the labels
        shifted once more; the last position and ignored labels give 0).
        The two terms are sown into ``moe_stats`` as sums, which the task
        turns into ``loss/next_token`` and ``loss/mtp``."""
        h, prenorm = self.model(
            tokens, positions, mask, padding_mask, with_prenorm=True
        )
        table = self._head_table()
        next_token = self.lm_head(h, labels, table)
        next_embedding = self._next_embedding(tokens)
        after = jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], LM_IGNORE_INDEX)],
            axis=1,
        )
        mtp = self.lm_head(
            self.mtp(prenorm, next_embedding, positions, mask, padding_mask),
            after, table,
        )
        for name, term in (("loss_next_token", next_token), ("loss_mtp", mtp)):
            self.sow(
                "moe_stats", name, term.sum(),
                reduce_fn=lambda a, b: a + b,
                init_fn=lambda: jnp.zeros((), jnp.float32),
            )
        return next_token + self.config.mtp_loss_weight * mtp

    def __call__(
        self,
        x: Array,
        positions: Array,
        labels: Optional[Array] = None,
        mask: Optional[Array] = None,
        padding_mask: Optional[Array] = None,
    ) -> Array:
        if self.config.num_mtp_modules and labels is not None:
            return self._loss_with_mtp(x, positions, labels, mask, padding_mask)
        h = self.model(x, positions, mask, padding_mask)
        if self.stage.is_last and labels is not None:
            return self.lm_head(h, labels, self._head_table())
        return h

    def logits(
        self,
        x: Array,
        positions: Array,
        mask: Optional[Array] = None,
        padding_mask: Optional[Array] = None,
    ) -> Array:
        h = self.model(x, positions, mask, padding_mask)
        if not self.stage.is_last:
            return h
        return self.lm_head.logits(h, self._head_table())

    def logits_last(
        self,
        x: Array,
        positions: Array,
        mask: Optional[Array] = None,
        padding_mask: Optional[Array] = None,
    ) -> Array:
        """Last-position logits ``[B, 1, V]`` — see the dense twin."""
        h = self.model(x, positions, mask, padding_mask)
        if not self.stage.is_last:
            return h
        return self.lm_head.logits(h[:, -1:], self._head_table())

    def mtp_logits(
        self,
        x: Array,
        positions: Array,
        mask: Optional[Array] = None,
        padding_mask: Optional[Array] = None,
    ) -> Array:
        """The multi-token-prediction module's logits ``[B, T, V]``:
        position i predicts ``t_{i+2}`` (the last position's are of no
        use). For comparisons; training goes through ``__call__``."""
        _, prenorm = self.model(
            x, positions, mask, padding_mask, with_prenorm=True
        )
        h = self.mtp(
            prenorm, self._next_embedding(x), positions, mask, padding_mask
        )
        return self.lm_head.logits(h, self._head_table())


class Qwen3MoeForClassification(nn.Module):
    """Backbone + last-token classification head (reference model.py:322)."""

    config: Qwen3MoeConfig
    sdpa: SdpaBackend
    num_classes: int = 2
    stage: PipelineStageInfo = PipelineStageInfo()
    act_sharding: Optional[NamedSharding] = None
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: Array,
        positions: Array,
        pooling_mask: Optional[Array] = None,
        mask: Optional[Array] = None,
        padding_mask: Optional[Array] = None,
    ) -> Array:
        h = Qwen3MoeBackbone(
            config=self.config,
            sdpa=self.sdpa,
            stage=self.stage,
            act_sharding=self.act_sharding,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="model",
        )(x, positions, mask, padding_mask)
        if not self.stage.is_last:
            return h
        if pooling_mask is None:
            pooled = h[:, -1]
        else:
            idx = jnp.maximum(pooling_mask.sum(axis=-1) - 1, 0)
            pooled = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
        return ClassificationHead(
            hidden_size=self.config.hidden_size,
            num_classes=self.num_classes,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )(pooled)


class Qwen3MoeForEmbedding(nn.Module):
    """Backbone + pooled L2-normalized embedding head (reference model.py:425)."""

    config: Qwen3MoeConfig
    sdpa: SdpaBackend
    stage: PipelineStageInfo = PipelineStageInfo()
    act_sharding: Optional[NamedSharding] = None
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: Array,
        positions: Array,
        pooling_mask: Optional[Array] = None,
        mask: Optional[Array] = None,
        padding_mask: Optional[Array] = None,
    ) -> Array:
        h = Qwen3MoeBackbone(
            config=self.config,
            sdpa=self.sdpa,
            stage=self.stage,
            act_sharding=self.act_sharding,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="model",
        )(x, positions, mask, padding_mask)
        if not self.stage.is_last:
            return h
        return EmbeddingHead()(h, pooling_mask)
