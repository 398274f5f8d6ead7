"""Solar Open 2 model family (Upstage, ``solar_open2``): Kimi delta
attention layers beside gated grouped-query attention without rotation,
sparse experts and a shared expert in every layer.

Beyond-reference family (the reference ships only Qwen3 models), on the
shared decoder (``models/qwen3/moe.py``) through its per-layer pattern of
kinds: layer ``i`` is grouped-query attention where the published
``gqa_layers`` lists it (0, 4, 8, ...: one period is GQA, KDA, KDA, KDA;
64 query heads on 8 key/value heads of 128, no positional encoding, no
q/k norm, no bias, an element-wise sigmoid gate from a projection of the
block's input before ``o_proj``) and a Kimi delta attention mixer
elsewhere (``nn/linear_attention.py KimiDeltaAttention``: 64 heads of
128 key and value channels, a float32 matrix state a head decayed a key
channel, a write strength in (0, 2), three 4-tap convolutions, low-rank
decay and output gates). Every layer then runs ``MoELayer``: sigmoid
scores over all experts, the 8 largest of the scores plus a selection
bias, weights renormalised, and an ungated shared expert of an expert's
width added once. Untied head, RMSNorm eps 1e-5, a float32 residual
stream (the decoder's rule for a stack with recurrent-state mixers);
the embedding table is drawn at the decoder's untied default (unit
variance) and the head by ``LanguageModellingHead``'s, as every untied
preset's are.

The catalog row leaves five readings open; each is a field here and a
function of ``benchmarks/references/solar_open2.py``, listed under
``assumed`` in the benchmark's configuration file: the gates' low-rank
pairs of rank ``head_dim`` (``kda_use_full_proj: false``), the GQA gate
element-wise, a ``noaux_tc`` router without expert groups, no q/k norm in
the GQA layers, and fla's KDA defaults for the initialisation.

Sharding plans, ``generate`` and ``ContinuousBatcher`` apply unchanged:
``delta_state [B, H, Dk, Dv]`` and ``conv_tail`` are per-row cache leaves
the serving loop zeroes on admission, beside the GQA layers' paged KV
pools, and a held range of the router's experts counts its rows in the
same fused chunk; the prefix cache and ``speculative_generate`` refuse a
model with such leaves. Held to the reference in
``tests/models/test_solar.py`` and, at published widths on the chip, in
the benchmark's ``solar-open2-250b-share8-decode`` cell. No Hugging Face
weight mapper exists yet.
"""

import dataclasses

from d9d_tpu.models.qwen3.moe import (
    KdaParameters,
    Qwen3MoeBackbone as SolarBackbone,
    Qwen3MoeCausalLM as SolarCausalLM,
    Qwen3MoeConfig,
)
from d9d_tpu.nn.moe import SharedExpertParameters

SolarConfig = Qwen3MoeConfig  # same static surface; layer_kinds set

__all__ = [
    "SolarBackbone", "SolarCausalLM", "SolarConfig", "solar_layer_kinds",
    "solar_open2_250b", "solar_open2_250b_share8", "solar_tiny",
]

# the published ``gqa_layers``: every fourth layer of 48, from 0
GQA_LAYERS = tuple(range(0, 48, 4))


def solar_layer_kinds(gqa_layers, num_layers: int) -> tuple[str, ...]:
    """The decoder's kind names from the published list of GQA layers
    (read under ``num_layers``)."""
    return tuple(
        "attention" if i in gqa_layers else "kda" for i in range(num_layers)
    )


def _solar(*, vocab_size, hidden_size, num_layers, gqa_layers, num_heads,
           num_kv_heads, head_dim, kda_num_heads, kda_head_dim,
           moe_intermediate_size, num_experts, num_routed_experts,
           num_experts_per_tok, first_held_expert=0,
           **extra) -> Qwen3MoeConfig:
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=hidden_size,
        num_layers=num_layers,
        layer_kinds=solar_layer_kinds(gqa_layers, num_layers),
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        head_dim=head_dim,
        qk_norm=False,
        rope_fraction=0.0,  # use_rope false
        rope_theta=10_000.0,  # a key of the source; nothing is rotated
        use_output_gate=True,  # use_gqa_gate
        kda=KdaParameters(
            num_heads=kda_num_heads, head_dim=kda_head_dim, conv_size=4,
            gate_rank=kda_head_dim,  # kda_use_full_proj false
            allow_neg_eigval=True,
        ),
        moe_intermediate_size=moe_intermediate_size,
        num_experts=num_experts,
        num_routed_experts=num_routed_experts,
        first_held_expert=first_held_expert,
        num_experts_per_tok=num_experts_per_tok,
        norm_topk_prob=True,
        routed_scaling_factor=1.0,
        router_score_function="sigmoid",
        router_expert_bias=True,
        shared_expert=SharedExpertParameters(
            intermediate_size=moe_intermediate_size  # n_shared_experts 1
        ),
        norm_eps=1e-5,
        **extra,
    )


def solar_tiny(vocab_size: int = 256, num_experts: int = 4,
               first_held_expert: int = 0) -> Qwen3MoeConfig:
    """CPU-runnable Solar-shaped config (tests, ``--tiny`` benchmark
    runs): one period of the published pattern (GQA, KDA, KDA, KDA: 4
    heads of 16 in both kinds, 2 key/value heads), 4 of 16 routed experts
    held (one of four shares; ``num_experts`` 16 is the uncut layer),
    top-4, a shared expert of an expert's width, an untied head."""
    return _solar(
        vocab_size=vocab_size, hidden_size=32, num_layers=4,
        gqa_layers=GQA_LAYERS, num_heads=4, num_kv_heads=2, head_dim=16,
        kda_num_heads=4, kda_head_dim=16, moe_intermediate_size=32,
        num_experts=num_experts, num_routed_experts=16,
        first_held_expert=first_held_expert, num_experts_per_tok=4,
        remat=False,
    )


def solar_open2_250b(
    vocab_size: int = 196_608, num_experts: int = 320,
    first_held_expert: int = 0,
) -> Qwen3MoeConfig:
    """Solar-Open2-250B geometry (250B total / 15B active): 48 layers at
    4,096, GQA at layers 0, 4, ..., 44 (64 query heads on 8 key/value
    heads of 128, gated, no rotation) and 36 Kimi delta attention mixers
    (64 heads of 128, mixers 8,192 wide); in every layer 320 experts of
    1,280, top-8 by sigmoid scores with a selection bias, and a shared
    expert of 1,280; an untied 196,608-row head. ``num_experts`` below
    320 and a smaller ``vocab_size`` give one chip's share of an
    expert-parallel deployment: that many experts from
    ``first_held_expert`` on under the 320-wide router, and the
    vocabulary's first rows."""
    return _solar(
        vocab_size=vocab_size, hidden_size=4096, num_layers=48,
        gqa_layers=GQA_LAYERS, num_heads=64, num_kv_heads=8, head_dim=128,
        kda_num_heads=64, kda_head_dim=128, moe_intermediate_size=1280,
        num_experts=num_experts, num_routed_experts=320,
        first_held_expert=first_held_expert, num_experts_per_tok=8,
    )


SHARE8_LAYERS = 4


def solar_open2_250b_share8() -> Qwen3MoeConfig:
    """One chip of the eight that share each layer of an 8-way
    expert-parallel Solar-Open2-250B deployment: experts 0 to 39 under
    the 320-wide router and vocabulary rows 0 to 24,575, every width as
    published. Four layers of the 48, one whole period in its published
    order (GQA, KDA, KDA, KDA); the other periods are other pipeline
    stages' (the benchmark's ``solar-open2-250b-share8-decode``
    configuration)."""
    whole = solar_open2_250b(vocab_size=24_576, num_experts=40)
    return dataclasses.replace(
        whole, num_layers=SHARE8_LAYERS,
        layer_kinds=whole.layer_kinds[:SHARE8_LAYERS],
    )
