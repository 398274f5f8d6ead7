"""Laguna-XS.2 (poolside, ``laguna``): window and full attention layers
whose query heads, rotation and window differ by kind, every head's
output gated, sparse experts beside a shared one.

Beyond-reference family (the reference ships only Qwen3 models), on the
shared decoder (``models/qwen3/moe.py``) through its per-layer pattern of
kinds (``Qwen3MoeConfig.layer_kinds``): layer ``l`` is full attention
where ``layer_types[l]`` is ``full_attention`` (``l % 4 == 0``) and
window attention elsewhere, three windows to a full layer. Heads of 128
on 8 key/value heads in both kinds, no q/k norm, no bias. The full kind
(the config's plain fields) has 48 query heads and rotates the first 64
numbers of a head (``partial_rotary_factor`` 0.5; pairs ``(i, i + 32)``)
at base 500,000 under YaRN (factor 64 over 4,096 positions,
``beta_fast`` 64, ``beta_slow`` 1, cos and sin times
``attention_factor``); the window kind (``attention_kinds["window"]``)
has 64 query heads, rotates the whole head at base 10,000 with no
scaling, and attends the last 512 positions. Every layer multiplies each
head's output by ``sigmoid(x W_g)``, one logit a head (``gating``).
Layer 0's feed-forward is a dense SwiGLU of 8,192; every other layer has
256 experts of 512, top-8 by a softmax over all 256, the weights over
their sum, the routed output times 2.5, and an ungated shared expert of
512 beside them.

Three readings no key of the published config settles are fixed here
and in the reference alike: a softmax router with renormalised top-k
weights (the Qwen-MoE family's, whose keys these are), an ungated shared
expert, no q/k norm. Held to ``benchmarks/references/laguna.py`` in
``tests/models/test_laguna.py`` and, at published widths on the chip, in
the benchmark's ``laguna-xs.2-share8`` cell. No Hugging Face weight
mapper exists yet.
"""

import dataclasses

from d9d_tpu.models.qwen3.moe import (
    AttentionKind,
    Qwen3MoeBackbone as LagunaBackbone,
    Qwen3MoeCausalLM as LagunaCausalLM,
    Qwen3MoeConfig,
)
from d9d_tpu.nn.moe import SharedExpertParameters
from d9d_tpu.ops import RopeScalingNone, RopeScalingYarn

LagunaConfig = Qwen3MoeConfig  # same static surface; layer_kinds set

__all__ = [
    "LagunaBackbone", "LagunaCausalLM", "LagunaConfig", "laguna_layer_kinds",
    "laguna_xs2", "laguna_xs2_share8", "laguna_tiny",
]

# the published ``layer_types``: a full layer, then three window layers
LAYER_TYPES = ("full_attention",) + ("sliding_attention",) * 3

# ``rope_parameters["full_attention"]`` as published
FULL_ROPE_SCALING = RopeScalingYarn(
    factor=64.0, original_max_position=4096, beta_fast=64.0, beta_slow=1.0,
    attention_factor=1.4158883083359672,
)


def laguna_layer_kinds(layer_types) -> tuple[str, ...]:
    """``layer_types`` entries as the decoder's kind names."""
    return tuple(
        "window" if kind == "sliding_attention" else "attention"
        for kind in layer_types
    )


def _laguna(*, vocab_size, hidden_size, layer_types, num_heads,
            window_heads, num_kv_heads, head_dim, window_size,
            intermediate_size, moe_intermediate_size, num_experts,
            num_routed_experts, num_experts_per_tok, first_held_expert=0,
            **extra) -> Qwen3MoeConfig:
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=hidden_size,
        num_layers=len(layer_types),
        # the plain fields are the full kind's
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        head_dim=head_dim,
        rope_theta=500_000.0,
        rope_fraction=0.5,
        rope_scaling=FULL_ROPE_SCALING,
        qk_norm=False,
        norm_eps=1e-6,
        use_output_gate=True,
        output_gate_per_head=True,
        layer_kinds=laguna_layer_kinds(layer_types),
        attention_kinds=(("window", AttentionKind(
            num_heads=window_heads, rope_theta=10_000.0, rope_fraction=1.0,
            rope_scaling=RopeScalingNone(), window_size=window_size,
        )),),
        intermediate_size=intermediate_size,
        mlp_only_layers=(0,),
        moe_intermediate_size=moe_intermediate_size,
        shared_expert=SharedExpertParameters(
            intermediate_size=moe_intermediate_size, enable_gate=False
        ),
        num_experts=num_experts,
        num_routed_experts=num_routed_experts,
        first_held_expert=first_held_expert,
        num_experts_per_tok=num_experts_per_tok,
        norm_topk_prob=True,
        router_score_function="softmax",
        routed_scaling_factor=2.5,
        **extra,
    )


# the window the tiny preset runs: ``build.hf_view`` cannot say it, so
# the benchmark's reference takes this where its sizes carry no
# ``sliding_window`` (benchmarks/references/laguna.py)
TINY_WINDOW = 16


def laguna_tiny(vocab_size: int = 256) -> Qwen3MoeConfig:
    """CPU-runnable Laguna-shaped config (tests, ``--tiny`` benchmark
    runs): a dense full-attention layer, two window layers and a full
    one; 16-wide heads on 2 key/value heads, 6 query heads in the full
    kind (8 numbers of a head rotated under the published YaRN law) and
    8 in the window kind (the whole head, base 10,000); a window of 16,
    a quarter of the tiny traffic's sequences; 4 of 32 routed experts
    held, one of eight shares, top-4; the family's constants (the two
    rotations, 2.5, epsilon 1e-6) as published."""
    return _laguna(
        vocab_size=vocab_size, hidden_size=64,
        layer_types=LAYER_TYPES[:3] + LAYER_TYPES[:1], num_heads=6,
        window_heads=8, num_kv_heads=2, head_dim=16,
        window_size=TINY_WINDOW, intermediate_size=128,
        moe_intermediate_size=32, num_experts=4, num_routed_experts=32,
        num_experts_per_tok=4, remat=False,
    )


def laguna_xs2(
    vocab_size: int = 100_352, num_experts: int = 256,
    first_held_expert: int = 0,
) -> Qwen3MoeConfig:
    """Laguna-XS.2 geometry (33.4B total / 3B active): 40 layers at
    2,048, heads of 128 on 8 key/value heads; 10 full layers (48 query
    heads, half a head rotated, base 500,000 under YaRN) and 30 window
    layers (64 query heads, the whole head rotated, base 10,000, window
    512); a sigmoid gate a head; dense 8,192 in layer 0, then 256 x 512
    top-8 times 2.5 beside a shared expert of 512; an untied 100,352-row
    vocabulary. ``num_experts`` below 256 and a smaller ``vocab_size``
    give one chip's share of an expert-parallel job: that many experts
    from ``first_held_expert`` on under the 256-wide router, and the
    vocabulary's first rows."""
    return _laguna(
        vocab_size=vocab_size, hidden_size=2048, layer_types=LAYER_TYPES * 10,
        num_heads=48, window_heads=64, num_kv_heads=8, head_dim=128,
        window_size=512, intermediate_size=8192, moe_intermediate_size=512,
        num_experts=num_experts, num_routed_experts=256,
        first_held_expert=first_held_expert, num_experts_per_tok=8,
        # five layers of the share claimed 12.9 GB of temporaries with the
        # compiler free to merge a rematerialised forward with the forward
        # it repeats and 10.3 GB without (described-v5e compile, PR 44)
        remat_prevent_cse=True,
    )


SHARE8_LAYERS = 5


def laguna_xs2_share8() -> Qwen3MoeConfig:
    """One chip of the eight that share each layer of an 8-way
    expert-parallel Laguna-XS.2 training job: experts 0 to 31 under the
    256-wide router and vocabulary rows 0 to 12,543, every width as
    published. Five layers of the 40: layer 0 (dense, full attention)
    and layers 1 to 4, one whole period of the pattern (window x 3,
    full); the other layers are other pipeline stages' (the benchmark's
    ``laguna-xs.2-share8`` configuration)."""
    whole = laguna_xs2(vocab_size=12_544, num_experts=32)
    return dataclasses.replace(
        whole, num_layers=SHARE8_LAYERS,
        layer_kinds=whole.layer_kinds[:SHARE8_LAYERS],
    )
