"""MiMo-V2-Flash (Xiaomi, ``mimo_v2_flash``): window and full attention
layers in one stack, sparse experts under a sigmoid ``noaux_tc`` router.

Beyond-reference family (the reference ships only Qwen3 models), on the
shared decoder (``models/qwen3/moe.py``) through its per-layer pattern of
kinds (``Qwen3MoeConfig.layer_kinds``): layer ``l`` is full attention
where ``hybrid_layer_pattern[l]`` is 0 and window attention where it is
1, five windows to a full layer. Both kinds have 64 query heads of 192
with the first 64 numbers rotated (``partial_rotary_factor`` 0.334 x 192,
truncated; pairs ``(i, i + 32)``), value heads of 128 scaled by
``attention_value_scale`` 0.707, no q/k norm and no bias. The full kind
(the config's plain fields) has 4 key/value heads and rotary base
5,000,000; the window kind (``attention_kinds["window"]``) 8 key/value
heads, base 10,000, a window of 128 positions and a learned sink logit a
query head that joins the softmax's denominator alone. Layer 0's
feed-forward is a dense SwiGLU of 16,384 (``moe_layer_freq[0]`` 0); every
other layer has 256 experts of 2,048, top-8 by ``sigmoid(x W_r) + b``,
weights the unbiased scores over their sum, no shared expert, no further
scale. The multi-token-prediction layers the family ships are not built:
they do not feed the next-token logits.

``generate`` keeps a whole context for every layer; the paged serving
loop gives a window layer a ring of pages a row (``nn/attention.py
_ring_page_table``) and serves such a model with the prefix cache off.
Held to ``benchmarks/references/mimo_v2_flash.py`` in
``tests/models/test_mimo_v2_flash.py`` and, at published widths on the
chip, in the benchmark's ``mimo-v2-flash-share16-decode`` cell. No
Hugging Face weight mapper exists yet.
"""

import dataclasses

from d9d_tpu.models.qwen3.moe import (
    AttentionKind,
    Qwen3MoeBackbone as MimoBackbone,
    Qwen3MoeCausalLM as MimoCausalLM,
    Qwen3MoeConfig,
)

MimoConfig = Qwen3MoeConfig  # same static surface; layer_kinds set

__all__ = [
    "MimoBackbone", "MimoCausalLM", "MimoConfig", "mimo_layer_kinds",
    "mimo_v2_flash", "mimo_v2_flash_share16", "mimo_v2_flash_tiny",
]

# the published ``hybrid_layer_pattern``: 0 full, 1 window; a full layer,
# then periods of five windows and a full layer, the first one a window short
HYBRID_LAYER_PATTERN = (0, 1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7


def mimo_layer_kinds(pattern) -> tuple[str, ...]:
    """``hybrid_layer_pattern`` entries as the decoder's kind names."""
    return tuple("window" if swa else "attention" for swa in pattern)


def _mimo(*, vocab_size, hidden_size, pattern, num_heads, num_kv_heads,
          window_kv_heads, head_dim, v_head_dim, intermediate_size,
          moe_intermediate_size, num_experts, num_routed_experts,
          num_experts_per_tok, window_size, first_held_expert=0,
          **extra) -> Qwen3MoeConfig:
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=hidden_size,
        num_layers=len(pattern),
        num_heads=num_heads,
        # the plain fields are the full kind's
        num_kv_heads=num_kv_heads,
        rope_theta=5_000_000.0,
        head_dim=head_dim,
        v_head_dim=v_head_dim,
        attention_value_scale=0.707,
        rope_fraction=0.334,
        qk_norm=False,
        norm_eps=1e-5,
        layer_kinds=mimo_layer_kinds(pattern),
        attention_kinds=(("window", AttentionKind(
            num_kv_heads=window_kv_heads, rope_theta=10_000.0,
            window_size=window_size, use_sinks=True,
        )),),
        intermediate_size=intermediate_size,
        mlp_only_layers=(0,),
        moe_intermediate_size=moe_intermediate_size,
        num_experts=num_experts,
        num_routed_experts=num_routed_experts,
        first_held_expert=first_held_expert,
        num_experts_per_tok=num_experts_per_tok,
        norm_topk_prob=True,
        router_score_function="sigmoid",
        router_expert_bias=True,
        **extra,
    )


# the window the tiny preset runs: ``build.hf_view`` cannot say it, so
# the benchmark's reference takes this where its sizes carry no
# ``sliding_window`` (benchmarks/references/mimo_v2_flash.py)
TINY_WINDOW = 6


def mimo_v2_flash_tiny(vocab_size: int = 256) -> Qwen3MoeConfig:
    """CPU-runnable MiMo-shaped config (tests, ``--tiny`` benchmark
    runs): a dense full-attention layer, two window layers and a full
    one; 24-wide query/key heads with 8 numbers rotated (0.334 x 24,
    truncated) and 16-wide value heads; 2 key/value heads in the full
    kind and 4 in the window kind; a window of 6, shorter than the tiny
    traffic's contexts (to 25) and than a tiny ring (pages of 4: 3 pages
    = 12 positions), so the ring wraps; 4 of 64 routed experts held,
    one of sixteen shares; the family's constants (0.707, 0.334, the two
    rotary bases, epsilon 1e-5) as published."""
    return _mimo(
        vocab_size=vocab_size, hidden_size=64, pattern=(0, 1, 1, 0),
        num_heads=4, num_kv_heads=2, window_kv_heads=4, head_dim=24,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        num_experts=4, num_routed_experts=64, num_experts_per_tok=4,
        window_size=TINY_WINDOW, remat=False,
    )


def mimo_v2_flash(
    vocab_size: int = 152_576, num_experts: int = 256,
    first_held_expert: int = 0,
) -> Qwen3MoeConfig:
    """MiMo-V2-Flash geometry (309B total / 15B active): 48 layers at
    4,096, 64 query heads of 192 (64 rotated) on value heads of 128; 9
    full layers (4 key/value heads, base 5,000,000) and 39 window layers
    (8 key/value heads, base 10,000, window 128, sink); dense 16,384 in
    layer 0, then 256 x 2,048 top-8; an untied 152,576-row vocabulary.
    ``num_experts`` below 256 and a smaller ``vocab_size`` give one
    chip's share of an expert-parallel deployment: that many experts
    from ``first_held_expert`` on under the 256-wide router, and the
    vocabulary's first rows."""
    return _mimo(
        vocab_size=vocab_size, hidden_size=4096,
        pattern=HYBRID_LAYER_PATTERN, num_heads=64, num_kv_heads=4,
        window_kv_heads=8, head_dim=192, v_head_dim=128,
        intermediate_size=16_384, moe_intermediate_size=2048,
        num_experts=num_experts, num_routed_experts=256,
        first_held_expert=first_held_expert, num_experts_per_tok=8,
        window_size=128,
    )


SHARE16_LAYERS = 7


def mimo_v2_flash_share16() -> Qwen3MoeConfig:
    """One chip of the sixteen that share each layer of a 16-way
    expert-parallel MiMo-V2-Flash deployment: experts 0 to 15 under the
    256-wide router and vocabulary rows 0 to 19,071, every width as
    published. Seven layers of the 48: layer 0 (dense, full attention)
    and layers 1 to 6, one whole period of the pattern (window x 4,
    full, window); the other layers are other pipeline stages' (the
    benchmark's ``mimo-v2-flash-share16-decode`` configuration)."""
    whole = mimo_v2_flash(vocab_size=19_072, num_experts=16)
    return dataclasses.replace(
        whole, num_layers=SHARE16_LAYERS,
        layer_kinds=whole.layer_kinds[:SHARE16_LAYERS],
    )
