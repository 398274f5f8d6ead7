"""Granite 4.0-H model family (IBM, ``granitemoehybrid``): Mamba-2
state-space layers beside grouped-query attention without rotation,
sparse experts and a shared expert after every mixer, four multipliers.

Beyond-reference family (the reference ships only Qwen3 models), on the
shared decoder (``models/qwen3/moe.py``) through its per-layer pattern of
kinds: layer ``i`` is a Mamba-2 mixer (``nn/mamba.py Mamba2Mixer``: heads
of 64 channels whose decay, skip and step bias are one number a head, B
and C shared by all heads, a state of ``64 x 128`` a head, a gated
RMSNorm over all channels before the out-projection) where the published
``layer_types[i]`` is ``"mamba"`` and grouped-query attention where it is
``"attention"`` (one layer in ten; no positional encoding, no q/k norm,
no bias, softmax at the stated ``attention_multiplier``, not
``head_dim ** -0.5``). Every layer, state-space layers included, then
runs ``MoELayer``: a softmax router over all experts, the top 10
renormalised (``softmax`` over the chosen logits, identically), and an
ungated shared expert of its own width added once. ``x = 12 embed(ids)``
(``embedding_multiplier``), both branches of every layer enter the
residual stream times 0.22 (``residual_multiplier``), and the tied head's
logits are divided by 16 (``logits_scaling``), in the fused loss too.
The residual stream is float32, as for any stack with state-space layers.

Sharding plans, the Trainer, ``generate`` and ``ContinuousBatcher`` apply
unchanged: ``ssm_state`` ``[B, H, P, N]`` and ``conv_tail`` are per-row
cache leaves the serving loop zeroes on admission, beside the attention
layers' paged KV pools, and a held range of the router's experts counts
its rows in the same fused chunk; the prefix cache and
``speculative_generate`` refuse a model with such leaves. Held to
``benchmarks/references/granite_moe_hybrid.py`` in
``tests/models/test_granite.py`` and, at published widths on the chip,
in the benchmark's ``granite-4.0-h-small-share4-decode`` cell. No
Hugging Face weight mapper exists yet.
"""

import dataclasses

from d9d_tpu.models.qwen3.moe import (
    Mamba2Parameters,
    Multipliers,
    Qwen3MoeBackbone as GraniteBackbone,
    Qwen3MoeCausalLM as GraniteCausalLM,
    Qwen3MoeConfig,
)
from d9d_tpu.nn.moe import SharedExpertParameters

GraniteConfig = Qwen3MoeConfig  # same static surface; layer_kinds set

__all__ = [
    "GraniteBackbone", "GraniteCausalLM", "GraniteConfig",
    "granite_layer_kinds", "granite_4_0_h_small",
    "granite_4_0_h_small_share4", "granite_tiny",
]

# the published ``layer_types``: attention at 5, 15, 25 and 35 of 40
LAYER_TYPES = (("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4


def granite_layer_kinds(layer_types) -> tuple[str, ...]:
    """``layer_types`` entries as the decoder's kind names."""
    return tuple("mamba2" if kind == "mamba" else kind for kind in layer_types)


def _granite(*, vocab_size, hidden_size, layer_types, num_heads,
             num_kv_heads, head_dim, mamba_num_heads, mamba_head_dim,
             mamba_d_state, moe_intermediate_size, shared_intermediate_size,
             num_experts, num_routed_experts, num_experts_per_tok,
             first_held_expert=0, **extra) -> Qwen3MoeConfig:
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=hidden_size,
        num_layers=len(layer_types),
        layer_kinds=granite_layer_kinds(layer_types),
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        head_dim=head_dim,
        qk_norm=False,
        rope_fraction=0.0,  # position_embedding_type "nope"
        rope_theta=10_000.0,  # a key of the source; nothing is rotated
        mamba2=Mamba2Parameters(
            num_heads=mamba_num_heads, head_dim=mamba_head_dim,
            d_state=mamba_d_state, n_groups=1, d_conv=4, chunk_size=256,
        ),
        moe_intermediate_size=moe_intermediate_size,
        num_experts=num_experts,
        num_routed_experts=num_routed_experts,
        first_held_expert=first_held_expert,
        num_experts_per_tok=num_experts_per_tok,
        norm_topk_prob=True,
        shared_expert=SharedExpertParameters(
            intermediate_size=shared_intermediate_size
        ),
        multipliers=Multipliers(
            embedding=12.0, residual=0.22, logits_divisor=16.0,
            attention_softmax_scale=0.0078125,
        ),
        tie_word_embeddings=True,
        embedding_init_std=0.02,
        norm_eps=1e-5,
        **extra,
    )


def granite_tiny(vocab_size: int = 256, num_experts: int = 4,
                 first_held_expert: int = 0) -> Qwen3MoeConfig:
    """CPU-runnable Granite-shaped config (tests, ``--tiny`` benchmark
    runs): two Mamba-2 layers (4 heads of 16 channels, a state of 8
    numbers a channel) around a grouped-query attention layer, 4 of 16
    routed experts held (one of four shares; ``num_experts`` 16 is the
    uncut layer), top-4, a shared expert twice an expert's width, a tied
    table; the four multipliers as published (12, 0.22, 16, 1/128)."""
    return _granite(
        vocab_size=vocab_size, hidden_size=32,
        layer_types=("mamba", "attention", "mamba"), num_heads=4,
        num_kv_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=16,
        mamba_d_state=8, moe_intermediate_size=32,
        shared_intermediate_size=64, num_experts=num_experts,
        num_routed_experts=16, first_held_expert=first_held_expert,
        num_experts_per_tok=4, remat=False,
    )


def granite_4_0_h_small(
    vocab_size: int = 100_352, num_experts: int = 72,
    first_held_expert: int = 0,
) -> Qwen3MoeConfig:
    """granite-4.0-h-small geometry (32B total / 9B active): 40 layers at
    4,096, 36 Mamba-2 mixers (128 heads of 64, state 128, one group, 4
    taps) and attention at layers 5, 15, 25 and 35 (32 query heads on 8
    key/value heads of 128, no rotation, softmax scale 1/128); in every
    layer 72 experts of 768, top-10, and a shared expert of 1,536; a tied
    100,352-row table. ``num_experts`` below 72 and a smaller
    ``vocab_size`` give one chip's share of an expert-parallel
    deployment: that many experts from ``first_held_expert`` on under
    the 72-wide router, and the vocabulary's first rows."""
    return _granite(
        vocab_size=vocab_size, hidden_size=4096, layer_types=LAYER_TYPES,
        num_heads=32, num_kv_heads=8, head_dim=128, mamba_num_heads=128,
        mamba_head_dim=64, mamba_d_state=128, moe_intermediate_size=768,
        shared_intermediate_size=1536, num_experts=num_experts,
        num_routed_experts=72, first_held_expert=first_held_expert,
        num_experts_per_tok=10,
    )


SHARE4_LAYERS = 10


def granite_4_0_h_small_share4() -> Qwen3MoeConfig:
    """One chip of the four that share each layer of a 4-way
    expert-parallel granite-4.0-h-small deployment: experts 0 to 17 under
    the 72-wide router and vocabulary rows 0 to 25,087, every width as
    published. Ten layers of the 40, one whole period in its published
    order (five Mamba-2, attention, four Mamba-2); the other periods are
    other pipeline stages' (the benchmark's
    ``granite-4.0-h-small-share4-decode`` configuration)."""
    whole = granite_4_0_h_small(vocab_size=25_088, num_experts=18)
    return dataclasses.replace(
        whole, num_layers=SHARE4_LAYERS,
        layer_kinds=whole.layer_kinds[:SHARE4_LAYERS],
    )
