"""ZAYA1 model family (Zyphra, ``zaya``; arXiv:2511.17127): compressed
convolutional attention and a top-1 router that is an MLP carrying state
from layer to layer, with learned scales on both residual additions.

Beyond-reference family (the reference ships only Qwen3 models), on the
shared decoder (``models/qwen3/moe.py``): every layer is of kind
``"cca"`` (``nn/cca.py CompressedConvAttention``: 8 query heads on 2
key/value heads of 128 in a hidden width of 2,048, two causal
convolutions of 2 taps over the joined query and key latents, a q-k
mean, half the value heads a token late, l2-normalised queries and keys
with a learned key temperature, the first half of a head rotated at base
5e6) followed by ``MoELayer`` under the ZAYA router (``nn/moe.py
TopKRouter``, ``mlp_hidden`` 256 with ``carry``): softmax over 16 experts
and a skip, top-1 by the scores plus a selection-only bias, the weight
the chosen score itself, no shared expert. The skip is the router's 17th
id, outside the 16 held (``num_routed_experts`` 17): a token routed
there gets nothing from the layer, by the held-range path every share
cell takes. RMSNorm eps 1e-5, a tied 262,272-row table drawn at 0.02, a
float32 residual stream (the decoder's rule for a stack with such
attention: ``FLOAT32_STREAM_KINDS``).

The catalog row leaves readings open; each is a field here
(``CcaParameters``, ``router_*``, ``residual_scaling``,
``norm_topk_prob``) and a function of ``benchmarks/references/zaya.py``,
listed under ``assumed`` in the benchmark's configuration file: the
first convolution depthwise with a bias, the second grouped by head, the
q-k mean, the value shift, the l2 norms and the temperature before the
rotation, the residual scales and biases, the router's depth carry, its
three matrices with exact GELU, the skip, and an unrenormalised top-1
weight.

Sharding plans, ``generate`` and ``ContinuousBatcher`` apply unchanged:
an attention layer keeps its paged key/value pools and, beside them,
three per-row tails (``conv_tail``, ``conv1_tail``, ``value_tail``) the
serving loop zeroes on admission; the prefix cache and
``speculative_generate`` refuse a model with such leaves. Not built: the
router's carry across pipeline stages (``Qwen3MoeBackbone`` raises), the
74B sibling's window layers, a Hugging Face weight mapper. Held to the
reference in ``tests/models/test_zaya.py`` and, at published widths on
the chip, in the benchmark's ``zaya1-8b-decode`` cell.
"""

import dataclasses

from d9d_tpu.models.qwen3.moe import (
    CcaParameters,
    Qwen3MoeBackbone as ZayaBackbone,
    Qwen3MoeCausalLM as ZayaCausalLM,
    Qwen3MoeConfig,
)

ZayaConfig = Qwen3MoeConfig  # same static surface; layer_kinds set

__all__ = [
    "ZayaBackbone", "ZayaCausalLM", "ZayaConfig", "zaya1_8b",
    "zaya1_8b_decode", "zaya_tiny",
]


def _zaya(*, vocab_size, hidden_size, num_layers, num_heads, num_kv_heads,
          head_dim, moe_intermediate_size, num_experts, router_hidden_size,
          **extra) -> Qwen3MoeConfig:
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=hidden_size,
        num_layers=num_layers,
        layer_kinds=("cca",) * num_layers,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        head_dim=head_dim,
        qk_norm=False,
        rope_fraction=0.5,  # partial_rotary_factor
        rope_theta=5_000_000.0,
        cca=CcaParameters(),  # cca_time0 2, cca_time1 2
        moe_intermediate_size=moe_intermediate_size,
        num_experts=num_experts,
        # the experts and the skip
        num_routed_experts=num_experts + 1,
        router_skip=True,
        num_experts_per_tok=1,
        norm_topk_prob=False,
        router_score_function="softmax",
        router_expert_bias=True,
        router_hidden_size=router_hidden_size,
        router_carry=True,
        residual_scaling=True,
        norm_eps=1e-5,
        tie_word_embeddings=True,
        embedding_init_std=0.02,
        **extra,
    )


def zaya_tiny(vocab_size: int = 256, init_jitter: float = 0.0) -> Qwen3MoeConfig:
    """CPU-runnable ZAYA-shaped config (tests, ``--tiny`` benchmark
    runs): 4 layers at 32, 4 query heads on 2 key/value heads of 16, 4
    experts of 32 and a skip, a router 8 wide."""
    return _zaya(
        vocab_size=vocab_size, hidden_size=32, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=16, moe_intermediate_size=32,
        num_experts=4, router_hidden_size=8, init_jitter=init_jitter,
        remat=False,
    )


def zaya1_8b(vocab_size: int = 262_272) -> Qwen3MoeConfig:
    """ZAYA1-8B geometry (8.4B total / 0.8B active): 40 layers at 2,048,
    8 query heads on 2 key/value heads of 128, 16 experts of 2,048 and a
    skip at top-1, a router 256 wide, a tied 262,272-row table."""
    return _zaya(
        vocab_size=vocab_size, hidden_size=2048, num_layers=40, num_heads=8,
        num_kv_heads=2, head_dim=128, moe_intermediate_size=2048,
        num_experts=16, router_hidden_size=256,
    )


DECODE_LAYERS = 12
# how far the seeded benchmark weights' learned vectors stand from the
# ones and zeros of the published init
DECODE_INIT_JITTER = 0.02


def zaya1_8b_decode() -> Qwen3MoeConfig:
    """The first 12 of the 40 layers at every published width, every
    expert and the whole table: one chip's pipeline stage of three (the
    benchmark's ``zaya1-8b-decode`` configuration). Its learned vectors
    are drawn ``DECODE_INIT_JITTER`` from their published init, so that
    seeded weights exercise each."""
    whole = zaya1_8b()
    return dataclasses.replace(
        whole, num_layers=DECODE_LAYERS,
        layer_kinds=whole.layer_kinds[:DECODE_LAYERS],
        init_jitter=DECODE_INIT_JITTER,
    )
