"""Jamba model family (AI21): Mamba-1 state-space layers beside attention.

Beyond-reference family (the reference ships only Qwen3 models). Jamba's
decoder is the shared stack (``models/qwen3/moe.py``) with a third kind
of token mixer: layer ``i`` is attention when ``i % attn_layer_period ==
attn_layer_offset`` and a Mamba-1 mixer (``nn/mamba.py``, with the
family's inner RMSNorms on dt, B and C) otherwise
(``Qwen3MoeConfig.mamba_layers``). The attention layers carry no
positional encoding (``rope_fraction`` 0: the backbone builds no
frequencies), no q/k norm and no bias; Jamba2-3B's are multi-query (20
query heads on one key/value head). Every layer's feed-forward is a
dense SwiGLU when ``num_experts`` is 1 (every layer in
``mlp_only_layers``; the expert period and offset then select nothing),
and the output head reads the embedding table
(``tie_word_embeddings``), which is drawn at the family's
``initializer_range`` 0.02. A stack with state-space layers adds its
residual stream in float32 (Mamba's ``residual_in_fp32``) whatever the
blocks' type.

Sharding plans, the Trainer (fused cross-entropy on the tied table),
``generate`` and ``ContinuousBatcher`` apply unchanged: the mixer's
``ssm_state`` and ``conv_tail`` are per-row cache leaves the serving
loop zeroes on admission, beside the attention layers' paged KV pools;
the prefix cache and ``speculative_generate`` refuse a model with such
leaves. Held to ``benchmarks/references/jamba.py`` in
``tests/models/test_jamba.py`` and, at published widths on the chip, in
the benchmark's ``jamba2-3b-decode`` cell. No Hugging Face weight mapper
exists yet.
"""

from d9d_tpu.models.qwen3.moe import (
    Qwen3MoeBackbone as JambaBackbone,
    Qwen3MoeCausalLM as JambaCausalLM,
    Qwen3MoeConfig,
)

JambaConfig = Qwen3MoeConfig  # same static surface; mamba_layers set


def _jamba(*, vocab_size, hidden_size, num_layers, num_heads, head_dim,
           intermediate_size, attn_layer_period, attn_layer_offset,
           mamba_dt_rank, **extra) -> Qwen3MoeConfig:
    layers = range(num_layers)
    return Qwen3MoeConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=hidden_size,
        num_layers=num_layers,
        num_heads=num_heads,
        num_kv_heads=1,
        head_dim=head_dim,
        # num_experts 1: a dense SwiGLU in every layer
        moe_intermediate_size=intermediate_size,
        num_experts=1,
        num_experts_per_tok=1,
        intermediate_size=intermediate_size,
        mlp_only_layers=tuple(layers),
        qk_norm=False,
        rope_fraction=0.0,
        mamba_layers=tuple(
            i for i in layers if i % attn_layer_period != attn_layer_offset
        ),
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_dt_rank=mamba_dt_rank,
        tie_word_embeddings=True,
        embedding_init_std=0.02,
        norm_eps=1e-6,
        **extra,
    )


def jamba_tiny(vocab_size: int = 256, num_layers: int = 2,
               attn_layer_period: int = 2,
               attn_layer_offset: int = 1) -> Qwen3MoeConfig:
    """CPU-runnable Jamba-shaped config (tests, ``--tiny`` benchmark
    runs): a Mamba-1 mixer and a multi-query attention layer, so both
    kinds of cache, dense SwiGLU in both, tied table."""
    return _jamba(
        vocab_size=vocab_size, hidden_size=64, num_layers=num_layers,
        num_heads=4, head_dim=16, intermediate_size=128,
        attn_layer_period=attn_layer_period,
        attn_layer_offset=attn_layer_offset, mamba_dt_rank=4, remat=False,
    )


def jamba2_3b(vocab_size: int = 65_536) -> Qwen3MoeConfig:
    """AI21-Jamba2-3B geometry (Jamba Reasoning 3B; 3.03 B parameters):
    28 layers of hidden size 2,560, attention (20 query heads on one
    key/value head of 128, no rotation) at layers 7 and 21 and a Mamba-1
    mixer (d_inner 5,120, d_state 16, d_conv 4, dt_rank 160) in the other
    26, a dense SwiGLU of 8,192 in every layer, tied 65,536-row table."""
    return _jamba(
        vocab_size=vocab_size, hidden_size=2560, num_layers=28,
        num_heads=20, head_dim=128, intermediate_size=8192,
        attn_layer_period=14, attn_layer_offset=7, mamba_dt_rank=160,
    )
