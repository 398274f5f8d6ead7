"""Llama-3 model family (Llama-3-70B-class 4D runs).

Llama-3's decoder is architecturally the Qwen3-dense stack minus the
q/k RMSNorms (and with Llama's rope theta / vocab): HF even uses the
same per-layer tensor names (``model.layers.N.self_attn.q_proj`` ...).
So the family is expressed as presets over :class:`Qwen3DenseConfig`
with ``qk_norm=False`` plus thin aliases — checkpoints, sharding plans,
pipelining stages, PEFT and the HF mappers (which already gate the
q/k-norm entries on ``config.qk_norm``,
models/qwen3/huggingface.py:159) all apply unchanged. Llama-3.1 long
context rides the ``llama3`` rope-scaling law (ops/rope.py
RopeScalingLlama3 — a scaling type beyond the reference's four).

Reference parity note: the reference ships only Qwen3 models
(d9d/module/model/); this family is beyond-reference surface for the
config-4 baseline target.
"""

from d9d_tpu.models.qwen3.config import Qwen3DenseConfig
from d9d_tpu.models.qwen3.dense import (
    Qwen3DenseBackbone as LlamaBackbone,
    Qwen3DenseCausalLM as LlamaCausalLM,
    Qwen3DenseForClassification as LlamaForClassification,
    Qwen3DenseForEmbedding as LlamaForEmbedding,
)
from d9d_tpu.models.qwen3.huggingface import (
    qwen3_dense_from_hf_mapper as llama_from_hf_mapper,
    qwen3_dense_to_hf_mapper as llama_to_hf_mapper,
)
from d9d_tpu.ops import RopeScalingLlama3

LlamaConfig = Qwen3DenseConfig  # same static surface; qk_norm=False


def llama3_tiny(vocab_size: int = 256) -> Qwen3DenseConfig:
    """2-layer CPU-runnable Llama-3-shaped config (tests / smoke)."""
    return Qwen3DenseConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=128,
        qk_norm=False,
        rope_theta=500_000.0,
        remat=False,
    )


def llama3_8b(vocab_size: int = 128_256) -> Qwen3DenseConfig:
    return Qwen3DenseConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=14_336,
        qk_norm=False,
        rope_theta=500_000.0,
        norm_eps=1e-5,
    )


def llama31_8b(vocab_size: int = 128_256) -> Qwen3DenseConfig:
    """Llama-3.1: 128k context via the llama3 piecewise rope scaling."""
    return Qwen3DenseConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=14_336,
        qk_norm=False,
        rope_theta=500_000.0,
        norm_eps=1e-5,
        rope_scaling=RopeScalingLlama3(
            factor=8.0,
            original_max_position=8192,
            low_freq_factor=1.0,
            high_freq_factor=4.0,
        ),
    )


def llama3_70b(vocab_size: int = 128_256) -> Qwen3DenseConfig:
    """The BASELINE config-4 geometry (PP x TP x FSDP pod-slice runs)."""
    return Qwen3DenseConfig(
        vocab_ranges=(("default", vocab_size),),
        hidden_size=8192,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=28_672,
        qk_norm=False,
        rope_theta=500_000.0,
        norm_eps=1e-5,
    )


__all__ = [
    "LlamaBackbone",
    "LlamaCausalLM",
    "LlamaConfig",
    "LlamaForClassification",
    "LlamaForEmbedding",
    "llama3_tiny",
    "llama3_8b",
    "llama31_8b",
    "llama3_70b",
    "llama_from_hf_mapper",
    "llama_to_hf_mapper",
]
