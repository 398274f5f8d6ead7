"""Model families: Qwen3 (dense / MoE / Next-hybrid — reference parity),
Llama-3 (beyond-reference, BASELINE config 4), DeepSeek-V2
(beyond-reference: MLA latent attention + shared-expert MoE, with
GLM-4.7-Flash on the same backbone), and Jamba (beyond-reference: Mamba-1
state-space layers beside multi-query attention without rotation, a
tied table)."""

from d9d_tpu.models import deepseek, jamba, llama, qwen3

__all__ = ["deepseek", "jamba", "llama", "qwen3"]
