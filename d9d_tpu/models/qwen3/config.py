"""Qwen3-dense model family configuration.

Reference: d9d/module/model/qwen3_dense/params.py:90. Pure-static dataclass
(hashable) so it can live inside jitted closures and flax module attributes.
"""

import dataclasses

from d9d_tpu.ops import RopeScaling, RopeScalingNone


@dataclasses.dataclass(frozen=True)
class Qwen3DenseConfig:
    vocab_ranges: tuple[tuple[str, int], ...]
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float = 1_000_000.0
    rope_scaling: RopeScaling = RopeScalingNone()
    qk_norm: bool = True
    norm_eps: float = 1e-6
    window_size: int | None = None
    use_sinks: bool = False
    use_output_gate: bool = False
    # single matmul for q/k/v (runtime kernel concat; see
    # nn/attention.py fused_qkv — leave off for TP plans)
    fused_qkv: bool = False
    remat: bool = True
    # "full" recomputes a layer from its input in backward but for the
    # flash call, whose output and log-sum-exp are kept under every policy
    # (T x H x (2 D + 4) bytes a layer; dense._remat_policy has the rule);
    # "dots_no_batch" also saves matmul outputs with no batch dims (XLA's
    # checkpoint_dots_with_no_batch_dims policy) — fewer recomputed FLOPs
    # for more activation memory. No cell sets it yet (ROADMAP S6).
    remat_policy: str = "full"

    @property
    def vocab_size(self) -> int:
        return sum(s for _, s in self.vocab_ranges)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "Qwen3DenseConfig":
        """2-layer CPU-runnable config."""
        return Qwen3DenseConfig(
            vocab_ranges=(("default", vocab_size),),
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            intermediate_size=128,
            remat=False,
        )

    @staticmethod
    def qwen3_8b(vocab_size: int = 151_936) -> "Qwen3DenseConfig":
        return Qwen3DenseConfig(
            vocab_ranges=(("default", vocab_size),),
            hidden_size=4096,
            num_layers=36,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            intermediate_size=12_288,
        )
