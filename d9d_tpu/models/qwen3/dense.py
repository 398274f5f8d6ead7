"""Qwen3-dense model family: stage-aware backbone + task heads.

Reference: d9d/module/model/qwen3_dense/model.py (stage-aware backbone with
layers keyed by *global* layer id) and the head variants. The backbone takes
token ids on the first pipeline stage and hidden states on later stages;
only the last stage applies the final norm / head. Layer params are named
``layers_{global_id}`` so checkpoints are stage-layout independent —
repartitioning the pipeline never remaps weights.
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding

from d9d_tpu.core.types import Array
from d9d_tpu.models.qwen3.config import Qwen3DenseConfig
from d9d_tpu.nn.decoder import DecoderLayer
from d9d_tpu.nn.embedding import TokenEmbedding
from d9d_tpu.nn.heads import ClassificationHead, EmbeddingHead, LanguageModellingHead
from d9d_tpu.nn.norm import RMSNorm
from d9d_tpu.nn.sdpa.protocol import SdpaBackend
from d9d_tpu.ops import compute_rope_frequencies, make_rope_cos_sin
from d9d_tpu.pipelining import (
    PipelineStageInfo,
    distribute_layers_for_pipeline_stage,
)
from d9d_tpu.telemetry import numerics


def _remat_policy(name: str):
    """Map a config string to a jax.checkpoint policy.

    One rule under every policy: a rematerialised layer is recomputed from
    its input except the flash call, whose output and log-sum-exp
    ("sdpa_out", "sdpa_lse": named on the kernel's own residuals inside
    ``ops/attention/pallas_flash.py``'s forward rules) are kept. They cost
    ``T x H x (2 D + 4)`` bytes a layer to keep and a whole forward kernel
    to make again; q, k and v are still recomputed (the projections and the
    rotation run again, as they must for their own gradients). Where the
    compiler already merges the recomputed forward with the first
    (``prevent_cse=False`` on an unrolled stack) no kernel stops running
    and the kept log-sum-exp costs its trip through the dense ``[B, H, T]``
    form (0.4 ms a layer of 32 heads at 4 x 4,096; PERF.md section 6,
    PR 46).

    ``full`` keeps nothing else. ``dots_no_batch`` also keeps every plain
    matmul output. ``save_expensive`` keeps those and the MoE
    grouped-matmul outputs and their permuted input rows
    ("moe_grouped_dot" / "moe_permuted_rows"), at activation memory
    proportional to the layer's width; "full" remains the default for
    memory-bound configs.
    """
    also_named = {
        "full": (),
        "dots_no_batch": (),
        "save_expensive": ("moe_grouped_dot", "moe_permuted_rows"),
    }
    if name not in also_named:
        raise ValueError(f"unknown remat_policy {name!r}")
    policies = jax.checkpoint_policies
    kept = policies.save_only_these_names(
        "sdpa_out", "sdpa_lse", *also_named[name]
    )
    if name == "full":
        return kept
    return policies.save_from_both_policies(
        policies.checkpoint_dots_with_no_batch_dims, kept
    )


class Qwen3DenseBackbone(nn.Module):
    config: Qwen3DenseConfig
    sdpa: SdpaBackend
    # KV-cache decode mode (loop/generate.py): 0 = training/eval path
    decode_max_length: int = 0
    stage: PipelineStageInfo = PipelineStageInfo()
    # residual-stream [B, T, E] sharding pin: anchors SPMD propagation at
    # every layer boundary so activation layouts can't drift into fused
    # batch shardings that force replicate-reshard at attention (the ring
    # SDPA wants [b@dp, t@cp_s, h@tp])
    act_sharding: Optional[NamedSharding] = None
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
    ) -> Array:
        cfg = self.config
        if self.stage.is_first:
            x = TokenEmbedding(
                vocab_ranges=cfg.vocab_ranges,
                hidden_size=cfg.hidden_size,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="embed_tokens",
            )(x)
        else:
            x = x.astype(self.dtype)
        x = self._pin(x)

        inv_freq, att_scale = compute_rope_frequencies(
            cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
        )
        cos, sin = make_rope_cos_sin(positions, inv_freq, att_scale)

        layer_cls = DecoderLayer
        # remat is a backward-pass tool; decode is forward-only and its
        # mutable cache variables don't compose with nn.remat
        if cfg.remat and self.decode_max_length == 0:
            layer_cls = nn.remat(
                DecoderLayer,
                prevent_cse=False,
                policy=_remat_policy(cfg.remat_policy),
            )

        for gid in distribute_layers_for_pipeline_stage(cfg.num_layers, self.stage):
            x = layer_cls(
                hidden_size=cfg.hidden_size,
                num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim,
                intermediate_size=cfg.intermediate_size,
                sdpa=self.sdpa,
                qk_norm=cfg.qk_norm,
                window_size=cfg.window_size,
                use_sinks=cfg.use_sinks,
                use_output_gate=cfg.use_output_gate,
                fused_qkv=cfg.fused_qkv,
                norm_eps=cfg.norm_eps,
                decode_max_length=self.decode_max_length,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"layers_{gid}",
            )(x, cos, sin, mask)
            x = self._pin(x)
            # numerics plane (telemetry/numerics.py): tap each layer's
            # residual-stream output HERE — outside the (possible)
            # nn.remat boundary — named by the layer's module path.
            # A no-op unless a numerics-enabled train step is tracing.
            numerics.tap(f"layers_{gid}", x)

        if self.stage.is_last:
            x = RMSNorm(cfg.hidden_size, eps=cfg.norm_eps, name="norm")(x)
            numerics.tap("norm", x)
        return x


class Qwen3DenseCausalLM(nn.Module):
    """Backbone + fused-CE LM head.

    On the last stage, ``__call__`` with labels returns per-token loss
    ``[B, T]``; non-last stages return the hidden state to send downstream.
    ``logits`` serves inference.
    """

    config: Qwen3DenseConfig
    sdpa: SdpaBackend
    stage: PipelineStageInfo = PipelineStageInfo()
    ce_chunk_size: "int | str" = "auto"
    act_sharding: Optional[NamedSharding] = None
    # KV-cache decode mode (loop/generate.py): 0 = training/eval path
    decode_max_length: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        self.model = Qwen3DenseBackbone(
            config=self.config,
            sdpa=self.sdpa,
            stage=self.stage,
            act_sharding=self.act_sharding,
            decode_max_length=self.decode_max_length,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        if self.stage.is_last:
            self.lm_head = LanguageModellingHead(
                vocab_ranges=self.config.vocab_ranges,
                hidden_size=self.config.hidden_size,
                ce_chunk_size=self.ce_chunk_size,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
            )

    def __call__(
        self,
        x: Array,
        positions: Array,
        labels: Optional[Array] = None,
        mask: Optional[Array] = None,
    ) -> Array:
        h = self.model(x, positions, mask)
        if self.stage.is_last and labels is not None:
            return self.lm_head(h, labels)
        return h

    def logits(
        self, x: Array, positions: Array, mask: Optional[Array] = None
    ) -> Array:
        h = self.model(x, positions, mask)
        if not self.stage.is_last:
            return h
        return self.lm_head.logits(h)

    def logits_last(
        self, x: Array, positions: Array, mask: Optional[Array] = None
    ) -> Array:
        """Logits for the LAST position only ``[B, 1, V]`` — the prefill
        fast path (loop/generate.py): the backbone runs over the full
        prompt (writing caches in decode mode) but the LM head matmul
        covers one row instead of P."""
        h = self.model(x, positions, mask)
        if not self.stage.is_last:
            return h
        return self.lm_head.logits(h[:, -1:])


class Qwen3DenseForClassification(nn.Module):
    """Backbone + last-token classification head (reference model.py heads)."""

    config: Qwen3DenseConfig
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
    ) -> Array:
        h = Qwen3DenseBackbone(
            config=self.config,
            sdpa=self.sdpa,
            stage=self.stage,
            act_sharding=self.act_sharding,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="model",
        )(x, positions, mask)
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


class Qwen3DenseForEmbedding(nn.Module):
    """Backbone + pooled L2-normalized embedding head."""

    config: Qwen3DenseConfig
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
    ) -> Array:
        h = Qwen3DenseBackbone(
            config=self.config,
            sdpa=self.sdpa,
            stage=self.stage,
            act_sharding=self.act_sharding,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="model",
        )(x, positions, mask)
        if not self.stage.is_last:
            return h
        return EmbeddingHead()(h, pooling_mask)
