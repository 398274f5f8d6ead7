"""Model heads: language modelling (fused CE), classification, embedding.

Reference: d9d/module/block/head/{language_modelling.py:14,
classification.py:7, embedding.py:8}.
"""

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from d9d_tpu.core.types import Array
from d9d_tpu.nn import logical_axes as la
from d9d_tpu.nn.vocab_ranges import concat_vocab_ranges, make_vocab_range_params
from d9d_tpu.ops import LM_IGNORE_INDEX, linear_cross_entropy


class LanguageModellingHead(nn.Module):
    """LM head over named vocab ranges with fused linear+CE loss.

    ``__call__`` returns per-token loss (never materializing full logits,
    reference language_modelling.py:14 via CCE); ``logits`` returns raw
    logits for inference/eval paths. ``tied`` heads own no parameters:
    both take the embedding table ``[V, D]`` the caller hands them
    (``tie_word_embeddings``), through the same fused cross-entropy.

    ``ce_chunk_size`` is ``linear_cross_entropy``'s ``chunk_size``:
    ``"auto"`` (what every model passes) is one slab for a short call and,
    beyond the single-slab size, a scan over blocks of the vocabulary with
    the tokens whole; an int pins the older loop over token chunks of that
    size with the vocabulary whole.
    """

    vocab_ranges: tuple[tuple[str, int], ...]
    hidden_size: int
    ce_chunk_size: "int | str" = "auto"
    logit_softcap: float | None = None
    # the logits are the products over this constant (Granite's
    # ``logits_scaling``), in the loss and in ``logits`` alike
    logits_divisor: float = 1.0
    tied: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        if self.tied:
            return
        self._tables = make_vocab_range_params(
            self.param,
            "head",
            self.vocab_ranges,
            self.hidden_size,
            self.param_dtype,
            nn.initializers.lecun_normal(),
        )

    def _weight(self, table: Optional[Array]) -> Array:
        if self.tied != (table is not None):
            raise ValueError(
                "a tied head, and only a tied head, is handed the table"
            )
        return table if self.tied else concat_vocab_ranges(self._tables)

    def __call__(
        self, hidden: Array, labels: Array, table: Optional[Array] = None
    ) -> Array:
        """hidden [B,T,D], labels [B,T] → per-token loss [B,T] (fp32)."""
        w = self._weight(table)
        b, t, d = hidden.shape
        if self.logits_divisor != 1.0:
            # (h / s) W^T = (h W^T) / s: the fused loss never holds logits
            hidden = hidden.astype(jnp.float32) / self.logits_divisor
        # CE matmul policy follows the activation dtype (linear_ce default):
        # bf16 models take the full-rate MXU path, fp32 models stay exact
        loss = linear_cross_entropy(
            hidden.reshape(b * t, d).astype(self.dtype),
            w,
            labels.reshape(b * t),
            chunk_size=self.ce_chunk_size,
            logit_softcap=self.logit_softcap,
        )
        return loss.reshape(b, t)

    def logits(self, hidden: Array, table: Optional[Array] = None) -> Array:
        w = self._weight(table)
        logits = hidden.astype(jnp.float32) @ w.astype(jnp.float32).T
        if self.logits_divisor != 1.0:
            logits = logits / self.logits_divisor
        return logits


class ClassificationHead(nn.Module):
    """Linear classifier over a pooled hidden state (reference classification.py:7)."""

    hidden_size: int
    num_classes: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, hidden: Array) -> Array:
        return nn.Dense(
            self.num_classes,
            use_bias=False,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), (la.EMBED, la.CLASSES)
            ),
            name="classifier",
        )(hidden).astype(jnp.float32)


class EmbeddingHead(nn.Module):
    """Mean-pool + L2-normalize sentence embeddings (reference embedding.py:8)."""

    @nn.compact
    def __call__(self, hidden: Array, pooling_mask: Optional[Array] = None) -> Array:
        """hidden [B,T,D], pooling_mask [B,T] (1 = include) → [B,D] fp32."""
        h = hidden.astype(jnp.float32)
        if pooling_mask is None:
            pooled = h.mean(axis=1)
        else:
            m = pooling_mask.astype(jnp.float32)[..., None]
            pooled = (h * m).sum(axis=1) / jnp.maximum(m.sum(axis=1), 1.0)
        norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
        return pooled / jnp.maximum(norm, 1e-12)


__all__ = [
    "LM_IGNORE_INDEX",
    "LanguageModellingHead",
    "ClassificationHead",
    "EmbeddingHead",
]
