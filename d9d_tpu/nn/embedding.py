"""Token embeddings with named vocabulary ranges.

Reference: d9d/module/block/embedding/shard_token_embedding.py:26
(``SplitTokenEmbeddings``) — the vocabulary is declared as an ordered dict
of named ranges (e.g. {"text": 151k, "special": 1k}); each range is a
separate parameter so checkpoints can remap/extend vocabularies per range.
Lookup concatenates the ranges logically. On TPU the concat embedding table
is gathered with one ``take``; the vocab axis carries the ``vocab`` logical
axis so a TP plan shards the lookup (XLA lowers the cross-shard gather to a
masked-sum + psum, the same trick the reference implements by hand).
"""

import flax.linen as nn
import jax.numpy as jnp

from d9d_tpu.core.types import Array
from d9d_tpu.nn.vocab_ranges import concat_vocab_ranges, make_vocab_range_params


class TokenEmbedding(nn.Module):
    """Embedding over named vocab ranges, stored as separate params."""

    vocab_ranges: tuple[tuple[str, int], ...]  # ordered (name, size)
    hidden_size: int
    # a table that is also the output head (tied) wants the family's
    # small initializer_range: logits are then of order 1 at init
    init_std: float = 1.0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @property
    def vocab_size(self) -> int:
        return sum(size for _, size in self.vocab_ranges)

    @nn.compact
    def __call__(self, token_ids: Array) -> Array:
        tables = make_vocab_range_params(
            self.param,
            "embedding",
            self.vocab_ranges,
            self.hidden_size,
            self.param_dtype,
            nn.initializers.normal(stddev=self.init_std),
        )
        table = concat_vocab_ranges(tables)
        return jnp.take(table, token_ids, axis=0).astype(self.dtype)
