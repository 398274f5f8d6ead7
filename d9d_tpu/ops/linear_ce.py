"""Fused linear + cross-entropy that never holds the full logit matrix.

TPU equivalent of the reference's vendored Cut Cross-Entropy
(d9d/kernel/cce/main.py:119): the LM head projection and the CE loss are
fused so the ``[tokens, vocab]`` logit tensor is only ever materialized one
slab at a time, and the backward pass recomputes each slab's logits
(``jax.checkpoint``) instead of storing them, trading MXU FLOPs (cheap)
for HBM (the bottleneck), which is exactly the trade the Triton kernel
makes on GPU.

Above the single-slab size the slab is a **block of the vocabulary with
the tokens whole**: a ``lax.scan`` whose scanned input is the weight's
``[Vb, D]`` blocks. Under autodiff a scanned input's cotangent is the
backward scan's stacked output, so each block of the weight gradient is
one product over all the tokens, written once; what the backward loop
carries is the hidden state's gradient ``[N, D]`` in float32. The older
loop, over 512-token chunks with the vocabulary whole, carries the
weight's whole gradient through HBM once a chunk (32 times at 16,384
tokens; a third of the one-layer Qwen3-30B-A3B step, PERF.md §6, PR 39);
``chunk_size=<int>`` still selects it, and the tests hold the two to
each other.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from d9d_tpu.core.compat import get_abstract_mesh
from d9d_tpu.core.types import Array

LM_IGNORE_INDEX = -100


def _slab_logits(
    hidden: Array,
    weight: Array,
    spec: str,
    logit_softcap: float | None,
    matmul_dtype: str,
) -> Array:
    """fp32 logits of one slab; ``spec`` contracts hidden with weight.

    ``matmul_dtype="bf16"`` runs the product — the largest matmul in an
    LM step — with bf16 inputs and fp32 accumulation
    (``preferred_element_type``), the full-throughput MXU path; "fp32"
    keeps fp32 inputs (half-rate MXU) for exact math.
    """
    if matmul_dtype == "bf16":
        logits = jnp.einsum(
            spec,
            hidden.astype(jnp.bfloat16),
            weight.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    else:
        logits = jnp.einsum(
            spec,
            hidden.astype(jnp.float32),
            weight.astype(jnp.float32),
            precision=lax.Precision.DEFAULT,
        )
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    return logits


def _chunk_loss(
    hidden: Array,
    labels: Array,
    weight_t: Array,
    logit_softcap: float | None,
    matmul_dtype: str = "fp32",
) -> Array:
    """Per-token loss for one chunk. hidden [C,D], labels [C], weight_t [D,V].

    The softmax/LSE math is fp32 whatever :func:`_slab_logits` multiplies in.
    """
    logits = _slab_logits(
        hidden, weight_t, "cd,dv->cv", logit_softcap, matmul_dtype
    )
    lse = jax.nn.logsumexp(logits, axis=-1)
    safe_labels = jnp.clip(labels, 0, logits.shape[-1] - 1)
    correct = jnp.take_along_axis(logits, safe_labels[:, None], axis=-1)[:, 0]
    loss = lse - correct
    return jnp.where(labels == LM_IGNORE_INDEX, 0.0, loss)


def _block_stats(
    hidden32: Array,
    labels: Array,
    w_block: Array,
    first_col: Array,
    logit_softcap: float | None,
    matmul_dtype: str,
) -> tuple[Array, Array]:
    """One vocabulary block's log-sum-exp and label logit, each ``[N]``.

    hidden32 [N,D] float32, labels [N] in ``[0, V)``, w_block [Vb,D] the
    weight's rows from ``first_col`` on. The label's logit is picked by a
    column mask, not a gather: its transpose is a select in the slab's own
    pass where a gather's is a scatter of N rows in every block.
    """
    # the barrier keeps the loop's slice of the scanned blocks out of the
    # product's fusion: the block is then staged in fast memory once, as
    # in the backward, where two products read it
    w_block = lax.optimization_barrier(w_block)
    logits = _slab_logits(
        hidden32, w_block, "nd,vd->nv", logit_softcap, matmul_dtype
    )
    cols = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    at_label = cols == (labels - first_col)[:, None]
    label_logit = jnp.sum(jnp.where(at_label, logits, 0.0), axis=-1)
    return jax.nn.logsumexp(logits, axis=-1), label_logit


# one slab, no loop and no recomputation, up to this many tokens AND this
# many float32 logits (2048 tokens × 32768 vocab ≈ 268 MB); beyond either
# the loss is looped, and the second is also the budget the vocabulary
# block's [N, Vb] slab is cut to (never hold [N, V])
_AUTO_SINGLE_CHUNK_MAX = 2048
_AUTO_SINGLE_CHUNK_MAX_LOGITS = 2048 * 32_768
_LANES = 128  # a vocabulary block is a multiple of the lane width


def _vocab_block_loss(
    hidden: Array,
    weight: Array,
    labels: Array,
    logit_softcap: float | None,
    matmul_dtype: str,
) -> Array:
    """Per-token loss through a scan over blocks of the vocabulary.

    The block is the widest multiple of 128 columns whose ``[N, Vb]``
    float32 slab stays inside ``_AUTO_SINGLE_CHUNK_MAX_LOGITS``; a ragged
    rest of the vocabulary (151,936 = 2^7 x 1,187 has no useful divisor) is
    one more block after the loop, so the scanned blocks are a copy of the
    weight's first rows and the gradient is put together from two pieces:
    one pass over the weight each, where V is not a multiple of the block.
    Tokens are tiled only where N x 128 columns would not fit (half a
    million tokens at the default budget): the tiles are an outer
    ``lax.map``, which then accumulates the weight's gradient once a tile.
    """
    n, d = hidden.shape
    v = weight.shape[0]
    budget = _AUTO_SINGLE_CHUNK_MAX_LOGITS
    tiles = -(-n * _LANES // budget)
    tile = -(-n // tiles)
    vb = max(budget // tile // _LANES, 1) * _LANES
    blocks, vb = (1, v) if vb >= v else (v // vb, vb)
    main = blocks * vb

    body = jax.checkpoint(
        functools.partial(
            _block_stats,
            logit_softcap=logit_softcap,
            matmul_dtype=matmul_dtype,
        )
    )

    w_blocks = weight[:main].reshape(blocks, vb, d)
    first_cols = jnp.arange(blocks, dtype=jnp.int32) * vb

    def tile_loss(hidden32, labels):
        _, (lse, label_logit) = lax.scan(
            lambda carry, xs: (carry, body(hidden32, labels, *xs)),
            None,
            (w_blocks, first_cols),
        )
        if main < v:
            rest = body(hidden32, labels, weight[main:], jnp.int32(main))
            lse = jnp.concatenate([lse, rest[0][None]])
            label_logit = jnp.concatenate([label_logit, rest[1][None]])
        return jax.nn.logsumexp(lse, axis=0) - label_logit.sum(axis=0)

    # the hidden state is closed over, so its gradient is the backward
    # loop's carry: in float32 it is rounded once, after the last block,
    # as the token-chunk loop rounds it once a chunk
    hidden32 = hidden.astype(jnp.float32)
    safe_labels = jnp.clip(labels, 0, v - 1)
    if tiles == 1:
        loss = tile_loss(hidden32, safe_labels)
    else:
        pad = tiles * tile - n
        hidden32 = jnp.pad(hidden32, ((0, pad), (0, 0)))
        safe_labels = jnp.pad(safe_labels, (0, pad))
        loss = lax.map(
            lambda xs: jax.checkpoint(tile_loss)(*xs),
            (hidden32.reshape(tiles, tile, d), safe_labels.reshape(tiles, tile)),
        ).reshape(-1)[:n]
    return jnp.where(labels == LM_IGNORE_INDEX, 0.0, loss)


def linear_cross_entropy(
    hidden: Array,
    weight: Array,
    labels: Array,
    *,
    chunk_size: "int | str" = "auto",
    logit_softcap: float | None = None,
    matmul_dtype: str | None = None,
) -> Array:
    """Per-token CE of ``hidden [N,D] @ weight[V,D].T`` against ``labels [N]``.

    Tokens labelled ``LM_IGNORE_INDEX`` (-100) contribute zero loss
    (reference: module/block/head/language_modelling.py:14). Returns fp32
    ``[N]`` — reduction/weighting is the caller's policy, and so is the
    per-token cotangent: the backward is plain autodiff, so a weight that
    is not differentiated (a frozen head) costs no weight-gradient product.

    ``matmul_dtype`` (see :func:`_slab_logits`) defaults to the policy
    implied by ``hidden.dtype``: bf16 activations take the full-rate MXU
    path, anything else stays exact fp32 — so fp32 callers never lose
    precision silently.

    ``chunk_size="auto"`` (default) picks the loop from the static shapes:
    one slab up to n=2048 AND a logit slab no bigger than 2048×32768;
    beyond, the scan over vocabulary blocks (:func:`_vocab_block_loss`),
    whose block is cut to the same slab budget (4,096 columns at 16,384
    tokens, 38 blocks of Qwen3's vocabulary). An int pins the older loop
    over token chunks of that size with the vocabulary whole (one slab
    where n fits the chunk). The budget is the one number left from the
    toy-width sweeps; the block loop's reading on the chip is in PERF.md
    §6, PR 39 (ROADMAP S7).

    Traced under an ambient mesh of several devices (``MeshParameters
    .build`` sets one), "auto" keeps the token-chunk loop at 512. The
    partitioner decides a loop's collectives from its shapes: scanning
    token chunks that are sharded makes it gather the tokens once and
    leave a row-sharded head (``la.VOCAB`` over the FSDP axes) where it
    is, each chip on its own columns; given the block loop it keeps the
    tokens and gathers every block of the head in both passes, then
    reduces every block's gradient (compiled for four chips, PERF.md §6,
    PR 39). A block loop for a sharded head has to name the axes; this
    function is told none.
    """
    if matmul_dtype is None:
        matmul_dtype = "bf16" if hidden.dtype == jnp.bfloat16 else "fp32"
    n, d = hidden.shape
    if chunk_size == "auto":
        v = weight.shape[0]
        single = (
            n <= _AUTO_SINGLE_CHUNK_MAX
            and n * v <= _AUTO_SINGLE_CHUNK_MAX_LOGITS
        )
        if single:
            chunk_size = n
        elif get_abstract_mesh().size > 1:
            chunk_size = 512
        else:
            return _vocab_block_loss(
                hidden, weight, labels, logit_softcap, matmul_dtype
            )
    chunk_size = int(chunk_size)
    weight_t = weight.T  # [D, V]

    if n <= chunk_size:
        return _chunk_loss(
            hidden, labels, weight_t, logit_softcap, matmul_dtype
        )

    pad = (-n) % chunk_size
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=LM_IGNORE_INDEX)
    num_chunks = hidden.shape[0] // chunk_size
    hidden = hidden.reshape(num_chunks, chunk_size, d)
    labels = labels.reshape(num_chunks, chunk_size)

    body = jax.checkpoint(
        functools.partial(
            _chunk_loss,
            logit_softcap=logit_softcap,
            matmul_dtype=matmul_dtype,
        )
    )

    def scan_fn(carry, xs):
        h, l = xs
        return carry, body(h, l, weight_t)

    _, losses = lax.scan(scan_fn, None, (hidden, labels))
    losses = losses.reshape(-1)
    return losses[:n] if pad else losses
