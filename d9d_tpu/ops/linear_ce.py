"""Fused linear + cross-entropy that never holds the full logit matrix.

TPU equivalent of the reference's vendored Cut Cross-Entropy
(d9d/kernel/cce/main.py:119): the LM head projection and the CE loss are
fused so the ``[tokens, vocab]`` logit tensor is only ever materialized one
token-chunk at a time. On TPU this is a ``lax.scan`` over token chunks with
rematerialization (``jax.checkpoint``) — the backward pass recomputes each
chunk's logits instead of storing them, trading MXU FLOPs (cheap) for HBM
(the bottleneck), which is exactly the trade the Triton kernel makes on GPU.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from d9d_tpu.core.types import Array

LM_IGNORE_INDEX = -100


def _chunk_loss(
    hidden: Array,
    labels: Array,
    weight_t: Array,
    logit_softcap: float | None,
    matmul_dtype: str = "fp32",
) -> Array:
    """Per-token loss for one chunk. hidden [C,D], labels [C], weight_t [D,V].

    ``matmul_dtype="bf16"`` runs the [C,D]x[D,V] einsum — the largest
    matmul in an LM step — with bf16 inputs and fp32 accumulation
    (``preferred_element_type``), the full-throughput MXU path; "fp32"
    keeps fp32 inputs (half-rate MXU) for exact math; the softmax/LSE
    math is fp32 either way.
    """
    if matmul_dtype == "bf16":
        logits = jnp.einsum(
            "cd,dv->cv",
            hidden.astype(jnp.bfloat16),
            weight_t.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    else:
        logits = jnp.einsum(
            "cd,dv->cv",
            hidden.astype(jnp.float32),
            weight_t.astype(jnp.float32),
            precision=lax.Precision.DEFAULT,
        )
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    lse = jax.nn.logsumexp(logits, axis=-1)
    safe_labels = jnp.clip(labels, 0, logits.shape[-1] - 1)
    correct = jnp.take_along_axis(logits, safe_labels[:, None], axis=-1)[:, 0]
    loss = lse - correct
    return jnp.where(labels == LM_IGNORE_INDEX, 0.0, loss)


# auto chunking: a single chunk wins up to this many tokens (r3 sweep) —
# but only while the live logit slab stays within the swept budget
# (2048 tokens × 32768 vocab ≈ 268 MB fp32); larger n·V keeps chunking,
# which is the whole point of CCE (never hold [N, V])
_AUTO_SINGLE_CHUNK_MAX = 2048
_AUTO_SINGLE_CHUNK_MAX_LOGITS = 2048 * 32_768


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
    ``[N]`` — reduction/weighting is the caller's policy.

    ``matmul_dtype`` (see :func:`_chunk_loss`) defaults to the policy
    implied by ``hidden.dtype``: bf16 activations take the full-rate MXU
    path, anything else stays exact fp32 — so fp32 callers never lose
    precision silently.

    ``chunk_size`` trades the live logit slab against per-chunk
    overhead: 512 holds the smallest slab on long inputs, a single chunk
    avoids the loop on short ones. ``"auto"`` (default): one chunk up to
    n=2048 AND a logit slab no bigger than 2048×32768, 512 beyond — pass
    an int to pin it. The thresholds come from toy-width sweeps and no
    cell has re-measured them; the head and loss are 53.2 % of device time
    on ``qwen3-30b-a3b-l1.train-16k`` (builder's chip run, PR 25; PERF.md
    §5; ROADMAP S7, B3).
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
        chunk_size = n if single else 512
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
