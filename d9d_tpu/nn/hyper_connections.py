"""Manifold-constrained hyper-connections: an n-stream residual path.

mHC (arXiv:2512.24880) on hyper-connections (arXiv:2409.19606). The
residual stream is ``n`` rows of width ``C`` a token, ``x_l`` in
``R^{n x C}``. Around one sublayer ``F`` (attention, or the dense or
expert feed-forward):

    x'      = RMSNorm(vec(x_l))                 over all n C numbers, no gain
    H~pre   = a_pre  (x' phi_pre)  + b_pre      phi_pre,  phi_post: [n C, n]
    H~post  = a_post (x' phi_post) + b_post
    H~res   = a_res mat(x' phi_res) + b_res     phi_res: [n C, n n]
    H_pre   = sigmoid(H~pre)
    H_post  = 2 sigmoid(H~post)
    H_res   = Sinkhorn(exp(clamp(H~res)))       rows to sum 1, then columns,
                                                ``eps`` in each denominator
    x_{l+1} = H_res x_l + H_post^T F(norm(H_pre x_l))

``H_res`` is (nearly) doubly stochastic: mixing the streams neither grows
nor shrinks their sum. The stream starts as ``n`` copies of the embedding
and is read out by the sum over the streams (:func:`expand_streams`,
:func:`sum_streams`).

The coefficients and the Sinkhorn rounds are float32 with the tokens on
the minor axis (``[n, n, B, T]``: sixteen numbers a token would waste a
vector register's tile each); the stream stays in the model's dtype. One
matmul forms all ``n n + 2 n`` projections, and the norm's ``rsqrt`` is
applied to its result, so forming the coefficients reads the stream once.
The ops carry ``mhc/{norm,coef,sinkhorn,pre,post}`` scopes (and the
stream's two ends ``mhc/{expand,readout}``): a trace tells the stream's
passes from the sublayers'. The path holds no state, so
decode mode runs it as it stands.

Initialisers (the papers give none this repo could copy; the benchmark's
configuration file lists them as assumed): ``phi_*`` normal at
``(n C) ** -0.5``, so a projection of the unit-RMS ``x'`` is of order 1;
``a_* = 0.5``; ``b_pre = b_post = 0`` (``H_pre`` about a half,
``H_post`` about 1); ``b_res = 2 I`` (about 0.7 of a stream stays in
it). Every term is live at seeded init: the coefficients depend on the
token from the first step on.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from d9d_tpu.core.types import Array
from d9d_tpu.nn import logical_axes as la

A_INIT = 0.5
B_RES_DIAGONAL = 2.0


def expand_streams(x: Array, streams: int) -> Array:
    """``[B, T, C]`` → ``[B, T, n, C]``: the stream starts as n copies."""
    with jax.named_scope("mhc/expand"):
        return jnp.broadcast_to(
            x[:, :, None, :], (*x.shape[:2], streams, x.shape[-1])
        )


def sum_streams(x: Array) -> Array:
    """``[B, T, n, C]`` → ``[B, T, C]``: read out by the sum, float32 sums."""
    with jax.named_scope("mhc/readout"):
        return x.astype(jnp.float32).sum(axis=2).astype(x.dtype)


# Sinkhorn rounds a trip of the loop: unrolled whole, the 20 rounds of a
# step's 36 passes (12 sublayers forward, rematerialised and backward) made
# the step program 140 s to compile and 8.1 GB of temporaries; in a scan 89 s
# and 7.4 GB (described-v5e compiles, PR 35)
SINKHORN_UNROLL = 4


def sinkhorn(log_weights: Array, iters: int, eps: float) -> Array:
    """``[n, n, ...]`` → the same shape, rows then columns brought to sum
    1, ``iters`` times, from ``exp(log_weights)``."""

    def one_round(m, _):
        m = m / (m.sum(axis=1, keepdims=True) + eps)
        m = m / (m.sum(axis=0, keepdims=True) + eps)
        return m, None

    m, _ = jax.lax.scan(
        one_round, jnp.exp(log_weights), None, length=iters,
        unroll=min(SINKHORN_UNROLL, iters),
    )
    return m


class HyperConnection(nn.Module):
    """The n-stream path around one sublayer: :meth:`read` gives the
    sublayer's ``[B, T, C]`` input and the mixing coefficients,
    :meth:`write` the next stream from the sublayer's output."""

    hidden_size: int
    streams: int
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    res_clamp: tuple[float, float] = (-30.0, 30.0)
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def setup(self) -> None:
        n, width = self.streams, self.streams * self.hidden_size

        def phi(name: str, columns: int):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=width ** -0.5),
                    (la.EMBED, None),
                ),
                (width, columns),
                self.param_dtype,
            )

        def small(name: str, init, shape):
            # float32 like a norm's gain: a handful of numbers that scale
            # or shift every token's coefficients
            return self.param(
                name, nn.with_logical_partitioning(init, (None,) * len(shape)),
                shape, jnp.float32,
            )

        self.phi_pre = phi("phi_pre", n)
        self.phi_post = phi("phi_post", n)
        self.phi_res = phi("phi_res", n * n)
        constant = nn.initializers.constant
        self.a_pre = small("a_pre", constant(A_INIT), ())
        self.a_post = small("a_post", constant(A_INIT), ())
        self.a_res = small("a_res", constant(A_INIT), ())
        self.b_pre = small("b_pre", nn.initializers.zeros, (n,))
        self.b_post = small("b_post", nn.initializers.zeros, (n,))
        self.b_res = small(
            "b_res",
            lambda key, shape, dtype: B_RES_DIAGONAL * jnp.eye(n, dtype=dtype),
            (n, n),
        )

    def _coefficients(self, x: Array) -> tuple[Array, Array, Array]:
        """``x [B, T, n, C]`` → ``H_pre [n, B, T]``, ``H_post [n, B, T]``,
        ``H_res [n, n, B, T]``, float32."""
        b, t, n, c = x.shape
        flat = x.reshape(b, t, n * c)
        with jax.named_scope("mhc/norm"):
            square = jnp.square(flat.astype(jnp.float32)).mean(axis=-1)
            inv_rms = jax.lax.rsqrt(square + self.norm_eps)  # [B, T]
        with jax.named_scope("mhc/coef"):
            phi = jnp.concatenate(
                [self.phi_pre, self.phi_post, self.phi_res], axis=1
            ).astype(self.dtype)
            raw = jnp.einsum(
                "btk,kj->btj", flat.astype(self.dtype), phi,
                preferred_element_type=jnp.float32,
            )
            raw = jnp.moveaxis(raw, -1, 0) * inv_rms  # [n n + 2 n, B, T]
            pre, post, res = raw[:n], raw[n:2 * n], raw[2 * n:]
            h_pre = jax.nn.sigmoid(
                self.a_pre * pre + self.b_pre[:, None, None]
            )
            h_post = 2.0 * jax.nn.sigmoid(
                self.a_post * post + self.b_post[:, None, None]
            )
            res = (
                self.a_res * res.reshape(n, n, b, t)
                + self.b_res[:, :, None, None]
            )
        with jax.named_scope("mhc/sinkhorn"):
            h_res = sinkhorn(
                jnp.clip(res, *self.res_clamp), self.sinkhorn_iters, self.eps
            )
        return h_pre, h_post, h_res

    def read(self, x: Array) -> tuple[Array, tuple[Array, Array]]:
        """``x [B, T, n, C]`` → the sublayer's input ``H_pre x [B, T, C]``
        and what :meth:`write` needs."""
        h_pre, h_post, h_res = self._coefficients(x)
        with jax.named_scope("mhc/pre"):
            mixed = sum(
                h_pre[j][:, :, None] * x[:, :, j].astype(jnp.float32)
                for j in range(self.streams)
            ).astype(x.dtype)
        return mixed, (h_post, h_res)

    def write(self, x: Array, out: Array, mix: tuple[Array, Array]) -> Array:
        """``H_res x + H_post^T out``: the next stream ``[B, T, n, C]``."""
        h_post, h_res = mix
        n = self.streams
        with jax.named_scope("mhc/post"):
            # stream by stream: n (n + 1) fused multiply-adds an element and
            # no array wider than the stream (a broadcast product summed
            # over j held [B, T, n, n, C] in float32: 4.6 GB more claimed at
            # the Xing4.0 sizes, described-v5e compile, PR 35)
            wide = out.astype(jnp.float32)
            rows = [
                (sum(
                    h_res[i, j][:, :, None] * x[:, :, j].astype(jnp.float32)
                    for j in range(n)
                ) + h_post[i][:, :, None] * wide).astype(x.dtype)
                for i in range(n)
            ]
            return jnp.stack(rows, axis=2)
