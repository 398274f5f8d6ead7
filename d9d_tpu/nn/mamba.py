"""State-space mixers: Mamba-1 as the Jamba family uses it
(:class:`MambaMixer`) and Mamba-2 as the Granite 4.0-H family does
(:class:`Mamba2Mixer`, below, with its own equations). The two share the
causal convolution with its decode tail, the padding rule, the float32
rule and the ``mamba/*`` scopes.

Mamba-1, on ``u [B, T, E]`` (the layer's normed input), with ``Di = expand·E``:

    [xs, z]       = u W_in                      (E -> 2·Di, no bias)
    xs            = silu(conv1d_causal(xs))     (depthwise, kernel K, bias)
    [dt_low, B, C] = xs W_x                     (Di -> R + N + N, no bias)
    dt_low, B, C  = RMSNorm_dt(dt_low), RMSNorm_B(B), RMSNorm_C(C)
    dt            = softplus(dt_low W_dt + b_dt)  (R -> Di)
    h_t           = exp(dt_t ⊗ A) ⊙ h_{t-1} + (dt_t ⊙ xs_t) ⊗ B_t,  A = -exp(A_log)
    y_t           = h_t C_t + D ⊙ xs_t
    out           = (y ⊙ silu(z)) W_out         (Di -> E, no bias)

The three inner RMSNorms are Jamba's (``modeling_jamba.py``
``dt_layernorm``, ``b_layernorm``, ``c_layernorm``); plain Mamba has
none. The projections are matmuls in the activation type; everything
between them (convolution, the step size, the recurrence in
``ops/selective_scan.py``, the gate) runs in float32, and ``dt`` — the
argument of an exponential that is applied once a token for as long as
the state remembers — comes from a float32 product.

Padding (left-padded ``generate`` batches): ``mask [B, T]`` zeroes the
padded positions before ``W_in`` and again after the convolution, whose
bias would otherwise leak into them, so a padded position leaves the
state at zero: its drive ``dt·xs·B`` is zero.

Decode mode keeps two ``cache`` leaves that lead with the batch
dimension, the contract ``GatedDeltaNet`` keeps: ``ssm_state``
``[B, N, Di]`` float32 (channels minor: see ``ops/selective_scan.py``)
and ``conv_tail`` ``[B, K-1, Di]``, the convolution's previous inputs in
the activation type. ``t == 1`` takes the one-token step, ``t > 1``
(prefill, a prefill chunk) the chunked scan from the carried state.
Neither is pageable nor can roll back: the serving loop zeroes a row's
leaves on admission (``loop/serve.py``) and keeps its prefix cache off.
"""

import functools
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from d9d_tpu.core.types import Array
from d9d_tpu.nn import logical_axes as la
from d9d_tpu.nn.linear_attention import (
    _a_log_uniform,
    _dt_bias_init,
    conv_with_tail,
)
from d9d_tpu.nn.norm import RMSNorm
from d9d_tpu.ops.ssd import ssd_chunked, ssd_step
from d9d_tpu.ops.selective_scan import (
    selective_scan_chunked,
    selective_scan_step,
)

F32 = jnp.float32


def _a_log_init(key, shape, dtype):
    """``A = -(1..N)`` for every channel (S4D-real), stored as its log."""
    del key
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[-1] + 1, dtype=F32)), shape
    ).astype(dtype)


def _proj(mixer, features, name, axes, dot_general=None):
    """A projection without bias in the mixer's activation type."""
    return nn.Dense(
        features, use_bias=False, name=name, dtype=mixer.dtype,
        param_dtype=mixer.param_dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), axes
        ),
        dot_general=dot_general,
    )


def _conv_with_tail(mixer, xs: Array, channels: int, keep) -> Array:
    """``silu(conv1d_causal(xs) + bias)`` in float32 under ``mamba/conv``,
    for either mixer: the helper every mixer with a short convolution
    shares (``nn/linear_attention.py conv_with_tail``, which says what
    the ``conv_tail`` leaf holds), with the state-space family's bias."""
    return conv_with_tail(
        mixer, xs, channels, taps=mixer.d_conv, name="conv1d",
        scope="mamba/conv", use_bias=True, keep=keep,
    )


class MambaMixer(nn.Module):
    hidden_size: int
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 = ceil(hidden_size / 16), Mamba's "auto"
    norm_eps: float = 1e-6
    chunk_size: int = 64
    # decode mode: carries ssm_state and conv_tail in the "cache" collection
    decode: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u: Array, mask: Optional[Array] = None) -> Array:
        batch, t, _ = u.shape
        d_inner, n = self.expand * self.hidden_size, self.d_state
        rank = self.dt_rank or math.ceil(self.hidden_size / 16)

        proj = functools.partial(_proj, self)

        def norm(size, name):
            return RMSNorm(size, eps=self.norm_eps, name=name,
                           param_dtype=self.param_dtype)

        keep = None if mask is None else mask[..., None]
        if keep is not None:
            u = u * keep.astype(u.dtype)
        with jax.named_scope("mamba/in_proj"):
            xz = proj(2 * d_inner, "in_proj", (la.EMBED, la.HEADS))(u)
            xs, z = xz[..., :d_inner], xz[..., d_inner:]

        xs = _conv_with_tail(self, xs, d_inner, keep)

        with jax.named_scope("mamba/x_proj"):
            # operands in the activation type, the sum kept in float32:
            # what follows is float32 and would only round it again
            low = proj(
                rank + 2 * n, "x_proj", (la.HEADS, None),
                dot_general=functools.partial(
                    lax.dot_general, preferred_element_type=F32
                ),
            )(xs.astype(self.dtype)).astype(F32)
            dt_low = norm(rank, "dt_layernorm")(low[..., :rank])
            b = norm(n, "b_layernorm")(low[..., rank:rank + n])
            c = norm(n, "c_layernorm")(low[..., rank + n:])

        with jax.named_scope("mamba/dt"):
            dt = jax.nn.softplus(nn.Dense(
                d_inner, name="dt_proj", dtype=F32,
                param_dtype=self.param_dtype,
                precision=lax.Precision.HIGHEST,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), (None, la.HEADS)
                ),
                bias_init=nn.with_logical_partitioning(
                    _dt_bias_init(1e-3, 0.1, 1e-4), (la.HEADS,)
                ),
            )(dt_low))
            a_log = self.param(
                "A_log",
                nn.with_logical_partitioning(_a_log_init, (la.HEADS, None)),
                (d_inner, n), self.param_dtype,
            )
            skip = self.param(
                "D",
                nn.with_logical_partitioning(
                    nn.initializers.ones, (la.HEADS,)
                ),
                (d_inner,), self.param_dtype,
            )
            a = -jnp.exp(a_log.astype(F32)).T  # [N, Di]

        state = self.variable(
            "cache", "ssm_state", lambda: jnp.zeros((batch, n, d_inner), F32)
        ) if self.decode else None
        if state is not None and t == 1:
            with jax.named_scope("mamba/state_update"):
                y, new = selective_scan_step(
                    state.value, xs[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], skip
                )
                y = y[:, None]
        else:
            with jax.named_scope("mamba/scan"):
                y, new = selective_scan_chunked(
                    xs, dt, a, b, c, skip, chunk_size=self.chunk_size,
                    initial_state=None if state is None else state.value,
                )
        if state is not None:
            state.value = new

        with jax.named_scope("mamba/gate"):
            gated = (y * jax.nn.silu(z.astype(F32))).astype(self.dtype)
        with jax.named_scope("mamba/out_proj"):
            return proj(self.hidden_size, "out_proj", (la.HEADS, la.EMBED))(
                gated
            )


class Mamba2Mixer(nn.Module):
    """Mamba-2 mixer (arXiv:2405.21060) as ``granitemoehybrid`` builds it.

    On ``u [B, T, E]``, with ``Di = num_heads·head_dim`` channels in ``H``
    heads of ``P``, a state of ``N`` numbers a channel and ``G`` groups:

        [z, xBC, dt] = u W_in                (E -> Di + (Di + 2 G N) + H)
        xBC          = silu(conv1d_causal(xBC) + bias)   (depthwise, K taps)
        [x, B, C]    = xBC                   (Di, G N, G N)
        dt           = softplus(dt + dt_bias)            (one a head)
        S_t[h]       = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] ⊗ B_t
        y_t[h]       = S_t[h] C_t + D[h] x_t[h],         A = -exp(A_log)
        out          = RMSNorm_Di(y ⊙ silu(z)) W_out     (Di -> E)

    ``A``, ``D`` and ``dt_bias`` are one number a head, ``B`` and ``C`` are
    shared by a group's heads, and the gated norm runs over all ``Di``
    channels with its weight applied after. No projection has a bias.
    Float32 as in :class:`MambaMixer`: everything between the projections
    (the step size is a slice of the in-projection's output, widened),
    the recurrence in ``ops/ssd.py``, the gate and the norm's sums.

    Decode mode keeps ``ssm_state`` ``[B, H, P, N]`` float32 (``N`` minor:
    whole lane tiles, see ``ops/ssd.py``) and ``conv_tail`` ``[B, K-1, Di
    + 2 G N]`` in the activation type; ``t == 1`` takes the one-token
    step, ``t > 1`` the chunked scan from the carried state. Padding and
    the serving loop's contract are :class:`MambaMixer`'s.
    """

    hidden_size: int
    num_heads: int
    head_dim: int
    d_state: int = 128
    n_groups: int = 1
    d_conv: int = 4
    norm_eps: float = 1e-5
    chunk_size: int = 256
    decode: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u: Array, mask: Optional[Array] = None) -> Array:
        batch, t, _ = u.shape
        h, p, n = self.num_heads, self.head_dim, self.d_state
        d_inner, bc = h * p, self.n_groups * n

        keep = None if mask is None else mask[..., None]
        if keep is not None:
            u = u * keep.astype(u.dtype)
        with jax.named_scope("mamba/in_proj"):
            zxbcdt = _proj(
                self, 2 * d_inner + 2 * bc + h, "in_proj",
                (la.EMBED, la.HEADS),
            )(u)
            z = zxbcdt[..., :d_inner]
            xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * bc]
            dt = zxbcdt[..., 2 * d_inner + 2 * bc:]

        xbc = _conv_with_tail(self, xbc, d_inner + 2 * bc, keep)
        xs = xbc[..., :d_inner].reshape(batch, t, h, p)
        b = xbc[..., d_inner:d_inner + bc].reshape(batch, t, self.n_groups, n)
        c = xbc[..., d_inner + bc:].reshape(batch, t, self.n_groups, n)

        def per_head(name, init):
            return self.param(
                name, nn.with_logical_partitioning(init, (la.HEADS,)),
                (h,), self.param_dtype,
            ).astype(F32)

        with jax.named_scope("mamba/dt"):
            dt = jax.nn.softplus(
                dt.astype(F32)
                + per_head("dt_bias", _dt_bias_init(1e-3, 0.1, 1e-4))
            )
            a = -jnp.exp(per_head("A_log", _a_log_uniform))
            skip = per_head("D", nn.initializers.ones)

        state = self.variable(
            "cache", "ssm_state", lambda: jnp.zeros((batch, h, p, n), F32)
        ) if self.decode else None
        if state is not None and t == 1:
            with jax.named_scope("mamba/state_update"):
                y, new = ssd_step(
                    state.value, xs[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], skip
                )
                y = y[:, None]
        else:
            with jax.named_scope("mamba/scan"):
                y, new = ssd_chunked(
                    xs, dt, a, b, c, skip, chunk_size=self.chunk_size,
                    initial_state=None if state is None else state.value,
                )
        if state is not None:
            state.value = new

        with jax.named_scope("mamba/gate_norm"):
            gated = y.reshape(batch, t, d_inner) * jax.nn.silu(z.astype(F32))
            normed = RMSNorm(
                d_inner, eps=self.norm_eps, name="norm",
                param_dtype=self.param_dtype,
            )(gated).astype(self.dtype)
        with jax.named_scope("mamba/out_proj"):
            return _proj(
                self, self.hidden_size, "out_proj", (la.HEADS, la.EMBED)
            )(normed)
