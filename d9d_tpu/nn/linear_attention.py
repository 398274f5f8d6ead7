"""Delta-rule linear-attention blocks: GatedDeltaNet and Kimi delta
attention.

Reference: d9d/module/block/attention/linear/gated_deltanet.py:232 (block),
:17 (CausalShortDepthwiseConv1d), :68 (LogSigmoidDecayGate), :103
(MambaDecayGate). The fla-core Triton kernels
(chunk_gated_delta_rule / causal_conv1d / fused_kda_gate) map to:
ops/gated_delta.py (chunked WY scan), a depthwise lax conv, and inline
gate math — all fused by XLA.

:class:`KimiDeltaAttention` (beyond-reference; Kimi Linear, arXiv
2510.26692, fla-core's ``KimiDeltaAttention``) is the same rule with the
decay a vector a head; its equations are in the class. The two blocks
and the state-space mixers (``nn/mamba.py``) share the causal convolution
and its decode tail (:func:`conv_with_tail`), and the two delta-rule
blocks the one-token step (``ops/gated_delta.py kda_step``) and the shape
of their scopes (``gdn/...``, ``kda/...``: ``qkv_proj``, ``conv``,
``gates``, ``state_update`` or ``scan``, ``gate_norm``, ``out_proj``).
"""

import enum
import functools
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from d9d_tpu.core.types import Array
from d9d_tpu.nn import logical_axes as la
from d9d_tpu.nn.norm import RMSNorm
from d9d_tpu.ops.gated_delta import (
    gated_delta_rule_chunked,
    kda_chunked,
    kda_step,
)
from d9d_tpu.ops.swiglu import silu_mul


class CausalShortConv1d(nn.Module):
    """Causal depthwise conv over time with SiLU (reference :17; fla's
    causal_conv1d). Weight [channels, kernel]; ``use_bias`` adds a
    per-channel bias before the SiLU (Mamba's conv1d). ``left_context``
    ``[B, K-1, C]`` stands in for the zero left pad: the true previous
    inputs of a decode-mode call. ``activation`` off gives the plain
    filter in float32 (compressed convolutional attention's first
    convolution, ``nn/cca.py``)."""

    channels: int
    kernel_size: int
    use_bias: bool = False
    activation: bool = True
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array, left_context: Optional[Array] = None) -> Array:  # [B,T,C]
        def conv_init(key, shape, dtype):
            # torch depthwise-conv default (kaiming_uniform a=√5):
            # U(-1/√K, 1/√K) with fan_in = kernel taps, NOT channels
            bound = self.kernel_size ** -0.5
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        w = self.param(
            "weight",
            nn.with_logical_partitioning(conv_init, (la.HEADS, None)),
            (self.channels, self.kernel_size),
            self.param_dtype,
        )
        xf = x.astype(jnp.float32)
        if left_context is None:
            pad = self.kernel_size - 1
            xp = jnp.pad(xf, ((0, 0), (pad, 0), (0, 0)))
        else:
            xp = jnp.concatenate([left_context.astype(jnp.float32), xf], axis=1)
        out = _depthwise_causal_conv(xp, w.astype(jnp.float32))
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_logical_partitioning(conv_init, (la.HEADS,)),
                (self.channels,),
                self.param_dtype,
            )
            out = out + bias.astype(jnp.float32)
        if not self.activation:
            return out
        return jax.nn.silu(out).astype(x.dtype)


def _depthwise_causal_conv(xp: Array, w: Array) -> Array:
    """xp [B, T+K-1, C] ⊛ w [C, K] → [B, T, C] (per-channel FIR).

    Tap convention matches torch ``F.conv1d`` with left pad K-1 (fla's
    causal_conv1d): ``y_t = Σ_j w[:, j] · x_{t-(K-1)+j}`` — the *last*
    weight column multiplies the current token. K is tiny (2-4); the
    unrolled form fuses into K fma passes.
    """
    k = w.shape[1]
    t = xp.shape[1] - (k - 1)
    out = jnp.zeros((xp.shape[0], t, xp.shape[2]), xp.dtype)
    for j in range(k):
        out = out + xp[:, j : j + t, :] * w[None, None, :, j]
    return out


def shift_tail(mixer: nn.Module, leaf: str, xs: Array, rows: int) -> Array:
    """The per-row cache leaf ``leaf [B, rows, C]`` of ``mixer``: the
    ``rows`` inputs before ``xs [B, T, C]``, zeros for a fresh row. It is
    returned as it stood and left holding the last ``rows`` of itself
    followed by ``xs``, in the mixer's activation type."""
    batch, t, channels = xs.shape
    tail = mixer.variable(
        "cache", leaf, lambda: jnp.zeros((batch, rows, channels), mixer.dtype)
    )
    context = tail.value
    tail.value = jnp.concatenate(
        [context, xs.astype(mixer.dtype)], axis=1
    )[:, t:]
    return context


def conv_with_tail(
    mixer: nn.Module, xs: Array, channels: int, *, taps: int, name: str,
    scope: str, use_bias: bool = False, keep: Optional[Array] = None,
    activation: bool = True, leaf: str = "conv_tail",
) -> Array:
    """``silu(conv1d_causal(xs) [+ bias])`` in float32 under the scope
    ``scope``, for any mixer with a short convolution (inside its
    ``@nn.compact`` call; ``mixer.decode``, ``.dtype`` and
    ``.param_dtype`` are read). In decode mode the convolution's previous
    ``taps - 1`` inputs are the ``conv_tail`` cache leaf ``[B, K-1,
    channels]`` in the activation type (``leaf`` names it where a mixer
    keeps more than one), read as the left context and shifted by the
    new inputs (:func:`shift_tail`). ``keep [B, T, 1]`` zeroes padded
    positions again: a bias would otherwise leak into them.
    ``activation`` off leaves the SiLU out."""
    with jax.named_scope(scope):
        conv = CausalShortConv1d(
            channels=channels, kernel_size=taps, use_bias=use_bias,
            activation=activation, name=name, param_dtype=mixer.param_dtype,
        )
        context = None
        if mixer.decode and taps > 1:
            context = shift_tail(mixer, leaf, xs, taps - 1)
        xs = conv(xs.astype(jnp.float32), context)
        if keep is not None:
            xs = xs * keep.astype(jnp.float32)
    return xs


class DecayGateKind(str, enum.Enum):
    mamba = "mamba"
    logsigmoid = "logsigmoid"


class LogSigmoidDecayGate(nn.Module):
    """g = logsigmoid(Wx) / τ ∈ (-∞, 0] (reference :68; GLA/HGRN-2)."""

    hidden_size: int
    num_heads: int
    normalizer: float = 16.0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        z = nn.Dense(
            self.num_heads, use_bias=False, name="proj", dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), (la.EMBED, la.HEADS)
            ),
        )(x)
        return jax.nn.log_sigmoid(z.astype(jnp.float32)) / self.normalizer


def _dt_bias_init(dt_min: float, dt_max: float, floor: float):
    def init(key, shape, dtype):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
        dt = jnp.maximum(dt, floor)
        # inverse softplus so softplus(dt_bias) == dt at init
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


def _a_log_init(normalizer: float):
    def init(key, shape, dtype):
        return jnp.log(
            jax.random.uniform(key, shape, jnp.float32, 1e-4, normalizer)
        ).astype(dtype)

    return init


class MambaDecayGate(nn.Module):
    """g = −exp(A_log)·softplus(Wx + dt_bias) (reference :103; the
    fused_kda_gate math; Mamba-2 / Qwen3-Next style)."""

    hidden_size: int
    num_heads: int
    normalizer: float = 16.0
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_init_floor: float = 1e-4
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        z = nn.Dense(
            self.num_heads, use_bias=False, name="proj", dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), (la.EMBED, la.HEADS)
            ),
        )(x)
        a_log = self.param(
            "A_log",
            nn.with_logical_partitioning(
                _a_log_init(self.normalizer), (la.HEADS,)
            ),
            (self.num_heads,),
            jnp.float32,
        )
        dt_bias = self.param(
            "dt_bias",
            nn.with_logical_partitioning(
                _dt_bias_init(self.dt_min, self.dt_max, self.dt_init_floor),
                (la.HEADS,),
            ),
            (self.num_heads,),
            jnp.float32,
        )
        zf = z.astype(jnp.float32)
        return -jnp.exp(a_log) * jax.nn.softplus(zf + dt_bias)


class GatedDeltaNet(nn.Module):
    """Gated DeltaNet block (reference :232): fused QKV projection → causal
    short conv → decay/write gates → GQA head expansion → chunked gated
    delta rule → per-head RMSNorm → SiLU output gate → output projection."""

    hidden_size: int
    num_qk_heads: int
    num_v_heads: int
    head_qk_dim: int
    head_v_dim: int
    conv_size: int = 4
    norm_eps: float = 1e-6
    decay_gate: DecayGateKind = DecayGateKind.mamba
    use_qk_l2norm: bool = True
    chunk_size: int = 64
    # Autoregressive decode (loop/generate.py): carries the recurrent
    # delta-rule state [B, Hv, Dk, Dv] and the conv's (K-1)-token input
    # tail in the "cache" collection — this is the linear-attention decode
    # advantage: O(1) state per token instead of a growing KV cache.
    decode: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        b, t, _ = x.shape
        hqk, hv = self.num_qk_heads, self.num_v_heads
        if hv % hqk != 0:
            raise ValueError(
                f"num_v_heads ({hv}) must be divisible by num_qk_heads ({hqk})"
            )
        groups = hv // hqk
        dqk, dv = self.head_qk_dim, self.head_v_dim
        q_dim = k_dim = hqk * dqk
        v_dim = hv * dv

        if mask is not None:
            x = x * mask[..., None].astype(x.dtype)

        def proj(features, name, axes):
            return nn.Dense(
                features, use_bias=False, name=name, dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), axes
                ),
            )

        channels = q_dim + k_dim + v_dim
        with jax.named_scope("gdn/qkv_proj"):
            qkv = proj(channels, "qkv_proj", (la.EMBED, la.HEADS))(x)
        qkv = conv_with_tail(
            self, qkv, channels, taps=self.conv_size, name="qkv_conv1d",
            scope="gdn/conv",
        ).astype(self.dtype)
        q, k, v = jnp.split(qkv, [q_dim, q_dim + k_dim], axis=-1)
        q = q.reshape(b, t, hqk, dqk)
        k = k.reshape(b, t, hqk, dqk)
        v = v.reshape(b, t, hv, dv)
        if groups > 1:
            q = jnp.repeat(q, groups, axis=2)
            k = jnp.repeat(k, groups, axis=2)

        gate_cls = (
            MambaDecayGate
            if self.decay_gate == DecayGateKind.mamba
            else LogSigmoidDecayGate
        )
        with jax.named_scope("gdn/gates"):
            g = gate_cls(
                hidden_size=self.hidden_size, num_heads=hv, name="decay_gate",
                dtype=self.dtype, param_dtype=self.param_dtype,
            )(x)
            beta = nn.sigmoid(
                proj(hv, "b_proj", (la.EMBED, la.HEADS))(x).astype(jnp.float32)
            )

        state = self.variable(
            "cache", "delta_state",
            lambda: jnp.zeros((b, hv, dqk, dv), jnp.float32),
        ) if self.decode else None
        if state is not None and t == 1:
            # the delta-rule mixers' shared one-token step, the decay one
            # number a head
            with jax.named_scope("gdn/state_update"):
                out, s_final = kda_step(
                    state.value, q[:, 0], k[:, 0], v[:, 0],
                    g[:, 0, :, None], beta[:, 0],
                    use_qk_l2norm=self.use_qk_l2norm,
                )
                out = out[:, None]
        else:  # a sequence: chunked WY form, threading the state
            with jax.named_scope("gdn/scan"):
                out, s_final = gated_delta_rule_chunked(
                    q, k, v, g, beta,
                    use_qk_l2norm=self.use_qk_l2norm,
                    chunk_size=self.chunk_size,
                    initial_state=None if state is None else state.value,
                )
        if state is not None:
            state.value = s_final

        with jax.named_scope("gdn/gate_norm"):
            out = RMSNorm(dv, eps=self.norm_eps, name="out_norm",
                          param_dtype=self.param_dtype)(out.astype(self.dtype))
            out = out.reshape(b, t, v_dim)
            gate = proj(v_dim, "g_proj", (la.EMBED, la.HEADS))(x)
            out = silu_mul(gate, out)
        with jax.named_scope("gdn/out_proj"):
            return proj(self.hidden_size, "o_proj", (la.HEADS, la.EMBED))(out)


def _a_log_uniform(key, shape, dtype):
    """``A`` uniform in [1, 16] a head (fla's KDA and Mamba-2 default), as
    its log."""
    return jnp.log(
        jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
    ).astype(dtype)


class KimiDeltaAttention(nn.Module):
    """Kimi delta attention mixer (Kimi Linear, arXiv 2510.26692), on ``x
    [B, T, E]`` with ``H`` heads of ``D = Dk = Dv`` and ``HD = H·D``:

        q~, k~, v = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))
        q = l2norm(q~)·D^-1/2        k = l2norm(k~)       (a head)
        g = -exp(A_log_h)·softplus((x Wfa) Wfb + dt_bias)   in R^D a head
        β = sigmoid(x Wb), times 2 under ``allow_neg_eigval``
        S' = diag(e^g) S_{t-1};  S_t = S' + β k (v - S'ᵀk)ᵀ;  o = S_tᵀ q
        out = (RMSNorm_D(o; w) · sigmoid((x Wga) Wgb + b_g)) Wo

    Three projections to ``HD`` and a depthwise causal convolution of
    ``conv_size`` taps over each, no bias (kept as ONE convolution over
    the ``3 HD`` joined channels, ``qkv_conv1d``: the same numbers, and
    one ``conv_tail`` leaf); the decay and output gates low-rank pairs of
    rank ``gate_rank``; the decay a number a key channel, which is what
    sets the block apart from :class:`GatedDeltaNet` (one fused
    projection, a full-rank scalar decay, a SiLU gate after the norm).
    Float32: the convolution, both gates (their second products keep
    their float32 sums), the recurrence and its state, the norm; the
    projections' operands and the conv tail are in the activation type.

    Decode mode keeps ``delta_state [B, H, D, D]`` float32 and
    ``conv_tail [B, K-1, 3 HD]``; ``t == 1`` takes the one-token step
    (``ops/gated_delta.py kda_step``: the state read and written once),
    ``t > 1`` the chunked form from the carried state. Padding and the
    serving loop's contract are :class:`GatedDeltaNet`'s; a padded
    position also leaves the state undecayed.
    """

    hidden_size: int
    num_heads: int
    head_dim: int
    conv_size: int = 4
    gate_rank: int = 0  # 0 = head_dim (Kimi Linear's pairs)
    allow_neg_eigval: bool = False
    norm_eps: float = 1e-5
    chunk_size: int = 64
    decode: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        b, t, _ = x.shape
        h, d = self.num_heads, self.head_dim
        hd, rank = h * d, self.gate_rank or self.head_dim
        f32 = jnp.float32

        keep = None if mask is None else mask[..., None]
        if keep is not None:
            x = x * keep.astype(x.dtype)

        def proj(features, name, axes, use_bias=False, **extra):
            return nn.Dense(
                features, name=name, use_bias=use_bias, dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), axes
                ),
                **extra,
            )

        def low_rank(name, **extra):
            """``(x W_a) W_b`` to ``HD``: operands in the activation type,
            the second product's sum kept in float32 for what follows."""
            low = proj(rank, f"{name}_a_proj", (la.EMBED, None))(x)
            return proj(
                hd, f"{name}_b_proj", (None, la.HEADS),
                dot_general=functools.partial(
                    lax.dot_general, preferred_element_type=f32
                ),
                **extra,
            )(low).astype(f32)

        def per_head(name, init, shape):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    init, (la.HEADS,) + (None,) * (len(shape) - 1)
                ),
                shape, self.param_dtype,
            ).astype(f32)

        with jax.named_scope("kda/qkv_proj"):
            qkv = jnp.concatenate([
                proj(hd, f"{n}_proj", (la.EMBED, la.HEADS))(x) for n in "qkv"
            ], axis=-1)
        qkv = conv_with_tail(
            self, qkv, 3 * hd, taps=self.conv_size, name="qkv_conv1d",
            scope="kda/conv",
        )
        q, k, v = (
            part.reshape(b, t, h, d) for part in jnp.split(qkv, 3, axis=-1)
        )

        with jax.named_scope("kda/gates"):
            a = jnp.exp(per_head("A_log", _a_log_uniform, (h,)))
            dt_bias = per_head(
                "dt_bias", _dt_bias_init(1e-3, 0.1, 1e-4), (h, d)
            )
            g = -a[:, None] * jax.nn.softplus(
                low_rank("f").reshape(b, t, h, d) + dt_bias
            )
            if keep is not None:
                g = g * keep[..., None].astype(f32)
            beta = nn.sigmoid(
                proj(h, "b_proj", (la.EMBED, la.HEADS))(x).astype(f32)
            )
            if self.allow_neg_eigval:
                beta = 2.0 * beta

        state = self.variable(
            "cache", "delta_state", lambda: jnp.zeros((b, h, d, d), f32)
        ) if self.decode else None
        if state is not None and t == 1:
            with jax.named_scope("kda/state_update"):
                out, new = kda_step(
                    state.value, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                    beta[:, 0],
                )
                out = out[:, None]
        else:
            with jax.named_scope("kda/scan"):
                out, new = kda_chunked(
                    q, k, v, g, beta, chunk_size=self.chunk_size,
                    initial_state=None if state is None else state.value,
                )
        if state is not None:
            state.value = new

        with jax.named_scope("kda/gate_norm"):
            normed = RMSNorm(
                d, eps=self.norm_eps, name="o_norm",
                param_dtype=self.param_dtype,
            )(out)
            gate = nn.sigmoid(low_rank(
                "g", use_bias=True,
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros, (la.HEADS,)
                ),
            ))
            gated = (normed.reshape(b, t, hd) * gate).astype(self.dtype)
        with jax.named_scope("kda/out_proj"):
            return proj(self.hidden_size, "o_proj", (la.HEADS, la.EMBED))(
                gated
            )
