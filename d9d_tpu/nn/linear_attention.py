"""GatedDeltaNet linear-attention block.

Reference: d9d/module/block/attention/linear/gated_deltanet.py:232 (block),
:17 (CausalShortDepthwiseConv1d), :68 (LogSigmoidDecayGate), :103
(MambaDecayGate). The fla-core Triton kernels
(chunk_gated_delta_rule / causal_conv1d / fused_kda_gate) map to:
ops/gated_delta.py (chunked WY scan), a depthwise lax conv, and inline
gate math — all fused by XLA.
"""

import enum
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from d9d_tpu.core.types import Array
from d9d_tpu.nn import logical_axes as la
from d9d_tpu.nn.norm import RMSNorm
from d9d_tpu.ops.gated_delta import (
    gated_delta_rule_chunked,
    gated_delta_rule_recurrent,
)
from d9d_tpu.ops.swiglu import silu_mul


class CausalShortConv1d(nn.Module):
    """Causal depthwise conv over time with SiLU (reference :17; fla's
    causal_conv1d). Weight [channels, kernel]; ``use_bias`` adds a
    per-channel bias before the SiLU (Mamba's conv1d). ``left_context``
    ``[B, K-1, C]`` stands in for the zero left pad: the true previous
    inputs of a decode-mode call."""

    channels: int
    kernel_size: int
    use_bias: bool = False
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

        qkv = proj(q_dim + k_dim + v_dim, "qkv_proj", (la.EMBED, la.HEADS))(x)
        conv = CausalShortConv1d(
            channels=q_dim + k_dim + v_dim,
            kernel_size=self.conv_size,
            name="qkv_conv1d",
            param_dtype=self.param_dtype,
        )
        if self.decode and self.conv_size > 1:
            # prepend the true previous K-1 pre-conv inputs (zeros on the
            # first call = the left pad the full path uses), conv over the
            # joined window, keep the new t outputs
            tail_len = self.conv_size - 1
            tail = self.variable(
                "cache", "conv_tail",
                lambda: jnp.zeros(
                    (b, tail_len, q_dim + k_dim + v_dim), self.dtype
                ),
            )
            joined = jnp.concatenate(
                [tail.value, qkv.astype(self.dtype)], axis=1
            )
            tail.value = joined[:, -tail_len:]
            qkv = conv(joined)[:, -t:]
        else:
            qkv = conv(qkv)
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
        g = gate_cls(
            hidden_size=self.hidden_size, num_heads=hv, name="decay_gate",
            dtype=self.dtype, param_dtype=self.param_dtype,
        )(x)
        beta = nn.sigmoid(
            proj(hv, "b_proj", (la.EMBED, la.HEADS))(x).astype(jnp.float32)
        )

        if self.decode:
            state = self.variable(
                "cache", "delta_state",
                lambda: jnp.zeros((b, hv, dqk, dv), jnp.float32),
            )
            if t == 1:
                out, s_final = gated_delta_rule_recurrent(
                    q, k, v, g, beta,
                    use_qk_l2norm=self.use_qk_l2norm,
                    initial_state=state.value,
                )
            else:  # prefill: chunked WY form, threading the state
                out, s_final = gated_delta_rule_chunked(
                    q, k, v, g, beta,
                    use_qk_l2norm=self.use_qk_l2norm,
                    chunk_size=self.chunk_size,
                    initial_state=state.value,
                )
            state.value = s_final
        else:
            out, _ = gated_delta_rule_chunked(
                q, k, v, g, beta,
                use_qk_l2norm=self.use_qk_l2norm,
                chunk_size=self.chunk_size,
            )

        out = RMSNorm(dv, eps=self.norm_eps, name="out_norm",
                      param_dtype=self.param_dtype)(out.astype(self.dtype))
        out = out.reshape(b, t, v_dim)
        gate = proj(v_dim, "g_proj", (la.EMBED, la.HEADS))(x)
        out = silu_mul(gate, out)
        return proj(self.hidden_size, "o_proj", (la.HEADS, la.EMBED))(out)
