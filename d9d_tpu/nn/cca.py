"""Compressed convolutional attention (CCA; Zyphra, arXiv:2510.04476; the
ZAYA1 family, arXiv:2511.17127).

Attention in a compressed latent: the queries are ``num_heads x
head_dim`` wide (half the hidden width in ZAYA1-8B), keys and values
``num_kv_heads x head_dim`` (an eighth), and what the narrow projections
lose is given back by mixing over the sequence before the softmax. On
``u [T, E]``, the block's normed input, with ``h`` query heads on ``g``
key/value heads of ``d``:

    c          = [u Wq ; u Wk]                         (h + g heads of d)
    c1[t]      = a0 * c[t-1] + a1 * c[t] + b           depthwise, causal
    c2[t, j]   = c1[t-1, j] B0_j + c1[t, j] B1_j + b'_j   a head j, d x d
    m_q[i]     = (q0[i] + k0[i // (h/g)]) / 2;  m_k[j] = mean of its m_q
    q, k       = c2[:h] + m_q, c2[h:] + m_k
    v          = [u Wv ; (u Wv')[t-1]]    half the value heads a token late
    q, k       = sqrt(d) q / |q|, sqrt(d) exp(theta_j) k / |k|   float32
    rotation of the first ``rope_fraction`` of each head, softmax at
    d ** -0.5 over ``k``, ``v``, then ``Wo``

with ``c[-1] = c1[-1] = 0`` and a zero value before the first token. What
a decode step caches is ``k`` after the rotation and ``v`` after the
shift, so the caches, the paged pools and the decode kernel are those of
:class:`~d9d_tpu.nn.attention.GroupedQueryAttention`
(``decode_attend``, ``_paged_append_kv``). Beside them a row keeps three
tails no pool can rebuild, per-row leaves the serving loop clears on
admission as it clears a state-space mixer's: ``conv_tail`` (``c[t-1]``),
``conv1_tail`` (``c1[t-1]``) and ``value_tail`` (``(u Wv')[t-1]``), in the
activation type. So that a decode step reads the ``c1[t-1]`` a prefill
computed, ``c1`` is rounded to the activation type on its way into the
second convolution in every mode.

The switches are the readings the published configuration leaves open
(``benchmarks/references/zaya.py`` has one function for each); the ZAYA
presets keep every default. Scopes: ``cca/{qk_proj, conv, qk_mean,
v_shift, norm_temp, rope, out_proj}`` under the module's own.
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from d9d_tpu.core.types import Array
from d9d_tpu.nn import logical_axes as la
from d9d_tpu.nn.attention import decode_attend, rotate_leading, sdpa_padded
from d9d_tpu.nn.linear_attention import conv_with_tail, shift_tail
from d9d_tpu.nn.sdpa.protocol import SdpaBackend
from d9d_tpu.ops import RopeStyle

F32 = jnp.float32
# inside the l2 norms' root, over a head's mean square
L2_EPS = 1e-6


def near(value: float, std: float):
    """Initializer: ``value`` plus normal noise of ``std`` (0: the
    constant). The learned vectors of the ZAYA blocks start at ones and
    zeros as published; seeded weights that are to exercise them are
    drawn a little away."""

    def init(key, shape, dtype):
        out = jnp.full(shape, value, F32)
        if std:
            out = out + std * jax.random.normal(key, shape, F32)
        return out.astype(dtype)

    return init


def grouped_conv_with_tail(
    mixer: nn.Module, xs: Array, groups: int, *, taps: int, name: str,
    scope: str, leaf: str,
) -> Array:
    """``conv_with_tail``'s sibling for taps that are matrices a group:
    ``out[t, g] = sum_j xs[t - (K-1) + j, g] W[j, g] + b[g]`` over ``xs
    [B, T, groups * D]`` seen as ``groups`` vectors of ``D``, ``W [K,
    groups, D, D]``, the last tap on the current token. Operands rounded
    to the mixer's activation type, sums and result in float32; in decode
    mode the previous ``taps - 1`` inputs are the per-row leaf ``leaf``."""
    batch, t, channels = xs.shape
    width = channels // groups
    with jax.named_scope(scope):
        weight = mixer.param(
            f"{name}_weight",
            nn.with_logical_partitioning(
                nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", in_axis=(0, 2), out_axis=3,
                    batch_axis=(1,),
                ),
                (None, None, None, None),
            ),
            (taps, groups, width, width), mixer.param_dtype,
        )
        bias = mixer.param(
            f"{name}_bias",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), (None,)
            ),
            (channels,), mixer.param_dtype,
        )
        xs = xs.astype(mixer.dtype)
        if mixer.decode and taps > 1:
            context = shift_tail(mixer, leaf, xs, taps - 1)
        else:
            context = jnp.zeros((batch, taps - 1, channels), xs.dtype)
        # rounded to the activation type, multiplied as float32: the
        # same product (the chip's default precision rounds float32
        # operands to bf16, which these already are), and a batched bf16
        # product with a float32 result is one the CPU backend lacks
        padded = jnp.concatenate([context, xs], axis=1).reshape(
            batch, t + taps - 1, groups, width
        ).astype(F32)
        weight = weight.astype(mixer.dtype).astype(F32)
        out = sum(
            jnp.einsum("btgd,gde->btge", padded[:, j:j + t], weight[j])
            for j in range(taps)
        )
        return out.reshape(batch, t, channels) + bias.astype(F32)


class CompressedConvAttention(nn.Module):
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    sdpa: SdpaBackend
    # taps of the two convolutions (``cca_time0``, ``cca_time1``)
    time0: int = 2
    time1: int = 2
    # the readings: the second convolution grouped by head (off:
    # depthwise, as the first); the q-k mean; half the value heads a
    # token late; a learned temperature a key head
    conv1_grouped: bool = True
    qk_mean: bool = True
    value_shift: bool = True
    key_temperature: bool = True
    rope_fraction: float = 0.5
    # noise on the temperature's zero init (see :func:`near`)
    init_jitter: float = 0.0
    # what ``decode_attend`` reads of a grouped-query module
    softmax_scale: Optional[float] = None
    window_size: Optional[int] = None
    decode_max_length: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @property
    def decode(self) -> bool:
        return self.decode_max_length > 0

    @nn.compact
    def __call__(
        self,
        x: Array,
        cos: Array,
        sin: Array,
        mask: Optional[Array] = None,
        padding_mask: Optional[Array] = None,
    ) -> Array:
        b, t, _ = x.shape
        h, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        if h % hkv or hkv % 2:
            raise ValueError(
                f"{h} query heads on {hkv} key/value heads: the query heads "
                "divide over the key/value heads, and half of those take "
                "the shifted value"
            )
        late = hkv // 2  # value heads that see the previous token

        def proj(features, name, axes):
            return nn.Dense(
                features, use_bias=False, name=name, dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), axes
                ),
            )

        # a left-padded prompt's pads (``generate``): zero inputs there,
        # and zero again behind a convolution's bias, so that the first
        # real token finds c[-1] = c1[-1] = 0 and no previous value
        keep = None if padding_mask is None else padding_mask[..., None]
        if keep is not None:
            x = x * keep.astype(x.dtype)

        with jax.named_scope("cca/qk_proj"):
            q0 = proj(h * d, "q_proj", (la.EMBED, la.HEADS))(x)
            k0 = proj(hkv * d, "k_proj", (la.EMBED, la.KV_HEADS))(x)
            c = jnp.concatenate([q0, k0], axis=-1)
        channels, groups = (h + hkv) * d, h + hkv
        c1 = conv_with_tail(
            self, c, channels, taps=self.time0, name="conv0",
            scope="cca/conv", use_bias=True, keep=keep,
            activation=False,
        )
        if self.conv1_grouped:
            c2 = grouped_conv_with_tail(
                self, c1, groups, taps=self.time1, name="conv1",
                scope="cca/conv", leaf="conv1_tail",
            )
        else:
            c2 = conv_with_tail(
                self, c1.astype(self.dtype), channels, taps=self.time1,
                name="conv1", scope="cca/conv", use_bias=True,
                activation=False, leaf="conv1_tail",
            )
        q = c2[..., : h * d].reshape(b, t, h, d)
        k = c2[..., h * d:].reshape(b, t, hkv, d)
        if self.qk_mean:
            with jax.named_scope("cca/qk_mean"):
                mean_q = 0.5 * (
                    q0.reshape(b, t, h, d).astype(F32)
                    + jnp.repeat(
                        k0.reshape(b, t, hkv, d).astype(F32), h // hkv,
                        axis=2,
                    )
                )
                q = q + mean_q
                k = k + mean_q.reshape(b, t, hkv, h // hkv, d).mean(axis=3)

        with jax.named_scope("cca/v_shift"):
            v_now = proj((hkv - late) * d, "v_proj", (la.EMBED, la.KV_HEADS))(x)
            v_late = proj(late * d, "v_prev_proj", (la.EMBED, la.KV_HEADS))(x)
            if self.value_shift:
                if self.decode:
                    before = shift_tail(self, "value_tail", v_late, 1)
                else:
                    before = jnp.zeros_like(v_late[:, :1])
                v_late = jnp.concatenate([before, v_late], axis=1)[:, :t]
            v = jnp.concatenate([v_now, v_late], axis=-1).reshape(
                b, t, hkv, d
            )

        with jax.named_scope("cca/norm_temp"):
            def unit(u):
                return u * jax.lax.rsqrt(
                    jnp.mean(jnp.square(u), axis=-1, keepdims=True) + L2_EPS
                )

            q, k = unit(q), unit(k)
            if self.key_temperature:
                theta = self.param(
                    "key_temperature",
                    nn.with_logical_partitioning(
                        near(0.0, self.init_jitter), (la.KV_HEADS,)
                    ),
                    (hkv,), self.param_dtype,
                )
                k = k * jnp.exp(theta.astype(F32))[:, None]
            q, k = q.astype(self.dtype), k.astype(self.dtype)

        rot = int(d * self.rope_fraction)
        if rot:
            with jax.named_scope("cca/rope"):
                q, k = (
                    rotate_leading(u, cos, sin, rot, RopeStyle.HALF)
                    for u in (q, k)
                )

        if self.decode:
            attn = self._decode_attend(q, k, v, None, mask, b, t)
        else:
            attn = self._sdpa_padded(
                q, k, v, causal=True, softmax_scale=self.softmax_scale,
                mask=mask,
            )
        with jax.named_scope("cca/out_proj"):
            return proj(self.hidden_size, "o_proj", (la.HEADS, la.EMBED))(
                attn.reshape(b, t, h * d)
            )

    # methods for their scopes, as the grouped-query module's are: a
    # trace finds the paged decode kernel under ``self_attn._decode_attend``
    def _sdpa_padded(self, q, k, v, **kwargs):
        return sdpa_padded(self, q, k, v, **kwargs)

    def _decode_attend(self, q, k, v, sinks, mask, b, t):
        return decode_attend(self, q, k, v, sinks, mask, b, t)
