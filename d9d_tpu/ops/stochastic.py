"""Stochastic rounding fp32 -> bf16.

TPU equivalent of the reference Triton stochastic-rounding kernels
(d9d/kernel/stochastic/adamw_step.py:97, copy.py:34, ops/round.py): add
16 uniform random bits below the bf16 mantissa cut and truncate, so the
expected value of the rounded number equals the fp32 input. Used by the
StochasticAdamW optimizer to train directly in bf16 without fp32 master
weights.

Where the bits come from: one Threefry-2x32 block (20 rounds) per
element, keyed by the caller's key and counted by the element's index
in the array's own shape (:func:`rounding_fields`). A block is 64 bits;
they are carved into three disjoint 16-bit fields (16 bits spare), so
up to three roundings of one element (the optimizer's parameter, ``mu``
and ``nu``) share one block and stay as independent as three draws. The
generator is all vector arithmetic and costs far more than the memory
traffic of the pass it is fused into (PERF.md, PR 26): a block is what
to economise on.

- :func:`stochastic_round_with_field` — the rounding rule given a 16-bit
  field; pure jnp bit-twiddling on ``bitcast_convert_type`` that XLA
  fuses into the surrounding optimizer arithmetic.
- :func:`stochastic_round_to_bf16` — the same with field 0 of a block
  drawn from ``key``.
"""

import jax
import jax.numpy as jnp
from jax.extend.random import threefry2x32_p

from d9d_tpu.core.types import Array

_MANTISSA_MASK = 0xFFFF  # bits dropped when truncating fp32 -> bf16
_BF16_MASK = 0xFFFF0000


def _sr_bits(x_bits: Array, rand_bits: Array) -> Array:
    """Core rounding rule on uint32 views: add 16 random low bits, truncate."""
    rnd = rand_bits & jnp.uint32(_MANTISSA_MASK)
    return (x_bits + rnd) & jnp.uint32(_BF16_MASK)


def rounding_fields(
    key: jax.Array, shape: tuple[int, ...]
) -> tuple[Array, Array, Array]:
    """Three independent 16-bit uniform fields per element from ONE
    Threefry-2x32 block per element.

    Returns ``uint32`` arrays of ``shape`` with values below 2**16: the low
    half of word 0, the high half of word 0 and the low half of word 1
    (the high half of word 1 is spare). The block's counter is the
    element's index, written in the array's own shape (first-dimension
    index in the high word, row-major index over the other dimensions in
    the low word) so that a sharded array needs no reshape and no
    collective. Fields nobody uses cost nothing beyond the block.
    """
    k0, k1 = jax.random.key_data(key)  # raw uint32[2] and typed threefry keys
    zero = jnp.zeros(shape, jnp.uint32)
    hi = jax.lax.broadcasted_iota(jnp.uint32, shape, 0) if shape else zero
    lo, stride = zero, 1
    for dim in range(len(shape) - 1, 0, -1):
        lo = lo + jax.lax.broadcasted_iota(jnp.uint32, shape, dim) * jnp.uint32(stride)
        stride *= shape[dim]
    if stride > 1 << 32:
        raise ValueError(f"{shape}: the trailing dimensions overflow a 32-bit counter")
    w0, w1 = threefry2x32_p.bind(k0, k1, hi, lo)
    low16 = jnp.uint32(_MANTISSA_MASK)
    return w0 & low16, w0 >> jnp.uint32(16), w1 & low16


def stochastic_round_with_field(x: Array, field: Array) -> Array:
    """Stochastically round ``x`` (any float dtype) to bfloat16 with the
    16 uniform bits of ``field`` (``uint32``, same shape, below 2**16).

    E[result] == x exactly (the two candidate bf16 neighbours are chosen
    with probability proportional to proximity). Non-finite values pass
    through deterministic casting.
    """
    xf = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(xf, jnp.uint32)
    out = jax.lax.bitcast_convert_type(_sr_bits(bits, field), jnp.float32)
    return jnp.where(jnp.isfinite(xf), out, xf).astype(jnp.bfloat16)


def stochastic_round_to_bf16(x: Array, key: jax.Array) -> Array:
    """:func:`stochastic_round_with_field` with field 0 of the block that
    ``key`` gives each element of ``x``."""
    return stochastic_round_with_field(x, rounding_fields(key, x.shape)[0])
