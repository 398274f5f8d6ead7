"""State-space duality scan (Mamba-2): one-token step + chunked scan.

The recurrence per head ``h`` (``P`` channels, a state of ``P x N``
numbers), with a step size ``dt_t > 0`` and a fixed negative rate ``a``
that are ONE number a head, and input and output maps ``b_t``, ``c_t``
of ``N`` numbers that the heads of a group share:

    S_t[h] = exp(dt_t[h]·a[h])·S_{t-1}[h] + dt_t[h]·x_t[h] ⊗ b_t
    y_t[h] = S_t[h]·c_t + skip[h]·x_t[h]

- :func:`ssd_step` — one token for ``[B]`` rows, state in and out: the
  decode step, element-wise over the state and bound by the bytes of the
  state it reads and writes.
- :func:`ssd_chunked` — a sequence in chunks of ``chunk_size``, the state
  threaded from ``initial_state`` across the chunks by a ``lax.scan``.
  Because the decay is a scalar a head, a chunk is matrix products (the
  "dual" form, arXiv:2405.21060 section 6): with ``cum_i`` the running
  sum of ``dt·a`` inside the chunk,

      y_i = Σ_{j<=i} exp(cum_i − cum_j)·(c_i·b_j)·dt_j·x_j   (within)
          + exp(cum_i)·S_0·c_i                               (carried in)
      S_L = exp(cum_L)·S_0 + Σ_j exp(cum_L − cum_j)·dt_j·x_j ⊗ b_j

  Every exponent is a sum of non-positive numbers (the mask is applied
  to the exponent, not to the exponential), so nothing overflows
  whatever ``dt`` and the chunk length are. Plain ``jax.numpy``/``lax``:
  differentiable by autodiff. The products run in float32 at ``highest``
  precision: a prefill's state is what every later step decays from.

The state is laid out ``[B, H, P, N]``: ``N`` (128) fills the TPU's 128
lanes and ``P`` (64) eight sublane tiles, so a row of the state is whole
tiles with no padding.

Shapes: ``x [B, T, H, P]``, ``dt [B, T, H]``, ``a, skip [H]``,
``b, c [B, T, G, N]`` with ``G`` dividing ``H`` (the step takes them
without ``T``). Computation runs in float32 whatever the inputs' types;
outputs are float32.
"""

import jax.numpy as jnp
from jax import lax

from d9d_tpu.core.types import Array

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def _per_head(v: Array, heads: int) -> Array:
    """``[..., G, N]`` -> ``[..., H, N]``: each group's map for its heads."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def ssd_step(
    state: Array, x: Array, dt: Array, a: Array, b: Array, c: Array,
    skip: Array,
) -> tuple[Array, Array]:
    """One token: ``state [B, H, P, N]``, ``x [B, H, P]``, ``dt [B, H]``,
    ``b, c [B, G, N]`` → ``(y [B, H, P], new state)``."""
    x, dt = x.astype(F32), dt.astype(F32)
    heads = x.shape[1]
    b, c = _per_head(b.astype(F32), heads), _per_head(c.astype(F32), heads)
    decay = jnp.exp(dt * a.astype(F32))
    drive = (dt[..., None] * x)[..., None] * b[:, :, None, :]
    state = decay[..., None, None] * state.astype(F32) + drive
    y = jnp.sum(state * c[:, :, None, :], axis=-1)
    return y + skip.astype(F32)[:, None] * x, state


def ssd_chunked(
    x: Array, dt: Array, a: Array, b: Array, c: Array, skip: Array,
    *, chunk_size: int = 256, initial_state: Array | None = None,
) -> tuple[Array, Array]:
    """A sequence: returns ``(y [B, T, H, P], final state [B, H, P, N])``.
    ``T`` need not divide into chunks: the tail is padded with ``dt = 0``
    steps, which leave the state as it is."""
    x, dt, a = x.astype(F32), dt.astype(F32), a.astype(F32)
    b, c = b.astype(F32), c.astype(F32)
    batch, t, heads, p = x.shape
    groups, n = b.shape[-2:]
    size = min(chunk_size, t)
    pad = (-t) % size
    if pad:
        widen = lambda v: jnp.pad(  # noqa: E731
            v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    n_chunks = (t + pad) // size

    def chunks(v):  # [B, T, ...] -> [chunks, B, size, ...]
        return v.reshape(batch, n_chunks, size, *v.shape[2:]).swapaxes(0, 1)

    def grouped(v, axis):  # the heads' axis as (groups, heads a group)
        return v.reshape(*v.shape[:axis], groups, -1, *v.shape[axis + 1:])

    lower = jnp.tril(jnp.ones((size, size), bool))

    def one_chunk(s0, inputs):
        x_c, dt_c, b_c, c_c = inputs  # [B, L, H, P], [B, L, H], [B, L, G, N]
        cum = jnp.cumsum(dt_c * a, axis=1)  # [B, L, H], non-increasing
        cum_h = cum.swapaxes(1, 2)  # [B, H, L]
        # exp(cum_i - cum_j) for j <= i: the mask on the exponent
        within = jnp.exp(jnp.where(
            lower, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf
        ))  # [B, H, L(i), L(j)]
        # c_i . b_j once a group, shared by the group's heads
        scores = jnp.einsum("bign,bjgn->bgij", c_c, b_c, precision=HIGHEST)
        weights = grouped(
            within * dt_c.swapaxes(1, 2)[:, :, None, :], 1
        ) * scores[:, :, None]  # [B, G, H/G, L, L]
        x_g = grouped(x_c, 2)  # [B, L, G, H/G, P]
        y = jnp.einsum("bgkij,bjgkp->bigkp", weights, x_g, precision=HIGHEST)
        carried = jnp.einsum(
            "bgkpn,bign->bigkp", grouped(s0, 1), c_c, precision=HIGHEST
        )
        y = y.reshape(x_c.shape) + (
            jnp.exp(cum)[..., None] * carried.reshape(x_c.shape)
        )
        # what each position still adds to the chunk's last state
        to_end = jnp.exp(cum[:, -1:] - cum) * dt_c  # [B, L, H]
        added = jnp.einsum(
            "bjgkp,bjgn->bgkpn", grouped(to_end[..., None] * x_c, 2), b_c,
            precision=HIGHEST,
        ).reshape(s0.shape)
        return jnp.exp(cum[:, -1])[..., None, None] * s0 + added, y

    s0 = (
        jnp.zeros((batch, heads, p, n), F32) if initial_state is None
        else initial_state.astype(F32)
    )
    final, y = lax.scan(
        one_chunk, s0, (chunks(x), chunks(dt), chunks(b), chunks(c))
    )
    y = y.swapaxes(0, 1).reshape(batch, t + pad, heads, p)[:, :t]
    return y + skip.astype(F32)[:, None] * x[:, :t], final
