"""Selective state-space scan (Mamba-1): one-token step + chunked scan.

The recurrence per channel ``d`` and state index ``n``, with step size
``dt_t > 0``, a fixed negative rate ``a`` and per-token input and output
maps ``b_t``, ``c_t``:

    h_t[n, d] = exp(dt_t[d]·a[n, d])·h_{t-1}[n, d] + dt_t[d]·x_t[d]·b_t[n]
    y_t[d]    = Σ_n h_t[n, d]·c_t[n] + skip[d]·x_t[d]

- :func:`selective_scan_step` — one token for ``[B]`` rows, state in and
  out: the decode step, element-wise over the state and bound by the
  bytes of the state it reads and writes.
- :func:`selective_scan_chunked` — a sequence in chunks of
  ``chunk_size``, threading the state from ``initial_state`` across the
  chunks with a ``lax.scan``; inside a chunk the diagonal recurrence is
  a ``lax.associative_scan`` over (decay, drive) pairs, so a chunk's
  ``[B, C, N, D]`` products exist only while it runs. Every decay is
  ``exp`` of a non-positive number and no quotient of decays is formed,
  so nothing overflows whatever ``dt`` and the chunk length are. Plain
  ``jax.numpy``/``lax``: differentiable by autodiff.

The state is laid out ``[B, N, D]`` with the channel dimension minor:
``D`` (thousands) fills the TPU's 128 lanes and ``N`` (16) two sublane
tiles of 8, where ``[B, D, N]`` would pad 16 to 128 lanes (eight times
the bytes of what a decode step streams) unless the compiler re-lays it.
``a`` is ``[N, D]`` accordingly.

Shapes: ``x, dt [B, T, D]``, ``b, c [B, T, N]``, ``skip [D]`` (the step
takes them without ``T``). Computation runs in float32 whatever the
inputs' types; outputs are float32.
"""

import jax.numpy as jnp
from jax import lax

from d9d_tpu.core.types import Array

F32 = jnp.float32


def selective_scan_step(
    state: Array, x: Array, dt: Array, a: Array, b: Array, c: Array,
    skip: Array,
) -> tuple[Array, Array]:
    """One token: ``state [B, N, D]``, ``x, dt [B, D]``, ``b, c [B, N]``
    → ``(y [B, D], new state)``."""
    x, dt = x.astype(F32), dt.astype(F32)
    decay = jnp.exp(dt[:, None, :] * a.astype(F32))
    drive = (dt * x)[:, None, :] * b.astype(F32)[:, :, None]
    state = decay * state.astype(F32) + drive
    y = jnp.sum(state * c.astype(F32)[:, :, None], axis=1)
    return y + skip.astype(F32) * x, state


def selective_scan_chunked(
    x: Array, dt: Array, a: Array, b: Array, c: Array, skip: Array,
    *, chunk_size: int = 64, initial_state: Array | None = None,
) -> tuple[Array, Array]:
    """A sequence: returns ``(y [B, T, D], final state [B, N, D])``.
    ``T`` need not divide into chunks: the tail is padded with ``dt = 0``
    steps, which leave the state as it is."""
    x, dt, a = x.astype(F32), dt.astype(F32), a.astype(F32)
    b, c = b.astype(F32), c.astype(F32)
    batch, t, d = x.shape
    n = a.shape[0]
    size = min(chunk_size, t)
    pad = (-t) % size
    if pad:
        widen = lambda v: jnp.pad(v, ((0, 0), (0, pad), (0, 0)))  # noqa: E731
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    n_chunks = (t + pad) // size

    def chunks(v):  # [B, T, F] -> [chunks, B, size, F]
        return v.reshape(batch, n_chunks, size, -1).swapaxes(0, 1)

    def combine(left, right):
        # (p1, u1) then (p2, u2): h -> p2·(p1·h + u1) + u2
        return left[0] * right[0], right[0] * left[1] + right[1]

    def one_chunk(h, inputs):
        x_c, dt_c, b_c, c_c = inputs
        decay = jnp.exp(dt_c[:, :, None, :] * a)  # [B, size, N, D]
        drive = (dt_c * x_c)[:, :, None, :] * b_c[..., None]
        through, added = lax.associative_scan(combine, (decay, drive), axis=1)
        states = through * h[:, None] + added
        y = jnp.sum(states * c_c[..., None], axis=2)
        return states[:, -1], y

    h0 = (
        jnp.zeros((batch, n, d), F32) if initial_state is None
        else initial_state.astype(F32)
    )
    final, y = lax.scan(
        one_chunk, h0, (chunks(x), chunks(dt), chunks(b), chunks(c))
    )
    y = y.swapaxes(0, 1).reshape(batch, t + pad, d)[:, :t]
    return y + skip.astype(F32) * x[:, :t], final
