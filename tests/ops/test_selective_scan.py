"""The selective scan (ops/selective_scan.py): the one-token step, the
chunked scan and a plain sequential loop are one recurrence; the state
threads across a split sequence and across chunks that do not divide it;
gradients through the chunked form are the sequential form's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.ops.selective_scan import (
    selective_scan_chunked,
    selective_scan_step,
)

B, T, D, N = 2, 37, 24, 8


def _inputs(seed=0, t=T):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (B, t, D))
    # step sizes across the range the mixer's bias is drawn for, and
    # rates -1..-N: decays from nearly 1 to nearly 0
    dt = jnp.exp(jax.random.uniform(k[1], (B, t, D), minval=-6.0, maxval=0.5))
    a = -jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, D))
    b = jax.random.normal(k[2], (B, t, N))
    c = jax.random.normal(k[3], (B, t, N))
    skip = jax.random.normal(k[4], (D,))
    h0 = jax.random.normal(k[5], (B, N, D))
    return x, dt, a, b, c, skip, h0


def sequential(x, dt, a, b, c, skip, h0):
    """The recurrence written as a Python loop over time."""
    h, ys = h0, []
    for t in range(x.shape[1]):
        decay = jnp.exp(dt[:, t, None, :] * a)
        h = decay * h + (dt[:, t] * x[:, t])[:, None, :] * b[:, t, :, None]
        ys.append(jnp.einsum("bnd,bn->bd", h, c[:, t]) + skip * x[:, t])
    return jnp.stack(ys, axis=1), h


def test_step_is_one_iteration_of_the_loop():
    x, dt, a, b, c, skip, h0 = _inputs()
    want_y, want_h = sequential(x[:, :1], dt[:, :1], a, b[:, :1], c[:, :1],
                                skip, h0)
    y, h = selective_scan_step(h0, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], skip)
    np.testing.assert_allclose(y, want_y[:, 0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(h, want_h, rtol=1e-6, atol=1e-6)
    assert y.dtype == h.dtype == jnp.float32


@pytest.mark.parametrize("chunk", [1, 5, 16, 37, 64])
def test_chunked_matches_the_loop_whatever_the_chunk(chunk):
    """5 and 16 do not divide 37: the tail is padded with steps that
    leave the state alone; 64 is longer than the sequence."""
    x, dt, a, b, c, skip, h0 = _inputs(1)
    want_y, want_h = sequential(x, dt, a, b, c, skip, h0)
    y, h = selective_scan_chunked(
        x, dt, a, b, c, skip, chunk_size=chunk, initial_state=h0
    )
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)


def test_steps_thread_the_state_like_the_chunked_scan():
    x, dt, a, b, c, skip, _ = _inputs(2)
    want_y, want_h = selective_scan_chunked(x, dt, a, b, c, skip, chunk_size=8)
    h, ys = jnp.zeros((B, N, D)), []
    for t in range(T):
        y, h = selective_scan_step(h, x[:, t], dt[:, t], a, b[:, t], c[:, t],
                                   skip)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cut", [1, 11, 36])
def test_a_carried_state_joins_a_split_sequence(cut):
    x, dt, a, b, c, skip, h0 = _inputs(3)
    whole_y, whole_h = selective_scan_chunked(
        x, dt, a, b, c, skip, chunk_size=8, initial_state=h0
    )
    head_y, mid = selective_scan_chunked(
        x[:, :cut], dt[:, :cut], a, b[:, :cut], c[:, :cut], skip,
        chunk_size=8, initial_state=h0,
    )
    tail_y, end = selective_scan_chunked(
        x[:, cut:], dt[:, cut:], a, b[:, cut:], c[:, cut:], skip,
        chunk_size=8, initial_state=mid,
    )
    np.testing.assert_allclose(
        jnp.concatenate([head_y, tail_y], 1), whole_y, rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(end, whole_h, rtol=2e-5, atol=2e-5)


def test_long_chunks_and_large_steps_do_not_overflow():
    """dt·a down to -16 a step over 64 steps: a form that divides by a
    cumulative decay would overflow float32 here."""
    x, _, a, b, c, skip, _ = _inputs(4, t=64)
    dt = jnp.full((B, 64, D), 2.0)
    y, h = selective_scan_chunked(x, dt, a, b, c, skip, chunk_size=64)
    want_y, want_h = sequential(x, dt, a, b, c, skip, jnp.zeros((B, N, D)))
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(h).all())
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)


def test_gradients_are_the_sequential_forms():
    x, dt, a, b, c, skip, h0 = _inputs(5, t=19)

    def loss(fn, x, dt, a, b, c, skip, h0):
        y, h = fn(x, dt, a, b, c, skip, h0)
        return jnp.sum(jnp.sin(y)) + jnp.sum(h * h)

    chunked = lambda *args: selective_scan_chunked(  # noqa: E731
        *args[:6], chunk_size=8, initial_state=args[6]
    )
    got = jax.grad(loss, argnums=range(1, 8))(chunked, x, dt, a, b, c, skip, h0)
    want = jax.grad(loss, argnums=range(1, 8))(
        sequential, x, dt, a, b, c, skip, h0
    )
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


def test_inputs_of_any_float_type_compute_in_float32():
    x, dt, a, b, c, skip, h0 = _inputs(6)
    half = lambda v: v.astype(jnp.bfloat16)  # noqa: E731
    y, h = selective_scan_chunked(
        half(x), dt, a, half(b), half(c), half(skip), chunk_size=8,
        initial_state=h0,
    )
    assert y.dtype == h.dtype == jnp.float32
    want_y, _ = sequential(
        half(x).astype(jnp.float32), dt, a, half(b).astype(jnp.float32),
        half(c).astype(jnp.float32), half(skip).astype(jnp.float32), h0,
    )
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
