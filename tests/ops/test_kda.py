"""Kimi delta attention ops (``ops/gated_delta.py``): the oracle against a
token-by-token ``numpy`` loop, the chunked form and the one-token step
against the oracle, and the scalar-decay functions through the shared
oracle.

Bounds: float32 against float32, the same sums in another order. 1e-5
relative with 2e-5 absolute is ``test_gated_delta.py``'s bound for the
scalar form, kept here (the CPU reads 1e-6 and less, my runs, PR 51)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.ops.gated_delta import (
    gated_delta_rule_chunked,
    gated_delta_rule_recurrent,
    kda_chunked,
    kda_recurrent,
    kda_step,
)

CLOSE = dict(rtol=1e-5, atol=2e-5)


def _inputs(key, b=2, t=37, h=2, dk=16, dv=8, g_scale=1.0):
    """``beta`` in (0, 2), ``g`` a number a channel, an initial state."""
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -g_scale * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h, dk)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = jax.random.normal(ks[5], (b, h, dk, dv))
    return q, k, v, g, beta, s0


def test_the_oracle_matches_a_token_by_token_loop():
    q, k, v, g, beta, s0 = _inputs(
        jax.random.PRNGKey(0), b=1, t=6, h=1, dk=4, dv=3)
    o, s = kda_recurrent(
        q, k, v, g, beta, use_qk_l2norm=False, initial_state=s0)
    qn, kn, vn, gn = (np.asarray(x[0, :, 0], np.float64) for x in (q, k, v, g))
    bn = np.asarray(beta[0, :, 0], np.float64)
    state = np.asarray(s0[0, 0], np.float64)
    outs = []
    for i in range(6):
        state = np.exp(gn[i])[:, None] * state  # a decay a key channel
        state = state + bn[i] * np.outer(kn[i], vn[i] - state.T @ kn[i])
        outs.append(state.T @ (qn[i] * 4 ** -0.5))
    np.testing.assert_allclose(o[0, :, 0], np.array(outs), **CLOSE)
    np.testing.assert_allclose(s[0, 0], state, **CLOSE)
    assert float(beta.max()) > 1.0  # I - beta k k^T with a negative eigenvalue


@pytest.mark.parametrize("chunk,t,g_scale", [
    (64, 37, 1.0),  # one chunk, T no multiple of a sub-block
    (32, 75, 1.0),  # three chunks, the last one padded
    (16, 37, 8.0),  # chunk = sub-block: diagonal blocks alone; g to -30
    (64, 70, 8.0),  # across sub-blocks at g to -30: nothing overflows
])
def test_the_chunked_form_matches_the_oracle(chunk, t, g_scale):
    q, k, v, g, beta, s0 = _inputs(jax.random.PRNGKey(1), t=t, g_scale=g_scale)
    if g_scale > 1:
        assert float(g.min()) < -25.0
    want_o, want_s = kda_recurrent(q, k, v, g, beta, initial_state=s0)
    o, s = kda_chunked(q, k, v, g, beta, chunk_size=chunk, initial_state=s0)
    assert np.isfinite(o).all() and np.isfinite(s).all()
    np.testing.assert_allclose(o, want_o, **CLOSE)
    np.testing.assert_allclose(s, want_s, **CLOSE)


@pytest.mark.parametrize("per_channel,h", [(True, 4), (False, 4), (True, 48)])
def test_the_step_matches_the_oracle(per_channel, h):
    """Single steps from a carried state through the Pallas call
    (interpreted here); ``g [B, H, 1]`` is the scalar decay of a Gated
    DeltaNet head; 48 heads are no whole groups of 32, so the oracle's
    own step in ``jax.numpy`` runs."""
    q, k, v, g, beta, s0 = _inputs(jax.random.PRNGKey(2), t=5, h=h, g_scale=4.0)
    if not per_channel:
        g = g[..., :1]
    want_o, want_s = kda_recurrent(q, k, v, g, beta, initial_state=s0)
    step = jax.jit(kda_step)
    state, outs = s0, []
    for i in range(5):
        o, state = step(state, q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i])
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, axis=1), want_o, **CLOSE)
    np.testing.assert_allclose(state, want_s, **CLOSE)


def test_the_scalar_decay_functions_ride_the_shared_oracle():
    """``gated_delta_rule_recurrent`` is ``kda_recurrent`` with the decay
    broadcast over a head's channels, and the scalar chunked form still
    agrees with it."""
    q, k, v, g, beta, s0 = _inputs(jax.random.PRNGKey(3), t=21)
    g, beta = g[..., 0], 0.5 * beta
    o, s = gated_delta_rule_recurrent(q, k, v, g, beta, initial_state=s0)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    want_o, want_s = kda_recurrent(q, k, v, wide, beta, initial_state=s0)
    np.testing.assert_allclose(o, want_o, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(s, want_s, rtol=1e-6, atol=1e-6)
    o_c, s_c = gated_delta_rule_chunked(
        q, k, v, g, beta, chunk_size=8, initial_state=s0)
    np.testing.assert_allclose(o_c, o, **CLOSE)
    np.testing.assert_allclose(s_c, s, **CLOSE)
    o_k, s_k = kda_chunked(q, k, v, wide, beta, chunk_size=16, initial_state=s0)
    np.testing.assert_allclose(o_k, o, **CLOSE)
    np.testing.assert_allclose(s_k, s, **CLOSE)


def test_the_chunked_forms_gradients_match_the_oracles():
    args = _inputs(jax.random.PRNGKey(4), t=20)

    def loss(fn, q, k, v, g, beta, s0):
        o, s = fn(q, k, v, g, beta, initial_state=s0)
        return jnp.sum(jnp.sin(o)) + jnp.sum(s * s)

    want = jax.grad(lambda *a: loss(kda_recurrent, *a), range(6))(*args)
    got = jax.grad(
        lambda *a: loss(
            lambda *b, **kw: kda_chunked(*b, chunk_size=16, **kw), *a),
        range(6),
    )(*args)
    for name, a, b in zip("q k v g beta s0".split(), got, want):
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max()), err_msg=name)
