"""The Mamba-2 scan (ops/ssd.py) and mixer (nn/mamba.py Mamba2Mixer): the
one-token update and the chunked scan of matrix products against the
step-by-step recurrence, values and gradients, with a carried state and a
chunk that does not divide the length; decode mode reproduces the full
forward; left padding leaves the state untouched; the state is float32
and four-dimensional, a head's matrix."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.nn.mamba import Mamba2Mixer
from d9d_tpu.ops.ssd import ssd_chunked, ssd_step

B, T, E = 3, 21, 32
H, P, N, G = 4, 16, 8, 2


def _operands(seed=0, t=T, groups=G):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (B, t, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (B, t, H))),
        a=-jnp.exp(jax.random.normal(k[2], (H,))),
        b=jax.random.normal(k[3], (B, t, groups, N)),
        c=jax.random.normal(k[4], (B, t, groups, N)),
        skip=jax.random.normal(k[5], (H,)),
        state=jax.random.normal(k[6], (B, H, P, N)),
    )


def _step_by_step(x, dt, a, b, c, skip, state):
    """The recurrence as it is written: a head's matrix decayed by one
    number, driven by an outer product, read out by C. No ops/ code."""
    heads = lambda v: jnp.repeat(v, H // v.shape[-2], axis=-2)  # noqa: E731
    ys = []
    for t in range(x.shape[1]):
        decay = jnp.exp(dt[:, t] * a)[..., None, None]
        drive = (dt[:, t, :, None] * x[:, t])[..., None]
        state = decay * state + drive * heads(b[:, t])[:, :, None, :]
        ys.append(
            jnp.einsum("bhpn,bhn->bhp", state, heads(c[:, t]))
            + skip[:, None] * x[:, t]
        )
    return jnp.stack(ys, axis=1), state


def _values_and_gradients(fn):
    """``fn``'s outputs and the gradient of a scalar of them by every
    operand, one compiled program a shape."""
    def loss(ops):
        return sum(jnp.sum(jnp.sin(v)) for v in fn(**ops))

    return jax.jit(lambda ops: (fn(**ops), jax.grad(loss)(ops)))


def _stepped(x, dt, a, b, c, skip, state):
    def body(s, xs):
        y, s = ssd_step(s, xs[0], xs[1], a, xs[2], xs[3], skip)
        return s, y
    major = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731
    s, y = jax.lax.scan(body, state, tuple(map(major, (x, dt, b, c))))
    return major(y), s


_want = _values_and_gradients(_step_by_step)
_step = _values_and_gradients(_stepped)


@functools.lru_cache(maxsize=None)
def _chunked(chunk_size):
    return _values_and_gradients(lambda x, dt, a, b, c, skip, state: ssd_chunked(
        x, dt, a, b, c, skip, chunk_size=chunk_size, initial_state=state))


def _assert_same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("groups", [1, 2])
def test_the_one_token_update_is_the_recurrence(groups):
    """Values and gradients over 21 steps from a carried state."""
    ops = _operands(groups=groups)
    _assert_same(_step(ops), _want(ops))


@pytest.mark.parametrize("chunk_size,groups", [(8, 1), (8, 2), (21, 1),
                                               (64, 2)])
def test_the_chunked_scan_is_the_recurrence(chunk_size, groups):
    """Values and gradients, from a carried state; 8 does not divide 21
    (two whole chunks and a padded one), 21 is one chunk, 64 is larger
    than the sequence."""
    ops = _operands(groups=groups)
    _assert_same(_chunked(chunk_size)(ops), _want(ops))


def test_long_steps_and_long_chunks_do_not_overflow():
    """Every exponent the chunked form takes is a sum of non-positive
    numbers: with ``dt A`` near -40 a step a chunk of 64 would overflow
    any quotient of decays, and here stays finite and right."""
    ops = _operands(1)
    ops["dt"] = ops["dt"] * 20.0
    want, _ = _step_by_step(**ops)
    got, _ = ssd_chunked(
        ops["x"], ops["dt"], ops["a"], ops["b"], ops["c"], ops["skip"],
        chunk_size=64, initial_state=ops["state"])
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# -- the mixer -----------------------------------------------------------------


def _mixer(dtype=jnp.float32, **kwargs):
    return Mamba2Mixer(
        hidden_size=E, num_heads=H, head_dim=P, d_state=N, n_groups=G,
        chunk_size=8, dtype=dtype, **kwargs,
    )


@functools.lru_cache(maxsize=None)
def _apply(dtype=jnp.float32, decode=False):
    return jax.jit(functools.partial(
        _mixer(dtype, decode=decode).apply,
        mutable=["cache"] if decode else False,
    ))


def _setup(seed=0):
    u = jax.random.normal(jax.random.PRNGKey(seed), (B, T, E))
    params = nn.unbox(jax.jit(_mixer().init)(jax.random.PRNGKey(1), u)["params"])
    # the gated norm's weight and D off one, so that a forgotten one shows
    rng = np.random.RandomState(seed)
    params["norm"]["weight"] = jnp.asarray(
        rng.uniform(0.5, 1.5, H * P), jnp.float32)
    params["D"] = jnp.asarray(rng.uniform(0.5, 1.5, H), jnp.float32)
    return u, params


def _decode(params, pieces, masks=None):
    cache, outs = None, []
    for i, piece in enumerate(pieces):
        variables = {"params": params}
        if cache is not None:
            variables["cache"] = cache
        out, state = _apply(decode=True)(
            variables, piece, None if masks is None else masks[i])
        cache = state["cache"]
        outs.append(out)
    return jnp.concatenate(outs, axis=1), cache


def test_the_mixer_is_its_equations():
    """The whole mixer against the equations written out on its own
    parameter tree: the split of the in-projection, the convolution over
    x, B and C together, a step size a head, the gated norm over all
    channels with its weight after."""
    u, p = _setup()
    got = _apply()({"params": p}, u)
    d_inner, bc = H * P, G * N
    zxbcdt = u @ p["in_proj"]["kernel"]
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * bc], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    w = p["conv1d"]["weight"]
    xbc = jax.nn.silu(
        sum(padded[:, j:j + T] * w[:, j] for j in range(4)) + p["conv1d"]["bias"])
    x, b, c = jnp.split(xbc, [d_inner, d_inner + bc], axis=-1)
    y, _ = _step_by_step(
        x.reshape(B, T, H, P), jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["A_log"]), b.reshape(B, T, G, N), c.reshape(B, T, G, N),
        p["D"], jnp.zeros((B, H, P, N)),
    )
    gated = y.reshape(B, T, d_inner) * jax.nn.silu(z)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated ** 2, axis=-1, keepdims=True) + 1e-5)
    want = (normed * p["norm"]["weight"]) @ p["out_proj"]["kernel"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("sizes", [(1,) * T, (5,) + (1,) * 16, (8, 8, 5),
                                   (3, 17, 1)])
def test_decode_mode_carries_state_and_tail(sizes):
    """A prefill then one token a step, and prefill in chunks that the
    scan's own chunk (8) does not divide, are the full forward."""
    u, params = _setup(1)
    full = _apply()({"params": params}, u)
    cuts = np.cumsum((0,) + sizes)
    out, _ = _decode(params, [u[:, a:b] for a, b in zip(cuts, cuts[1:])])
    np.testing.assert_allclose(out, full, rtol=2e-4, atol=2e-5)


def test_the_state_is_a_matrix_a_head_and_leads_with_the_batch():
    u, params = _setup(2)
    _, cache = _decode(params, [u[:, :6]])
    assert set(cache) == {"ssm_state", "conv_tail"}
    assert cache["ssm_state"].shape == (B, H, P, N)  # state numbers minor
    assert cache["ssm_state"].dtype == jnp.float32
    assert cache["conv_tail"].shape == (B, 3, H * P + 2 * G * N)
    _, alone = _decode(params, [u[1:2, :6]])
    for leaf in cache:
        np.testing.assert_allclose(
            cache[leaf][1], alone[leaf][0], rtol=1e-5, atol=1e-6)


def test_left_padding_leaves_the_state_untouched():
    u, params = _setup(3)
    pad = 7
    real = u[:1, pad:]
    mask = (jnp.arange(T) >= pad)[None]
    garbage = u[:1].at[:, :pad].multiply(50.0)
    full = _apply()({"params": params}, real)
    padded = _apply()({"params": params}, garbage, mask)
    np.testing.assert_allclose(padded[:, pad:], full, rtol=2e-4, atol=2e-5)
    _, after_pad = _decode(params, [garbage[:, :pad]], [mask[:, :pad]])
    assert not np.asarray(after_pad["ssm_state"]).any()
    assert not np.asarray(after_pad["conv_tail"]).any()
    pieces = [garbage[:, :pad + 2]] + [
        garbage[:, t:t + 1] for t in range(pad + 2, T)]
    masks = [mask[:, :pad + 2]] + [mask[:, t:t + 1] for t in range(pad + 2, T)]
    out, _ = _decode(params, pieces, masks)
    np.testing.assert_allclose(out[:, pad:], full, rtol=2e-4, atol=2e-5)


def test_initialisation_is_mamba_2s():
    fresh = nn.unbox(jax.jit(_mixer().init)(
        jax.random.PRNGKey(7), jnp.zeros((1, 4, E)))["params"])
    a = np.exp(np.asarray(fresh["A_log"]))
    assert a.shape == (H,) and a.min() >= 1.0 and a.max() <= 16.0
    assert np.all(np.asarray(fresh["D"]) == 1.0)
    assert np.all(np.asarray(fresh["norm"]["weight"]) == 1.0)
    dt = np.asarray(jax.nn.softplus(fresh["dt_bias"]))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    assert fresh["in_proj"]["kernel"].shape == (E, 2 * H * P + 2 * G * N + H)
    assert "bias" not in fresh["in_proj"] and "bias" not in fresh["out_proj"]
    assert set(fresh) == {"in_proj", "conv1d", "dt_bias", "A_log", "D",
                          "norm", "out_proj"}


def test_a_bf16_mixer_keeps_its_state_in_float32():
    u, params = _setup(4)
    half = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    back = jax.tree.map(lambda p: p.astype(jnp.float32), half)
    want = _apply()({"params": back}, u)
    got, state = _apply(jnp.bfloat16, decode=True)(
        {"params": half}, u.astype(jnp.bfloat16))
    assert got.dtype == jnp.bfloat16
    assert state["cache"]["ssm_state"].dtype == jnp.float32
    assert state["cache"]["conv_tail"].dtype == jnp.bfloat16
    rel = float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))
    assert rel < 0.02
