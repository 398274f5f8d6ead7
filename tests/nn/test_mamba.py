"""The Mamba-1 mixer (nn/mamba.py): decode mode (prefill, then one token
a step, and chunked prefill) reproduces the full forward; left padding
leaves the state untouched; the cache leaves lead with the batch
dimension; in a bf16 mixer only the projections' operands and the conv
tail are bf16, the state and everything between the projections float32."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.nn.mamba import MambaMixer

B, T, E = 3, 21, 32


def _mixer(dtype=jnp.float32, **kwargs):
    return MambaMixer(
        hidden_size=E, d_state=8, d_conv=4, expand=2, dt_rank=4,
        chunk_size=8, dtype=dtype, **kwargs,
    )


@functools.lru_cache(maxsize=None)
def _apply(dtype=jnp.float32, decode=False):
    """The mixer's ``apply``, one compiled program a shape (un-jitted it
    is a compile an operation a shape, a step at a time)."""
    return jax.jit(functools.partial(
        _mixer(dtype, decode=decode).apply,
        mutable=["cache"] if decode else False,
    ))


def _setup(seed=0):
    u = jax.random.normal(jax.random.PRNGKey(seed), (B, T, E))
    params = nn.unbox(jax.jit(_mixer().init)(jax.random.PRNGKey(1), u)["params"])
    # the norms' weights off one, so that a forgotten norm shows
    params = jax.tree.map(
        lambda p: p * 1.1 if p.ndim == 1 and p.shape[0] in (4, 8) else p,
        params,
    )
    return u, params


def _decode(params, pieces, masks=None):
    """The pieces through one decode-mode cache, outputs joined."""
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


@pytest.mark.parametrize("prefill", [1, 5, 20])
def test_prefill_then_one_token_a_step_is_the_full_forward(prefill):
    u, params = _setup()
    full = _apply()({"params": params}, u)
    pieces = [u[:, :prefill]] + [u[:, t:t + 1] for t in range(prefill, T)]
    out, _ = _decode(params, pieces)
    np.testing.assert_allclose(out, full, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sizes", [(8, 8, 5), (3, 17, 1), (10, 11)])
def test_chunked_prefill_carries_state_and_tail(sizes):
    u, params = _setup(1)
    full = _apply()({"params": params}, u)
    cuts = np.cumsum((0,) + sizes)
    out, _ = _decode(params, [u[:, a:b] for a, b in zip(cuts, cuts[1:])])
    np.testing.assert_allclose(out, full, rtol=2e-5, atol=2e-5)


def test_cache_leaves_lead_with_the_batch_dimension():
    u, params = _setup(2)
    _, cache = _decode(params, [u[:, :6]])
    assert set(cache) == {"ssm_state", "conv_tail"}
    assert cache["ssm_state"].shape == (B, 8, 2 * E)  # channels minor
    assert cache["ssm_state"].dtype == jnp.float32
    assert cache["conv_tail"].shape == (B, 3, 2 * E)
    # rows are independent: a row's leaves depend on that row alone
    _, alone = _decode(params, [u[1:2, :6]])
    np.testing.assert_allclose(
        cache["ssm_state"][1], alone["ssm_state"][0], rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        cache["conv_tail"][1], alone["conv_tail"][0], rtol=1e-6, atol=1e-6
    )


def test_left_padding_leaves_the_state_untouched():
    """A row left-padded by 7 gives, on its real positions, what the
    unpadded row gives, and its state after the padding alone is zero
    although the convolution has a bias."""
    u, params = _setup(3)
    pad = 7
    real = u[:1, pad:]
    mask = (jnp.arange(T) >= pad)[None]
    garbage = u[:1].at[:, :pad].multiply(50.0)
    full = _apply()({"params": params}, real)
    padded = _apply()({"params": params}, garbage, mask)
    np.testing.assert_allclose(padded[:, pad:], full, rtol=2e-5, atol=2e-5)
    # decode mode: the padding alone, then the real tokens one at a time
    _, after_pad = _decode(params, [garbage[:, :pad]], [mask[:, :pad]])
    assert not np.asarray(after_pad["ssm_state"]).any()
    assert not np.asarray(after_pad["conv_tail"]).any()
    pieces = [garbage[:, :pad + 2]] + [
        garbage[:, t:t + 1] for t in range(pad + 2, T)
    ]
    masks = [mask[:, :pad + 2]] + [mask[:, t:t + 1] for t in range(pad + 2, T)]
    out, _ = _decode(params, pieces, masks)
    np.testing.assert_allclose(out[:, pad:], full, rtol=2e-5, atol=2e-5)


def test_initialisation_is_the_familys():
    _, params = _setup()
    fresh = nn.unbox(jax.jit(_mixer().init)(
        jax.random.PRNGKey(7), jnp.zeros((1, 4, E)))["params"])
    a = -np.exp(np.asarray(fresh["A_log"]))
    np.testing.assert_allclose(
        a, -np.tile(np.arange(1.0, 9.0), (2 * E, 1)), rtol=1e-6
    )
    assert np.all(np.asarray(fresh["D"]) == 1.0)
    dt = np.asarray(jax.nn.softplus(fresh["dt_proj"]["bias"]))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    assert fresh["conv1d"]["bias"].shape == (2 * E,)
    assert "bias" not in fresh["in_proj"] and "bias" not in fresh["out_proj"]


def test_a_bf16_mixer_keeps_its_state_in_float32():
    """bf16 is the projections' operand type and the conv tail's; the
    SSM state stays float32 and the output stays near the float32
    mixer's (the roundings are the four projections' inputs)."""
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


def test_a_bf16_step_follows_a_bf16_prefill():
    """Decode mode in bf16: a prefill of 20 then one token gives the
    last position of a prefill of 21 (the tail and the state carry what
    the convolution and the recurrence need, whatever the type)."""
    u, params = _setup(5)
    half = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    u = u.astype(jnp.bfloat16)
    mixer = _apply(jnp.bfloat16, decode=True)
    whole, _ = mixer({"params": half}, u)
    _, state = mixer({"params": half}, u[:, :-1])
    last, _ = mixer({"params": half, "cache": state["cache"]}, u[:, -1:])
    np.testing.assert_allclose(
        np.asarray(last[:, 0], np.float32),
        np.asarray(whole[:, -1], np.float32), rtol=0.03, atol=0.03,
    )
