"""``CompressedConvAttention`` (``nn/cca.py``) alone against the plain
reference's sublayer (``benchmarks/references/zaya.py
attention_sublayer``): training mode; decode mode from prefills of 1, 2
and 3 tokens (across the two taps' left edge) on through single steps,
unpaged and through page pools; left padding; each switch against the
reference's reading of it."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import zaya as reference
from d9d_tpu.nn.cca import CompressedConvAttention
from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu.ops.rope import compute_rope_frequencies, make_rope_cos_sin
from tests.nn.test_paged_cache_modules import B, DML, _paged_cache

E, H, G, D, T = 32, 4, 2, 16, 7
THETA = 10_000.0
HF = {"num_attention_heads": H, "num_key_value_heads": G, "head_dim": D,
      "rope_theta": THETA}


def _module(**extra):
    # the temperature 0.3 from its zero, so that a forgotten one shows
    return CompressedConvAttention(
        hidden_size=E, num_heads=H, num_kv_heads=G, head_dim=D,
        sdpa=eager_sdpa, dtype=jnp.float32, init_jitter=0.3, **extra)


def _rope(start, t):
    inv, scale = compute_rope_frequencies(D // 2, THETA)
    pos = jnp.broadcast_to(jnp.arange(start, start + t), (B, t))
    return make_rope_cos_sin(pos, inv, scale)


@pytest.fixture(scope="module")
def drawn():
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, E))
    params = nn.unbox(jax.jit(_module().init)(
        jax.random.PRNGKey(0), x, *_rope(0, T))["params"])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: reference.attention_sublayer(
            x, p, HF, jnp.arange(T)))(params, x)
    return params, x, np.asarray(want)


def test_training_mode_is_the_references_sublayer(drawn):
    params, x, want = drawn
    got = jax.jit(lambda p, x: _module().apply(
        {"params": p}, x, *_rope(0, T)))(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _decoded(module, params, x, first, cache=None):
    """A prefill of ``first`` tokens (a single step where a cache is
    given: a paged one takes no more), then a token a step."""
    outs, at = [], 0
    for t in (first, *[1] * (T - first)):
        variables = {"params": params}
        if cache is not None:
            variables["cache"] = cache
        out, state = module.apply(
            variables, x[:, at:at + t], *_rope(at, t), mutable=["cache"])
        cache, at = state["cache"], at + t
        outs.append(out)
    return jnp.concatenate(outs, axis=1), cache


@pytest.mark.parametrize("first", [1, 2, 3])
def test_a_prefill_then_single_steps_equal_the_full_pass(drawn, first):
    """The three tails carry what the full pass sees, whichever side of
    the taps' left edge the prefill ends on."""
    params, x, want = drawn
    module = _module(decode_max_length=DML)
    got, cache = jax.jit(
        lambda p, x: _decoded(module, p, x, first))(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert cache["conv_tail"].shape == (B, 1, (H + G) * D)
    assert cache["conv1_tail"].shape == (B, 1, (H + G) * D)
    assert cache["value_tail"].shape == (B, 1, G // 2 * D)
    assert cache["cached_key"].shape == (B, G, DML, D)  # heads-major


def test_paged_single_steps_equal_the_full_pass(drawn):
    """The same steps through page pools behind a table (the serving
    loop's layout): the pool holds the rotated key and the shifted
    value, the tails stay per-row leaves."""
    params, x, want = drawn
    module = _module(decode_max_length=DML)
    dense = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), x[:, :1], *_rope(0, 1))["cache"])
    paged = _paged_cache(
        jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), dense))
    got, cache = jax.jit(
        lambda p, x, c: _decoded(module, p, x, 1, c))(params, x, paged)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert cache["cached_key"].shape[1:] == (G, 4, D)  # [pages, G, ps, D]
    assert cache["conv_tail"].shape == (B, 1, (H + G) * D)


def test_left_padding_leaves_the_first_token_its_zeros(drawn):
    """``generate``'s left pads: zero inputs there and zero again behind
    the first convolution's bias, so the real positions read as the
    unpadded sequence does."""
    params, x, want = drawn
    pad = 3
    padded = jnp.concatenate([jnp.ones((B, pad, E)), x], axis=1)
    real = jnp.broadcast_to(jnp.arange(pad + T) >= pad, (B, pad + T))
    inv, scale = compute_rope_frequencies(D // 2, THETA)
    cos, sin = make_rope_cos_sin(
        jnp.maximum(jnp.arange(pad + T) - pad, 0)[None].repeat(B, 0),
        inv, scale)
    got = jax.jit(lambda p, x: _module().apply(
        {"params": p}, x, cos, sin, real[:, None, None, :], real))(
        params, padded)
    np.testing.assert_allclose(got[:, pad:], want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("field,reading", [
    ("qk_mean", "qk_mean"), ("value_shift", "value_shift"),
    ("key_temperature", "temperature"),
])
def test_a_switch_off_is_the_references_reading_off(drawn, field, reading):
    params, x, _ = drawn
    got = jax.jit(lambda p, x: _module(**{field: False}).apply(
        {"params": p}, x, *_rope(0, T)))(params, x)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: reference.attention_sublayer(
            x, p, HF, jnp.arange(T),
            {**reference.READINGS, reading: False}))(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_a_depthwise_second_convolution_decodes_as_it_trains():
    """``conv1_grouped`` off (the other reading of the second
    convolution): its own tree (a tap a channel), its tail the same
    leaf."""
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, E))
    module = _module(conv1_grouped=False, decode_max_length=DML)
    params = nn.unbox(jax.jit(module.clone(decode_max_length=0).init)(
        jax.random.PRNGKey(0), x, *_rope(0, T))["params"])
    assert params["conv1"]["weight"].shape == ((H + G) * D, 2)
    full = jax.jit(lambda p, x: module.clone(decode_max_length=0).apply(
        {"params": p}, x, *_rope(0, T)))(params, x)
    got, cache = jax.jit(lambda p, x: _decoded(module, p, x, 2))(params, x)
    np.testing.assert_allclose(got, full, rtol=1e-5, atol=1e-5)
    assert cache["conv1_tail"].shape == (B, 1, (H + G) * D)
