"""``KimiDeltaAttention`` (``nn/linear_attention.py``): decode mode (a
chunked prefill, then single steps through the state and the conv tail)
equals the full pass; padded positions leave the state alone; the
published widths count Solar-Open2-250B's 137,740,480 a mixer."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.nn.linear_attention import KimiDeltaAttention

E, H, D = 32, 4, 16


def _mixer(**extra):
    return KimiDeltaAttention(
        hidden_size=E, num_heads=H, head_dim=D, allow_neg_eigval=True,
        chunk_size=16, dtype=jnp.float32, **extra,
    )


@pytest.fixture(scope="module")
def drawn():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 23, E))
    params = nn.unbox(
        _mixer().init(jax.random.PRNGKey(0), x)["params"])
    # off their initial values, so that a forgotten one shows
    rng = np.random.RandomState(0)
    params["o_norm"]["weight"] = jnp.asarray(rng.uniform(0.5, 1.5, (D,)))
    params["g_b_proj"]["bias"] = jnp.asarray(rng.normal(size=(H * D,)))
    return params, x


def test_a_chunked_prefill_then_single_steps_equal_the_full_pass(drawn):
    """19 positions through the chunked form from an empty state (two
    chunks, the second padded), then four one-token steps (the Pallas
    call, interpreted): the state and the conv tail carry what the full
    pass sees. Float32, the same sums in another order: 1e-5."""
    params, x = drawn
    full = jax.jit(lambda p, x: _mixer().apply({"params": p}, x))(params, x)
    mixer = _mixer(decode=True)

    @jax.jit
    def decoded(params, x):
        head, state = mixer.apply(
            {"params": params}, x[:, :19], mutable=["cache"])
        outs = [head]
        for i in range(19, 23):
            out, state = mixer.apply(
                {"params": params, "cache": state["cache"]}, x[:, i:i + 1],
                mutable=["cache"])
            outs.append(out)
        return jnp.concatenate(outs, axis=1), state["cache"]

    got, cache = decoded(params, x)
    np.testing.assert_allclose(got, full, rtol=1e-5, atol=1e-5)
    assert cache["delta_state"].shape == (2, H, D, D)
    assert cache["delta_state"].dtype == jnp.float32
    assert cache["conv_tail"].shape == (2, 3, 3 * H * D)  # one joined tail


def test_padded_positions_leave_the_state_alone(drawn):
    """Left padding (``generate``'s): the masked positions neither write
    nor decay, so the real positions' outputs are those of the unpadded
    sequence."""
    params, x = drawn
    pad = 5
    padded = jnp.concatenate([jnp.ones((2, pad, E)), x], axis=1)
    mask = jnp.arange(pad + x.shape[1])[None] >= pad
    run = jax.jit(lambda p, x, m: _mixer().apply({"params": p}, x, m))
    got = run(params, padded, jnp.broadcast_to(mask, padded.shape[:2]))
    want = jax.jit(lambda p, x: _mixer().apply({"params": p}, x))(params, x)
    np.testing.assert_allclose(got[:, pad:], want, rtol=1e-5, atol=1e-5)


def test_the_published_widths_count_a_solar_open2_mixer():
    mixer = KimiDeltaAttention(
        hidden_size=4096, num_heads=64, head_dim=128, allow_neg_eigval=True)
    shapes = nn.unbox(jax.eval_shape(lambda: mixer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 4096), jnp.bfloat16)
    )["params"]))
    assert shapes["qkv_conv1d"]["weight"].shape == (24_576, 4)
    assert shapes["f_a_proj"]["kernel"].shape == (4096, 128)  # rank = head_dim
    assert shapes["g_b_proj"]["bias"].shape == (8192,)
    assert shapes["dt_bias"].shape == (64, 128)
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 137_740_480
