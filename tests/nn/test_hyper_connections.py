"""The n-stream residual path (``nn/hyper_connections.py``): the mixing
matrix is doubly stochastic and stays finite at the clamp's ends, the
module computes the papers' update (against the plain reference), a
stream of copies read out by the sum carries the plain residual path's
numbers, and every parameter gets a spec under the three plans."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import xing4_0 as reference
from d9d_tpu.core import MeshParameters
from d9d_tpu.nn.hyper_connections import (
    HyperConnection,
    expand_streams,
    sinkhorn,
    sum_streams,
)
from d9d_tpu import parallel
from d9d_tpu.parallel.plan import logical_to_mesh_sharding

C, N = 32, 4


def module(**extra):
    return HyperConnection(
        hidden_size=C, streams=N, dtype=jnp.float32, param_dtype=jnp.float32,
        **extra,
    )


def seeded(seed=0, scale=1.0):
    """Parameters away from their initialisers: every a and b random."""
    x = jnp.zeros((1, 2, N, C))
    params = nn.meta.unbox(module().init(
        jax.random.PRNGKey(seed), x, method="read")["params"])
    rng = np.random.RandomState(seed)
    for name, value in params.items():
        if name[0] in "ab":
            params[name] = jnp.asarray(
                scale * rng.normal(size=value.shape), jnp.float32)
    return params


@pytest.mark.parametrize("spread", [0.1, 0.5, 1.0, 5.0, 100.0])
def test_h_res_is_doubly_stochastic_after_20_rounds(spread):
    """From the module's kind of start (``spread`` x noise + 2 I) rows and
    columns sum to 1 within 1e-5 (rows within 1e-4 at the initialisers' own
    noise, 0.5) after the published 20 rounds; a wilder start has not converged
    in 20 rounds, but its columns (the last step) are exact and nothing
    leaves [0, 1]. ``spread`` 100 puts most entries on the clamp's ends
    (-30 and 30, a ratio of e^60) and everything stays finite."""
    rng = np.random.RandomState(int(spread * 10))
    raw = spread * rng.normal(size=(N, N, 3, 50)) + 2.0 * np.eye(N)[
        :, :, None, None]
    m = sinkhorn(jnp.clip(jnp.asarray(raw, jnp.float32), -30.0, 30.0), 20, 1e-6)
    assert np.isfinite(np.asarray(m)).all()
    assert float(m.min()) >= 0.0 and float(m.max()) <= 1.0 + 1e-5
    np.testing.assert_allclose(m.sum(axis=0), 1.0, atol=1e-5)
    if spread <= 0.5:
        # 1e-5 at the noise a seeded model starts from (a = 0.5 on a
        # unit-variance projection gives 0.5; 0.1 is the papers' small a)
        np.testing.assert_allclose(
            m.sum(axis=1), 1.0, atol=1e-5 if spread < 0.5 else 1e-4)
    else:
        np.testing.assert_allclose(m.sum(axis=1).mean(axis=0), 1.0, atol=1e-5)


@pytest.mark.parametrize("end", [-30.0, 30.0, -1e9, 1e9])
def test_the_clamps_ends_stay_finite_through_the_module(end):
    params = seeded()
    params["b_res"] = jnp.full((N, N), end, jnp.float32)
    params["a_res"] = jnp.zeros((), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, N, C))

    def loss(p, x):
        u, mix = module().apply({"params": p}, x, method="read")
        return module().apply(
            {"params": p}, x, jnp.tanh(u), mix, method="write").sum()

    value, grads = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    assert np.isfinite(float(value))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))
    _, (_, h_res) = module().apply({"params": params}, x, method="read")
    # a constant matrix, however large, balances to 1/n everywhere
    np.testing.assert_allclose(h_res, 1.0 / N, atol=1e-5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_update_is_the_papers(seed):
    """``x_{l+1} = H_res x_l + H_post^T F(H_pre x_l)`` with the
    coefficients of the reference's ``mixing``, for a sublayer that is not
    linear."""
    params = seeded(seed)
    x = jax.random.normal(jax.random.PRNGKey(seed + 10), (2, 16, N, C))
    cfg = {"rms_norm_eps": 1e-6}

    def sublayer(u):
        return jnp.tanh(u) * 3.0

    u, mix = module().apply({"params": params}, x, method="read")
    got = module().apply({"params": params}, x, sublayer(u), mix, method="write")
    with jax.default_matmul_precision("highest"):
        want = reference.around(x, params, cfg, sublayer)
        h_pre, h_post, h_res = reference.mixing(x, params, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        jnp.moveaxis(mix[1], (0, 1), (-2, -1)), h_res, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(jnp.moveaxis(mix[0], 0, -1), h_post, rtol=1e-5)
    # the coefficients depend on the token: every term is live
    assert float(jnp.std(h_pre, axis=(0, 1)).min()) > 1e-3
    assert float(jnp.std(h_res, axis=(0, 1)).min()) > 1e-4


def test_the_initialisers_keep_every_term_live():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, N, C))
    params = nn.meta.unbox(
        module().init(jax.random.PRNGKey(1), x, method="read")["params"])
    assert all(float(params[a]) != 0.0 for a in ("a_pre", "a_post", "a_res"))
    assert all(np.asarray(params[p]).std() > 0
               for p in ("phi_pre", "phi_post", "phi_res"))
    _, (h_post, h_res) = module().apply({"params": params}, x, method="read")
    assert float(jnp.std(h_post, axis=(1, 2)).min()) > 1e-3
    # most of a stream stays in it, and some of every other arrives
    diagonal = jnp.stack([h_res[i, i] for i in range(N)])
    assert 0.5 < float(diagonal.mean()) < 0.9
    assert float(h_res.min()) > 0.0


def test_copies_read_out_by_the_sum_are_the_plain_stream():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, C), jnp.bfloat16)
    wide = expand_streams(x, N)
    assert wide.shape == (2, 5, N, C) and wide.dtype == x.dtype
    np.testing.assert_array_equal(
        np.asarray(sum_streams(wide), np.float32),
        np.asarray(x, np.float32) * N,
    )


@pytest.mark.parametrize("plan", ["replicate", "fsdp", "fsdp_ep"])
def test_every_parameter_gets_a_spec_under_the_plan(plan):
    ctx = MeshParameters(dp_shard=2).build(jax.devices()[:2])
    x = jnp.zeros((1, 2, N, C))
    abstract = jax.eval_shape(
        lambda: module().init(jax.random.PRNGKey(0), x, method="read"))
    spec = nn.get_partition_spec(abstract)["params"]
    rules = getattr(parallel, plan + "_plan")(ctx).rules
    shardings = logical_to_mesh_sharding(spec, ctx.mesh, rules)
    assert set(shardings) == {
        "phi_pre", "phi_post", "phi_res", "a_pre", "a_post", "a_res",
        "b_pre", "b_post", "b_res",
    }
    for name, sharding in shardings.items():
        wide = name.startswith("phi") and plan != "replicate"
        # the maps shard on their n C rows like any embed dimension
        assert any(a is not None for a in sharding.spec) == wide, (
            name, sharding.spec)
