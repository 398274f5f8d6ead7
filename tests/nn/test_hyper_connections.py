"""The n-stream residual path (``nn/hyper_connections.py``): the mixing
matrix is doubly stochastic and stays finite at the clamp's ends, the
module computes the papers' update (against the plain reference), a
stream of copies read out by the sum carries the plain residual path's
numbers, and every parameter gets a spec under the three plans. The
stream's passes are Pallas calls (``ops/mhc.py``): values and every
gradient against the plain reference over streams, widths, token counts
and dtypes; what a block traces (two calls a sublayer, two in its
transpose, no float32 array of the stream's shape); four devices against
one."""

import dataclasses

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
from tests.jaxpr_tools import equations

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


# -- the stream's passes as Pallas calls --------------------------------------


@pytest.fixture
def one_device():
    """``MeshParameters.build`` leaves its mesh ambient: the tests of one
    device's program pin the one-device mesh they are about."""
    MeshParameters().build(jax.devices()[:1])


def seeded_for(mod, n, c, seed):
    x = jnp.zeros((1, 2, n, c))
    params = nn.meta.unbox(
        mod.init(jax.random.PRNGKey(seed), x, method="read")["params"])
    rng = np.random.RandomState(seed)
    for name, value in params.items():
        if name[0] in "ab":
            params[name] = jnp.asarray(
                rng.normal(size=value.shape), jnp.float32)
    return params


def around_one_sublayer(mod, probe):
    """``(params, x, w, extra) -> (probe . x_next, x_next)``: the module
    around a sublayer that is not linear, ``extra`` added to the
    sublayer's output so that its gradient is the output's."""

    def sublayer(u, w, extra):
        return jnp.tanh(u.astype(jnp.float32) @ w) * 3.0 + extra

    def program(p, x, w, extra):
        u, mix = mod.apply({"params": p}, x, method="read")
        y = mod.apply(
            {"params": p}, x, sublayer(u, w, extra).astype(x.dtype), mix,
            method="write")
        return (y.astype(jnp.float32) * probe).sum(), y

    def plain(p, x, w, extra):
        with jax.default_matmul_precision("highest"):
            y = reference.around(
                x.astype(jnp.float32), p, {"rms_norm_eps": 1e-6},
                lambda u: sublayer(u, w, extra))
        return (y * probe).sum(), y

    return program, plain


def rel_rms(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(
        np.sqrt(np.mean((got - want) ** 2))
        / (np.sqrt(np.mean(want ** 2)) + 1e-30))


@pytest.mark.parametrize("n,c,b,t,dtype", [
    (4, 128, 1, 1, jnp.float32),  # a generate step: one token in a tile
    (4, 128, 1, 127, jnp.bfloat16),  # one short of two row blocks
    (4, 256, 2, 300, jnp.float32),  # several tiles, the last one padded
    (4, 768, 1, 127, jnp.float32),  # a stream of two column chunks
    (4, 32, 3, 5, jnp.bfloat16),  # a width that is no lane tile
    (2, 128, 1, 1, jnp.bfloat16),
    (2, 256, 1, 255, jnp.float32),  # one short of a tile
    (2, 128, 3, 200, jnp.bfloat16),
], ids=lambda v: getattr(v, "__name__", None) or str(v))
def test_the_calls_and_their_transposes_are_the_plain_forms(
        one_device, n, c, b, t, dtype):
    """Values and the gradients with respect to the stream, the
    sublayer's output and every parameter against the plain reference
    (float32 at ``highest`` on the same numbers). Float32 inputs agree to
    rounding; bf16 inputs to what rounding the stream, the product's
    operands and each output once costs."""
    mod = HyperConnection(
        hidden_size=c, streams=n, dtype=dtype, param_dtype=jnp.float32)
    params = seeded_for(mod, n, c, seed=n + c + t)
    keys = jax.random.split(jax.random.PRNGKey(t), 4)
    x = jax.random.normal(keys[0], (b, t, n, c)).astype(dtype)
    w = jax.random.normal(keys[1], (c, c)) / c ** 0.5
    extra = jax.random.normal(keys[2], (b, t, c))
    probe = jax.random.normal(keys[3], (b, t, n, c))
    program, plain = around_one_sublayer(mod, probe)

    def grad(f):
        return jax.jit(jax.value_and_grad(
            f, argnums=(0, 1, 2, 3), has_aux=True))

    (_, got), got_grads = grad(program)(params, x, w, extra)
    (_, want), want_grads = grad(plain)(params, x, w, extra)
    assert got.dtype == dtype and got.shape == x.shape
    assert got_grads[1].dtype == dtype
    exact = dtype == jnp.float32
    assert rel_rms(got, want) < (1e-5 if exact else 6e-3)
    names = ("params", "stream", "w", "output")
    for name, g, wanted in zip(names, got_grads, want_grads):
        leaves = (
            {name: (g, wanted)} if name != "params"
            else {k: (g[k], wanted[k]) for k in wanted})
        for leaf, (a, z) in leaves.items():
            assert np.isfinite(np.asarray(a, np.float32)).all(), leaf
            # a handful of tokens leave a scalar's gradient a few numbers'
            # difference: compare those to the gradient's own scale
            assert rel_rms(a, z) < (2e-4 if exact else 3e-2), (
                leaf, rel_rms(a, z))


def test_a_block_traces_two_calls_a_sublayer_and_two_in_its_transpose(
        one_device):
    """The gradient of one tiny Xing4.0 block: under each ``mhc`` module
    one read and one write forward, the same two replayed by the layer's
    remat (but the block's last write, whose result nothing in the
    block's backward reads), one transposed read and one transposed write;
    outside the kernels no float32 array of the stream's shape, flat or
    not."""
    from d9d_tpu.models.deepseek import DeepseekCausalLM, xing4_0_tiny
    from d9d_tpu.ops.attention.eager import eager_sdpa

    cfg = dataclasses.replace(
        xing4_0_tiny(64), num_layers=1, num_mtp_modules=0, remat=True)
    model = DeepseekCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    ids = jnp.zeros((2, 8), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(8), (2, 8))
    params = jax.eval_shape(lambda: nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), ids, positions)))

    def loss(p):
        return model.apply(p, ids, positions).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    calls = {}
    for eqn in equations(jaxpr):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            calls[name] = calls.get(name, 0) + 1
    sublayers = 2
    assert calls == {
        "mhc_read": 2 * sublayers, "mhc_write": 2 * sublayers - 1,
        "mhc_read_bwd": sublayers, "mhc_write_bwd": sublayers,
    }
    n, c = cfg.hc_mult, cfg.hidden_size
    stream = {(2, 8, n, c), (2, 8, n * c), (16, n * c)}

    def outside_kernels(jaxpr, scope=""):
        for eqn in jaxpr.eqns:
            inner = f"{scope}/{eqn.source_info.name_stack}"
            yield eqn, inner
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from outside_kernels(sub, inner)

    # around the sublayers; the stream's read-out (``mhc/readout``) sums
    # the streams in float32 inside one fusion and is not a pass of theirs
    wide = [
        (eqn.primitive.name, var.aval.shape)
        for eqn, scope in outside_kernels(jaxpr) if "_mhc" in scope
        for var in (*eqn.invars, *eqn.outvars)
        if getattr(getattr(var, "aval", None), "dtype", None) == jnp.float32
        and var.aval.shape in stream
    ]
    assert [s for _, s in outside_kernels(jaxpr) if "_mhc" in s]
    assert wide == []


def test_four_devices_give_one_devices_values_and_gradients(one_device):
    """Under a mesh of four devices the calls run per shard of the batch
    axes, the maps whole: the stream, the sublayer's input and ``d phi``
    (summed over the shards) are the one-device run's."""
    n, c, b, t = 4, 128, 4, 24
    mod = HyperConnection(
        hidden_size=c, streams=n, dtype=jnp.float32, param_dtype=jnp.float32)
    params = seeded_for(mod, n, c, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, t, n, c))
    probe = jax.random.normal(jax.random.PRNGKey(2), (b, t, n, c))
    program, _ = around_one_sublayer(mod, probe)
    w, extra = jnp.eye(c), jnp.zeros((b, t, c))
    grad = jax.value_and_grad(program, argnums=(0, 1), has_aux=True)
    (_, want), want_grads = jax.jit(grad)(params, x, w, extra)
    # off the one device, so that the four-device program may place them
    params, x, w, extra = jax.tree.map(np.asarray, (params, x, w, extra))
    ctx = MeshParameters(dp_replicate=2, dp_shard=2).build(jax.devices()[:4])
    try:
        traced = jax.make_jaxpr(grad)(params, x, w, extra)
        assert "shard_map" in {
            eqn.primitive.name for eqn in equations(traced.jaxpr)}
        sharded = jax.device_put(x, ctx.batch_sharding())
        (_, got), got_grads = jax.jit(grad)(params, sharded, w, extra)
    finally:
        MeshParameters().build(jax.devices()[:1])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        got_grads[1], want_grads[1], rtol=1e-4, atol=1e-5)
    for name in want_grads[0]:
        np.testing.assert_allclose(
            got_grads[0][name], want_grads[0][name], rtol=2e-4, atol=1e-5,
            err_msg=name)
