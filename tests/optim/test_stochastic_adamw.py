"""StochasticAdamW + stochastic rounding tests.

Mirrors the reference test strategy for kernel/stochastic/* and
optim/stochastic/adamw.py: (a) rounding is mean-preserving and lands only on
the two bf16 neighbours; (b) the bf16 optimizer tracks an fp32 optax.adamw
trajectory; (c) RNG state lives in the optimizer state (reproducible).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
pytestmark = pytest.mark.e2e  # slow tier: long fp32-tracking sweep


from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from d9d_tpu.ops.stochastic import (
    rounding_fields,
    stochastic_round_to_bf16,
    stochastic_round_with_field,
)
from d9d_tpu.optim import StochasticAdamW
from d9d_tpu.telemetry.audit_capture import _collective_census
from tests.jaxpr_tools import equations


class TestStochasticRounding:
    def test_lands_on_neighbours(self):
        x = jnp.array([1.0 + 1 / 256.0] * 1024, jnp.float32)  # between bf16 grid pts
        out = stochastic_round_to_bf16(x, jax.random.PRNGKey(0))
        lo = np.float32(jnp.asarray(x[0]).astype(jnp.bfloat16))  # nearest = 1.0
        vals = set(np.unique(np.asarray(out.astype(jnp.float32))))
        grid = {1.0, 1.0 + 1 / 128.0}
        assert vals <= grid, (vals, grid, lo)
        assert len(vals) == 2  # both neighbours hit

    def test_mean_preserving(self):
        # value 1/4 of the way between two bf16 neighbours -> P(up) = 0.25
        lo, hi = 1.0, 1.0 + 1 / 128.0
        x = jnp.full((200_000,), lo + (hi - lo) * 0.25, jnp.float32)
        out = stochastic_round_to_bf16(x, jax.random.PRNGKey(1))
        frac_up = float(jnp.mean((out.astype(jnp.float32) > lo).astype(jnp.float32)))
        assert abs(frac_up - 0.25) < 0.01
        mean = float(jnp.mean(out.astype(jnp.float32)))
        assert abs(mean - float(x[0])) < 1e-5

    def test_exact_values_unchanged(self):
        x = jnp.array([0.0, 1.0, -2.0, 0.5], jnp.float32)  # exact in bf16
        out = stochastic_round_to_bf16(x, jax.random.PRNGKey(2))
        np.testing.assert_array_equal(
            np.asarray(out.astype(jnp.float32)), np.asarray(x)
        )

    def test_nonfinite_passthrough(self):
        x = jnp.array([jnp.inf, -jnp.inf, jnp.nan], jnp.float32)
        out = stochastic_round_to_bf16(x, jax.random.PRNGKey(3))
        o = np.asarray(out.astype(jnp.float32))
        assert np.isposinf(o[0]) and np.isneginf(o[1]) and np.isnan(o[2])


def _tree_close(a, b, tol):
    flat_a = jax.tree.leaves(a)
    flat_b = jax.tree.leaves(b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32), atol=tol, rtol=tol
        )


class TestStochasticAdamW:
    def _problem(self, dtype):
        params = {
            "w": jnp.linspace(-1, 1, 64, dtype=jnp.float32).astype(dtype),
            "b": jnp.zeros((8,), dtype),
        }
        def grads_at(step):
            g = jax.random.normal(jax.random.PRNGKey(100 + step), (64,))
            return {"w": g.astype(jnp.float32), "b": jnp.ones((8,), jnp.float32)}
        return params, grads_at

    @pytest.mark.slow  # compile-bound minutes-class on the 2-core rig; e2e tier covers it
    def test_tracks_fp32_adamw(self):
        lr, wd = 1e-2, 0.1
        params_bf, grads_at = self._problem(jnp.bfloat16)
        params_32 = jax.tree.map(lambda p: p.astype(jnp.float32), params_bf)

        opt = StochasticAdamW(lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=wd)
        ref = optax.adamw(lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=wd)
        state = opt.init(params_bf)
        ref_state = ref.init(params_32)

        for step in range(50):
            g = grads_at(step)
            new_p, state = jax.jit(opt.update)(g, state, params_bf)
            params_bf = opt.apply_updates(params_bf, new_p)
            upd, ref_state = ref.update(g, ref_state, params_32)
            params_32 = optax.apply_updates(params_32, upd)

        # bf16 stochastic trajectory stays near the fp32 one; individual
        # elements random-walk a few bf16 grid points, the mean error is tight
        _tree_close(params_bf, params_32, tol=8e-2)
        err = np.asarray(params_bf["w"].astype(jnp.float32)) - np.asarray(
            params_32["w"]
        )
        assert abs(err.mean()) < 5e-3
        assert jax.tree.leaves(params_bf)[0].dtype == jnp.bfloat16

    def test_reproducible_from_state(self):
        params, grads_at = self._problem(jnp.bfloat16)
        opt = StochasticAdamW(1e-2, seed=7)
        s0 = opt.init(params)
        p1, s1 = opt.update(grads_at(0), s0, params)
        p2, s2 = opt.update(grads_at(0), s0, params)
        _tree_close(p1, p2, tol=0.0)
        assert int(s1.count) == 1

    def test_moment_dtype_bf16(self):
        params, grads_at = self._problem(jnp.bfloat16)
        opt = StochasticAdamW(1e-2, moment_dtype=jnp.bfloat16)
        state = opt.init(params)
        assert jax.tree.leaves(state.mu)[0].dtype == jnp.bfloat16
        new_p, state = opt.update(grads_at(0), state, params)
        assert jax.tree.leaves(state.mu)[0].dtype == jnp.bfloat16
        assert jax.tree.leaves(new_p)[0].dtype == jnp.bfloat16

    def test_in_trainer_loop_loss_decreases(self):
        # tiny quadratic: params should descend
        params = {"w": jnp.full((128,), 2.0, jnp.bfloat16)}
        opt = StochasticAdamW(5e-2)
        state = opt.init(params)

        def loss_fn(p):
            return jnp.sum(p["w"].astype(jnp.float32) ** 2)

        losses = []
        for _ in range(100):
            g = jax.grad(loss_fn)(params)
            g = {"w": g["w"].astype(jnp.float32)}
            new_p, state = opt.update(g, state, params)
            params = opt.apply_updates(params, new_p)
            losses.append(float(loss_fn(params)))
        assert losses[-1] < losses[0] * 0.2


LO, HI = 1.0, 1.0 + 1 / 128.0  # bf16 neighbours


@pytest.mark.parametrize("field", [0, 1, 2])
class TestRoundingWithField:
    """The entry that takes its 16 bits as an argument, for each of the
    three fields that one Threefry block gives an element."""

    def _round(self, x, field, seed=0):
        fields = rounding_fields(jax.random.PRNGKey(seed), x.shape)
        assert all(f.dtype == jnp.uint32 and f.shape == x.shape for f in fields)
        assert int(fields[field].max()) < 1 << 16
        out = stochastic_round_with_field(x, fields[field])
        assert out.dtype == jnp.bfloat16
        return np.asarray(out.astype(jnp.float32))

    def test_lands_on_neighbours_only(self, field):
        out = self._round(jnp.full((4096,), LO + 1 / 256.0, jnp.float32), field)
        assert set(np.unique(out)) == {np.float32(LO), np.float32(HI)}

    def test_p_up_is_the_distance(self, field):
        x = jnp.full((200_000,), LO + (HI - LO) * 0.25, jnp.float32)
        out = self._round(x, field, seed=1)
        assert abs(float((out > LO).mean()) - 0.25) < 0.01
        assert abs(float(out.mean()) - float(x[0])) < 1e-4

    def test_exact_bf16_values_unchanged(self, field):
        x = jnp.array([0.0, 1.0, -2.0, 0.5, -0.0078125, 3.0e38], jnp.float32)
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        np.testing.assert_array_equal(self._round(x, field, seed=2), np.asarray(x))

    def test_nonfinite_unchanged(self, field):
        x = jnp.array([jnp.inf, -jnp.inf, jnp.nan], jnp.float32)
        o = self._round(x, field, seed=3)
        assert np.isposinf(o[0]) and np.isneginf(o[1]) and np.isnan(o[2])


def _ups(a):
    """1 where a two-valued array holds its upper value."""
    a = np.asarray(a.astype(jnp.float32))
    lo, hi = np.unique(a)
    return (a == hi).astype(np.float64)


class TestOneBlockPerElement:
    """StochasticAdamW draws one Threefry block an element and rounds
    parameter, mu and nu with disjoint 16-bit fields of it."""

    def _constant_leaf(self, shape=(1000, 1000), lr=1 / 512.0, **kw):
        # every element sees the same fp32 values, none exact in bf16
        params = {"w": jnp.full(shape, 1.0, jnp.bfloat16)}
        grads = {"w": jnp.full(shape, 0.3, jnp.float32)}
        opt = StochasticAdamW(lr, moment_dtype=jnp.bfloat16, **kw)
        return opt, params, grads

    def test_three_roundings_are_independent(self):
        opt, params, grads = self._constant_leaf()
        new_p, st = jax.jit(opt.update)(grads, opt.init(params), params)
        ups = [_ups(new_p["w"]), _ups(st.mu["w"]), _ups(st.nu["w"])]
        for u in ups:
            assert 0.05 < u.mean() < 0.95
            # the counter numbers every dimension: no row or column repeats
            assert not np.array_equal(u[0], u[1])
            assert not np.array_equal(u[:, 0], u[:, 1])
        for a, b in ((0, 1), (0, 2), (1, 2)):
            r = np.corrcoef(ups[a].ravel(), ups[b].ravel())[0, 1]
            assert abs(r) < 0.01, (a, b, r)

    @pytest.mark.parametrize("moment_dtype", [jnp.bfloat16, jnp.float32])
    def test_one_draw_per_rounded_leaf(self, moment_dtype):
        params = {
            "a": jnp.zeros((4, 6), jnp.bfloat16),
            "b": (jnp.zeros((5,), jnp.bfloat16), jnp.zeros((2, 3, 4), jnp.bfloat16)),
            "kept_fp32": jnp.zeros((3,), jnp.float32),
        }
        grads = jax.tree.map(lambda p: jnp.ones(p.shape, jnp.float32), params)
        opt = StochasticAdamW(1e-2, moment_dtype=moment_dtype)
        names = [eqn.primitive.name for eqn in equations(
            jax.make_jaxpr(opt.update)(grads, opt.init(params), params).jaxpr
        )]
        draws = [n for n in names if n in ("threefry2x32", "random_bits")]
        # the fp32 leaf with fp32 moments rounds nothing, but its block is
        # in the jaxpr until XLA drops it: one per leaf, never three
        assert len(draws) == len(jax.tree.leaves(params)), names
        assert "random_split" not in names

    def test_same_state_same_bits_next_count_other_bits(self):
        # lr 0 keeps the parameter; mu32 = 0.1 * g whatever the count, so
        # only the bits tell one count's rounding of it from another's
        opt, params, grads = self._constant_leaf((64, 64), lr=0.0)
        s0 = opt.init(params)
        _, a = opt.update(grads, s0, params)
        _, b = opt.update(grads, s0, params)
        np.testing.assert_array_equal(_ups(a.mu["w"]), _ups(b.mu["w"]))
        _, c = opt.update(grads, s0._replace(count=s0.count + 1), params)
        assert 0.3 < np.mean(_ups(a.mu["w"]) != _ups(c.mu["w"])) < 0.7
        # and another leaf index is another stream
        two = {"v": params["w"], "w": params["w"]}
        _, d = opt.update({"v": grads["w"], "w": grads["w"]}, opt.init(two), two)
        np.testing.assert_array_equal(_ups(d.mu["v"]), _ups(a.mu["w"]))
        assert 0.3 < np.mean(_ups(d.mu["v"]) != _ups(d.mu["w"])) < 0.7

    def test_saved_and_restored_state_continues_the_stream(self):
        opt, params, grads = self._constant_leaf((32, 48), seed=11)
        p1, s1 = opt.update(grads, opt.init(params), params)
        p2, s2 = opt.update(grads, s1, p1)
        saved = jax.tree.map(np.asarray, (p1, s1))
        rp1, rs1 = jax.tree.map(jnp.asarray, saved)
        q2, t2 = opt.update(grads, rs1, rp1)
        for x, y in zip(jax.tree.leaves((p2, s2)), jax.tree.leaves((q2, t2))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    @pytest.mark.parametrize(
        "shape,spec",
        [
            ((64, 40), P("x", None)),
            ((24, 64), P(None, "x")),
            ((16, 6, 10), P("x", None, None)),
            ((6, 10, 16), P(None, None, "x")),
        ],
    )
    def test_sharded_leaf_is_bit_identical_and_needs_no_collective(
        self, devices, shape, spec
    ):
        mesh = Mesh(np.array(devices), ("x",))
        key = jax.random.PRNGKey(5)
        params = {"w": jax.random.normal(key, shape).astype(jnp.bfloat16)}
        grads = {"w": jax.random.normal(jax.random.fold_in(key, 1), shape)}
        opt = StochasticAdamW(1e-2, weight_decay=0.1, moment_dtype=jnp.bfloat16)
        state = opt.init(params)
        want = jax.jit(opt.update)(grads, state, params)

        def shard(x):
            s = spec if x.ndim == len(shape) else P()
            return jax.device_put(x, NamedSharding(mesh, s))

        args = jax.tree.map(shard, (grads, state, params))
        compiled = jax.jit(opt.update).lower(*args).compile()
        assert _collective_census(compiled.as_text()) == {}
        got = compiled(*args)
        assert got[0]["w"].sharding.spec == spec
        for x, y in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
