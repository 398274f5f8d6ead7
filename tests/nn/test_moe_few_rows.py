"""The local expert path in a call of few rows (``ops/moe.py
all_experts_swiglu``): plain products over every expert against the grouped
form (sort, permute, ``ragged_dot``, combine) on the same weights, ids and
probabilities; which static shapes take it (``few_rows_touch_all_experts``);
and what its program holds: no ``ragged_dot``, no copy of the weights, no
row moved, every product under the scopes a trace's reader takes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.core import MeshParameters
from d9d_tpu.nn.moe import MoELayer, grouped_swiglu_apply
from d9d_tpu.ops.moe import (
    FEW_ROWS_LIMIT,
    HELD_FEW_ROWS_LIMIT,
    all_experts_swiglu,
    few_rows_touch_all_experts,
    permute_tokens,
    sort_tokens_by_expert,
    unpermute_combine,
)
from tests.jaxpr_tools import scoped_equations

D, F = 48, 24  # unlike any E, K or N below: a width names its array


def grouped_form(x, ids, probs, gate_w, up_w, down_w, dtype):
    """What ``MoELayer._forward_local`` runs outside the criterion."""
    sort = sort_tokens_by_expert(ids, gate_w.shape[0])
    rows, row_probs = permute_tokens(x, probs, sort)
    y = grouped_swiglu_apply(
        rows, row_probs, sort.group_sizes, gate_w, up_w, down_w, dtype
    )
    return unpermute_combine(y, sort, x.shape[0]).astype(x.dtype)


def drawn(n, k, e, seed=0, dtype=jnp.float32):
    """Rows, a softmax router's top-k and three weights, from a seed."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, D), dtype)
    probs, ids = jax.lax.top_k(
        jax.nn.softmax(jax.random.normal(ks[1], (n, e))), k
    )
    weights = tuple(
        (jax.random.normal(key, shape) * 0.2).astype(dtype)
        for key, shape in zip(ks[2:], ((e, D, F), (e, D, F), (e, F, D)))
    )
    return x, ids.astype(jnp.int32), probs, weights


# -- the criterion --------------------------------------------------------------


@pytest.mark.parametrize("n,k,e,taken", [
    (64, 8, 128, True),  # the Qwen3 serving cell's step
    (64, 4, 64, True),  # the GLM serving cell's
    (FEW_ROWS_LIMIT, 8, 128, True),  # the last row count that is few
    (FEW_ROWS_LIMIT + 1, 8, 128, False),
    (32, 8, 128, True),  # N * K == 2 * E: 86 % of the experts expected
    (31, 8, 128, False),
    (1, 8, 128, False),  # a one-row generate step reads 8 experts of 128
    (8, 8, 128, False),
    (16_384, 8, 128, False),  # a training call
    (16_384, 6, 64, False),
    (8, 2, 8, True),
    (7, 2, 8, False),
], ids=lambda v: str(v))
def test_the_criterion_is_a_function_of_static_shapes(n, k, e, taken):
    assert few_rows_touch_all_experts(n, k, e) is taken


# -- the two forms agree --------------------------------------------------------


def outputs_and_gradients(form, x, ids, probs, weights, dtype):
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def fn(x, probs, weights):
        out = form(x, ids, probs, *weights, dtype)
        return (out.astype(jnp.float32) * probe).sum(), out

    (_, out), grads = jax.jit(
        jax.value_and_grad(fn, argnums=(0, 1, 2), has_aux=True)
    )(x, probs, weights)
    return out, grads


@pytest.mark.parametrize("n,k,e", [
    (64, 8, 128), (64, 4, 64),  # inside
    (FEW_ROWS_LIMIT, 8, 128), (32, 8, 128),  # on the edges
    (FEW_ROWS_LIMIT + 1, 8, 128), (31, 8, 128), (4, 2, 16),  # outside
], ids=lambda v: str(v))
def test_float32_outputs_and_gradients_match_the_grouped_form(n, k, e):
    """Whatever the criterion says of a shape, the two forms are the same
    function: outputs and the gradients of the rows, the three weights and
    the probabilities."""
    x, ids, probs, weights = drawn(n, k, e)
    want, want_grads = outputs_and_gradients(
        grouped_form, x, ids, probs, weights, jnp.float32)
    got, got_grads = outputs_and_gradients(
        all_experts_swiglu, x, ids, probs, weights, jnp.float32)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n,k,e", [(64, 8, 128), (64, 4, 64)],
                         ids=lambda v: str(v))
def test_bf16_forms_are_as_close_to_float32_as_each_other(n, k, e):
    x, ids, probs, weights = drawn(n, k, e, seed=3)
    def run(form, dtype):
        return jax.jit(lambda x, weights: form(
            x.astype(dtype), ids, probs,
            *(w.astype(dtype) for w in weights), dtype,
        ))(x, weights)

    exact = run(grouped_form, jnp.float32)

    def distance(form):
        out = run(form, jnp.bfloat16)
        gap = out.astype(jnp.float32) - exact
        return float(jnp.sqrt((gap ** 2).mean() / (exact ** 2).mean()))

    plain, grouped = distance(all_experts_swiglu), distance(grouped_form)
    assert plain < 0.02 and grouped < 0.02, (plain, grouped)
    assert plain < 1.5 * grouped, (plain, grouped)


def chosen_by_hand(case: str, n=64, k=4, e=16):
    """Routing a seeded router would not draw."""
    x, ids, probs, (gate_w, up_w, down_w) = drawn(n, k, e, seed=5)
    if case == "an_expert_nobody_chose":
        # the last expert is never picked, and what it makes of a row
        # overflows float32: selected away it adds an exact zero, where a
        # product with a zero weight would add a NaN
        ids = ids % (e - 1)
        ids = (ids + jnp.arange(k)[None, :]) % (e - 1)  # distinct per row
        gate_w = gate_w.at[e - 1].set(3e18)
        up_w = up_w.at[e - 1].set(3e18)
    elif case == "a_heavy_expert":
        # every row's first choice is expert 3, with most of its weight
        rest = jnp.where(ids[:, 1:] == 3, (ids[:, 1:] + 5) % e, ids[:, 1:])
        ids = jnp.concatenate([jnp.full((n, 1), 3, jnp.int32), rest], axis=1)
        probs = probs.at[:, 0].set(0.9)
    elif case == "an_expert_drawn_twice_by_a_row":
        # no router draws this; the forms still agree: both probabilities
        ids = ids.at[:, 1].set(ids[:, 0])
    return x, ids, probs, (gate_w, up_w, down_w)


@pytest.mark.parametrize("case", [
    "an_expert_nobody_chose", "a_heavy_expert",
    "an_expert_drawn_twice_by_a_row",
])
def test_uneven_routing_matches_the_grouped_form(case):
    x, ids, probs, weights = chosen_by_hand(case)
    want, want_grads = outputs_and_gradients(
        grouped_form, x, ids, probs, weights, jnp.float32)
    got, got_grads = outputs_and_gradients(
        all_experts_swiglu, x, ids, probs, weights, jnp.float32)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    if case == "an_expert_nobody_chose":
        # the grouped form never reads that expert; here its weights get
        # an exact zero and nothing it overflowed leaks into the others
        for grad in got_grads[2]:
            assert not np.asarray(grad[-1]).any()
        got_grads = (*got_grads[:2], tuple(g[:-1] for g in got_grads[2]))
        want_grads = (*want_grads[:2], tuple(g[:-1] for g in want_grads[2]))
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


# -- what the program holds -----------------------------------------------------


def layer_program(rows, k, e, **extra):
    layer = MoELayer(
        hidden_dim=D, intermediate_dim_grouped=F, num_grouped_experts=e,
        top_k=k, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **extra,
    )
    x = jax.ShapeDtypeStruct((rows, 1, D), jnp.bfloat16)
    params = jax.eval_shape(
        lambda x: layer.init(jax.random.PRNGKey(0), x)["params"], x)
    return list(scoped_equations(
        jax.make_jaxpr(lambda p, x: layer.apply({"params": p}, x))(
            params, x).jaxpr
    ))


def shapes(eqn):
    return [
        tuple(var.aval.shape) for var in (*eqn.invars, *eqn.outvars)
        if hasattr(getattr(var, "aval", None), "shape")
    ]


@pytest.mark.parametrize("rows,k,e", [(64, 8, 128), (64, 4, 64)],
                         ids=["qwen3-step", "glm-step"])
def test_a_decode_step_holds_plain_scoped_products_and_moves_no_row(rows, k, e):
    program = layer_program(rows, k, e)
    names = {eqn.primitive.name for eqn, _ in program}
    assert not any(name.startswith("ragged_dot") for name in names), names
    for eqn, scope in program:
        name = eqn.primitive.name
        if name == "concatenate":
            # no gate|up copy, nor any other array as large as a weight
            assert all(np.prod(s) < e * D * F for s in shapes(eqn)), scope
        if name in ("gather", "scatter", "scatter-add", "sort"):
            assert not any(
                s[-1:] in ((D,), (F,), (2 * F,)) for s in shapes(eqn)
            ), (name, scope)
    products = [
        scope for eqn, scope in program
        if eqn.primitive.name == "dot_general" and "moe/router" not in scope
    ]
    assert len(products) == 3, products
    scoped = re.compile(r"moe/experts/(gate_up|down)/all_experts")
    assert [scoped.search(s)[1] for s in products] == [
        "gate_up", "gate_up", "down"], products
    assert not any(re.search(r"moe/(permute|combine)", s) for _, s in program)


HELD = dict(
    num_routed_experts=64, first_held_expert=8,
    router_score_function="sigmoid",
)


@pytest.mark.parametrize("rows", [32, HELD_FEW_ROWS_LIMIT])
def test_a_decode_step_through_a_held_range_takes_the_plain_products(rows):
    """Since PR 41: up to the chip's ridge in rows, once the pairs would
    reach the router's width twice over, every held expert's weights go
    once through the plain products whatever the routing; the buffer ladder
    and its grouped matmuls are for the calls beside those."""
    program = layer_program(rows, 4, 8, **HELD)
    names = {eqn.primitive.name for eqn, _ in program}
    assert not any(name.startswith("ragged_dot") for name in names), names
    assert "cond" not in names  # no ladder
    scoped = re.compile(r"moe/experts/(gate_up|down)/all_experts")
    assert [
        scoped.search(s)[1] for eqn, s in program
        if eqn.primitive.name == "dot_general" and "moe/router" not in s
    ] == ["gate_up", "gate_up", "down"]


@pytest.fixture(scope="module")
def ep_axes():
    ctx = MeshParameters(dp_shard=4, ep_shard=4).build(jax.devices()[:4])
    return tuple(ctx.ep_shard_axes)


@pytest.mark.parametrize("flow", [
    "local-16384-rows", "local-one-row", "held-range",
    "held-range-few-pairs", "ep-dropless", "ep-capacity",
])
def test_every_other_flow_keeps_the_grouped_matmuls(flow, ep_axes):
    """A training call and a one-row step bypass by shape, and so does a
    held range past ``HELD_FEW_ROWS_LIMIT`` rows or with too few pairs for
    the router's width; the two EP flows never reach the local path, at 64
    rows either. (Their jaxpr text was compared with the parent commit's
    once, by digest: PR 36's entry in CHANGES.md.)"""
    rows, k, e, extra = {
        "local-16384-rows": (16_384, 8, 128, {}),
        "local-one-row": (1, 8, 128, {}),
        "held-range": (HELD_FEW_ROWS_LIMIT + 1, 4, 8, HELD),
        "held-range-few-pairs": (16, 4, 8, HELD),
        "ep-dropless": (64, 2, 16, dict(ep_axes=ep_axes)),
        "ep-capacity": (64, 2, 16, dict(
            ep_axes=ep_axes, ep_capacity_factor=1.25)),
    }[flow]
    program = layer_program(rows, k, e, **extra)
    assert sum(
        eqn.primitive.name.startswith("ragged_dot") for eqn, _ in program
    ) >= 2
    assert not any("all_experts" in scope for _, scope in program)
