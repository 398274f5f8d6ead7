"""A held range of a wider router's experts (``MoELayer.num_routed_experts``,
``first_held_expert``): one chip's share of an expert-parallel layer, seen
from that chip alone. The shares add up to the uncut layer, forward and in
the gradients; only the rows that land here are gathered, multiplied and
folded (a count over the jaxpr); routing however uneven drops no pair."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.nn.moe import (
    MoELayer,
    SharedExpertParameters,
    held_experts_apply,
    held_ladder,
)
from d9d_tpu.ops.moe import fold_held, sort_held_pairs, spread_held
from tests.jaxpr_tools import equations

D, F = 32, 16
SHARED = SharedExpertParameters(intermediate_size=16, enable_gate=False)


def layer(routed: int, held: int, first: int, top_k: int, **extra):
    return MoELayer(
        hidden_dim=D, intermediate_dim_grouped=F, num_grouped_experts=held,
        top_k=top_k, router_score_function="sigmoid",
        router_enable_expert_bias=True, routed_scaling=2.0,
        num_routed_experts=routed if held != routed else 0,
        first_held_expert=first, dtype=jnp.float32, param_dtype=jnp.float32,
        **extra,
    )


def whole_layer_params(routed: int, top_k: int, x, shared=None, seed=1):
    params = nn.meta.unbox(jax.jit(
        layer(routed, routed, 0, top_k, shared_expert=shared).init
    )(jax.random.PRNGKey(seed), x)["params"])
    params["router"]["e_score_correction_bias"] = jnp.asarray(
        np.random.RandomState(seed).uniform(-0.3, 0.3, routed), jnp.float32
    )
    return params


def probed(module, probe):
    """A layer's output, what it sows into ``moe_stats``, and the
    gradients of ``(out * probe).sum()`` to the parameters and the input,
    from one traversal: ``((_, (out, stats)), (d_params, d_x))``."""

    def run(p, x):
        out, stats = module.apply({"params": p}, x, mutable=["moe_stats"])
        return (out * probe).sum(), (out, stats)

    return jax.value_and_grad(run, argnums=(0, 1), has_aux=True)


def share_of(params, first: int, held: int):
    cut = dict(params)
    cut["grouped_experts"] = {
        k: v[first:first + held] for k, v in params["grouped_experts"].items()
    }
    return cut


@pytest.mark.parametrize("routed,held,top_k,path", [
    (16, 4, 3, "ladder"), (16, 8, 2, "ladder"), (8, 2, 4, "ladder"),
    (64, 8, 4, "ladder"),
    (16, 4, 3, "plain-products"), (64, 8, 4, "plain-products"),
], ids=lambda v: str(v))
def test_the_shares_add_up_to_the_uncut_layer(
    routed, held, top_k, path, monkeypatch
):
    """Over all shares of a layer the routed parts, with the shared expert
    counted once, sum to the uncut layer's output; so do the gradients to
    the input, the router and (share by share) the experts. By both of a
    held range's paths: these 128 rows take the plain products over the
    held experts (their pairs reach the router's width twice over in
    every case), and the buffer ladder with that rule off."""
    if path == "ladder":
        monkeypatch.setattr("d9d_tpu.nn.moe.HELD_FEW_ROWS_LIMIT", 0)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, D))
    whole = layer(routed, routed, 0, top_k, shared_expert=SHARED)
    params = whole_layer_params(routed, top_k, x, SHARED)
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def uncut_and_shares(params, x):
        results = [probed(whole, probe)(params, x)]
        for first in range(0, routed, held):
            # the shared expert rides with the first share only
            shared = SHARED if first == 0 else None
            part = layer(routed, held, first, top_k, shared_expert=shared)
            p = share_of(params, first, held)
            if shared is None:
                p.pop("shared_expert_module")
            results.append(probed(part, probe)(p, x))
        return results

    # One compiled program a case: un-jitted, a share is three traversals
    # of a compile an operation each, and a ladder's ``lax.switch``
    # compiles its branches anew every call.
    ((_, (want, _)), (want_dp, want_dx)), *shares = jax.jit(
        uncut_and_shares)(params, x)

    total = 0.0
    total_dx = 0.0
    gate_grad = 0.0
    held_rows = 0.0
    for first, ((_, (out, stats)), (dp, dx)) in zip(
            range(0, routed, held), shares):
        total, total_dx = total + out, total_dx + dx
        gate_grad = gate_grad + dp["router"]["gate"]["kernel"]
        held_rows += float(stats["moe_stats"]["rows_held"])
        assert float(stats["moe_stats"]["rows_routed"]) == 128 * top_k
        assert stats["moe_stats"]["tokens_per_expert"].shape == (routed,)
        for name, g in dp["grouped_experts"].items():
            np.testing.assert_allclose(
                g, want_dp["grouped_experts"][name][first:first + held],
                rtol=1e-4, atol=1e-6,
            )
    assert held_rows == 128 * top_k  # every pair lands on exactly one share
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(total_dx, want_dx, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        gate_grad, want_dp["router"]["gate"]["kernel"], rtol=1e-4, atol=1e-6
    )


@pytest.mark.parametrize("skew", ["even", "all_here", "none_here"])
def test_no_pair_is_dropped_however_uneven_the_routing(skew):
    """The buffer is chosen from the count of pairs that land here: the
    snug one for an even router, the chunked fallback when every pair
    does, and a layer nobody routes to gives zeros."""
    n, k, routed, held = 64, 2, 16, 4
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    probs = jnp.asarray(rng.uniform(0.1, 1.0, size=(n, k)), jnp.float32)
    ids = {
        "even": rng.randint(0, routed, size=(n, k)),
        "all_here": rng.randint(0, held, size=(n, k)),
        "none_here": rng.randint(held, routed, size=(n, k)),
    }[skew]
    local = jnp.asarray(np.where(ids < held, ids, held), jnp.int32)
    weights = tuple(
        jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
        for s in ((held, D, F), (held, D, F), (held, F, D))
    )
    buffers, passes = held_ladder(n, k, held, routed)
    # a quarter above the even 32 rows, then a quarter above that, ...;
    # beyond the last, two chunks of 32 tokens x 2
    assert buffers == (40, 56, 64, 80, 104) and passes == 2
    assert skew != "even" or 20 < (ids < held).sum() <= 40

    @jax.jit
    def run(x, probs, weights):
        return held_experts_apply(
            x, local, probs, weights, num_routed=routed, dtype=jnp.float32
        )

    @jax.jit
    def dense(x, probs, weights):
        gate, up, down = weights
        out = jnp.zeros_like(x)
        for j in range(k):
            e = jnp.minimum(local[:, j], held - 1)
            h = jax.nn.silu(jnp.einsum("nd,ndf->nf", x, gate[e])) * jnp.einsum(
                "nd,ndf->nf", x, up[e])
            y = jnp.einsum("nf,nfd->nd", h, down[e]) * probs[:, j:j + 1]
            out = out + jnp.where((local[:, j] < held)[:, None], y, 0.0)
        return out

    np.testing.assert_allclose(
        run(x, probs, weights), dense(x, probs, weights), rtol=1e-4, atol=1e-6
    )
    probe = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    got = jax.jit(jax.grad(
        lambda *a: (run(*a) * probe).sum(), argnums=(0, 1, 2)
    ))(x, probs, weights)
    want = jax.jit(jax.grad(
        lambda *a: (dense(*a) * probe).sum(), argnums=(0, 1, 2)
    ))(x, probs, weights)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
    if skew == "none_here":
        assert not np.asarray(run(x, probs, weights)).any()


def test_spread_and_fold_are_each_others_transpose():
    n, k, held, buf = 24, 3, 4, 48
    rng = np.random.RandomState(1)
    local = jnp.asarray(rng.randint(0, held + 3, size=(n, k)).clip(max=held))
    sort = sort_held_pairs(local, held, buf)
    rows = int(sort.rows_held)
    assert rows == int((local < held).sum()) <= buf
    assert int(sort.group_sizes.sum()) == buf  # padding rides in the last
    x = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(buf, D)), jnp.float32)
    spread = spread_held(x, sort, k)
    assert not np.asarray(spread[rows:]).any()
    np.testing.assert_allclose(
        (spread * y).sum(), (x * fold_held(y, sort, n, k)).sum(), rtol=1e-5
    )
    # the fold is the plain scatter-add of the live rows
    want = jnp.zeros((n, D)).at[sort.token_of_row[:rows]].add(y[:rows])
    np.testing.assert_allclose(fold_held(y, sort, n, k), want, atol=1e-5)


def held_ids(rng, n, k, held, how):
    """``[n, k]`` local ids, ``held`` for a pair that lands elsewhere."""
    if how == "all":
        return rng.randint(0, held, size=(n, k))
    if how == "none":
        return np.full((n, k), held)
    if how == "mixed":
        # a third of the tokens hold every pair, a third none
        ids = rng.randint(0, 3 * held, size=(n, k))
        ids[: n // 3] = rng.randint(0, held, size=(n // 3, k))
        ids[n // 3: 2 * (n // 3)] = held
        return ids.clip(max=held)
    return rng.randint(0, 4 * held, size=(n, k)).clip(max=held)  # a quarter


@pytest.mark.parametrize("n,k,how,buf,dtype", [
    # every top_k the models route by, a quarter of the pairs held
    (40, 1, "quarter", 24, jnp.float32),
    (40, 3, "quarter", 56, jnp.float32),
    (40, 4, "quarter", 72, jnp.float32),
    (40, 8, "quarter", 136, jnp.float32),
    (40, 10, "quarter", 160, jnp.bfloat16),
    # a one-row generate step: all of one token's pairs, and none of them
    (1, 8, "all", 8, jnp.bfloat16),
    (1, 10, "all", 10, jnp.float32),
    (1, 8, "none", 8, jnp.float32),
    # nothing held, every row held
    (48, 4, "none", 80, jnp.float32),
    (48, 4, "all", 192, jnp.bfloat16),
    # tokens with every pair and with no pair beside each other
    (96, 8, "mixed", 400, jnp.float32),
    # the chunked fallback: room for every pair of its tokens
    (32, 4, "quarter", 128, jnp.float32),
    (32, 4, "all", 128, jnp.float32),
    # several blocks of tokens and chunks of slots, the last chunk part
    # padding, runs that straddle chunks
    (600, 3, "quarter", 700, jnp.bfloat16),
    (600, 3, "mixed", 1000, jnp.float32),
    (520, 8, "all", 4160, jnp.bfloat16),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_the_fold_is_the_float32_sum_of_a_tokens_live_rows(
    n, k, how, buf, dtype
):
    """Against the plain scatter-add of the live rows in float32, rounded
    once: one body for every ``top_k``, buffer and dtype."""
    held = 4
    rng = np.random.RandomState(n + k)
    local = jnp.asarray(held_ids(rng, n, k, held, how), jnp.int32)
    here = np.asarray(local) < held
    # rows past the live ones must add nothing whatever they hold
    y = jnp.asarray(rng.normal(size=(buf, D)), dtype)

    @jax.jit
    def folded_and_scattered(y):
        sort = sort_held_pairs(local, held, buf)
        live = (jnp.arange(buf) < sort.rows_held)[:, None]
        want = jnp.zeros((n, D), jnp.float32).at[sort.token_of_row].add(
            jnp.where(live, y.astype(jnp.float32), 0))
        return fold_held(y, sort, n, k), want, sort.rows_held

    got, want, rows = folded_and_scattered(y)
    assert int(rows) == here.sum() <= buf
    assert {"all": rows == n * k, "none": rows == 0}.get(how, 0 < rows < n * k)
    assert got.shape == (n, D) and got.dtype == dtype
    if dtype == jnp.bfloat16:
        np.testing.assert_array_equal(
            got.astype(jnp.float32), want.astype(dtype).astype(jnp.float32))
    else:
        np.testing.assert_allclose(got, want, atol=1e-5)
    assert not np.asarray(got, np.float32)[~here.any(axis=1)].any()


def test_bf16_rows_are_summed_in_float32_and_rounded_once():
    """Eight bf16 rows of a token whose running bf16 sum would lose every
    addend (256 + 1 rounds back to 256): the fold gives the float32 sum,
    263, rounded to bf16 once, 264."""
    n, k, held = 16, 8, 8
    local = jnp.tile(jnp.arange(k, dtype=jnp.int32), (n, 1))
    sort = sort_held_pairs(local, held, n * k)
    ones = jnp.ones((n, k)).at[:, 0].set(256.0)  # by (token, choice)
    y = jnp.zeros((n * k, D), jnp.bfloat16).at[
        jnp.argsort(sort.pair_of_row)].set(
        jnp.broadcast_to(ones.reshape(n * k, 1), (n * k, D)).astype(jnp.bfloat16))
    running = jnp.zeros((n, D), jnp.bfloat16)
    for j in range(k):
        running = running + ones[:, j:j + 1].astype(jnp.bfloat16)
    assert float(running[0, 0]) == 256.0
    got = jax.jit(lambda y: fold_held(y, sort, n, k))(y)
    np.testing.assert_array_equal(got.astype(jnp.float32), 264.0)


@pytest.mark.parametrize("k", [4, 8])
def test_the_fold_is_one_kernel_over_rows_of_their_own_dtype(k):
    """One body whatever ``top_k``: a gather into slot order in the rows'
    own dtype and one Pallas call. No float32 array as long as the buffer
    and as wide as a row, no scatter, no loop of shifted adds outside the
    kernel."""
    n, held, buf = 64, 4, 40 * k
    rng = np.random.RandomState(k)
    local = jnp.asarray(held_ids(rng, n, k, held, "quarter"), jnp.int32)
    sort = sort_held_pairs(local, held, buf)
    y = jnp.asarray(rng.normal(size=(buf, D)), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda y: fold_held(y, sort, n, k))(y).jaxpr
    names = [eqn.primitive.name for eqn in equations(jaxpr)]
    assert names.count("pallas_call") == 1
    assert not [name for name in names if "scatter" in name]
    assert not {"scan", "while"} & {
        eqn.primitive.name for eqn in jaxpr.eqns}
    long_float32 = [
        (eqn.primitive.name, var.aval.shape)
        for eqn in equations(jaxpr) for var in (*eqn.invars, *eqn.outvars)
        if getattr(getattr(var, "aval", None), "dtype", None) == jnp.float32
        and len(var.aval.shape) >= 2 and var.aval.shape[-1] >= D
        and var.aval.shape[-2] >= buf
    ]
    assert long_float32 == []
    gathered = [
        eqn.outvars[0].aval for eqn in equations(jaxpr)
        if eqn.primitive.name == "gather" and eqn.outvars[0].aval.ndim == 2
    ]
    assert [(a.shape[1], a.dtype) for a in gathered] == [(D, jnp.bfloat16)]
    assert buf <= gathered[0].shape[0] < buf + 256  # padded to whole chunks


def wide_rows(jaxpr, rows: int, width: int):
    """Equations anywhere in ``jaxpr`` with an operand or result of
    ``rows`` rows and ``width`` or more columns, by primitive."""
    found = {}
    for eqn in equations(jaxpr):
        for var in (*eqn.invars, *eqn.outvars):
            shape = getattr(getattr(var, "aval", None), "shape", ())
            if len(shape) >= 2 and shape[-2] == rows and shape[-1] >= width:
                found[eqn.primitive.name] = found.get(eqn.primitive.name, 0) + 1
    return found


def grouped_matmuls(found: dict) -> int:
    return sum(n for name, n in found.items() if name.startswith("ragged_dot"))


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_no_row_buffer_is_as_long_as_all_routed_pairs(what, monkeypatch):
    """With a range held no gather, matmul or any other op under the layer
    touches an ``N x k``-row array of hidden or expert width, forward or
    backward, in any branch of the ladder; without one the local path
    gathers exactly that."""
    # the ladder is for calls past a decode step's rows (PR 41: up to
    # HELD_FEW_ROWS_LIMIT a held range takes the plain products): held
    # to it here at these 64 rows, which keep the buffers small
    monkeypatch.setattr("d9d_tpu.nn.moe.HELD_FEW_ROWS_LIMIT", 0)
    n, k, routed, held = 64, 4, 16, 2
    x = jax.random.normal(jax.random.PRNGKey(0), (1, n, D))
    params = whole_layer_params(routed, k, x)

    def program(module, p, x=x):
        def fn(p, x):
            return module.apply({"params": p}, x).sum()

        return jax.make_jaxpr(jax.grad(fn) if what == "gradient" else fn)(
            p, x).jaxpr

    # (at four times the rows: a call of 128 or fewer meets every expert
    # through plain products and moves no row at all)
    many = jnp.tile(x, (1, 4, 1))
    uncut = wide_rows(
        program(layer(routed, routed, 0, k), params, many), 4 * n * k, F)
    assert grouped_matmuls(uncut) >= 2 and uncut.get("gather", 0) >= 1
    cut = wide_rows(
        program(layer(routed, held, 4, k), share_of(params, 4, held)),
        n * k, F,
    )
    assert cut == {}
    # and the rows it does move are the ladder's
    buffers, passes = held_ladder(n, k, held, routed)
    assert buffers == (40, 56, 64, 80, 104) and n // passes * k == 64
    jaxpr = program(layer(routed, held, 4, k), share_of(params, 4, held))
    for rows in (*buffers, n // passes * k):
        assert grouped_matmuls(wide_rows(jaxpr, rows, F)) >= 2, rows


def test_without_a_range_the_layer_is_the_layer_it_was():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, D))
    plain = layer(8, 8, 0, 2)
    same = MoELayer(
        hidden_dim=D, intermediate_dim_grouped=F, num_grouped_experts=8,
        top_k=2, router_score_function="sigmoid",
        router_enable_expert_bias=True, routed_scaling=2.0,
        num_routed_experts=8, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    params = plain.init(jax.random.PRNGKey(1), x)
    assert str(jax.make_jaxpr(lambda p: plain.apply(p, x))(params)) == str(
        jax.make_jaxpr(lambda p: same.apply(p, x))(params))
    assert "rows_held" not in plain.apply(
        params, x, mutable=["moe_stats"])[1]["moe_stats"]


@pytest.mark.parametrize("kwargs,why", [
    (dict(routed=17, held=16, first=2), "lie inside"),
    (dict(routed=16, held=4, first=13), "lie inside"),
    (dict(routed=16, held=4, first=0, ep_axes=("ep",)), "ep_axes"),
    # a skip is the router's last id: the held range may not reach it
    (dict(routed=17, held=16, first=1, router_skip=True), "skip"),
])
def test_a_range_that_is_no_share_is_refused(kwargs, why):
    x = jnp.zeros((1, 8, D))
    module = layer(top_k=2, **kwargs)
    with pytest.raises(ValueError, match=why):
        module.init(jax.random.PRNGKey(0), x)


def test_a_range_need_not_divide_the_routers_width():
    """16 experts held of a router 17 wide, the last id a skip (ZAYA's):
    the range lies inside the width, which is all a range has to do. The
    layer counts the skip's rows from the last entry of
    ``tokens_per_expert``, and what is neither held nor skipped is
    nothing here."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, D))
    module = layer(routed=17, held=16, first=0, top_k=1, router_skip=True)
    params = jax.jit(module.init)(jax.random.PRNGKey(1), x)
    _, sown = jax.jit(lambda p, x: module.apply(
        p, x, mutable=["moe_stats"]))({"params": params["params"]}, x)
    stats = sown["moe_stats"]
    per_expert = np.asarray(stats["tokens_per_expert"])
    assert per_expert.shape == (17,) and per_expert.sum() == 48
    assert float(stats["rows_skipped"]) == per_expert[-1]
    assert float(stats["rows_routed"]) == 48
    assert float(stats["rows_held"]) == 48 - per_expert[-1]


@pytest.mark.parametrize("n,k,held,routed,want", [
    (1, 10, 18, 72, ((10,), 1)),  # a one-row generate step at top-10
    (1, 4, 4, 16, ((4,), 1)),
    (128, 10, 18, 72, ((400, 504, 632, 784, 984), 2)),
    # top-1 over 16 held of 17 routed: a one-row step, a batcher's two rows
    (1, 1, 16, 17, ((1,), 1)),
    (2, 1, 16, 17, ((2,), 1)),
])
def test_one_tokens_pairs_never_overflow_the_last_rung(n, k, held, routed, want):
    """A call so small that one token's own pairs pass the last one-pass
    buffer cannot be cut into chunks of tokens: it gets one buffer of all
    its pairs (the ladder used to find no chunking and raise)."""
    assert held_ladder(n, k, held, routed) == want
    buffers, passes = want
    assert n // passes * k <= max(buffers[-1], n * k)
