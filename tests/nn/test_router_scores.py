"""TopKRouter's score functions: the DeepSeek-V3 line's sigmoid
``noaux_tc`` routing against a NumPy transcription of the published
code, with a non-zero selection bias; and the softmax routers of the
Qwen3 and DeepSeek-V2 presets, which must trace to the program they had
before the score function became a field."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from d9d_tpu.nn import logical_axes as la
from d9d_tpu.nn.moe import TopKRouter

E, K, D = 16, 4, 32


def _sigmoid_router(renormalize=True, **kw):
    return TopKRouter(
        dim=D, num_experts=E, top_k=K, score_function="sigmoid",
        enable_expert_bias=True, renormalize_probabilities=renormalize,
        dtype=jnp.float32, **kw,
    )


def _init(router, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (3, 7, D))
    params = nn.unbox(router.init(jax.random.PRNGKey(1), x))["params"]
    return x, params


def _noaux_tc(x, kernel, bias, renormalize):
    """``DeepseekV3TopkRouter`` with ``n_group`` 1, in NumPy float64 then
    compared in float32: scores = sigmoid(x W); choice by scores + bias;
    weights are the UNBIASED scores of the chosen."""
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64) @ kernel)))
    chosen = np.argsort(-(scores + bias), axis=-1, kind="stable")[..., :K]
    weights = np.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return chosen, weights


@pytest.mark.parametrize("renormalize", [True, False])
def test_sigmoid_router_matches_the_published_routing(renormalize):
    router = _sigmoid_router(renormalize)
    x, params = _init(router)
    assert params["e_score_correction_bias"].dtype == jnp.float32
    assert not np.asarray(params["e_score_correction_bias"]).any()  # zero at init
    bias = np.random.RandomState(2).uniform(-0.4, 0.4, E)
    params["e_score_correction_bias"] = jnp.asarray(bias, jnp.float32)
    ids, weights = router.apply({"params": params}, x)
    want_ids, want_w = _noaux_tc(
        x, np.asarray(params["gate"]["kernel"], np.float64), bias, renormalize
    )
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    order = np.argsort(np.asarray(ids), -1)
    want_order = np.argsort(want_ids, -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weights), order, -1),
        np.take_along_axis(want_w, want_order, -1), rtol=2e-6,
    )
    if renormalize:
        np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)


def test_the_bias_changes_who_is_chosen_and_never_their_weights():
    router = _sigmoid_router(renormalize=False)
    x, params = _init(router, seed=3)
    ids0, w0 = router.apply({"params": params}, x)
    biased = dict(params, e_score_correction_bias=jnp.asarray(
        np.random.RandomState(4).uniform(-0.4, 0.4, E), jnp.float32
    ))
    ids1, w1 = router.apply({"params": biased}, x)
    ids0, ids1 = np.asarray(ids0), np.asarray(ids1)
    changed = (np.sort(ids0, -1) != np.sort(ids1, -1)).any(-1)
    assert changed.any() and not changed.all()
    # an expert chosen both times carries the same (unbiased) weight
    scores = np.asarray(jax.nn.sigmoid(x @ params["gate"]["kernel"]))
    for got_ids, got_w in ((ids0, w0), (ids1, w1)):
        np.testing.assert_allclose(
            np.asarray(got_w), np.take_along_axis(scores, got_ids, -1),
            rtol=1e-6,
        )


def test_the_bias_is_outside_the_gradient():
    router = _sigmoid_router()
    x, params = _init(router, seed=5)
    params["e_score_correction_bias"] = jnp.full((E,), 0.1, jnp.float32)
    grads = jax.grad(
        lambda p: router.apply({"params": p}, x)[1][..., 0].sum()
    )(params)
    assert not np.asarray(grads["e_score_correction_bias"]).any()
    assert np.asarray(grads["gate"]["kernel"]).any()


def test_an_unknown_score_function_is_refused():
    router = TopKRouter(dim=D, num_experts=E, top_k=K, score_function="tanh")
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        router.init(jax.random.PRNGKey(0), jnp.zeros((1, 2, D)))


class _ParentRouter(nn.Module):
    """``TopKRouter.__call__`` as the parent commit had it (softmax only,
    no bias requested), kept here to compare traced programs with."""

    num_experts: int
    top_k: int
    renormalize_probabilities: bool
    n_group: int
    topk_group: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, hidden):
        scores = nn.Dense(
            self.num_experts, use_bias=False, name="gate", dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), (la.EMBED, None)
            ),
        )(hidden)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        sel = probs
        if self.n_group > 1:
            per = self.num_experts // self.n_group
            group_score = sel.reshape(
                *sel.shape[:-1], self.n_group, per
            ).max(axis=-1)
            _, top_g = lax.top_k(group_score, self.topk_group)
            gmask = (
                jax.nn.one_hot(top_g, self.n_group, dtype=jnp.bool_)
                .any(axis=-2)
            )
            emask = jnp.repeat(gmask, per, axis=-1)
            sel = jnp.where(emask, sel, -jnp.inf)
        _, selected_idx = lax.top_k(sel, self.top_k)
        selected_probs = jnp.take_along_axis(probs, selected_idx, axis=-1)
        if self.renormalize_probabilities:
            selected_probs = selected_probs / (
                selected_probs.sum(axis=-1, keepdims=True) + 1e-20
            )
        return selected_idx.astype(jnp.int32), selected_probs


@pytest.mark.parametrize("renormalize,n_group,topk_group", [
    (True, 1, 1),    # Qwen3-30B-A3B
    (False, 1, 1),   # DeepSeek-V2-Lite
    (False, 8, 3),   # DeepSeek-V2 (group_limited_greedy)
], ids=["qwen3", "deepseek-v2-lite", "deepseek-v2"])
def test_softmax_presets_trace_to_the_program_they_had(
    renormalize, n_group, topk_group
):
    kw = dict(num_experts=E, top_k=K, renormalize_probabilities=renormalize,
              n_group=n_group, topk_group=topk_group)
    now = TopKRouter(dim=D, **kw)  # the presets leave score_function alone
    before = _ParentRouter(**kw)
    x = jnp.zeros((2, 5, D), jnp.bfloat16)
    params = nn.unbox(now.init(jax.random.PRNGKey(0), x))
    assert set(params["params"]) == {"gate"}  # no bias leaf unless asked for
    traced_now = jax.make_jaxpr(lambda p, h: now.apply(p, h))(params, x)
    traced_before = jax.make_jaxpr(lambda p, h: before.apply(p, h))(params, x)
    assert str(traced_now) == str(traced_before)
