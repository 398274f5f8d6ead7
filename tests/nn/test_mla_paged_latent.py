"""A paged latent layer's one-token step under the ``pallas`` decode
backend (interpret mode here): the append is one ``paged_append`` call and
the absorbed attend reads the pools through the page table
(``pallas_decode.latent_decode_attention``); every other case keeps the
scatter and the gathered per-row view. Which way a step went is read off
its jaxpr (tests/jaxpr_tools.py), never off a printed program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.nn.attention import MultiHeadLatentAttention
from d9d_tpu.nn.decode_flags import (
    PAGE_TABLE_LEAF,
    PAGED_SCALE_SUFFIX,
    continuation_chunk,
    ring_caches,
)
from d9d_tpu.nn.sdpa import build_sdpa_backend
from d9d_tpu.telemetry import get_telemetry
from tests import jaxpr_tools

B, PS, N_PAGES, HIDDEN, RANK, D_ROPE = 5, 8, 4, 32, 32, 8
STARTS = [0, 7, 8, 0, PS * N_PAGES - 1]  # row 3 idles on the garbage page
LATENT, ROPE = "cached_latent", "cached_rope_key"


def _module(absorbed=True, dtype=jnp.float32):
    return MultiHeadLatentAttention(
        hidden_size=HIDDEN, num_heads=4, qk_nope_head_dim=16,
        qk_rope_head_dim=D_ROPE, v_head_dim=16, kv_lora_rank=RANK,
        q_lora_rank=24, sdpa=build_sdpa_backend(),
        decode_max_length=PS * N_PAGES, decode_absorbed=absorbed, dtype=dtype,
    )


def _inputs(t=1):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, t, HIDDEN), jnp.float32)
    cos = jnp.asarray(np.cos(rng.randn(B, t, D_ROPE // 2)), jnp.float32)
    sin = jnp.asarray(np.sin(rng.randn(B, t, D_ROPE // 2)), jnp.float32)
    return x, cos, sin


def _paged_step(monkeypatch, backend, *, absorbed=True, quant=False):
    """One step of a module on a paged cache seeded as the serving loop
    seeds it (shapes from an ``init`` inside ``ring_caches``, the leaves
    made pools, a table beside them), under ``backend``. Returns the
    output, the cache after the step, the step's jaxpr and the leaf shapes
    the module declared."""
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", backend)
    module, (x, cos, sin) = _module(absorbed), _inputs()
    with ring_caches(PS):
        declared = jax.eval_shape(
            lambda: module.init(jax.random.PRNGKey(0), x, cos, sin))["cache"]
    params = module.init(jax.random.PRNGKey(0), x, cos, sin)["params"]
    pool_n = B * N_PAGES + 1
    rng = np.random.RandomState(2)
    draw = rng.randn(pool_n, PS, 128)
    cache = {"cache_index": jnp.asarray(STARTS, jnp.int32)}
    for name, width in ((LATENT, RANK), (ROPE, D_ROPE)):
        pool = np.zeros((pool_n, PS, declared[name].shape[-1]), np.float32)
        pool[..., :width] = draw[..., :width]
        cache[name] = jnp.asarray(pool)
        if quant:
            cache[name] = jnp.asarray(np.round(pool * 20), jnp.int8)
            cache[name + PAGED_SCALE_SUFFIX] = jnp.full(
                (pool_n, PS), 0.05, jnp.float32)
    table = 1 + np.random.RandomState(3).permutation(pool_n - 1).reshape(
        B, N_PAGES)
    table[3] = 0
    cache[PAGE_TABLE_LEAF] = jnp.asarray(table, jnp.int32)

    def step(p, c):
        return module.apply({"params": p, "cache": c}, x, cos, sin,
                            mutable=["cache"])

    traced = jax.jit(step).trace(params, cache)  # one trace for both
    out, new = traced.lower().compile()(params, cache)
    shapes = {k: v.shape for k, v in declared.items()}
    return out, new["cache"], traced.jaxpr, shapes


def _pool_gathers(jaxpr) -> int:
    """Gathers whose operand is a pool: ``[pool pages, PS, .]``."""
    pool_n = B * N_PAGES + 1
    return jaxpr_tools.count(
        jaxpr, lambda e: e.primitive.name == "gather"
        and e.invars[0].aval.shape[:2] == (pool_n, PS))


def _pallas_calls(jaxpr) -> list[str]:
    return [e.params["name"] for e in jaxpr_tools.equations(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]


def _latent_gauges():
    gauges = get_telemetry().registry.gauges
    return (gauges["decode/latent/paged_layers"].value,
            gauges["decode/latent/gathered_layers"].value)


@pytest.fixture
def fresh_paths(monkeypatch):
    """The gauges count the layers of this test alone."""
    from d9d_tpu.nn import attention

    monkeypatch.setattr(attention, "_LATENT_DECODE_PATHS", {})


def test_the_step_reads_its_pools_through_the_table(monkeypatch, fresh_paths):
    out, cache, jaxpr, shapes = _paged_step(monkeypatch, "pallas")
    # the leaf the kernel cuts pages from is a whole lane tile wide
    assert shapes[ROPE] == (B, PS * N_PAGES, 128)
    assert shapes[LATENT] == (B, PS * N_PAGES, RANK)
    assert _pool_gathers(jaxpr.jaxpr) == 0
    assert jaxpr_tools.count(
        jaxpr.jaxpr, lambda e: e.primitive.name.startswith("scatter")) == 0
    assert sorted(_pallas_calls(jaxpr)) == ["latent_decode_p4", "paged_append"]
    assert _latent_gauges() == (1.0, 0.0)

    want, eager_cache, eager_jaxpr, eager_shapes = _paged_step(
        monkeypatch, "eager")
    assert eager_shapes[ROPE] == (B, PS * N_PAGES, D_ROPE)
    assert _pool_gathers(eager_jaxpr.jaxpr) == 2
    assert not _pallas_calls(eager_jaxpr)
    assert _latent_gauges() == (0.0, 1.0)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    oracle, *_ = _paged_step(monkeypatch, "eager", absorbed=False)
    np.testing.assert_allclose(out, oracle, rtol=1e-4, atol=1e-5)

    # the pools after the step, bit for bit; the fill stays zeros
    np.testing.assert_array_equal(cache[LATENT], eager_cache[LATENT])
    np.testing.assert_array_equal(
        cache[ROPE][..., :D_ROPE], eager_cache[ROPE])
    np.testing.assert_array_equal(cache[ROPE][..., D_ROPE:], 0)
    np.testing.assert_array_equal(
        cache["cache_index"], eager_cache["cache_index"])


@pytest.mark.parametrize("case", ["int8-pools", "oracle"])
def test_the_other_paged_cases_keep_the_gathered_view(
        monkeypatch, fresh_paths, case):
    """Under the ``pallas`` backend too: int8 pools (their scales have no
    reader in the kernel) and ``decode_absorbed=False``."""
    kwargs = {"int8-pools": dict(quant=True), "oracle": dict(absorbed=False)}
    out, _, jaxpr, shapes = _paged_step(monkeypatch, "pallas", **kwargs[case])
    assert not _pallas_calls(jaxpr)
    # the two pools, and an int8 pool's two scale pools
    assert _pool_gathers(jaxpr.jaxpr) == (4 if case == "int8-pools" else 2)
    assert _latent_gauges() == (0.0, 1.0)
    want, *_ = _paged_step(monkeypatch, "eager", **kwargs[case])
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_a_pool_seeded_under_another_backend_is_gathered(
        monkeypatch, fresh_paths):
    """Rotary key rows of 8 numbers, as the ``eager`` backend's init
    declares them, are no pool the kernel can cut pages from: the step
    follows the leaf it is handed."""
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "eager")
    module, (x, cos, sin) = _module(), _inputs()
    params = module.init(jax.random.PRNGKey(0), x, cos, sin)["params"]
    pool_n = B * N_PAGES + 1
    cache = {
        "cache_index": jnp.asarray(STARTS, jnp.int32),
        LATENT: jnp.zeros((pool_n, PS, RANK)),
        ROPE: jnp.zeros((pool_n, PS, D_ROPE)),
        PAGE_TABLE_LEAF: jnp.ones((B, N_PAGES), jnp.int32),
    }
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "pallas")
    jaxpr = jax.make_jaxpr(lambda p, c: module.apply(
        {"params": p, "cache": c}, x, cos, sin, mutable=["cache"]))(
            params, cache)
    assert not _pallas_calls(jaxpr)
    assert _pool_gathers(jaxpr.jaxpr) == 2
    assert _latent_gauges() == (0.0, 1.0)


@pytest.mark.parametrize("t", [1, 3])
def test_a_contiguous_cache_keeps_its_path(monkeypatch, t):
    """``generate``'s cache under the ``pallas`` backend: rows as they
    are, no call, for a single-token step and a continuation chunk."""
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "pallas")
    module, (x, cos, sin) = _module(), _inputs(t)
    variables = module.init(jax.random.PRNGKey(0), x[:, :1], cos[:, :1],
                            sin[:, :1])
    assert variables["cache"][ROPE].shape == (B, PS * N_PAGES, D_ROPE)

    def step(p, c):
        with continuation_chunk():
            return module.apply({"params": p, "cache": c}, x, cos, sin,
                                mutable=["cache"])

    jaxpr = jax.make_jaxpr(step)(variables["params"], variables["cache"])
    assert not _pallas_calls(jaxpr)
    assert jaxpr_tools.count(
        jaxpr.jaxpr, lambda e: e.primitive.name == "gather") == 0
