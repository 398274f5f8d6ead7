"""What a decode step of a paged model must not pay for, held by the
program's shape on the CPU rig (PERF.md section 6, PRs 41 and 55, has
what it cost on the chip): under the eager backend and for int8 pools the
cache append is a scatter of whole rows into the pool seen flat, equal to
the indexed form it replaces; under the pallas backend one
``paged_append`` call writes both pools and the step holds no scatter on
a pool."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.nn.attention import GroupedQueryAttention, _scatter_head_rows
from d9d_tpu.ops.attention.eager import eager_sdpa
from tests import jaxpr_tools
from tests.nn.test_paged_cache_modules import B, DML, _paged_cache, _rope


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_the_flat_row_scatter_is_the_indexed_one(dtype):
    rng = np.random.RandomState(0)
    p, h, ps, d, b = 7, 3, 4, 5, 6
    pool = jnp.asarray(rng.randint(-9, 9, (p, h, ps, d)), dtype)
    rows = jnp.asarray(rng.randint(-9, 9, (b, h, d)), dtype)
    page = jnp.asarray(rng.permutation(p)[:b], jnp.int32)  # distinct rows
    off = jnp.asarray(rng.randint(0, ps, b), jnp.int32)
    want = pool.at[page, :, off, :].set(rows)
    np.testing.assert_array_equal(_scatter_head_rows(pool, page, off, rows), want)
    # one scatter, of rows, with the pool's leading dimensions folded
    eqns = jax.make_jaxpr(_scatter_head_rows)(pool, page, off, rows).eqns
    (scatter,) = [e for e in eqns if e.primitive.name == "scatter"]
    assert scatter.invars[0].aval.shape == (p * h * ps, d)
    assert scatter.invars[2].aval.shape == (b * h, d)


@pytest.mark.parametrize("backend,quant,scatters", [
    ("pallas", False, 0), ("eager", False, 2),
    # int8 pools keep the scatter for rows and scales under both backends
    ("pallas", True, 2), ("eager", True, 2),
])
def test_a_paged_step_scatters_into_a_pool_only_off_the_kernel_path(
        monkeypatch, backend, quant, scatters):
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", backend)
    blk = GroupedQueryAttention(
        hidden_size=32, num_heads=4, num_kv_heads=2, head_dim=8,
        sdpa=eager_sdpa, dtype=jnp.float32, decode_max_length=DML,
    )
    x = jnp.zeros((B, 1, 32))
    cos, sin = _rope(B, 0, 1, 8)
    variables = jax.eval_shape(blk.init, jax.random.PRNGKey(1), x, cos, sin)
    zero = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype), variables["cache"])
    cache = _paged_cache(zero, quant=quant)
    pool = cache["cached_key"]
    step = jax.make_jaxpr(lambda p, c: blk.apply(
        {"params": p, "cache": c}, x, cos, sin, mutable=["cache"]))
    jaxpr = step(variables["params"], cache).jaxpr

    def on_a_pool(eqn):  # the pool seen flat, rows wide
        return (eqn.primitive.name == "scatter"
                and eqn.invars[0].aval.shape == (pool.size // 8, 8))

    assert jaxpr_tools.count(jaxpr, on_a_pool) == scatters
    appends = [
        scope for eqn, scope in jaxpr_tools.scoped_equations(jaxpr)
        if eqn.primitive.name == "pallas_call"
        and eqn.params["name"] == "paged_append"
    ]
    assert len(appends) == (backend == "pallas" and not quant)
    assert all(s.endswith("cache_append/paged_append") for s in appends)
