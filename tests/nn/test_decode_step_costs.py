"""What a decode step of a paged model must not pay for, held by the
program's shape on the CPU rig (PERF.md section 6, PR 41, has what it
cost on the chip): the cache append is a scatter of whole rows into the
pool seen flat, equal to the indexed form it replaces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.nn.attention import _scatter_head_rows


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
