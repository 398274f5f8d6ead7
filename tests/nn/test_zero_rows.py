"""The serving loop's one row reset, ``decode_flags.zero_rows``, alone:
it writes a zero row into each per-row leaf at each masked index, and
what it leaves is what the masked pass over every leaf left (kept here
as the plain reference: ``jnp.where`` over each leaf that leads with the
batch dimension)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict, unflatten_dict

from d9d_tpu.nn.decode_flags import per_row_leaves, zero_rows
from tests import jaxpr_tools

B = 6
SHARED_WHEN_PAGED = {
    "cached_key", "cached_value", "cached_key_scale", "page_table",
    "ring_key", "ring_value",
}


def _cache(paged: bool):
    """One attention layer (paged: a pool, an int8 pool's scales, a table
    and a ring, none led by the batch; unpaged: dense keys and values a
    row), a Mamba-2 mixer and a short convolution's tail in another
    type, a toy ``[B]`` memory: nothing is zero to begin with."""
    rng = np.random.default_rng(0)

    def arr(*shape, dtype=jnp.float32):
        return jnp.asarray(1 + rng.integers(1, 9, shape), dtype)

    attn = {"cache_index": arr(B, dtype=jnp.int32)}
    if paged:
        attn.update(
            cached_key=arr(9, 2, 4, 8, dtype=jnp.bfloat16),
            cached_value=arr(9, 2, 4, 8, dtype=jnp.int8),
            cached_key_scale=arr(9, 2, 4),
            page_table=arr(B, 3, dtype=jnp.int32),
            ring_key=arr(B * 2, 2, 4, 8, dtype=jnp.bfloat16),
            ring_value=arr(B * 2, 2, 4, 8, dtype=jnp.bfloat16),
        )
    else:
        attn.update(
            cached_key=arr(B, 2, 12, 8, dtype=jnp.bfloat16),
            cached_value=arr(B, 2, 12, 8, dtype=jnp.bfloat16),
        )
    return {
        "layers_0": {"self_attn": attn},
        "layers_1": {"mamba": {
            "ssm_state": arr(B, 4, 16, 8),
            "conv_tail": arr(B, 3, 24, dtype=jnp.bfloat16),
        }},
        "memory": {"seen": arr(B, dtype=jnp.int32)},
    }


def masked_reference(cache, row_mask, paged: bool):
    """The masked pass the serving loop made before: every per-row leaf
    read and written whole."""
    flat = flatten_dict(cache)
    for path, x in flat.items():
        if paged and path[-1] in SHARED_WHEN_PAGED:
            continue
        m = row_mask.reshape((-1,) + (1,) * (x.ndim - 1))
        flat[path] = jnp.where(m, jnp.zeros_like(x), x)
    return unflatten_dict(flat)


ROWS = {
    "no-row": [], "one-row": [2], "several-rows": [0, 3, 4],
    "the-last-row": [B - 1], "all-rows": list(range(B)),
}


@pytest.mark.parametrize("rows", ROWS.values(), ids=ROWS.keys())
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "unpaged"])
def test_zero_rows_is_the_masked_pass(paged, rows):
    cache = _cache(paged)
    mask = jnp.zeros((B,), bool).at[jnp.asarray(rows, jnp.int32)].set(True)
    got = jax.jit(zero_rows)(cache, mask)  # the mask is traced
    want = masked_reference(cache, mask, paged)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)
    ):
        assert g.dtype == w.dtype and g.shape == w.shape, path
        np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(w, np.float32), str(path))
    kept = [r for r in range(B) if r not in rows]
    state = got["layers_1"]["mamba"]["ssm_state"]
    assert not np.asarray(state[jnp.asarray(rows, jnp.int32)]).any()
    assert np.asarray(state[jnp.asarray(kept, jnp.int32)]).all()


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "unpaged"])
def test_which_leaves_lead_with_the_batch(paged):
    """A pool has the name of the dense leaf it replaces: the table
    seeded beside it is what tells them apart."""
    names = sorted(p[-1] for p in per_row_leaves(_cache(paged)))
    rows = ["cache_index", "conv_tail", "seen", "ssm_state"]
    assert names == sorted(
        rows if paged else rows + ["cached_key", "cached_value"])


def test_the_reset_passes_over_no_leaf():
    """The traced reset holds one loop whose trip count is data and no
    op over a leaf's whole shape but the in-place row writes; the
    ``[B]``-sized leaves alone are masked."""
    cache = _cache(paged=True)
    jaxpr = jax.make_jaxpr(zero_rows)(cache, jnp.zeros((B,), bool)).jaxpr
    whole = {x.shape for x in per_row_leaves(cache).values() if x.ndim > 1}
    selects = [
        e for e in jaxpr_tools.equations(jaxpr) if e.primitive.name == "select_n"
    ]
    assert all(e.outvars[0].aval.shape not in whole for e in selects)
    assert sum(e.outvars[0].aval.shape == (B,) for e in selects) >= 2
    loops = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    assert len(loops) == 1
    writes = [
        e for e in jaxpr_tools.equations(loops[0].params["body_jaxpr"].jaxpr)
        if e.primitive.name == "dynamic_update_slice"
    ]
    assert sorted(e.outvars[0].aval.shape for e in writes) == sorted(whole)
    assert all(e.invars[1].aval.shape[0] == 1 for e in writes)
    scopes = {
        scope for e, scope in jaxpr_tools.scoped_equations(jaxpr)
        if e.primitive.name in ("while", "select_n")
    }
    assert all("serve/reset_rows" in s for s in scopes)


def test_a_tree_with_no_per_row_leaf_traces_no_loop():
    pools = {"layers_0": {"self_attn": {
        "cached_key": jnp.ones((9, 2, 4, 8)),
        "page_table": jnp.ones((B, 3), jnp.int32),
    }}}
    jaxpr = jax.make_jaxpr(zero_rows)(pools, jnp.ones((B,), bool)).jaxpr
    assert not jaxpr.eqns
