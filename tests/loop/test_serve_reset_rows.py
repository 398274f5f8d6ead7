"""An admission clears the rows it admits (``decode_flags.zero_rows``):
the fused chunk that admits passes over no recurrent leaf, and a request
admitted into a row that another left dirty emits what it emits on a
fresh batcher, on the paged and the unpaged path, for each kind of
per-row state the serving cells carry (Mamba's, Mamba-2's matrix a head,
the Kimi delta rule's)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import build, manifest
from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.nn.decode_flags import per_row_leaves, recurrent_leaves
from tests import jaxpr_tools

CELLS = {
    "mamba": "jamba2-3b-decode.serve-reason-closed",
    "mamba2": "granite-4.0-h-small-share4-decode.serve-reason-closed",
    "kda": "solar-open2-250b-share8-decode.serve-reason-closed",
}
K = 4


def _batcher(kind: str, paged: bool) -> ContinuousBatcher:
    config = manifest.cell(CELLS[kind]).config
    cfg, _ = build.sizes(config, tiny=True)
    serving = config["tiny"]["serving"]
    model = build.decode_model(config, cfg, serving["decode_max_length"])
    params = build.seeded_weights(model, 0)
    return ContinuousBatcher(
        model, params, batch_size=2, chunk_size=K,
        page_size=serving["page_size"] if paged else None,
    )


# tier-1 keeps every kind paged, as the cells serve them, and the unpaged
# path on the cheapest model; the other two unpaged cases cost 40 s
_SLOW = {("mamba2", False), ("kda", False)}


@pytest.mark.parametrize("kind,paged", [
    pytest.param(
        kind, paged, id=f"{kind}-{'paged' if paged else 'unpaged'}",
        marks=[pytest.mark.slow] if (kind, paged) in _SLOW else [],
    )
    for kind in CELLS for paged in (True, False)
])
def test_a_dirty_row_serves_what_a_fresh_one_serves(kind, paged):
    b = _batcher(kind, paged)
    probe = [5, 9, 2, 7]
    rid = b.submit(probe, max_new_tokens=5)
    fresh = b.drain()[rid]
    # two other requests leave both rows' state behind them, and the
    # idle row keeps stepping on token 0 meanwhile
    for prompt in ([3, 1, 4, 1, 5], [8, 6]):
        b.submit(prompt, max_new_tokens=4)
    b.drain()
    state = recurrent_leaves(b._cache)
    assert state and all(
        np.asarray(x, np.float32).reshape(2, -1).any(axis=1).all()
        for x in state.values()
    ), "both rows dirty in every recurrent leaf"
    again = [b.submit(probe, max_new_tokens=5) for _ in range(2)]
    out = b.drain()
    assert [out[r] for r in again] == [fresh, fresh]
    assert b.stats.rows_reset == 5
    # what the device wrote: a row's share of every per-row leaf a reset
    # (unpaged, the dense keys and values are such leaves too)
    a_row = sum(x.nbytes for x in per_row_leaves(b._cache).values()) // 2
    assert a_row > b.stats.recurrent_state_bytes // 2
    assert b.stats.rows_reset_device_bytes == 5 * a_row
    b.close()


def test_the_admitting_chunk_selects_over_no_recurrent_leaf():
    """The ``with_admit`` fused program of a small hybrid model: outside
    the steps' scan there is no ``select_n`` of a recurrent leaf's whole
    shape (the masked pass that was), and the reset's loop stands under
    its own scope."""
    b = _batcher("mamba2", paged=True)
    slots = 2
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    args = (
        b._params, b._cache, i32(slots), i32(slots),
        jnp.zeros((slots,), bool), i32(slots), jax.random.PRNGKey(0),
        i32(slots, K + 5 + b._cache_mgr.pages_per_row),
    )
    whole = {x.shape for x in recurrent_leaves(b._cache).values()}
    assert whole and all(len(s) > 1 for s in whole)

    def admission(with_admit):
        """Equations outside the steps' scan, with their scopes."""
        (call,) = jax.make_jaxpr(
            b._build_fused(K, with_admit).jitted)(*args).jaxpr.eqns
        eqns = call.params["jaxpr"].jaxpr.eqns
        top = [e for e in eqns if e.primitive.name != "scan"]
        assert len(top) == len(eqns) - 1  # one scan of steps
        return list(
            jaxpr_tools.scoped_equations(types.SimpleNamespace(eqns=top)))

    with_admit, without = admission(True), admission(False)
    for eqn, _ in with_admit:
        if eqn.primitive.name == "select_n":
            assert eqn.outvars[0].aval.shape not in whole
    resets = [
        e for e, scope in with_admit
        if e.primitive.name == "while" and "serve/reset_rows" in scope
    ]
    assert len(resets) == 1
    assert not any("serve/reset_rows" in scope for _, scope in without)
    b.close()
