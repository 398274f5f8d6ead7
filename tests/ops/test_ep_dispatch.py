"""ragged-all-to-all EP dispatch: routing math, compute-scaling contract,
capacity clamping, differentiability (VERDICT r1 item 3).

Uses a transparent expert_fn (adds a per-expert constant) so routing
errors can't hide inside GEMM numerics. The local oracle computes the same
top-k combine on unsharded arrays.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.core.compat import HAS_MODERN_JAX

# the SPMD/multiprocess e2e tier needs the modern jax runtime
# (core/compat.py emulates only ambient-mesh bookkeeping)
requires_modern_jax = pytest.mark.skipif(
    not HAS_MODERN_JAX, reason="needs the modern-jax SPMD runtime"
)
# slow tier: heavy kernel/e2e parity
pytestmark = [pytest.mark.e2e, requires_modern_jax]

from jax.sharding import Mesh, PartitionSpec as P

from d9d_tpu.core import compat
from d9d_tpu.ops import ep_dispatch
from d9d_tpu.ops.ep_dispatch import (
    ep_buffer_ladder,
    ep_buffer_rows,
    ep_dispatch_compute_combine,
)

W = 4  # ep world
E = 8  # global experts
E_LOC = E // W
K = 2
N_LOC = 6  # tokens per shard
D = 16


def _mesh(devices):
    return Mesh(np.array(devices[:W]), ("ep",))


def _expert_fn_factory(seen_rows):
    """Expert e transforms rows as x * (2 + global_e). Records GEMM size."""

    def fn(rows, group_sizes):
        seen_rows.append(rows.shape[0])
        shard_offset = jax.lax.axis_index(("ep",)) * E_LOC
        # build per-row scale from group membership
        bounds = jnp.cumsum(group_sizes)
        local_e = (jnp.arange(rows.shape[0])[:, None] >= bounds[None, :]).sum(1)
        global_e = shard_offset + jnp.clip(local_e, 0, group_sizes.shape[0] - 1)
        return rows * (2.0 + global_e[:, None])

    return fn


def _run_dispatch(devices, x, ids, probs, capacity_factor):
    mesh = _mesh(devices)
    seen: list[int] = []

    def body(x_loc, ids_loc, probs_loc):
        return ep_dispatch_compute_combine(
            x_loc,
            ids_loc,
            probs_loc,
            _expert_fn_factory(seen),
            ep_axes=("ep",),
            e_loc=E_LOC,
            ep_world=W,
            capacity_factor=capacity_factor,
        )[0]

    run = jax.jit(
        compat.shard_map(
            body,
            mesh=mesh,
            in_specs=(P("ep"), P("ep"), P("ep")),
            out_specs=P("ep"),
            check_vma=False,
        )
    )
    # scope the mesh: earlier tests may have left a process-wide full mesh
    # (MeshParameters.build calls jax.set_mesh) that would conflict
    with jax.set_mesh(mesh):
        out = run(x, ids, probs)
    return np.asarray(out), seen


def _oracle(x, ids, probs):
    """Unsharded top-k combine with the same transparent experts."""
    scale = 2.0 + ids.astype(np.float32)  # [N, K]
    return (x[:, None, :] * scale[..., None] * probs[..., None]).sum(axis=1)


def _data(seed=0):
    rng = np.random.RandomState(seed)
    n = W * N_LOC
    x = rng.randn(n, D).astype(np.float32)
    ids = rng.randint(0, E, size=(n, K)).astype(np.int32)
    # distinct experts per row keep the oracle simple
    ids[:, 1] = (ids[:, 0] + 1 + ids[:, 1] % (E - 1)) % E
    probs = rng.rand(n, K).astype(np.float32)
    return x, ids.astype(np.int32), probs


def test_dropless_matches_oracle(devices):
    x, ids, probs = _data()
    out, seen = _run_dispatch(devices, x, ids, probs, capacity_factor=None)
    np.testing.assert_allclose(out, _oracle(x, ids, probs), rtol=1e-5, atol=1e-5)


def test_gemm_rows_follow_capacity_contract(devices):
    """Per-shard GEMM row count must be the static buffer size, i.e.
    capacity_factor × N_global·k/ep — not the all-gather's N_global·k."""
    x, ids, probs = _data()
    m = N_LOC * K
    _, seen = _run_dispatch(devices, x, ids, probs, capacity_factor=2.0)
    expected = ep_buffer_rows(m, W, 2.0)
    assert all(s == expected for s in seen)
    assert expected < m * W  # strictly below the all-gather row count

    # dropless: one program per rung of the ladder, the worst case last
    _, seen_dropless = _run_dispatch(devices, x, ids, probs, None)
    assert set(seen_dropless) == set(ep_buffer_ladder(m, W))
    assert max(seen_dropless) == ep_buffer_rows(m, W, None)


def test_generous_capacity_matches_oracle(devices):
    """A capacity that no shard overflows must be numerically dropless."""
    x, ids, probs = _data(seed=3)
    out, _ = _run_dispatch(devices, x, ids, probs, capacity_factor=float(W))
    np.testing.assert_allclose(out, _oracle(x, ids, probs), rtol=1e-5, atol=1e-5)


def test_capacity_drops_are_deterministic_zeros(devices):
    """Force overflow: all assignments target shard 0's experts. The kept
    rows must match the oracle; dropped ones contribute exactly zero."""
    rng = np.random.RandomState(1)
    n = W * N_LOC
    x = rng.randn(n, D).astype(np.float32)
    ids = np.zeros((n, K), np.int32)
    ids[:, 1] = 1  # all rows → experts 0 and 1 (both shard 0)
    probs = np.full((n, K), 0.5, np.float32)

    out, _ = _run_dispatch(devices, x, ids, probs, capacity_factor=1.0)
    m = N_LOC * K
    cap = ep_buffer_rows(m, W, 1.0)  # 16: shard 0's whole 12 + 4 of shard 1
    assert cap == 16
    full = _oracle(x, ids, probs)
    # earliest source wins: shard 0's tokens fully kept
    np.testing.assert_allclose(out[:N_LOC], full[:N_LOC], rtol=1e-5, atol=1e-5)
    # shard 1 got 4 rows in — the expert-0 assignments of its first 4
    # tokens (its block is expert-sorted); expert 0 scales by 2.0
    np.testing.assert_allclose(
        out[N_LOC : N_LOC + 4], x[N_LOC : N_LOC + 4] * 2.0 * 0.5,
        rtol=1e-5, atol=1e-5,
    )
    # everything else dropped → exact zeros
    np.testing.assert_array_equal(out[N_LOC + 4 :], 0.0)


def test_dispatch_is_differentiable(devices):
    x, ids, probs = _data(seed=5)
    mesh = _mesh(devices)

    def loss(x, probs):
        def body(x_loc, ids_loc, probs_loc):
            return ep_dispatch_compute_combine(
                x_loc, ids_loc, probs_loc,
                _expert_fn_factory([]),
                ep_axes=("ep",), e_loc=E_LOC, ep_world=W,
                capacity_factor=None,
            )[0]

        out = compat.shard_map(
            body, mesh=mesh, in_specs=(P("ep"), P("ep"), P("ep")),
            out_specs=P("ep"), check_vma=False,
        )(x, ids, probs)
        return (out ** 2).sum()

    with jax.set_mesh(mesh):
        gx, gp = jax.jit(jax.grad(loss, argnums=(0, 1)))(
            jnp.asarray(x), jnp.asarray(probs)
        )

    def oracle_loss(x, probs):
        scale = 2.0 + jnp.asarray(ids, jnp.float32)
        out = (x[:, None, :] * scale[..., None] * probs[..., None]).sum(axis=1)
        return (out ** 2).sum()

    egx, egp = jax.jit(jax.grad(oracle_loss, argnums=(0, 1)))(
        jnp.asarray(x), jnp.asarray(probs)
    )
    np.testing.assert_allclose(gx, egx, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gp, egp, rtol=1e-4, atol=1e-4)


# -- the dropless ladder: a rung chosen from the exchanged counts -------------

INTER = 8  # expert FFN width of the real-experts tests
M = N_LOC * K  # assignment rows a shard


def _routing(kind, world):
    """``ids [N, K]`` over ``E`` experts on ``world`` shards whose largest
    per-shard intake is ``M`` ("uniform"), 30 rows on shard 0 ("skewed",
    eight shards: between the snug rung and the worst case) or every row
    ("one_shard")."""
    t = np.arange(world * N_LOC)
    if kind == "uniform":
        cols = [t % E, (t + E // 2) % E]
    elif kind == "skewed":
        assert world == E
        cols = [np.where(t < 30, 0, 1 + t % (E - 1)), 1 + t % (E - 1)]
    else:
        assert kind == "one_shard"
        cols = [0 * t, 0 * t + E // world - 1]
    return np.stack(cols, axis=1).astype(np.int32)


def _needed(ids, world):
    """The largest intake of any shard, reckoned from the ids alone."""
    return int(np.bincount(ids.reshape(-1) // (E // world), minlength=world).max())


def _swiglu_experts(rows, group_sizes, gate_w, up_w, down_w):
    from d9d_tpu.nn.moe import grouped_swiglu_apply

    return grouped_swiglu_apply(
        rows, jnp.ones((rows.shape[0],), jnp.float32), group_sizes,
        gate_w, up_w, down_w, jnp.float32,
    )


def _expert_weights(seed=7):
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)
        for shape in ((E, D, INTER), (E, D, INTER), (E, INTER, D))
    )


def _loss_grads_and_use(
    devices, world, x, ids, probs, weights, capacity_factor=None, remat=False
):
    """``(out, grads of x, probs and the three expert weights, use
    [world, 3])`` through real grouped-SwiGLU experts sharded over the ep
    axis of ``world`` shards: dropless, or under ``capacity_factor``;
    with ``remat`` the whole dispatch sits under ``jax.checkpoint``."""
    mesh = Mesh(np.array(devices[:world]), ("ep",))

    def body(x_loc, ids_loc, probs_loc, *w_loc):
        out, use = ep_dispatch_compute_combine(
            x_loc, ids_loc, probs_loc, _swiglu_experts, w_loc,
            ep_axes=("ep",), e_loc=E // world, ep_world=world,
            capacity_factor=capacity_factor,
        )
        return out, jnp.stack(use)[None]

    run = compat.shard_map(
        jax.checkpoint(body) if remat else body,
        mesh=mesh, in_specs=(P("ep"),) * 6,
        out_specs=(P("ep"), P("ep")), check_vma=False,
    )

    def loss(x, probs, *w):
        out, use = run(x, ids, probs, *w)
        return (out ** 2).sum(), (out, use)

    with jax.set_mesh(mesh):
        (_, (out, use)), grads = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
        )(jnp.asarray(x), jnp.asarray(probs), *weights)
    return np.asarray(out), [np.asarray(g) for g in grads], np.asarray(use)


@pytest.mark.parametrize("world,kind,rung", [
    (4, "uniform", 0), (4, "one_shard", 1),
    (8, "uniform", 0), (8, "skewed", 1), (8, "one_shard", 2),
])
def test_ladder_matches_the_worst_case_buffer(
    devices, monkeypatch, world, kind, rung
):
    """Whatever rung the counts select (the snug one, the middle one eight
    shards have, the worst case), outputs and every gradient are bit-equal
    to the ``m·W`` buffer's through the same switch, and every gradient
    equal to plain autodiff's through the single ``m·W`` buffer; the rung
    and the rows needed are reported identically by every shard."""
    rng = np.random.RandomState(11)
    n = world * N_LOC
    x = rng.randn(n, D).astype(np.float32)
    probs = rng.rand(n, K).astype(np.float32)
    ids = _routing(kind, world)
    weights = _expert_weights()
    ladder = ep_buffer_ladder(M, world)
    assert ladder == {4: (16, 48), 8: (16, 40, 96)}[world]
    needed = _needed(ids, world)
    assert ladder[rung] >= needed > (ladder[rung - 1] if rung else 0)

    out, grads, use = _loss_grads_and_use(devices, world, x, ids, probs, weights)
    assert np.all(use == use[0])  # the same on every shard
    assert use[0].tolist() == [
        ladder[rung], needed, int(rung == len(ladder) - 1)
    ]

    # the worst-case buffer on both rungs: the same switch around the
    # exchange, so XLA:CPU cuts its fusions at the same places and every
    # rounding is the same one
    monkeypatch.setattr(
        ep_dispatch, "ep_buffer_ladder", lambda rows, w: (rows * w,) * 2
    )
    ref_out, ref_grads, ref_use = _loss_grads_and_use(
        devices, world, x, ids, probs, weights
    )
    assert ref_use[0].tolist() == [M * world, needed, 0]
    np.testing.assert_array_equal(out, ref_out)
    assert np.abs(out).max() > 0
    names = ("x", "probs", "gate", "up", "down")
    for name, g, ref in zip(names, grads, ref_grads):
        np.testing.assert_array_equal(g, ref, err_msg=name)

    # the parent's dropless path: one worst-case buffer, plain autodiff.
    # With no switch the probabilities' multiply, the gather and the k-row
    # sum are one CPU fusion, where the multiply-add contracts (one
    # rounding; a fill's select stood between them until PR 62): the
    # output is this one's to a last bit, not bit for bit
    monkeypatch.setattr(
        ep_dispatch, "ep_buffer_ladder", lambda rows, w: (rows * w,)
    )
    plain_out, plain_grads, plain_use = _loss_grads_and_use(
        devices, world, x, ids, probs, weights
    )
    assert plain_use[0].tolist() == [M * world, needed, 0]
    np.testing.assert_allclose(out, plain_out, rtol=1e-6, atol=1e-6)
    for name, g, ref in zip(names, grads, plain_grads):
        np.testing.assert_allclose(g, ref, rtol=1e-6, atol=1e-6, err_msg=name)
        assert np.abs(ref).max() > 0, name


@pytest.mark.parametrize(
    "m,world", [(12, 4), (32768, 4), (8, 2), (100, 8), (5, 4), (12, 1), (4096, 64)]
)
def test_ladder_is_a_function_of_rows_and_world(m, world):
    ladder = ep_buffer_ladder(m, world)
    assert ladder == ep_buffer_ladder(m, world)
    assert ladder[-1] == m * world == ep_buffer_rows(m, world, None)
    assert list(ladder) == sorted(set(ladder))  # strictly ascending
    assert all(r % 8 == 0 and r >= m for r in ladder[:-1])
    # the worst case is far enough from the snug rung for one between
    assert len(ladder) <= (3 if world >= 8 else 2)
    if world == 1:
        assert ladder == (m,)
    elif m >= 64:
        # the snug rung holds a near-even split with a quarter to spare
        assert 1.25 * m <= ladder[0] < 1.25 * m + 8


@pytest.mark.parametrize("capacity_factor", [0.5, 1.0, 2.0, 4.0])
def test_capacity_factor_drops_the_same_tail_rows(devices, capacity_factor):
    """One static buffer, as before the ladder: with every assignment on
    shard 0 the first ``cap`` rows of its intake (sources in order, each
    source's block expert-sorted) are kept and the rest contribute zero."""
    rng = np.random.RandomState(2)
    n = W * N_LOC
    x = rng.randn(n, D).astype(np.float32)
    ids = _routing("one_shard", W)
    probs = rng.rand(n, K).astype(np.float32)
    m = N_LOC * K
    cap = ep_buffer_rows(m, W, capacity_factor)

    out, seen = _run_dispatch(devices, x, ids, probs, capacity_factor)
    assert set(seen) == {cap}  # one program, one buffer

    expected = np.zeros_like(x)
    for src in range(W):
        for pos in range(m):  # the source's rows, sorted by expert then token
            expert, token = divmod(pos, N_LOC)
            if src * m + pos < cap:
                t = src * N_LOC + token
                expected[t] += x[t] * (2.0 + expert) * probs[t, expert]
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-5)


# -- row movements whose transposes are gathers -------------------------------


def _plain_autodiff_rows(monkeypatch):
    """The three row movements as the plain ``jnp.take`` they were, whose
    transposes autodiff writes as scatter-adds (the forward gathers clip
    as ``ops/moe.py``'s do, so the forward is one program either way)."""

    def take_rows(x, idx, *unused):
        return jnp.take(x, idx, axis=0, mode="clip")

    def take_and_fold(y, token_idx, dest, num_tokens):
        pair_y = jnp.take(y, dest, axis=0, mode="clip")
        return pair_y.reshape(num_tokens, -1, y.shape[-1]).sum(axis=1)

    monkeypatch.setattr(ep_dispatch, "permute_rows", take_rows)
    monkeypatch.setattr(ep_dispatch, "spread_to_pairs", take_rows)
    monkeypatch.setattr(ep_dispatch, "combine_pairs", take_and_fold)


@pytest.mark.parametrize("world,kind,capacity_factor,remat,grouping,use", [
    (4, "uniform", None, False, "one_hot", [16, 12, 0]),  # snug, padding rows
    (4, "uniform", None, True, "argsort", [16, 12, 0]),
    (4, "one_shard", None, False, "one_hot", [48, 48, 1]),  # fallback rung
    (4, "one_shard", None, True, "argsort", [48, 48, 1]),
    (8, "skewed", None, False, "one_hot", [40, 30, 0]),  # the rung between
    (4, "one_shard", 1.0, False, "one_hot", [16, 48, 0]),  # 32 rows dropped
    (4, "one_shard", 1.0, True, "argsort", [16, 48, 0]),
    (4, "uniform", 0.5, False, "argsort", [8, 12, 0]),  # every shard drops
])
def test_gather_transposes_match_plain_autodiff(
    devices, monkeypatch, world, kind, capacity_factor, remat, grouping, use
):
    """Gradients through the dispatch with the given transposes (gathers by
    the inverse permutation, the k-row fold) against the same dispatch
    under plain ``jnp.take`` autodiff (scatter-adds): on both branches of
    ``stable_expert_order``, with padding rows in the buffer, on the
    ladder's fallback rung under forced skew, under a capacity factor that
    drops rows, and under ``jax.checkpoint``."""
    from d9d_tpu.ops import moe as moe_ops

    if grouping == "argsort":
        monkeypatch.setattr(moe_ops, "_ONE_HOT_GROUPING_LIMIT", 0)
    rng = np.random.RandomState(13)
    n = world * N_LOC
    x = rng.randn(n, D).astype(np.float32)
    probs = rng.rand(n, K).astype(np.float32)
    ids = _routing(kind, world)
    weights = _expert_weights()
    args = (devices, world, x, ids, probs, weights, capacity_factor, remat)

    out, grads, got_use = _loss_grads_and_use(*args)
    assert got_use[0].tolist() == use
    _plain_autodiff_rows(monkeypatch)
    ref_out, ref_grads, ref_use = _loss_grads_and_use(*args)
    assert ref_use[0].tolist() == use

    np.testing.assert_array_equal(out, ref_out)  # the forward is the same
    assert np.abs(out).max() > 0
    for name, g, ref in zip(
        ("x", "probs", "gate", "up", "down"), grads, ref_grads
    ):
        np.testing.assert_allclose(g, ref, rtol=1e-6, atol=1e-6, err_msg=name)
        assert np.abs(ref).max() > 0, name
