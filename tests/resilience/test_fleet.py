"""Preemption-driven serving-fleet shrink/grow
(resilience/elastic.ServingFleet, docs/design/elasticity.md): requests
route across replicas under the PR 5 backpressure contract, a shrinking
replica drains its queue into survivors, a replica killed mid-drain has
its unfinished requests recovered as continuation prompts (no committed
token lost), and a grown replica cold-starts from the latest published
weights."""

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.chaos

from tests.resilience.conftest import ToyDecodeLM, toy_expected

from d9d_tpu.loop.serve import ContinuousBatcher, QueueFullError
from d9d_tpu.resilience import PreemptionGuard, ServingFleet, WeightPublisher
from d9d_tpu.resilience.chaos import kill_replica_mid_drain, shrink_at_step
from d9d_tpu.telemetry import get_telemetry


def _kv(batcher):
    """The batcher's host page allocator (``loop/kv_paging.py``)."""
    return batcher._cache_mgr.allocator


def _make_batcher(params=None, **kwargs):
    model = ToyDecodeLM()
    if params is None:
        z = jnp.zeros((2, 1), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), z, z, z).get("params", {})
    kwargs.setdefault("batch_size", 2)
    kwargs.setdefault("chunk_size", 4)
    return ContinuousBatcher(model, params, **kwargs)


def _fleet(n_replicas=2, publisher=None, **batcher_kwargs):
    fleet = ServingFleet(publisher=publisher)
    for _ in range(n_replicas):
        fleet.add_replica(_make_batcher(**batcher_kwargs))
    return fleet


def test_fleet_routes_and_drains():
    fleet = _fleet(2)
    prompts = [[3], [7, 8], [1], [5], [9], [2, 6]]
    frids = [fleet.submit(p, max_new_tokens=4) for p in prompts]
    out = fleet.drain()
    for frid, p in zip(frids, prompts):
        assert out[frid] == toy_expected(p, 4), frid
    # both replicas actually served traffic (least-loaded routing)
    assert all(
        fleet._replicas[i].stats.emitted_tokens > 0 for i in (0, 1)
    )


def test_fleet_backpressure_cascades():
    """Every replica's bounded queue full → fleet-level QueueFullError
    (the PR 5 admission contract, one level up)."""
    fleet = _fleet(2, max_queue=1)
    # nothing admitted yet, so capacity = one bounded-queue slot per
    # replica; the third submit must cascade the rejection to the caller
    frids = [fleet.submit([3], max_new_tokens=8) for _ in range(2)]
    with pytest.raises(QueueFullError):
        fleet.submit([4], max_new_tokens=2)
    out = fleet.drain()
    for frid in frids:
        assert out[frid] == toy_expected([3], 8)
    # post-drain the queues are free again: the shed request retries fine
    retry = fleet.submit([4], max_new_tokens=2)
    assert fleet.drain()[retry] == toy_expected([4], 2)


def test_shrink_migrates_queue_into_survivors():
    fleet = _fleet(2, batch_size=1)
    # replica 0 least-loaded first: overload it so its queue is deep
    prompts = [[4], [8], [11], [2]]
    frids = [fleet.submit(p, max_new_tokens=5) for p in prompts]
    queued_before = sum(
        len(fleet._replicas[i]._queue) for i in (0, 1)
    )
    assert queued_before >= 1  # at least one never-admitted request
    fleet.shrink(0)
    assert fleet.live_replicas == (1,)
    assert 0 in fleet.retired
    out = fleet.drain()
    for frid, p in zip(frids, prompts):
        assert out[frid] == toy_expected(p, 5), frid


def test_shrink_at_step_chaos_is_deterministic():
    results = []
    for _ in range(2):
        fleet = _fleet(2)
        frids = [
            fleet.submit(p, max_new_tokens=6)
            for p in ([3], [7], [12], [1])
        ]
        shrink_at_step(fleet, 0, step=2)
        out = fleet.drain()
        results.append([out[f] for f in frids])
        assert fleet.live_replicas == (1,)
    assert results[0] == results[1]
    for toks, p in zip(results[0], ([3], [7], [12], [1])):
        assert toks == toy_expected(p, 6)


def test_kill_mid_drain_recovers_unfinished_as_continuations():
    fleet = _fleet(2)
    prompts = [[3], [7], [12], [1]]
    frids = [fleet.submit(p, max_new_tokens=10) for p in prompts]
    migrated_before = get_telemetry().counter("serve/fleet_migrated").value
    # let some chunks land so the dying replica holds partial progress
    fleet.step()
    shrink_at_step(fleet, 0, step=2)
    kill_replica_mid_drain(fleet, 0, after_chunks=1)
    out = fleet.drain()
    assert 0 in fleet.dead
    # every request completes with its FULL expected token stream:
    # committed tokens from the dead replica survive as the prefix and
    # the survivor's greedy decode continues token-identically
    for frid, p in zip(frids, prompts):
        assert out[frid] == toy_expected(p, 10), frid
    assert get_telemetry().counter("serve/fleet_migrated").value \
        > migrated_before, "the kill must have migrated at least one request"
    # retired records stay readable through the bounded snapshot store
    # (and the live maps were pruned so a long-lived fleet stays flat)
    assert fleet.outputs(frids[0]) == out[frids[0]]
    assert not fleet._reqs and not fleet._by_replica


def test_kill_mid_drain_recovers_paged_requests_token_identically():
    """The chaos leg for paged KV (ISSUE 11): replicas running the
    paged cache + page allocator, one killed mid-drain — its requests
    resume on the survivor as continuation prompts, token-identical,
    and the survivor's page bookkeeping stays exact."""
    fleet = _fleet(2, page_size=4, num_pages=17)
    prompts = [[3], [7], [12], [1]]
    frids = [fleet.submit(p, max_new_tokens=10) for p in prompts]
    fleet.step()
    shrink_at_step(fleet, 0, step=2)
    kill_replica_mid_drain(fleet, 0, after_chunks=1)
    out = fleet.drain()
    assert 0 in fleet.dead
    for frid, p in zip(frids, prompts):
        assert out[frid] == toy_expected(p, 10), frid
    survivor = fleet._replicas[1]
    _kv(survivor).check_invariants()
    assert _kv(survivor).pages_in_use == 0  # everything retired cleanly
    # the fleet-level page rollup reflects the one live paged replica
    assert fleet._kv_pages("pages_free") == _kv(survivor).pages_free


def test_kill_mid_drain_quantized_fleet_token_identical():
    """The chaos leg for LOW-PRECISION serving: a fleet running int8 KV
    pages (``kv_quant="int8"``), one replica killed mid-drain — the
    continuation handoff re-prefills the committed tokens into the
    survivor's own quantized pages, so recovery must be token-identical
    to the fleet's own quantized baseline (the toy's margins make that
    baseline the exact oracle)."""
    fleet = _fleet(2, page_size=4, num_pages=17, kv_quant="int8")
    prompts = [[3], [7], [12], [1]]
    frids = [fleet.submit(p, max_new_tokens=10) for p in prompts]
    fleet.step()
    shrink_at_step(fleet, 0, step=2)
    kill_replica_mid_drain(fleet, 0, after_chunks=1)
    out = fleet.drain()
    assert 0 in fleet.dead
    for frid, p in zip(frids, prompts):
        assert out[frid] == toy_expected(p, 10), frid
    survivor = fleet._replicas[1]
    _kv(survivor).check_invariants()
    assert _kv(survivor).pages_in_use == 0
    assert fleet._kv_pages("pages_free") == _kv(survivor).pages_free


def test_submit_validation_error_leaves_no_ghost():
    """A replica-side validation error must not strand an unplaceable
    fleet request that wedges every later drain()."""
    fleet = _fleet(1)
    with pytest.raises(ValueError):
        fleet.submit([3], max_new_tokens=10_000)  # > decode_max_length
    assert not fleet._reqs
    ok = fleet.submit([3], max_new_tokens=3)
    assert fleet.drain()[ok] == toy_expected([3], 3)


def test_shrink_fails_unmanaged_queued_requests_explicitly():
    """A request submitted DIRECTLY to a batcher that the fleet later
    shrinks can't be migrated (the caller holds that replica's rid) —
    it must surface as an explicit failure, never vanish."""
    fleet = _fleet(1, batch_size=1)
    b = ContinuousBatcher(
        ToyDecodeLM(), {}, batch_size=1, chunk_size=4
    )
    direct_busy = b.submit([4], max_new_tokens=2)
    b.step_chunk()  # admitted into the single slot
    direct_queued = b.submit([6], max_new_tokens=2)  # stays queued
    fleet.add_replica(b)
    fleet.shrink(1)  # idx 1: added after the initial replica
    assert b.failed[direct_queued] == "shrunk"
    assert direct_queued in b.done
    assert b.outputs[direct_queued] == []  # observable, just unserved
    assert b.outputs[direct_busy] == toy_expected([4], 2)
    # a shrink retirement is NOT a deadline expiry: the degraded-mode
    # expired signal must stay clean
    assert b.stats.expired == 0


def test_replica_deadline_failure_surfaces_at_fleet():
    """A deadline expiry handled BY THE REPLICA must reach fleet.failed
    — a truncated result must not read as a short success."""
    import time as _time

    fleet = _fleet(1)
    doomed = fleet.submit([5], max_new_tokens=4, deadline_s=0.005)
    ok = fleet.submit([9], max_new_tokens=4)
    _time.sleep(0.02)  # expires while queued on the replica
    out = fleet.drain()
    assert fleet.failed[doomed] == "deadline"
    assert out[doomed] == []
    assert out[ok] == toy_expected([9], 4)


def test_retention_horizon_is_graceful():
    """Past the bounded snapshot horizon, finished() still answers True
    (the request DID retire) and outputs() raises with an explanation —
    never a bare KeyError crash on a healthy long-lived fleet."""
    fleet = _fleet(1)
    f = fleet.submit([3], max_new_tokens=2)
    fleet.drain()
    assert fleet.finished(f) and fleet.outputs(f) == toy_expected([3], 2)
    fleet._MAX_FINISHED = 0  # instance override: force eviction
    fleet._retire_finished()
    assert fleet.finished(f) is True
    with pytest.raises(KeyError, match="retention horizon"):
        fleet.outputs(f)
    with pytest.raises(KeyError, match="unknown"):
        fleet.finished(10_000)


def test_weights_version_monotonic_across_publishers():
    """A publisher whose counter lags the batcher's own generation must
    not regress it: stamps stay unique per batcher."""
    from tests.resilience.conftest import ToyDecodeLM

    b = ContinuousBatcher(ToyDecodeLM(), {}, batch_size=2, chunk_size=4)
    b.submit([3], max_new_tokens=2)
    assert b.install_weights({}) == 1
    b.drain()  # applies generation 1
    pub = WeightPublisher()  # fresh counter: its first publish is "1"
    pub.attach(b)
    v = pub.publish({})
    assert v == 1
    b.submit([3], max_new_tokens=2)
    b.drain()
    # the batcher floored the lagging external version past its own
    assert b.weights_version == 2


def test_grow_cold_starts_from_published_weights():
    pub = WeightPublisher()
    fleet = _fleet(1, publisher=pub)
    model = ToyDecodeLM()
    z = jnp.zeros((2, 1), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), z, z, z).get("params", {})
    with pytest.raises(RuntimeError):
        fleet.grow(lambda p: _make_batcher(params=p))
    pub.publish(params)
    idx = fleet.grow(lambda p: _make_batcher(params=p))
    assert fleet.live_replicas == (0, idx)
    frid = fleet.submit([5], max_new_tokens=4)
    out = fleet.drain()
    assert out[frid] == toy_expected([5], 4)


def test_preemption_signal_triggers_shrink():
    """PR 5's preemption flag is the shrink trigger: once the guard
    trips, the next scheduling round drains the bound replica."""
    fleet = _fleet(2)
    guard = PreemptionGuard(enabled=False)  # flag surface only
    fleet.bind_preemption(guard, 0)
    frids = [fleet.submit(p, max_new_tokens=6) for p in ([3], [9], [1])]
    fleet.step()
    assert fleet.live_replicas == (0, 1)  # not triggered yet
    guard.trip()
    out = fleet.drain()
    assert fleet.live_replicas == (1,)
    assert 0 in fleet.retired
    for frid, p in zip(frids, ([3], [9], [1])):
        assert out[frid] == toy_expected(p, 6), frid


def test_migration_preserves_absolute_deadline():
    """A migration must never extend a request's deadline: the fleet
    stores the ABSOLUTE deadline at submit, so a queued request whose
    contract already expired retires at migration time (partial output
    kept, counted expired) instead of getting a fresh window on the
    survivor."""
    import time as _time

    fleet = _fleet(2, batch_size=1)
    # fill replica slots+queues so a later submit stays queued
    long_frids = [fleet.submit([3], max_new_tokens=6) for _ in range(2)]
    doomed = fleet.submit([9], max_new_tokens=4, deadline_s=0.01)
    _time.sleep(0.03)  # the contract expires while still queued
    # shrink whichever replica holds the doomed request's queue entry
    holder = fleet._reqs[doomed].replica
    fleet.shrink(holder)
    assert doomed in fleet.failed and fleet.failed[doomed] == "deadline"
    out = fleet.drain()  # the rest of the fleet is unaffected
    for frid in long_frids:
        assert out[frid] == toy_expected([3], 6)
    assert out[doomed] == []  # never ran; retired cleanly


def test_shrunk_fleet_keeps_serving_new_traffic():
    fleet = _fleet(2)
    f1 = fleet.submit([4], max_new_tokens=3)
    fleet.shrink(0)
    f2 = fleet.submit([8], max_new_tokens=3)
    out = fleet.drain()
    assert out[f1] == toy_expected([4], 3)
    assert out[f2] == toy_expected([8], 3)
