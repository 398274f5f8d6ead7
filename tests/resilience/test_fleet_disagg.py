"""Disaggregated prefill→decode serving (resilience/elastic.py roles +
KV page shipment, docs/design/elasticity.md "Disaggregated serving"):
the prefill leg emits the first token and hands its filled pages off to
a decode replica token-identically; the fleet-wide prefix directory
ships a shared prompt's pages instead of recomputing them (once per
FLEET); every failure point — version skew, corrupt shipment, a prefill
replica dying mid-handoff — degrades to the continuation re-prefill
with zero leaked pages; placement is KV-capacity-aware; and the
autopilot scales the two pools independently with distinct decision
kinds. Fully deterministic: fake clock, scripted traffic, exact token
oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.chaos

from tests.resilience.conftest import PagedToyLM, paged_toy_expected

from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.resilience import (
    AutopilotConfig,
    FleetAutopilot,
    ServingFleet,
    WeightPublisher,
    read_decisions,
)
from d9d_tpu.resilience.chaos import (
    corrupt_handoff_payload,
    kill_prefill_mid_handoff,
)
from d9d_tpu.telemetry import (
    JsonlSink,
    SloMonitor,
    SloPolicy,
    Telemetry,
    get_telemetry,
    iter_events,
    set_telemetry,
)


def _kv(batcher):
    """The batcher's host page allocator (``loop/kv_paging.py``)."""
    return batcher._cache_mgr.allocator


@pytest.fixture(autouse=True)
def _fresh_hub():
    old = get_telemetry()
    hub = set_telemetry(Telemetry())
    yield hub
    set_telemetry(old)


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


_MODEL = PagedToyLM()
_Z = jnp.zeros((2, 1), jnp.int32)
_PARAMS = _MODEL.init(jax.random.PRNGKey(0), _Z, _Z).get("params", {})


def make_paged_batcher(params=None, **kw):
    kw.setdefault("batch_size", 2)
    kw.setdefault("chunk_size", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 17)
    return ContinuousBatcher(
        _MODEL, params if params is not None else dict(_PARAMS), **kw
    )


def _drain(fleet, frids, rounds=400):
    for _ in range(rounds):
        fleet.step()
        if all(fleet.finished(f) for f in frids):
            return
    raise RuntimeError("fleet did not drain the submitted requests")


def _assert_no_leaks(fleet):
    """Zero leaked pages on every live replica: only prefix-cache
    entries may hold pages after a full drain, and the refcount audit
    must balance exactly."""
    for i in fleet.live_replicas:
        kv = _kv(fleet._replicas[i])
        kv.check_invariants()
        assert kv.pages_in_use == len(kv._entries), (
            f"replica {i} leaked pages: {kv.pages_in_use} in use, "
            f"{len(kv._entries)} prefix entries"
        )


# ---------------------------------------------------------------------------
# handoff token-identity


@pytest.mark.parametrize("k", [1, 4])
def test_handoff_token_identity_vs_unified(k, tmp_path):
    """The tentpole pin: a prefill→decode fleet must emit EXACTLY what
    a single unified replica emits, across chunk sizes — the handoff
    (first-token leg, page shipment, decode continuation) is invisible
    in the token stream. The ``handoff`` trace milestone rides the
    ORIGINAL trace id."""
    prompts = [
        [3, 5, 7, 11, 2, 9, 4],
        [1, 2],
        [8, 8, 8, 8, 8, 8, 8, 8, 6],
        [13, 4, 2],
    ]
    n = 6
    unified = ServingFleet()
    unified.add_replica(make_paged_batcher(chunk_size=k))
    u_frids = [unified.submit(p, max_new_tokens=n) for p in prompts]
    u_out = unified.drain()

    hub = get_telemetry()
    sink = hub.add_sink(JsonlSink(tmp_path, run_name="disagg"))
    fleet = ServingFleet()
    fleet.add_replica(make_paged_batcher(chunk_size=k), role="prefill")
    fleet.add_replica(make_paged_batcher(chunk_size=k), role="decode")
    frids = [fleet.submit(p, max_new_tokens=n) for p in prompts]
    out = fleet.drain()
    for uf, f, p in zip(u_frids, frids, prompts):
        want = paged_toy_expected(p, n)
        assert u_out[uf] == want, p
        assert out[f] == want, p
    snap = hub.registry.snapshot()["counters"]
    # prompts with at least one full page ship it; shorter ones carry
    # zero pages and take the (token-identical) re-prefill path
    n_shipped = sum(1 for p in prompts if (len(p) - 1) // 4 > 0)
    assert snap["serve/fleet_handoffs"] == n_shipped
    assert snap["serve/fleet_handoffs"] \
        + snap.get("serve/fleet_handoff_fallbacks", 0) == len(prompts)
    assert snap.get("serve/handoff_checksum_failures", 0) == 0
    _assert_no_leaks(fleet)
    hub.flush(step=0)
    hub.remove_sink(sink)
    traces = {}
    for ev in iter_events(sink.path):
        if ev["kind"] == "request_trace":
            traces.setdefault(ev["trace_id"], []).append(ev["event"])
    handed = [evs for evs in traces.values() if "handoff" in evs]
    assert len(handed) == len(prompts)
    for evs in handed:
        # one continuous track under the ORIGINAL id: the prefill leg
        # (submit..first_token..finish), the handoff milestone, then
        # the decode continuation ending in the real finish
        assert evs[0] == "submit"
        assert evs.index("first_token") < evs.index("handoff")
        assert evs[-1] == "finish"


def test_prefill_role_runs_first_token_leg():
    """Stage routing: with a prefill replica live, a new request's
    first-token leg lands there (TTFT at the prefill pool), and the
    remaining budget runs on the decode replica after the handoff."""
    fleet = ServingFleet()
    fleet.add_replica(make_paged_batcher(), role="prefill")
    fleet.add_replica(make_paged_batcher(), role="decode")
    prompt = [3, 5, 7, 11, 2]
    frid = fleet.submit(prompt, max_new_tokens=5)
    assert fleet._reqs[frid].stage == "prefill"
    assert fleet._reqs[frid].replica == 0
    _drain(fleet, [frid])
    assert fleet.outputs(frid) == paged_toy_expected(prompt, 5)
    # the prefill replica emitted exactly the first token; the decode
    # replica emitted the rest
    assert fleet._replicas[0].stats.emitted_tokens == 1
    assert fleet._replicas[1].stats.emitted_tokens == 4
    _assert_no_leaks(fleet)


def test_single_token_budget_finishes_at_prefill():
    """max_new_tokens=1 never hands off: the first token IS the
    request; the prefill leg retires it in place."""
    fleet = ServingFleet()
    fleet.add_replica(make_paged_batcher(), role="prefill")
    fleet.add_replica(make_paged_batcher(), role="decode")
    prompt = [4, 9, 1]
    frid = fleet.submit(prompt, max_new_tokens=1)
    _drain(fleet, [frid])
    assert fleet.outputs(frid) == paged_toy_expected(prompt, 1)
    snap = get_telemetry().registry.snapshot()["counters"]
    assert snap.get("serve/fleet_handoffs", 0) == 0
    assert snap.get("serve/fleet_handoff_fallbacks", 0) == 0
    assert fleet._replicas[1].stats.emitted_tokens == 0


# ---------------------------------------------------------------------------
# weights-version pinning


def test_weights_publish_boundary_forces_reprefill():
    """A handoff whose shipment was minted under a superseded weights
    generation must NOT import (cached KV is weights-dependent): the
    continuation re-prefills instead, token-identically — same
    invariant as install_weights prefix invalidation."""
    pub = WeightPublisher()
    fleet = ServingFleet(publisher=pub)
    fleet.add_replica(make_paged_batcher(), role="prefill")
    fleet.add_replica(make_paged_batcher(), role="decode")
    prompt = [3, 5, 7, 11, 2, 9, 4, 6, 1]
    frid = fleet.submit(prompt, max_new_tokens=6)
    # run until the prefill LEG is done but the handoff has not been
    # polled yet, then move the weights generation
    for _ in range(50):
        fleet.step()
        req = fleet._reqs.get(frid)
        if req is not None and req.stage == "prefill" \
                and req.replica is not None \
                and req.local_rid in fleet._replicas[req.replica].done:
            break
    else:
        pytest.fail("prefill leg never finished")
    pub.publish(dict(_PARAMS))
    _drain(fleet, [frid])
    assert fleet.outputs(frid) == paged_toy_expected(prompt, 6)
    snap = get_telemetry().registry.snapshot()["counters"]
    # the stale-generation pages never cross: the exporter's staged
    # publish invalidates them at the boundary, so the handoff ships
    # nothing and the decode replica re-prefills under the new weights
    assert snap["serve/fleet_handoff_fallbacks"] >= 1
    assert snap.get("serve/fleet_handoffs", 0) == 0
    assert snap.get("serve/handoff_imports", 0) == 0
    _assert_no_leaks(fleet)


def test_fleet_directory_invalidated_on_publish():
    """A weight publish clears the fleet prefix directory fleet-wide
    (entries describe KV minted under the OLD generation); it
    repopulates from post-publish caches on later rounds."""
    pub = WeightPublisher()
    fleet = ServingFleet(publisher=pub)
    fleet.add_replica(make_paged_batcher(), role="unified")
    fleet.add_replica(make_paged_batcher(), role="unified")
    prompt = [2] * 9
    frid = fleet.submit(prompt, max_new_tokens=3)
    _drain(fleet, [frid])
    fleet.step()
    assert len(fleet._prefix_dir) >= 1
    pub.publish(dict(_PARAMS))
    fleet.step()
    assert fleet._prefix_dir == {}
    snap = get_telemetry().registry.snapshot()["counters"]
    assert snap["serve/fleet_prefix_invalidations"] == 1
    # post-publish traffic repopulates the directory under the new
    # generation (replicas applied the publish at their boundaries)
    frid2 = fleet.submit([5] * 9, max_new_tokens=3)
    _drain(fleet, [frid2])
    fleet.step()
    assert len(fleet._prefix_dir) >= 1
    assert fleet.outputs(frid2) == paged_toy_expected([5] * 9, 3)


# ---------------------------------------------------------------------------
# fleet-wide prefix cache


def test_shared_prompt_prefills_once_per_fleet():
    """Local miss + directory hit ships the prefix pages: the second
    replica's admission prefix-hits pages it never computed."""
    hub = get_telemetry()
    fleet = ServingFleet()
    fleet.add_replica(make_paged_batcher(), role="unified")
    fleet.add_replica(make_paged_batcher(), role="unified")
    shared = [3, 5, 7, 11, 2, 9, 4, 6]  # two full pages
    f1 = fleet.submit(shared + [1], max_new_tokens=4)
    _drain(fleet, [f1])
    # least-loaded routing spreads the next two across both replicas:
    # the one that never saw `shared` must get its pages shipped
    f2 = fleet.submit(shared + [8], max_new_tokens=4)
    f3 = fleet.submit(shared + [13], max_new_tokens=4)
    _drain(fleet, [f2, f3])
    for f, tail in ((f1, [1]), (f2, [8]), (f3, [13])):
        assert fleet.outputs(f) == paged_toy_expected(shared + tail, 4)
    snap = hub.registry.snapshot()["counters"]
    assert snap["serve/fleet_prefix_hits"] >= 1
    assert snap.get("serve/fleet_prefix_misses", 0) == 0
    # both allocators saw prefix hits: one locally, one via shipment
    assert all(
        _kv(fleet._replicas[i]).prefix_hits >= 1 for i in (0, 1)
    )
    _assert_no_leaks(fleet)


def test_dead_owner_never_wedges_a_waiter():
    """Directory entries owned by a dead replica are dropped at the
    death, and a placement that would have shipped from it falls back
    to a local prefill — never an error, never a wedge."""
    fleet = ServingFleet()
    fleet.add_replica(make_paged_batcher(), role="unified")
    fleet.add_replica(make_paged_batcher(), role="unified")
    shared = [7] * 9
    f1 = fleet.submit(shared, max_new_tokens=3)
    _drain(fleet, [f1])
    fleet.step()
    owner = next(iter(fleet._prefix_dir.values()))
    # hard-kill the owner (no drain): its pages are gone with it
    fleet._live.discard(owner)
    fleet._recover_killed(owner)
    assert all(i != owner for i in fleet._prefix_dir.values())
    f2 = fleet.submit(shared, max_new_tokens=3)
    _drain(fleet, [f2])
    assert fleet.outputs(f2) == paged_toy_expected(shared, 3)
    _assert_no_leaks(fleet)


# ---------------------------------------------------------------------------
# KV-capacity-aware placement


def test_placement_ranks_full_pool_behind_capacity():
    """A paged replica with zero free pages ranks behind one with
    headroom — the request must not accept a head-of-line wait when a
    peer could run it now."""
    fleet = ServingFleet()
    fleet.add_replica(make_paged_batcher(num_pages=9))   # 8 allocatable
    fleet.add_replica(make_paged_batcher(num_pages=9))
    prompt = [9, 8, 7, 6, 5]
    # baseline: both pools free -> least-loaded tiebreak picks 0
    f0 = fleet.submit(prompt, max_new_tokens=2)
    assert fleet._reqs[f0].replica == 0
    _drain(fleet, [f0])
    # fill replica 0's pool completely with pinned prefix chains
    kv0 = _kv(fleet._replicas[0])
    kv0.invalidate_prefix_cache()
    assert kv0.import_pages(list(range(16)), 4) is not None
    assert kv0.import_pages(list(range(100, 116)), 4) is not None
    assert kv0.pages_free_after_flush() == 0
    # same submit now ranks replica 1 first despite the index tiebreak
    f1 = fleet.submit(prompt, max_new_tokens=2)
    assert fleet._reqs[f1].replica == 1
    _drain(fleet, [f1])
    assert fleet.outputs(f1) == paged_toy_expected(prompt, 2)


# ---------------------------------------------------------------------------
# chaos: the new failure surface


def test_corrupt_handoff_payload_falls_back_token_identically(tmp_path):
    hub = get_telemetry()
    hub.configure_flight_recorder(tmp_path / "flight")
    fleet = ServingFleet()
    fleet.add_replica(make_paged_batcher(), role="prefill")
    fleet.add_replica(make_paged_batcher(), role="decode")
    corrupt_handoff_payload(fleet)
    prompt = [3, 5, 7, 11, 2, 9]
    frid = fleet.submit(prompt, max_new_tokens=6)
    _drain(fleet, [frid])
    assert fleet.outputs(frid) == paged_toy_expected(prompt, 6)
    snap = hub.registry.snapshot()["counters"]
    # the checksum caught the flip BEFORE anything was written; the
    # continuation re-prefilled on the decode replica
    assert snap["serve/handoff_checksum_failures"] == 1
    assert snap["serve/fleet_handoff_fallbacks"] == 1
    assert snap.get("serve/fleet_handoffs", 0) == 0
    assert fleet.live_replicas == (0, 1)  # corruption kills no replica
    _assert_no_leaks(fleet)


def test_kill_prefill_mid_handoff_recovers_via_continuation(tmp_path):
    """The prefill replica dies with exported-but-unimported pages in
    flight: the shipment is lost, every in-flight request recovers via
    continuation onto the survivor, zero pages leak, and the flight
    recorder explains the death."""
    hub = get_telemetry()
    hub.configure_flight_recorder(tmp_path / "flight")
    fleet = ServingFleet()
    fleet.add_replica(make_paged_batcher(), role="prefill")
    fleet.add_replica(make_paged_batcher(), role="decode")
    kill_prefill_mid_handoff(fleet, 0)
    prompts = [[3, 5, 7, 11, 2, 9], [8, 1]]
    frids = [fleet.submit(p, max_new_tokens=6) for p in prompts]
    _drain(fleet, frids)
    for f, p in zip(frids, prompts):
        assert fleet.outputs(f) == paged_toy_expected(p, 6), p
    assert fleet.live_replicas == (1,)
    assert 0 in fleet.dead
    snap = hub.registry.snapshot()["counters"]
    assert snap["serve/fleet_handoff_fallbacks"] >= 1
    assert snap["serve/fleet_replica_deaths"] == 1
    _assert_no_leaks(fleet)
    assert (tmp_path / "flight"
            / "flight_recorder_replica_death.json").exists()


# ---------------------------------------------------------------------------
# role-aware autopilot


def _burn_monitor(clock):
    return SloMonitor(
        [
            SloPolicy(name="ttft_p99", kind="quantile",
                      metric="serve/ttft_s", quantile=0.99,
                      target=0.5, window_s=4.0),
            SloPolicy(name="tpot_p99", kind="quantile",
                      metric="serve/tpot_s", quantile=0.99,
                      target=0.1, window_s=4.0),
        ],
        clock=clock,
    )


def test_autopilot_scales_pools_independently(tmp_path):
    """TTFT burn grows the PREFILL pool, TPOT burn grows the DECODE
    pool — distinct decision kinds in the log; idle shrink respects the
    per-role minimums."""
    hub = get_telemetry()
    clock = FakeClock()
    pub = WeightPublisher()
    pub.publish(dict(_PARAMS))
    fleet = ServingFleet(publisher=pub)
    fleet.add_replica(make_paged_batcher(), role="prefill")
    fleet.add_replica(make_paged_batcher(), role="decode")
    monitor = _burn_monitor(clock).attach(hub)
    log = tmp_path / "decisions.jsonl"
    FleetAutopilot(
        fleet, monitor,
        replica_factory=lambda p: make_paged_batcher(params=dict(_PARAMS)),
        config=AutopilotConfig(
            grow_after_s=3.0, cooldown_s=6.0, min_replicas=2,
            max_replicas=4, idle_after_s=5.0, idle_queue_depth=0,
            idle_slot_utilization=0.5, eval_interval_s=1.0,
            prefill_policies=("ttft_p99",), decode_policies=("tpot_p99",),
            min_prefill_replicas=1, min_decode_replicas=1,
        ),
        decision_log=log, clock=clock,
    ).attach()

    def tick(rounds, *, ttft=None, tpot=None):
        for _ in range(rounds):
            if ttft is not None:
                hub.observe("serve/ttft_s", ttft)
            if tpot is not None:
                hub.observe("serve/tpot_s", tpot)
            fleet.step()
            clock.advance(1.0)

    tick(6, ttft=2.0)  # sustained TTFT burn -> prefill capacity
    assert fleet._roles[max(fleet.live_replicas)] == "prefill"
    tick(8)            # cooldown + window age-out
    tick(6, tpot=1.0)  # sustained TPOT burn -> decode capacity
    assert fleet._roles[max(fleet.live_replicas)] == "decode"
    assert len(fleet.live_replicas) == 4
    # sustained idle: shrink back down, but NEVER through a role floor
    tick(40)
    assert len(fleet.live_replicas) == 2
    roles_left = sorted(fleet._role(i) for i in fleet.live_replicas)
    assert roles_left == ["decode", "prefill"]
    actions = [d["action"] for d in read_decisions(log)]
    assert "grow_prefill" in actions and "grow_decode" in actions
    shrink_kinds = {a for a in actions if a.startswith("shrink")}
    assert shrink_kinds <= {"shrink", "shrink_prefill", "shrink_decode"}
    assert len([a for a in actions if a.startswith("shrink")]) == 2


def test_replica_health_reports_roles():
    fleet = ServingFleet()
    fleet.add_replica(make_paged_batcher(), role="prefill")
    fleet.add_replica(make_paged_batcher(), role="decode")
    fleet.add_replica(make_paged_batcher())
    health = fleet.replica_health()
    assert health["roles"] == {"prefill": 1, "decode": 1, "unified": 1}
    by_idx = {k: v["role"] for k, v in health["replicas"].items()}
    assert by_idx == {"0": "prefill", "1": "decode", "2": "unified"}


def test_add_replica_rejects_unknown_role():
    fleet = ServingFleet()
    with pytest.raises(ValueError, match="role"):
        fleet.add_replica(make_paged_batcher(), role="speculate")


# ---------------------------------------------------------------------------
# e2e acceptance: the whole story under one deterministic clock


def test_e2e_disagg_chaos_acceptance(tmp_path):
    """The ISSUE 20 acceptance leg: a role-split fleet under a mixed
    shared-prefix workload where (1) TTFT and TPOT burns are resolved
    by DIFFERENT scaling decisions, (2) the fleet prefix hit rate for
    the shared prompt is 1.0 (every shipment attempt lands), (3) every
    handoff is token-identical to the unified oracle, and (4) a
    corrupted shipment AND a prefill replica killed mid-handoff both
    recover via continuation with zero leaked pages and flight-recorder
    dumps explaining each action."""
    hub = get_telemetry()
    hub.configure_flight_recorder(tmp_path / "flight")
    clock = FakeClock()
    pub = WeightPublisher()
    pub.publish(dict(_PARAMS))
    fleet = ServingFleet(publisher=pub)
    fleet.add_replica(make_paged_batcher(), role="prefill")
    fleet.add_replica(make_paged_batcher(), role="decode")
    monitor = _burn_monitor(clock).attach(hub)
    log = tmp_path / "decisions.jsonl"
    FleetAutopilot(
        fleet, monitor,
        replica_factory=lambda p: make_paged_batcher(params=dict(_PARAMS)),
        config=AutopilotConfig(
            grow_after_s=3.0, cooldown_s=6.0, min_replicas=2,
            max_replicas=4, idle_after_s=1e9, eval_interval_s=1.0,
            prefill_policies=("ttft_p99",), decode_policies=("tpot_p99",),
            min_prefill_replicas=1, min_decode_replicas=1,
        ),
        decision_log=log, clock=clock,
    ).attach()

    shared = [3, 5, 7, 11, 2, 9, 4, 6]  # two full pages
    expected = {}

    def submit(prompt, n):
        frid = fleet.submit(prompt, max_new_tokens=n)
        expected[frid] = paged_toy_expected(prompt, n)
        return frid

    def tick(rounds, *, ttft=None, tpot=None):
        for _ in range(rounds):
            if ttft is not None:
                hub.observe("serve/ttft_s", ttft)
            if tpot is not None:
                hub.observe("serve/tpot_s", tpot)
            fleet.step()
            clock.advance(1.0)

    # phase 1: mixed-length shared-prefix ramp under a TTFT burn — the
    # autopilot must answer with PREFILL capacity
    for i, n in enumerate((3, 6, 4, 7)):
        submit(shared + [i + 1], n)
    tick(6, ttft=2.0)
    # phase 2: decode-side pressure — TPOT burn, DECODE capacity
    tick(8)
    for i, n in enumerate((5, 6)):
        submit(shared + [20 + i], n)
    tick(6, tpot=1.0)
    assert len(fleet.live_replicas) == 4
    # phase 3: corrupt the next shipment — checksum must catch it
    corrupt_handoff_payload(fleet)
    submit(shared + [27], 5)
    tick(8)
    # phase 4: kill a prefill replica at its next handoff
    prefills = [i for i in fleet.live_replicas
                if fleet._role(i) == "prefill"]
    kill_prefill_mid_handoff(fleet, prefills[0])
    # route the victim's leg onto the armed replica deterministically
    f_kill = fleet.submit(shared + [31], max_new_tokens=5)
    expected[f_kill] = paged_toy_expected(shared + [31], 5)
    if fleet._reqs[f_kill].replica != prefills[0]:
        fleet._chaos_kill_handoff = fleet._reqs[f_kill].replica
    tick(12)
    _drain(fleet, list(expected))
    # (3) every request token-identical to the unified oracle
    for frid, want in expected.items():
        assert fleet.outputs(frid) == want, frid
    # (1) different burns, different decisions
    actions = [d["action"] for d in read_decisions(log)]
    assert "grow_prefill" in actions and "grow_decode" in actions
    snap = hub.registry.snapshot()["counters"]
    # (2) shared-prefix shipments: every attempt landed
    assert snap["serve/fleet_prefix_hits"] >= 1
    assert snap.get("serve/fleet_prefix_misses", 0) == 0
    assert snap["serve/fleet_handoffs"] >= 1
    # (4) both chaos events resolved via fallback, with dumps
    assert snap["serve/handoff_checksum_failures"] >= 1
    assert snap["serve/fleet_handoff_fallbacks"] >= 2
    assert snap["serve/fleet_replica_deaths"] == 1
    assert len(fleet.dead) == 1
    _assert_no_leaks(fleet)
    assert (tmp_path / "flight"
            / "flight_recorder_replica_death.json").exists()
