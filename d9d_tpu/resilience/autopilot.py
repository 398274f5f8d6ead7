"""SLO autopilot: burn-rate-driven fleet control
(docs/design/elasticity.md "SLO autopilot", ROADMAP item 3).

PR 9 built the senses — burn-rate ``SloMonitor`` policies, per-replica
``serve/r{i}/*`` instruments, the flight recorder — and PR 8 built the
actuators — ``ServingFleet.grow/shrink``, eject/migrate, the
zero-recompile ``install_weights`` publish path. Nothing connected
them: a burning TTFT policy paged an operator who acted by hand. This
module is the controller in between. :class:`FleetAutopilot` subscribes
to ``SloMonitor`` evaluations (sense) and drives the fleet through
three policies (act), every action producing an auditable
``autopilot/*`` telemetry bump, a JSONL decision-log line, and — for
destructive actions — a flight-recorder dump:

- **Burn-driven autoscaling.** A scale policy burning continuously for
  ``grow_after_s`` grows a cold replica from the publisher's latest
  (known-good) weights; a fleet that is idle — queue depth AND slot
  utilization under their floors — for ``idle_after_s`` shrinks back
  toward ``min_replicas`` through the existing migration path.
  Hysteresis both directions: sustained-burn / sustained-idle windows
  plus a shared ``cooldown_s`` between scale actions, so an oscillating
  load cannot flap the fleet.
- **Admission tiering under burn.** While a scale policy burns, queued
  traffic beyond ``shed_queue_depth`` is shed lowest-priority /
  longest-deadline first (``ServingFleet.shed_queued`` →
  ``failed[frid] == "shed"``, ``serve/shed``) instead of failing
  uniformly with ``QueueFullError`` at the front door — the
  backpressure contract is unchanged, the autopilot just chooses WHO
  absorbs it.
- **Canaried weight publish.** ``WeightPublisher.publish_canary``
  installs a candidate generation on one replica; the autopilot scopes
  temporary per-replica SLO policies over that replica's
  ``serve/r{i}/*`` instruments (``SloMonitor.extend``) next to
  same-window rollup twins, and after ``canary_window_s`` compares the
  deltas: a canary observably worse than both the policy target and
  the fleet rollup (× ``canary_tolerance``) rolls back to the retained
  prior tree (flight-recorder dump); otherwise it promotes fleet-wide.

Control-loop discipline (the bench-gated contract): SLO evaluations may
run on scrape threads, so the subscriber only *records* the latest
statuses; all fleet mutation happens in :meth:`poll`, which
``ServingFleet.step`` calls once per scheduling round at the clean
boundary before any chunk dispatches. The autopilot is pure host work —
no jax imports, zero added per-token dispatches/readbacks
(``tools/bench_compare.py``'s autopilot leg pins the structural counts
byte-identical to the plain serving leg).

Every quantity the controller reasons about flows through the
injectable ``clock`` (default ``time.monotonic``), so hysteresis,
decision windows and the chaos acceptance leg run deterministically
without sleeping wall time.
"""

import dataclasses
import json
import logging
import math
import threading
import time
from pathlib import Path
from typing import Any, Callable, Optional, Sequence

from d9d_tpu.telemetry import get_telemetry
from d9d_tpu.telemetry.slo import SloPolicy, SloStatus

logger = logging.getLogger("d9d_tpu.resilience")

__all__ = [
    "AutopilotConfig",
    "DecisionLog",
    "FleetAutopilot",
    "read_decisions",
]

# canary comparator twins must never page or bump slo/violations on
# their own — they exist to be READ at the decision point, so their
# burn threshold is unreachable (observed/target can't meaningfully hit
# 1e18x) and ``violating`` stays False however bad the canary is
_CANARY_BURN_RATE = 1e18


@dataclasses.dataclass(frozen=True)
class AutopilotConfig:
    """Control thresholds (all durations in clock seconds).

    ``scale_policies`` / ``canary_policies`` name which of the
    monitor's policies drive autoscaling+shedding / the canary verdict;
    ``None`` means every registered policy. ``shed_queue_depth=None``
    disables shedding. ``canary_min_samples=0`` makes the canary
    promote-unless-observably-bad (an unobserved canary promotes at the
    window end instead of waiting for traffic); with it positive, a
    canary still unobserved after ``canary_max_wait_s`` rolls back —
    never promote weights nobody has watched serve.
    """

    scale_policies: Optional[tuple[str, ...]] = None
    grow_after_s: float = 30.0
    cooldown_s: float = 60.0
    min_replicas: int = 1
    max_replicas: int = 4
    # disaggregated serving (docs/design/elasticity.md): policies named
    # here steer burn-driven grows into a role pool — a burning TTFT
    # policy should add PREFILL capacity and a burning TPOT policy
    # DECODE capacity (the two pools bottleneck on different resources).
    # Unlisted policies grow a unified replica, exactly as before roles
    # existed. The per-role minimums floor idle shrink per pool.
    prefill_policies: Optional[tuple[str, ...]] = None
    decode_policies: Optional[tuple[str, ...]] = None
    min_prefill_replicas: int = 0
    min_decode_replicas: int = 0
    idle_after_s: float = 120.0
    idle_queue_depth: float = 0.0
    idle_slot_utilization: float = 0.25
    shed_queue_depth: Optional[int] = None
    canary_policies: Optional[tuple[str, ...]] = None
    canary_window_s: float = 30.0
    canary_tolerance: float = 1.25
    canary_min_samples: int = 1
    canary_max_wait_s: float = 120.0
    # staleness bound on the cached statuses: poll() triggers its own
    # monitor evaluation when nothing (flush/scrape) evaluated recently
    eval_interval_s: float = 1.0

    def __post_init__(self):
        if self.min_replicas < 0 or self.max_replicas < self.min_replicas:
            raise ValueError(
                f"need 0 <= min_replicas <= max_replicas, got "
                f"{self.min_replicas}, {self.max_replicas}"
            )
        for name in ("grow_after_s", "cooldown_s", "idle_after_s",
                     "canary_max_wait_s", "eval_interval_s",
                     "min_prefill_replicas", "min_decode_replicas"):
            if getattr(self, name) < 0:
                raise ValueError(f"{name} must be >= 0")
        if self.canary_window_s <= 0:
            # the canary twins are real SloPolicy windows, which must
            # be positive; use an epsilon window for an immediate
            # next-poll decision (deterministic tests, the bench leg)
            raise ValueError("canary_window_s must be > 0")
        if self.canary_tolerance < 1.0:
            raise ValueError(
                f"canary_tolerance must be >= 1, got {self.canary_tolerance}"
            )


class DecisionLog:
    """Append-only JSONL audit log of control decisions.

    One line per decision (schema below, validated by
    :func:`read_decisions`); each line is flushed as written —
    decisions are rare and the log must survive the crash it may be
    explaining::

        {"kind": "autopilot_decision", "schema": 1, "action": "grow",
         "unix_time": ..., "reason": "...", "detail": {...}}
    """

    SCHEMA = 1
    REQUIRED = ("kind", "schema", "action", "unix_time", "reason")

    def __init__(self, path: str | Path):
        self.path = Path(path)
        self._fh = None

    def append(
        self, action: str, *, reason: str, detail: dict | None = None
    ) -> dict:
        rec: dict[str, Any] = {
            "kind": "autopilot_decision",
            "schema": self.SCHEMA,
            "action": action,
            "unix_time": time.time(),
            "reason": reason,
        }
        if detail:
            rec["detail"] = detail
        try:
            if self._fh is None:
                self.path.parent.mkdir(parents=True, exist_ok=True)
                self._fh = open(self.path, "a")
            self._fh.write(json.dumps(rec, sort_keys=True) + "\n")
            self._fh.flush()
        except OSError:
            logger.exception("autopilot decision log write failed")
        return rec

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


def read_decisions(path: str | Path) -> list[dict]:
    """Parse + validate a decision log; raises ``ValueError`` on a
    malformed line (the round-trip contract tests pin)."""
    out = []
    with open(path) as fh:
        for i, line in enumerate(fh):
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            missing = [k for k in DecisionLog.REQUIRED if k not in rec]
            if missing:
                raise ValueError(
                    f"{path}:{i + 1}: decision missing fields {missing}"
                )
            if rec["kind"] != "autopilot_decision":
                raise ValueError(
                    f"{path}:{i + 1}: unexpected kind {rec['kind']!r}"
                )
            if not (
                isinstance(rec["schema"], int)
                and 1 <= rec["schema"] <= DecisionLog.SCHEMA
            ):
                raise ValueError(
                    f"{path}:{i + 1}: schema {rec['schema']!r} not in "
                    f"supported range [1, {DecisionLog.SCHEMA}]"
                )
            out.append(rec)
    return out


@dataclasses.dataclass
class _CanaryTrack:
    """Autopilot-side state for one pending canary decision."""

    publish: Any           # the publisher's _CanaryPublish identity
    label: str             # canary replica's serve/{label}/* namespace
    t0: float              # clock() at tracking start
    # (watched policy, canary twin name, baseline twin name)
    twins: list[tuple[SloPolicy, str, str]]


class FleetAutopilot:
    """Close the sense→act loop between an ``SloMonitor`` and a
    ``ServingFleet`` (module docstring for the control policies).

    ``replica_factory(params) -> batcher`` is what ``grow`` hands to
    ``ServingFleet.grow``; without it (or without a publisher holding
    published weights) grow decisions are skipped with a logged
    ``grow_blocked`` decision. ``decision_log`` (a path) enables the
    JSONL audit log. ``clock`` must be the same clock the monitor uses
    when determinism matters (the chaos tests share one fake clock).

    Call :meth:`attach` to wire in (idempotent to :meth:`detach`); the
    fleet then polls the autopilot once per scheduling round.
    """

    def __init__(
        self,
        fleet,
        monitor,
        *,
        publisher=None,
        replica_factory: Optional[Callable[[Any], Any]] = None,
        config: AutopilotConfig | None = None,
        decision_log: str | Path | None = None,
        telemetry=None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.fleet = fleet
        self.monitor = monitor
        self.publisher = (
            publisher if publisher is not None else fleet._publisher
        )
        self.replica_factory = replica_factory
        self.config = config if config is not None else AutopilotConfig()
        self.log = (
            DecisionLog(decision_log) if decision_log is not None else None
        )
        self._tele = telemetry if telemetry is not None else get_telemetry()
        self._clock = clock
        # the subscriber may run on scrape threads; poll() runs on the
        # fleet's scheduling thread — the cached statuses are the only
        # shared state, everything fleet-mutating stays in poll()
        self._lock = threading.Lock()
        self._statuses: dict[str, SloStatus] = {}
        self._last_eval_t: float | None = None
        self._burn_since: float | None = None
        self._burning_names: tuple[str, ...] = ()
        self._idle_since: float | None = None
        self._last_scale_t: float = -math.inf
        self._grow_blocked_logged = False
        self._canary: Optional[_CanaryTrack] = None
        self._last_decision: dict | None = None
        self._in_poll = False

    # -- wiring ---------------------------------------------------------

    def attach(self) -> "FleetAutopilot":
        if self._on_evaluation not in self.monitor.subscribers:
            self.monitor.subscribers.append(self._on_evaluation)
        self.fleet._autopilot = self
        return self

    def detach(self) -> None:
        if self._on_evaluation in self.monitor.subscribers:
            self.monitor.subscribers.remove(self._on_evaluation)
        if self.fleet._autopilot is self:
            self.fleet._autopilot = None
        if self._canary is not None:
            self.monitor.remove(
                [n for _, c, b in self._canary.twins for n in (c, b)]
            )
            self._canary = None
        if self.log is not None:
            self.log.close()

    def _on_evaluation(self, statuses: Sequence[SloStatus]) -> None:
        """Monitor subscriber: cache the freshest statuses. Bookkeeping
        only — may run on a scrape thread, must never touch the fleet.
        The cache is REPLACED, not upserted: every evaluation covers all
        current policies, so a policy retired via ``monitor.remove``
        must drop out here too — a stale violating status would keep
        shedding and growing forever with no live policy behind it."""
        with self._lock:
            self._statuses = {s.policy.name: s for s in statuses}
            self._last_eval_t = self._clock()

    # -- introspection (fleet /healthz autopilot block) -----------------

    def status(self) -> dict[str, Any]:
        # snapshot the racy fields into locals first: poll() (the
        # scheduling thread) rebinds them without the lock, and a
        # /healthz scrape must never crash between a None-check and the
        # deref because _finish_canary ran in the gap
        track = self._canary
        burn_since = self._burn_since
        idle_since = self._idle_since
        with self._lock:
            last_decision = self._last_decision
        now = self._clock()
        canary = None
        if track is not None:
            canary = {
                "label": track.label,
                "version": track.publish.version,
                "age_s": round(now - track.t0, 3),
            }
        return {
            "burning": list(self._burning_names),
            "burn_age_s": (
                round(now - burn_since, 3)
                if burn_since is not None else None
            ),
            "idle_age_s": (
                round(now - idle_since, 3)
                if idle_since is not None else None
            ),
            "canary": canary,
            "last_decision": last_decision,
        }

    # -- decision plumbing ---------------------------------------------

    def _decide(
        self, action: str, *, reason: str, detail: dict | None = None
    ) -> None:
        """One auditable decision: counter + log line + cached status."""
        self._tele.counter("autopilot/decisions").add(1)
        rec: dict[str, Any] = {"action": action, "reason": reason}
        if detail:
            rec["detail"] = detail
        if self.log is not None:
            rec = self.log.append(action, reason=reason, detail=detail)
        with self._lock:
            self._last_decision = rec
        logger.info("autopilot: %s (%s)", action, reason)

    # -- the control loop (fleet scheduling-round cadence) --------------

    def poll(self) -> None:
        """One control tick, called by ``ServingFleet.step`` at the
        round boundary. Refreshes stale SLO state, then runs the three
        policies: canary decision, burn actions (shed, grow), idle
        shrink. Re-entrant calls (a shrink's nested stepping) no-op."""
        if self._in_poll:
            return
        self._in_poll = True
        try:
            now = self._clock()
            with self._lock:
                last_eval = self._last_eval_t
            if (
                last_eval is None
                or now - last_eval >= self.config.eval_interval_s
            ):
                # nothing flushed/scraped recently: evaluate ourselves
                # (pure host work; the subscriber refreshes the cache)
                self.monitor.evaluate()
            self._poll_canary(now)
            self._poll_scaling(now)
        finally:
            self._in_poll = False

    def _watched(self, names: Optional[tuple[str, ...]]) -> list[SloStatus]:
        with self._lock:
            statuses = dict(self._statuses)
        if names is None:
            # every non-temporary policy (canary twins judge the canary,
            # they must not drive autoscaling of the whole fleet)
            temp = set()
            if self._canary is not None:
                for _, c, b in self._canary.twins:
                    temp.add(c)
                    temp.add(b)
            return [s for n, s in statuses.items() if n not in temp]
        return [statuses[n] for n in names if n in statuses]

    # -- policy (a): burn-driven autoscaling + (b): shedding ------------

    def _utilization(self) -> float:
        busy = total = 0
        for i in self.fleet._live:
            b = self.fleet._replicas[i]
            busy += sum(1 for s in b._slots if s.rid >= 0)
            total += len(b._slots)
        return busy / total if total else 0.0

    def _poll_scaling(self, now: float) -> None:
        cfg = self.config
        burning = [
            s for s in self._watched(cfg.scale_policies) if s.violating
        ]
        with self._lock:
            self._burning_names = tuple(
                sorted(s.policy.name for s in burning)
            )
        self._tele.gauge("autopilot/burning_policies").set(
            float(len(burning))
        )
        live = len(self.fleet._live)
        if burning:
            self._idle_since = None
            if self._burn_since is None:
                self._burn_since = now
            self._shed(now, burning)
            if (
                now - self._burn_since >= cfg.grow_after_s
                and now - self._last_scale_t >= cfg.cooldown_s
                and live < cfg.max_replicas
            ):
                self._grow(now, burning)
            return
        self._burn_since = None
        self._grow_blocked_logged = False
        # idle shrink: queue AND utilization under their floors
        depth = self.fleet._queue_depth()
        util = self._utilization()
        idle = (
            live > cfg.min_replicas
            and depth <= cfg.idle_queue_depth
            and util <= cfg.idle_slot_utilization
        )
        if not idle:
            self._idle_since = None
            return
        if self._idle_since is None:
            self._idle_since = now
            return
        if (
            now - self._idle_since >= cfg.idle_after_s
            and now - self._last_scale_t >= cfg.cooldown_s
        ):
            # never shrink the pending canary replica out from under
            # its own decision window: a retired batcher stays strongly
            # referenced by the fleet, so the comparator would just see
            # an eternally-unobserved canary and roll back good weights
            canary_b = (
                self.publisher.canary.target()
                if self.publisher is not None
                and self.publisher.canary is not None else None
            )
            # idle shrink is per POOL when roles are in play: a role's
            # last replicas above its floor are fair game, the floor
            # itself is not — min_prefill/min_decode keep each side of
            # a disaggregated fleet from shrinking to nothing while the
            # other side's idleness drives the decision
            role_counts: dict[str, int] = {}
            for i in self.fleet._live:
                r = self.fleet._role(i)
                role_counts[r] = role_counts.get(r, 0) + 1
            floors = {"prefill": cfg.min_prefill_replicas,
                      "decode": cfg.min_decode_replicas}
            candidates = [
                i for i in sorted(self.fleet._live, reverse=True)
                if self.fleet._replicas[i] is not canary_b
                and role_counts[self.fleet._role(i)]
                > floors.get(self.fleet._role(i), 0)
            ]
            if not candidates:
                return  # only the canary / role floors are left
            idx = candidates[0]
            role = self.fleet._role(idx)
            action = {
                "prefill": "shrink_prefill", "decode": "shrink_decode",
            }.get(role, "shrink")
            # dump BEFORE the drain so the black box shows the fleet
            # the decision was made against
            self._tele.dump_flight_record(
                "autopilot_shrink",
                extra={"replica": idx, "queue_depth": depth,
                       "slot_utilization": util},
            )
            self.fleet.shrink(idx)
            self._last_scale_t = now
            self._idle_since = None
            self._tele.counter("autopilot/shrinks").add(1)
            self._decide(
                action,
                reason=(
                    f"idle {self.config.idle_after_s:g}s: queue_depth "
                    f"{depth:g} <= {cfg.idle_queue_depth:g}, utilization "
                    f"{util:.3f} <= {cfg.idle_slot_utilization:g}"
                ),
                detail={"replica": idx, "role": role,
                        "live_replicas": live - 1},
            )

    def _grow(self, now: float, burning: list[SloStatus]) -> None:
        # the guard checks the FLEET's publisher, not self.publisher:
        # fleet.grow() cold-starts the new replica from fleet._publisher
        # and raises without one — a divergent publisher= kwarg must
        # produce a logged grow_blocked, never crash the scheduling loop
        fleet_pub = self.fleet._publisher
        if (
            self.replica_factory is None
            or fleet_pub is None
            or fleet_pub.latest_params is None
        ):
            if not self._grow_blocked_logged:
                self._grow_blocked_logged = True
                self._decide(
                    "grow_blocked",
                    reason="no replica_factory or no weights published "
                           "on the fleet's publisher to cold-start from",
                    detail={"burning": [s.policy.name for s in burning]},
                )
            return
        cfg = self.config
        worst = max(burning, key=lambda s: s.burn)
        # role-aware capacity (disaggregated serving): the WORST burning
        # policy picks the pool — a TTFT burn means prefill is the
        # bottleneck, a TPOT burn means decode is; distinct decision
        # kinds keep the audit log attributable per pool
        role, action = "unified", "grow"
        if cfg.prefill_policies and worst.policy.name in cfg.prefill_policies:
            role, action = "prefill", "grow_prefill"
        elif cfg.decode_policies and worst.policy.name in cfg.decode_policies:
            role, action = "decode", "grow_decode"
        idx = self.fleet.grow(self.replica_factory, role=role)
        self._last_scale_t = now
        self._tele.counter("autopilot/grows").add(1)
        self._decide(
            action,
            reason=(
                f"{worst.policy.name} burning {worst.burn:.2f}x for >= "
                f"{self.config.grow_after_s:g}s"
            ),
            detail={
                "replica": idx,
                "role": role,
                "live_replicas": len(self.fleet._live),
                "weights_version": fleet_pub.latest_version,
                "burning": {
                    s.policy.name: round(s.burn, 4) for s in burning
                },
            },
        )

    def _shed(self, now: float, burning: list[SloStatus]) -> None:
        cfg = self.config
        if cfg.shed_queue_depth is None:
            return
        depth = self.fleet._queue_depth()
        excess = int(depth - cfg.shed_queue_depth)
        if excess <= 0:
            return
        shed = self.fleet.shed_queued(excess)
        if not shed:
            return
        self._tele.counter("autopilot/shed_requests").add(len(shed))
        self._tele.dump_flight_record(
            "autopilot_shed",
            extra={"shed": len(shed), "queue_depth": depth,
                   "burning": [s.policy.name for s in burning]},
        )
        self._decide(
            "shed",
            reason=(
                f"queue depth {depth:g} > {cfg.shed_queue_depth} while "
                f"{', '.join(s.policy.name for s in burning)} burning"
            ),
            detail={"shed_frids": shed, "queue_depth_after":
                    self.fleet._queue_depth()},
        )

    # -- policy (c): canaried weight publish ----------------------------

    def publish_canary(self, params, *, replica: Optional[int] = None) -> int:
        """Stage a canary generation on one live fleet replica (default:
        the highest-index one — usually the most recently grown) and
        start the decision clock; returns the canary generation stamp.
        Thin orchestration over ``WeightPublisher.publish_canary`` so
        callers never have to pick a batcher by hand."""
        if self.publisher is None:
            raise RuntimeError("publish_canary needs a WeightPublisher")
        if not self.fleet._live:
            raise RuntimeError("publish_canary needs a live replica")
        idx = replica if replica is not None else max(self.fleet._live)
        if idx not in self.fleet._live:
            raise ValueError(f"replica {idx} is not live")
        return self.publisher.publish_canary(
            params, batcher=self.fleet._replicas[idx]
        )

    def _replica_scoped(self, name: str, label: str) -> str:
        return (
            f"serve/{label}/{name[6:]}" if name.startswith("serve/")
            else name
        )

    @staticmethod
    def _already_replica_scoped(p: SloPolicy) -> bool:
        """Does the policy read a replica-labeled instrument already?
        Base serve instruments are ``serve/{name}`` (one segment);
        labeled ones are ``serve/{label}/{name}``. An already-scoped
        policy is a per-replica objective — rewriting it for the canary
        would fabricate ``serve/{canary}/{label}/...`` names nothing
        records, and comparing one replica against another replica's
        objective is not a canary-vs-fleet comparison at all."""
        return any(
            n.startswith("serve/") and n.count("/") >= 2
            for n in (p.metric, p.bad, *p.good)
        )

    def _canary_twins(
        self, label: str
    ) -> list[tuple[SloPolicy, str, str]]:
        """Temporary policy pairs for one canary decision: a
        replica-scoped twin of each watched policy plus a same-window
        rollup baseline twin — same horizon, so the comparison is
        apples to apples. Neither can page (``_CANARY_BURN_RATE``)."""
        cfg = self.config
        twins = []
        for p in self._canary_watched():
            cname = f"canary_{label}_{p.name}"
            bname = f"canary_base_{p.name}"
            common = dict(
                target=p.target, window_s=cfg.canary_window_s,
                burn_rate=_CANARY_BURN_RATE, kind=p.kind,
                quantile=p.quantile,
            )
            canary_p = SloPolicy(
                name=cname,
                metric=self._replica_scoped(p.metric, label),
                bad=self._replica_scoped(p.bad, label),
                good=tuple(
                    self._replica_scoped(g, label) for g in p.good
                ),
                min_samples=max(cfg.canary_min_samples, 1)
                if p.kind == "rate" else cfg.canary_min_samples,
                **common,
            )
            base_p = SloPolicy(
                name=bname, metric=p.metric, bad=p.bad, good=p.good,
                min_samples=1, **common,
            )
            twins.append((p, cname, bname))
            # isolate: the twins' decision window must start clean even
            # when (metric, window) collides with a standing policy
            self.monitor.extend([canary_p, base_p], isolate=True)
        return twins

    def _canary_watched(self) -> list[SloPolicy]:
        names = self.config.canary_policies
        out = []
        for p in self.monitor.policies:
            if p.name.startswith(("canary_",)):
                continue
            if self._already_replica_scoped(p):
                continue  # per-replica objectives are not fleet baselines
            if names is None or p.name in names:
                out.append(p)
        return out

    def _rollback_canary(self, *, reason: str, detail: dict) -> None:
        """The ONE rollback contract, however the decision was reached:
        publisher rollback (fresh stamp), tracking teardown, counter,
        flight-recorder black box (a rollback is destructive — the dump
        is promised for every one of them), decision-log entry."""
        version = self.publisher.rollback_canary()
        self._finish_canary()
        self._tele.counter("autopilot/canary_rollbacks").add(1)
        self._tele.dump_flight_record(
            "autopilot_rollback", extra={"reason": reason, **detail},
        )
        self._decide(
            "canary_rollback", reason=reason,
            detail={**detail, "rollback_version": version},
        )

    def _poll_canary(self, now: float) -> None:
        cfg = self.config
        pub = self.publisher
        pending = pub.canary if pub is not None else None
        if self._canary is None:
            if pending is None:
                self._tele.gauge("autopilot/canary_pending").set(0.0)
                return
            b = pending.target()
            label = getattr(b, "replica_label", None) if b else None
            if label is None:
                # unlabeled / dead target: nothing to compare against —
                # roll straight back rather than promote blind
                self._rollback_canary(
                    reason="canary replica has no serve/{label}/* "
                           "namespace (dead or unlabeled): cannot be "
                           "observed, never promoted blind",
                    detail={"version": pending.version},
                )
                return
            self._canary = _CanaryTrack(
                publish=pending, label=label, t0=now,
                twins=self._canary_twins(label),
            )
            self._tele.gauge("autopilot/canary_pending").set(1.0)
            self._decide(
                "canary_start",
                reason=f"generation {pending.version} canaried on "
                       f"{label}; deciding in {cfg.canary_window_s:g}s",
                detail={"version": pending.version, "replica": label},
            )
            return
        track = self._canary
        if pending is not track.publish:
            # superseded (a plain publish landed) or externally resolved
            self._finish_canary()
            self._decide(
                "canary_superseded",
                reason="a fleet-wide publish (or external resolution) "
                       "replaced the pending canary before its decision",
                detail={"version": track.publish.version},
            )
            return
        if track.publish.target() is None:
            # the canary replica died mid-window (kill): its device
            # tree died with it — clear, don't promote
            self._rollback_canary(
                reason="canary replica died before the decision window "
                       "closed",
                detail={"version": track.publish.version,
                        "replica": track.label},
            )
            return
        if now - track.t0 < cfg.canary_window_s:
            return
        self._decide_canary(now, track)

    def _decide_canary(self, now: float, track: _CanaryTrack) -> None:
        cfg = self.config
        statuses = {s.policy.name: s for s in self.monitor.evaluate()}
        verdicts = {}
        unobserved = []
        bad = False
        # a 1-replica fleet has no independent baseline: the rollup IS
        # the canary's own traffic, so canary > rollup x tolerance is
        # unsatisfiable there and a bad canary would always promote —
        # fall back to the absolute policy target as the verdict line
        sole = len(self.fleet._live) <= 1
        for orig, cname, bname in track.twins:
            cs, bs = statuses.get(cname), statuses.get(bname)
            if cs is None:
                continue
            if cs.samples < max(cfg.canary_min_samples, 1):
                if cfg.canary_min_samples > 0:
                    unobserved.append(orig.name)
                continue
            base_obs = bs.observed if bs is not None else float("nan")
            worse_than_fleet = (
                sole
                or not math.isfinite(base_obs)
                or cs.observed > base_obs * cfg.canary_tolerance
            )
            this_bad = (
                math.isfinite(cs.observed)
                and cs.observed > orig.target
                and worse_than_fleet
            )
            bad = bad or this_bad
            verdicts[orig.name] = {
                "canary": round(cs.observed, 6)
                if math.isfinite(cs.observed) else None,
                "fleet": round(base_obs, 6)
                if math.isfinite(base_obs) else None,
                "target": orig.target,
                "samples": cs.samples,
                "bad": this_bad,
            }
        if unobserved and not bad:
            if now - track.t0 < cfg.canary_max_wait_s:
                return  # keep waiting for traffic to reach the canary
            self._rollback_canary(
                reason=(
                    f"canary on {track.label} saw no traffic on "
                    f"{', '.join(unobserved)} within "
                    f"{cfg.canary_max_wait_s:g}s: never promote weights "
                    "nobody watched serve"
                ),
                detail={"version": track.publish.version,
                        "replica": track.label, "verdicts": verdicts},
            )
            return
        if bad:
            self._rollback_canary(
                reason=(
                    f"canary on {track.label} over the policy target "
                    "with no independent fleet baseline (1-replica "
                    f"fleet) over {cfg.canary_window_s:g}s"
                    if sole else
                    f"canary on {track.label} worse than the fleet "
                    f"rollup beyond {cfg.canary_tolerance:g}x over "
                    f"{cfg.canary_window_s:g}s"
                ),
                detail={"version": track.publish.version,
                        "replica": track.label, "verdicts": verdicts},
            )
        else:
            version = self.publisher.promote_canary()
            self._finish_canary()
            self._tele.counter("autopilot/canary_promotes").add(1)
            self._decide(
                "canary_promote",
                reason=(
                    f"canary on {track.label} within the policy "
                    f"targets over {cfg.canary_window_s:g}s (1-replica "
                    "fleet: no independent baseline)"
                    if sole else
                    f"canary on {track.label} within {cfg.canary_tolerance:g}x "
                    f"of the fleet rollup over {cfg.canary_window_s:g}s"
                ),
                detail={"version": version, "verdicts": verdicts},
            )

    def _finish_canary(self) -> None:
        track = self._canary
        self._canary = None
        self._tele.gauge("autopilot/canary_pending").set(0.0)
        if track is not None:
            self.monitor.remove(
                [n for _, c, b in track.twins for n in (c, b)]
            )
