"""The serving loop's records and instruments.

``ServeAccounting`` is where ``loop/serve.py``'s scheduler reports what
happened: a request was admitted, emitted its first token, finished; a
chunk emitted so many tokens. It owns the counters of a measurement
window (``ServeStats``), the per-request records (``RequestTelemetry``),
the instrument names (replica namespacing), the request trace events and
the live-rate gauge. It decides nothing and touches no device.

Telemetry (docs/design/observability.md): per-request TTFT / TPOT /
queue-wait and per-chunk slot-occupancy histograms are derived from the
host clock at the SAME boundaries the token readbacks already happen at
— the host-interaction contract (one dispatch + one readback per chunk)
is untouched; ``tests/telemetry`` pins ``stats.readbacks`` against it.
The monitoring plane rides the same boundaries: every request carries
a fleet-stable trace id (``request_trace`` JSONL milestones),
``replica_label`` namespaces the serve instruments per replica
(``serve/r{i}/...`` with base-name rollups), and ``metrics_port``
serves live Prometheus ``/metrics`` + ``/healthz`` + ``/readyz`` from
a background thread — all pure host work, zero added readbacks (gated
by ``tools/bench_compare.py``'s exporter leg). The chunk's phase clock
(``serve/phase/*`` closed by ``serve/step``) is the scheduler's: its
marks stand where the phases are.
"""

import dataclasses
import time
import weakref
from typing import Callable, Optional

import numpy as np

# slot-occupancy fraction per chunk/step: 20 linear bins over [0, 1]
UTIL_EDGES = tuple(i / 20 for i in range(21))

# tokens-per-completed-request distribution: 1 .. 4096 tokens, log bins.
# A generation-quality canary signal (docs/design/elasticity.md "SLO
# autopilot"): a bad weight publish that stops hitting EOS shows up as
# this distribution jumping to the budget ceiling on the canary replica
# long before any latency SLO moves.
_REQ_TOKENS_EDGES = tuple(
    1.0 * (4096.0 ** (i / 24)) for i in range(25)
)

# rolling-window span for the live throughput gauge: long enough to
# average over scheduling noise, short enough that a collapse shows
# within seconds on an operator's console/dashboard
_RATE_WINDOW_S = 10.0


@dataclasses.dataclass
class RequestTelemetry:
    """Host-clock milestones for one request, harvested at the same
    boundaries the token readbacks already happen at (chunk
    boundaries) — deriving latency telemetry costs ZERO additional
    device readbacks.

    Granularity contract: first-token and finish times are observed at
    chunk-boundary harvests, so TTFT/TPOT carry up-to-one-chunk
    quantization — exactly the latency a caller of
    ``step_chunk``/``drain`` experiences.
    """

    submit_t: float
    admit_t: float | None = None
    first_tok_t: float | None = None
    finish_t: float | None = None
    tokens: int = 0
    # weights generation of the chunk that FINISHED this request (the
    # publish-versioning audit trail: which params produced the tail)
    weights_version: int | None = None
    # fleet-stable per-request trace id (schema v3 request_trace events)
    trace_id: str | None = None

    @property
    def queue_wait_s(self) -> float | None:
        if self.admit_t is None:
            return None
        return self.admit_t - self.submit_t

    @property
    def ttft_s(self) -> float | None:
        """Submit → first emitted token visible on the host."""
        if self.first_tok_t is None:
            return None
        return self.first_tok_t - self.submit_t

    @property
    def tpot_s(self) -> float | None:
        """Mean per-output-token latency after the first token (the
        serving TPOT convention); None until finished or for
        single-token requests."""
        if self.finish_t is None or self.tokens < 2:
            return None
        return (self.finish_t - self.first_tok_t) / (self.tokens - 1)


@dataclasses.dataclass
class ServeStats:
    """Host-interaction and utilization counters (reset with ``reset()``).

    ``host_dispatches`` counts jitted-call dispatches (the quantity the
    fused loop divides by K); ``readbacks`` counts device→host token
    fetches; ``device_steps`` counts single-token decode steps executed
    on device; ``slot_steps_busy / slot_steps_total`` give slot
    occupancy (busy includes prompt-consumption steps);
    ``slot_steps_prompt`` is the part of busy in which a row only
    consumed a prompt token and emitted nothing, so busy less prompt is
    the generation steps, exactly. ``positions_attended`` sums, over the
    busy slot-steps, the cache positions the step attends (the row's own
    new token included), so over ``slot_steps_busy`` it is the mean
    context a step reads. ``pool_pages_total`` sums the page pool's
    pages in use at each chunk boundary (over ``chunks``: the mean) and
    ``pool_pages_peak`` is the most a boundary saw; both stay 0 without
    paging. ``recurrent_state_bytes`` is a level, not a sum: the bytes
    of the per-row recurrent leaves the batcher's cache holds (0 for an
    attention-only model), as of the last chunk; ``rows_reset`` counts
    the rows whose per-row leaves an admission zeroed and
    ``rows_reset_device_bytes`` the bytes the device wrote to do it
    (rows times a row's share of every per-row leaf).
    ``window_cache_bytes`` is a level too: the bytes of the window
    layers' rings of pages (0 without such layers, or unpaged), and
    ``window_positions_attended`` is ``positions_attended`` for those
    layers: each busy slot-step's context or the layer's window, the
    smaller, summed over the layers that keep a ring. All of that is
    host arithmetic on the plan: no readback. ``moe_rows_held``
    and ``moe_rows_routed`` sum, over the fused chunks' steps and the
    expert layers that hold a range of their router's experts, the
    routed (token, expert) pairs that landed on the held ones and all of
    them, dead rows' included (they step on token 0): the layers' own
    counts, carried out in the chunk's one token readback; 0 for a model
    whose layers hold every expert. ``moe_rows_skipped`` is, of those
    routed pairs, the ones a router with a skip sent to it (ZAYA's
    mixture-of-depths; 0 for a router without one).
    """

    host_dispatches: int = 0
    readbacks: int = 0
    chunks: int = 0
    device_steps: int = 0
    emitted_tokens: int = 0
    slot_steps_busy: int = 0
    slot_steps_prompt: int = 0
    slot_steps_total: int = 0
    positions_attended: int = 0
    pool_pages_total: int = 0
    pool_pages_peak: int = 0
    recurrent_state_bytes: int = 0
    rows_reset: int = 0
    rows_reset_device_bytes: int = 0
    window_cache_bytes: int = 0
    window_positions_attended: int = 0
    moe_rows_held: int = 0
    moe_rows_routed: int = 0
    moe_rows_skipped: int = 0
    # degraded-mode counters: submits rejected by the bounded queue,
    # requests expired by their deadline (queued or running), requests
    # shed by the autopilot's burn-driven admission tiering
    rejected: int = 0
    expired: int = 0
    shed: int = 0

    def reset(self) -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)

    @property
    def dispatches_per_1k_tokens(self) -> float:
        if self.emitted_tokens == 0:
            return float("inf")
        return 1000.0 * self.host_dispatches / self.emitted_tokens

    @property
    def slot_utilization(self) -> float:
        if self.slot_steps_total == 0:
            return 0.0
        return self.slot_steps_busy / self.slot_steps_total


def positions_under(row_spans, window: int) -> int:
    """Positions a window layer attends over ``row_spans``, ``(first
    position, busy steps)`` a row: step ``j`` of a row at ``pos`` sees a
    context of ``pos + j`` (its own token included, ``j`` from 1) and
    the layer reads that or its window, the smaller."""
    if not row_spans:
        return 0
    pos, steps = np.asarray(row_spans, np.int64).T
    whole = np.clip(window - pos, 0, steps)  # steps whose context fits
    return int(np.sum(
        whole * pos + whole * (whole + 1) // 2 + (steps - whole) * window
    ))


def _validate_label(label: str) -> str:
    if not label or "/" in label:
        raise ValueError(f"replica_label must be path-free, got {label!r}")
    return str(label)


class ServeAccounting:
    """One batcher's counters, request records and instruments.

    Monitoring-plane knobs (docs/design/observability.md):
    ``replica_label`` (e.g. ``"r0"`` — ``ServingFleet.add_replica``
    assigns these) namespaces this batcher's serve instruments as
    ``serve/{label}/...`` so N same-process replicas stop blending into
    the shared ``serve/*`` names; counters and latency histograms
    additionally feed the base name as the fleet rollup.
    """

    def __init__(self, telemetry, replica_label: Optional[str] = None):
        self.tele = telemetry
        self.stats = ServeStats()
        # finished-request records are retained bounded-FIFO by the
        # scheduler, with the outputs they describe
        self.request_stats: dict[int, RequestTelemetry] = {}
        # serve/tokens_per_s two-bucket rolling window, evaluated at
        # snapshot time via gauge_fn: a lifetime average would flatten
        # into a constant on a long-lived server, and a last-write-wins
        # gauge would freeze at the last healthy value through a stall —
        # this way an idle/stalled server's rate decays toward zero.
        # Registered through a weakref so the hub (whose gauge_fn
        # registrations are process-lifetime) never pins a discarded
        # batcher's records in memory.
        self.reset_rate_window()
        this = weakref.ref(self)
        self._rate_fn = (
            lambda: a.live_rate() if (a := this()) is not None
            else float("nan")
        )
        # label set BEFORE the first gauge_fn registration: a batcher
        # constructed with a label must never transiently claim (and on
        # labeling, delete) the base-name registration an earlier
        # unlabeled batcher may hold
        self.replica_label: Optional[str] = (
            None if replica_label is None else _validate_label(replica_label)
        )
        self.tele.gauge_fn(self._rate_gauge_name(), self._rate_fn)

    def close(self) -> None:
        """Give up this batcher's gauge registration."""
        self.tele.registry.unregister_gauge_fn(
            self._rate_gauge_name(), self._rate_fn
        )

    def start_metrics_server(self, port: int, probe: Callable[[], Optional[dict]]):
        """The opt-in live metrics endpoint (telemetry/export.py).
        ``probe()`` gives the batcher's ``{"active", "ready",
        "stalled"}``, or None once the batcher is gone: the endpoint
        holds neither it nor this object."""
        from d9d_tpu.telemetry import MetricsServer

        this = weakref.ref(self)

        def status() -> Optional[dict]:
            acct, state = this(), probe()
            if acct is None or state is None:
                return None
            return {"replica": acct.replica_label, **state}

        return MetricsServer(
            self.tele,
            port=port,
            readiness=lambda: (
                (s["ready"], {"replica": s["replica"]})
                if (s := status()) is not None else (False, {})
            ),
            health=lambda: (
                s if (s := status()) is not None else {"gone": True}
            ),
        ).start()

    # -- instrument naming (replica namespacing) -----------------------

    def _rate_gauge_name(self) -> str:
        return (
            f"serve/{self.replica_label}/tokens_per_s"
            if self.replica_label else "serve/tokens_per_s"
        )

    def set_replica_label(self, label: str) -> None:
        """Namespace the serve instruments as ``serve/{label}/...`` (the
        fleet assigns ``r{i}``). Re-homes the live-rate callback gauge;
        subsequent records use the new name. Counters/histograms keep
        feeding the base ``serve/*`` name too — the fleet rollup the
        unlabeled world saw stays intact. (Prefer ``replica_label=`` at
        construction: an unlabeled batcher holds the base-name rate
        gauge until this call, with the pre-existing
        last-registration-wins semantics across unlabeled batchers.)"""
        label = _validate_label(label)
        # fn-guarded: only tears down THIS batcher's registration
        self.close()
        self.replica_label = label
        self.tele.gauge_fn(self._rate_gauge_name(), self._rate_fn)

    def _mname(self, name: str) -> str:
        # name always carries the "serve/" prefix at call sites
        return f"serve/{self.replica_label}/{name[6:]}"

    def counter_add(self, name: str, n: float = 1.0) -> None:
        self.tele.counter(name).add(n)
        if self.replica_label:
            self.tele.counter(self._mname(name)).add(n)

    def observe(self, name: str, v: float, edges=None) -> None:
        # base name first: SLO digests key on the fleet-level metric
        self.tele.observe(name, v, edges)
        if self.replica_label:
            self.tele.observe(self._mname(name), v, edges)

    def gauge_set(self, name: str, v: float) -> None:
        # gauges are last-write-wins: a shared base name would blend N
        # replicas, so labeled batchers write ONLY their namespaced
        # gauge; fleet-level gauges are computed by ServingFleet as
        # explicit rollups
        self.tele.gauge(
            self._mname(name) if self.replica_label else name
        ).set(v)

    # -- per-request trace events (schema v3, docs/design/observability.md)

    def trace(
        self,
        trace_id: Optional[str],
        event: str,
        t: float,
        *,
        rid: Optional[int] = None,
        **meta,
    ) -> None:
        if trace_id is None:
            return
        rec: dict = {"trace_id": trace_id, "event": event, "t": t}
        if self.replica_label is not None:
            rec["replica"] = self.replica_label
        if rid is not None:
            rec["rid"] = rid
        if meta:
            rec["meta"] = meta
        self.tele.record_request_trace(rec)

    # -- request milestones (host clock only; see RequestTelemetry) ----

    def note_submit(
        self, rid: int, now: float, trace_id: str, queued: int, **meta
    ) -> None:
        self.request_stats[rid] = RequestTelemetry(
            submit_t=now, trace_id=trace_id
        )
        self.gauge_set("serve/queued", queued)
        self.trace(trace_id, "submit", now, rid=rid, **meta)

    def note_prefix_lookup(self, hit_tokens: int) -> None:
        """An admission walked the prefix cache: what it found."""
        if hit_tokens:
            self.counter_add("serve/prefix_cache_hits")
            self.counter_add("serve/prefix_cache_hit_tokens", hit_tokens)
        else:
            self.counter_add("serve/prefix_cache_misses")

    def note_admit(self, rid: int, queued: int) -> None:
        rec = self.request_stats[rid]
        rec.admit_t = time.perf_counter()
        self.observe("serve/queue_wait_s", rec.queue_wait_s)
        self.gauge_set("serve/queued", queued)
        self.trace(rec.trace_id, "admit", rec.admit_t, rid=rid)

    def note_tokens(self, rid: int, n: int, now: float) -> None:
        rec = self.request_stats[rid]
        if rec.first_tok_t is None:
            rec.first_tok_t = now
            self.observe("serve/ttft_s", rec.ttft_s)
            self.trace(rec.trace_id, "first_token", now, rid=rid)
        rec.tokens += n

    def note_finish(self, rid: int, now: float, version: int) -> None:
        rec = self.request_stats[rid]
        rec.finish_t = now
        rec.weights_version = version
        tpot = rec.tpot_s
        if tpot is not None:
            self.observe("serve/tpot_s", tpot)
        self.observe(
            "serve/request_tokens", float(rec.tokens), _REQ_TOKENS_EDGES
        )
        self.counter_add("serve/requests_finished")
        self.trace(
            rec.trace_id, "finish", now, rid=rid,
            tokens=rec.tokens, weights_version=rec.weights_version,
        )

    def note_failed(self, rid: int, reason: str, now: float) -> None:
        """Accounting keyed on the reason: "expired" means deadline
        expiry and nothing else (the degraded-mode signal operators
        alert on); "shed" is the autopilot's deliberate load-shedding
        (its own alertable signal — shed traffic is policy, not a
        fault); other retirements (fleet shrink) count serve/failed."""
        if reason == "deadline":
            self.stats.expired += 1
            self.counter_add("serve/expired")
        elif reason == "shed":
            self.stats.shed += 1
            self.counter_add("serve/shed")
        else:
            self.counter_add("serve/failed")
        rec = self.request_stats.get(rid)
        if rec is None:
            return
        if rec.finish_t is None:
            rec.finish_t = now
        self.trace(
            rec.trace_id,
            "expired" if reason == "deadline" else "failed",
            now, rid=rid, reason=reason, tokens=rec.tokens,
        )

    # -- the live rate -------------------------------------------------

    def reset_rate_window(self) -> None:
        now = time.perf_counter()
        self._rate_win_t0 = now
        self._rate_win_tokens = 0
        self._rate_prev_t0 = now
        self._rate_prev_tokens = 0

    def live_rate(self) -> float:
        """Tokens over the current + previous window, against the age of
        the older one — evaluated at flush/snapshot time, so it reflects
        'now' even when no harvest has run since the last flush."""
        dt = time.perf_counter() - self._rate_prev_t0
        if dt <= 0:
            return float("nan")
        return (self._rate_win_tokens + self._rate_prev_tokens) / dt

    def note_throughput(self, new_tokens: int, now: float) -> None:
        self.counter_add("serve/tokens", new_tokens)
        self.gauge_set(
            "serve/slot_utilization", self.stats.slot_utilization
        )
        self._rate_win_tokens += new_tokens
        if now - self._rate_win_t0 >= _RATE_WINDOW_S:
            self._rate_prev_t0 = self._rate_win_t0
            self._rate_prev_tokens = self._rate_win_tokens
            self._rate_win_t0 = now
            self._rate_win_tokens = 0
