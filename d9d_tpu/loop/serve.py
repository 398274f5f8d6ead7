"""Continuous batching: a slot-based serving loop over decode models.

Beyond-reference surface (the reference's ``Inference`` is forward-only
batch scoring; its serving story ends there). ``ContinuousBatcher``
keeps a fixed batch of ``batch_size`` slots decoding through a jitted
decode loop; requests are admitted into free slots as they arrive and
evicted on EOS/budget — rows never wait for each other (the vLLM-style
iteration-level scheduling loop, in its static-shape TPU form).

Host-interaction contract (the perf-defining design decision): the
inner decode loop is FUSED — ``chunk_size`` (K) single-token steps run
as one jitted ``lax.scan`` that advances all slots, applies per-row
stop/length masks in-device, and accumulates emitted tokens into a
device-side ``[B, K]`` buffer. The host performs ONE dispatch and ONE
token readback per K generated tokens instead of per token; admission,
eviction and finished-row harvesting happen only at chunk boundaries.
Rows that finish mid-chunk (budget or EOS) are masked dead in-device —
their emissions stop and their ``cache_index`` pins to 0 the same step,
so the capacity contract holds without per-token host intervention —
and are harvested at the boundary. ``drain()`` additionally
double-buffers: while no admissions are waiting, chunk N+1 is
dispatched before chunk N's tokens are fetched (its plan is
deterministic — prompt feeding and positions advance device-side), so
the readback overlaps device compute via XLA async dispatch.

Static shapes are the law under XLA, so admission is TOKEN-LEVEL: the
loop always processes exactly one token per slot per device step. A
newly admitted request spends its first ``len(prompt)`` steps consuming
its prompt (teacher-forced through the same decode step — cache
contents and the final-position logits are bit-identical to a one-shot
prefill), then flips to generation. The price is prompt consumption at
one token per step; long prompts can instead be pre-filled out-of-band
with ``generate``'s chunked prefill and handed over — the primitives
compose, this loop stays shape-static.

Parity contract: greedy serving of any admission schedule must emit,
per request, exactly the tokens ``generate(model, params, prompt)``
produces — ``tests/loop/test_serve.py`` drives staggered schedules
against that oracle. (With ``temperature > 0`` the RNG stream is
consumed per chunk, so sampled outputs are valid draws but not
bitwise-identical across chunk sizes.)

Telemetry (docs/design/observability.md): every chunk is partitioned
gap-free by an always-on phase clock, the serving twin of the Trainer's
(``serve/phase/{admit,plan,dispatch,readback,commit}`` closed by
``serve/step``: host clock only, on whether or not a profiler is), so a
stall inside a chunk names its phase with no capture live. The
admission, plan, dispatch, readback and commit regions additionally
carry ``serve.*`` ``core/tracing.annotate`` labels inside profiler
capture windows (``benchmarks/harness/trace.py`` attributes device-idle
gaps to them), and each chunk's ``serve/step`` span says what its
dispatch cost the host: the wrapper's signature walk, the enqueue and
the argument leaves (``TrackedJit.last_call``), the stagings' seconds
and count.

This module is the scheduler and the program it dispatches. What the
cache is and who resides in it is ``loop/serve_cache.py``'s
(``CacheManager``: the layout of pools, tables, rings and per-row
state, the page allocator and prefix cache, the shipment format); the
counters, request records, instrument names and the metrics endpoint
are ``loop/serve_accounting.py``'s (``ServeAccounting``). Both are
plain objects built in ``ContinuousBatcher.__init__``; neither knows
the scheduler or the other.

Live weight publish (docs/design/elasticity.md): the jitted executables
take the parameter tree as a *traced argument* — never a trace-time
closure constant — so :meth:`ContinuousBatcher.install_weights` can
swap in a freshly published tree at a chunk boundary with an unchanged
``tracked_jit`` fingerprint (same shapes/dtypes/placements): no
restart, no steady-state recompile (``tools/bench_compare.py`` gates
this). Swaps are generation-stamped (``weights_version``); chunks
already dispatched complete on the weights they were dispatched with,
and ``defer_to_idle`` holds the swap until every in-flight request has
finished, so those requests complete wholly on the old generation.
"""

import _thread
import collections
import contextlib
import dataclasses
import inspect
import itertools
import os
import threading
import time
import weakref
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from d9d_tpu.core.tracing import annotate
from d9d_tpu.core.tree_sharding import normalize_params
from d9d_tpu.loop.quantize import dequantize_params, is_quantized_tree
from d9d_tpu.loop.serve_accounting import (
    UTIL_EDGES,
    ServeAccounting,
    positions_under,
)
from d9d_tpu.loop.serve_cache import (
    TRANSFER_BUDGET_BYTES,
    CacheManager,
    KVPageShipment,
    admit_rows,
    pin_idle_rows,
    write_table,
)
from d9d_tpu.nn.decode_flags import caller_holds_bounds
from d9d_tpu.telemetry import get_telemetry, tracked_jit

# what the expert layers that hold a range of their router's experts sow
# into ``moe_stats`` a step (nn/moe.py), in the order the fused chunk
# carries their sums out: ``ServeStats.moe_<name>``
_MOE_ROW_COUNTS = ("rows_held", "rows_routed", "rows_skipped")

# per-request trace ids (docs/design/observability.md): pid + a process
# counter — unique across a multi-process fleet without coordination,
# deterministic within one process (chaos tests assert exact sequences)
_TRACE_IDS = itertools.count()


def mint_trace_id() -> str:
    """A fleet-stable request trace id: minted once at the FIRST submit
    (fleet front door or direct batcher submit) and carried through
    queue → chunk dispatch → migration → kill-recovery continuation, so
    one id follows the request across every replica it touches."""
    return f"req-{os.getpid():x}-{next(_TRACE_IDS):x}"


class QueueFullError(RuntimeError):
    """``submit()`` rejected: the bounded admission queue is full.

    Degraded-mode backpressure (docs/design/resilience.md): an overload
    becomes an explicit, retryable rejection the caller can shed or
    redirect — not an unbounded host-memory queue that dies later.
    """


class ServeStalledError(RuntimeError):
    """``drain()`` aborted by the stall watchdog: no dispatch/readback
    progress within ``stall_timeout_s`` while work was outstanding —
    a wedged device/runtime surfaces as an error, not a silent hang."""


@dataclasses.dataclass
class _Slot:
    rid: int = -1            # active request id, -1 = idle
    pos: int = 0             # next cache position this row writes
    # prompt tokens not yet dispatched as step inputs
    feed: list = dataclasses.field(default_factory=list)
    emitted: int = 0         # committed (harvested) emissions
    budget: int = 0          # max_new_tokens for the active request
    deadline_t: float | None = None  # absolute perf_counter deadline


@dataclasses.dataclass
class _Request:
    rid: int
    prompt: list
    max_new_tokens: int
    deadline_t: float | None = None
    trace_id: str | None = None
    # admission tier (docs/design/elasticity.md "SLO autopilot"): higher
    # = more important. Admission itself stays FIFO (token-identity
    # contract); priority is what burn-driven shedding orders on —
    # lowest priority / longest deadline sheds first.
    priority: int = 0

    @property
    def total_tokens(self) -> int:
        """Cache slots this request writes over its lifetime: every
        prompt token plus every generated token except the final
        sample (emitted but never fed back). THE footprint every page
        computation keys on — submit validation, the queue-full
        capacity credit and allocation must never disagree by a page."""
        return len(self.prompt) + self.max_new_tokens - 1


@dataclasses.dataclass
class _ChunkPlan:
    """Host-side record of one dispatched fused chunk, consumed FIFO at
    harvest time: enough to replay the device's emission/stop logic on
    the readback without fetching any mask buffers."""

    k: int
    rids: list            # rid per slot at dispatch (-1 = idle)
    emit_from: list       # first step index (within the chunk) that emits
    pos: list             # cache position each row writes at the first step
    version: int = 0      # weights generation this chunk dispatched with
    index: int = 0        # chunk index since the last stats reset


class _ChunkColumns(NamedTuple):
    """Columns of a fused chunk's one host-made argument, an int32
    ``[B, K + 5 (+ pages a row)]`` array: ``_dispatch_chunk`` fills them
    on the host, ``_build_fused``'s program slices them."""

    forced: slice      # [B, K]: prompt tokens forced step by step
    n_forced: int      # how many of them the row has this chunk
    emit_from: int     # first step index that emits
    admit_mask: int    # 1 on a row admitted with this chunk
    admit_budget: int  # its max_new_tokens
    admit_pos: int     # paged: first position past its prefix-cache hit
    table: slice       # paged: the allocator's page table, to the end


def _chunk_columns(k: int) -> _ChunkColumns:
    return _ChunkColumns(
        slice(0, k), k, k + 1, k + 2, k + 3, k + 4, slice(k + 5, None)
    )


class ContinuousBatcher:
    """Iteration-level scheduler over a KV-cache decode model.

    ``model`` must be built with ``decode_max_length`` ≥ the longest
    ``len(prompt) + max_new_tokens - 1`` it will serve. ``submit()``
    queues a request (admitted into the first free slot at the next
    step/chunk boundary); ``step()`` advances every active slot by one
    token and returns ``{rid: token}`` for tokens EMITTED this step
    (generation phase only); ``step_chunk()`` advances by ``chunk_size``
    tokens in one dispatch and returns ``{rid: [tokens]}``.
    ``outputs[rid]`` accumulates; ``drain()`` runs (double-buffered)
    chunks until every submitted request finishes.

    ``chunk_size``: decode steps fused per dispatch (default 8);
    ``drain()`` keeps one chunk in flight while the previous chunk's
    tokens are fetched.
    """

    # finished RequestTelemetry records retained for the host stats API
    _MAX_FINISHED_STATS = 50_000

    def __init__(
        self,
        model,
        params,
        *,
        batch_size: int,
        eos_id: Optional[int] = None,
        temperature: float = 0.0,
        rng: Optional[jax.Array] = None,
        chunk_size: int = 8,
        telemetry=None,
        max_queue: Optional[int] = None,
        stall_timeout_s: Optional[float] = None,
        replica_label: Optional[str] = None,
        metrics_port: Optional[int] = None,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        prefix_cache: Optional[bool] = None,
        kv_quant: Optional[str] = None,
    ):
        """Degraded-mode knobs (docs/design/resilience.md): ``max_queue``
        bounds the admission queue — ``submit()`` past it raises
        :class:`QueueFullError` (explicit backpressure). Requests may
        carry per-request deadlines (``submit(..., deadline_s=...)``)
        that expire them cleanly whether queued or running.
        ``stall_timeout_s`` arms a drain watchdog: no host
        dispatch/readback progress for that long with work outstanding
        raises :class:`ServeStalledError` instead of hanging.

        ``replica_label`` namespaces this batcher's serve instruments
        (:class:`~d9d_tpu.loop.serve_accounting.ServeAccounting`) and
        ``metrics_port`` (0 = ephemeral) starts a
        :class:`~d9d_tpu.telemetry.MetricsServer` for this batcher —
        ``/metrics`` in Prometheus text, ``/readyz`` not-ready until the
        first readback has round-tripped; call :meth:`close` (or use the
        fleet's endpoint instead) to shut it down.

        ``page_size``, ``num_pages``, ``prefix_cache`` and ``kv_quant``
        are the cache's
        (:class:`~d9d_tpu.loop.serve_cache.CacheManager`): a paged KV
        pool with a prefix cache, optionally int8."""
        if temperature > 0.0 and rng is None:
            raise ValueError("temperature > 0 needs an rng key")
        if chunk_size is None or chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if stall_timeout_s is not None and stall_timeout_s <= 0:
            raise ValueError(
                f"stall_timeout_s must be > 0, got {stall_timeout_s}"
            )
        self._model = model
        self._cache_mgr = CacheManager(
            model, batch_size=batch_size, page_size=page_size,
            num_pages=num_pages, prefix_cache=prefix_cache,
            kv_quant=kv_quant,
        )
        # latent-placement fix (same class as the PR 5 resume bug): a
        # param tree handed over from a restored checkpoint can carry
        # uncommitted scalar leaves whose single-device placement
        # conflicts with the mesh-placed majority at the first dispatch
        self._params = normalize_params(params)
        self._b = batch_size
        self._eos = eos_id
        self._temp = temperature
        # a copy of the caller's key: the fused chunk takes the key as a
        # donated carry (it splits it in the program), and the caller's
        # array must outlive that
        self._rng = (
            jnp.copy(rng) if rng is not None else jax.random.PRNGKey(0)
        )
        self._k = chunk_size

        self._slots = [_Slot() for _ in range(batch_size)]
        self._queue: collections.deque[_Request] = collections.deque()
        self._next_rid = 0
        self.outputs: dict[int, list[int]] = {}
        self.done: set[int] = set()
        # degraded-mode state: rid → failure reason ("deadline") for
        # requests retired without completing; done includes them so
        # drain() terminates and harvests skip their rows
        self.failed: dict[int, str] = {}
        self._max_queue = max_queue
        self._stall_timeout_s = stall_timeout_s
        self._progress_t = time.perf_counter()
        self._stalled = False
        # per-request latency telemetry (serve/* namespace): recorded into
        # the process hub unless an isolated hub is injected
        self._tele = telemetry if telemetry is not None else get_telemetry()
        self._acct = ServeAccounting(self._tele, replica_label)
        self.stats = self._acct.stats
        # finished-request state (stats records, output token lists, done
        # flags) is retained bounded-FIFO (_MAX_FINISHED_STATS): a
        # long-lived server must not grow host memory linearly with total
        # requests served — read results within that retention horizon
        self.request_stats = self._acct.request_stats
        self._finished_rids: collections.deque[int] = collections.deque()
        # readiness (telemetry/export.py /readyz contract): a batcher is
        # ready once one readback has round-tripped — the executables
        # are compiled and the device answered. Deliberately NOT reset
        # by reset_measurement: warmth survives a bench window reset.
        self._first_readback_t: Optional[float] = None

        method = getattr(model, "logits_last", None) or model.logits
        self._method = method
        accepts_padding = (
            "padding_mask" in inspect.signature(method).parameters
        )
        self._step_pad = (
            jnp.ones((batch_size, 1), jnp.bool_) if accepts_padding else None
        )

        # jitted executables are built lazily: each distinct fused K
        # compiles its own scan
        self._fused: dict[tuple[int, bool], object] = {}  # (k, with_admit)
        self._cache = self._cache_mgr.zeros()
        # static per-batcher fact: what of the cache is per-row recurrent
        # state, beside the serve/kv_* gauges of the paged part
        self._acct.gauge_set(
            "serve/recurrent_state_bytes",
            self._cache_mgr.recurrent_state_bytes,
        )
        self._acct.gauge_set(
            "serve/window_cache_bytes", self._cache_mgr.window_cache_bytes
        )
        if self._cache_mgr.paged:
            # static per-batcher fact, but exported so dashboards (and
            # the bench accounting) can tell quantized pools apart
            # without reverse-engineering bytes-per-page
            self._acct.gauge_set(
                "serve/kv_quant_enabled", 0.0 if kv_quant is None else 1.0
            )

        # live weight publish (docs/design/elasticity.md): staged tree
        # swapped in at the next dispatch boundary, generation-stamped
        self.weights_version = 0
        self._pending_weights: tuple | None = None

        # device carries (one buffer each, donated through)
        self._tok_d = jnp.zeros((batch_size,), jnp.int32)
        self._pos_d = jnp.zeros((batch_size,), jnp.int32)
        self._live_d = jnp.zeros((batch_size,), jnp.bool_)
        self._rem_d = jnp.zeros((batch_size,), jnp.int32)
        # dispatched-but-unharvested fused chunks, FIFO
        self._pending: collections.deque[tuple] = collections.deque()
        # the open chunk's phase clock (step_chunk / step); None inside
        # the overlapped drain, where one chunk's harvest overlaps the
        # next one's compute: each dispatch and each harvest then times
        # its own phases and no ``serve/step`` partition is emitted
        self._clock = None

        # opt-in live metrics endpoint; a weakref so the endpoint can
        # never pin a discarded batcher's device cache
        self.metrics_server = None
        if metrics_port is not None:
            ref = weakref.ref(self)
            self.metrics_server = self._acct.start_metrics_server(
                metrics_port,
                lambda: (
                    {"active": b.active, "ready": b.ready,
                     "stalled": b._stalled}
                    if (b := ref()) is not None else None
                ),
            )

    @property
    def ready(self) -> bool:
        """Past the first readback round-trip (compiled + device alive)
        — the /readyz contract for this batcher."""
        return self._first_readback_t is not None

    def close(self) -> None:
        """Release host-side attachments (the metrics endpoint and this
        batcher's gauge registrations); the batcher itself stays usable
        except for scraping."""
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None
        self._acct.close()

    @property
    def replica_label(self) -> Optional[str]:
        return self._acct.replica_label

    def set_replica_label(self, label: str) -> None:
        """:meth:`ServeAccounting.set_replica_label` (the fleet assigns
        ``r{i}``)."""
        self._acct.set_replica_label(label)

    def live_rate(self) -> float:
        """Tokens a second over the live-rate gauge's rolling window."""
        return self._acct.live_rate()

    # ------------------------------------------------------------------
    def submit(
        self,
        prompt: Sequence[int],
        *,
        max_new_tokens: int,
        deadline_s: Optional[float] = None,
        trace_id: Optional[str] = None,
        priority: int = 0,
    ) -> int:
        """Queue a request; returns its request id. Admission happens at
        the next step/chunk boundary with a free slot.

        ``deadline_s`` (relative, host clock) expires the request at the
        next boundary after the deadline passes — whether it is still
        queued or already decoding (partial output is kept, the request
        lands in ``failed[rid] == "deadline"``). With ``max_queue``
        configured, a full queue rejects with :class:`QueueFullError`
        before a rid is allocated.

        ``priority`` is the admission tier (higher = more important).
        It does NOT reorder admission (FIFO — the token-identity
        contract); it orders burn-driven shedding: while an SLO policy
        burns, the fleet autopilot retires the lowest-priority /
        longest-deadline queued requests first (:meth:`cancel_queued`,
        ``failed[rid] == "shed"``) instead of failing traffic uniformly
        at the front door.

        ``trace_id`` carries an existing per-request trace id (the fleet
        mints one at ITS front door and re-submits with it across
        migrations); a direct submit mints a fresh one. Milestones ride
        schema-v3 ``request_trace`` events and the id is readable as
        ``request_stats[rid].trace_id``.
        """
        prompt = [int(x) for x in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        need = len(prompt) + max_new_tokens - 1
        dml = self._cache_mgr.decode_max_length
        if need > dml:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens}"
                f" - 1 = {need} exceeds decode_max_length={dml}"
            )
        self._cache_mgr.check_fits(need)
        now = time.perf_counter()
        minted_here = trace_id is None
        if minted_here:
            trace_id = mint_trace_id()
        if self._max_queue is not None:
            # count only live waiters: requests whose deadline already
            # passed must not hold queue capacity against new traffic
            self._expire_queued(now)
            if len(self._queue) >= self._max_queue:
                # running-side mirror of the PR 5 queued-side fix: a
                # deadline-expired RUNNING row frees a slot this
                # boundary, which the queue head is guaranteed to admit
                # into — count those frees as capacity before rejecting
                freed = self._expire_running(now)
                # paged admission is PAGE-bounded, not slot-bounded: the
                # freed slot is only real capacity if the queue head can
                # map onto pages by the next admit boundary
                if freed and not self._cache_mgr.fits_after_flush(
                    self._queue[0].total_tokens
                ):
                    freed = 0
                if len(self._queue) - freed >= self._max_queue:
                    self.stats.rejected += 1
                    self._acct.counter_add("serve/rejected")
                    if minted_here:
                        # terminal only for a front-door submit: a fleet
                        # placement attempt (external trace id) that
                        # this replica rejects may still land on a
                        # survivor — the fleet emits the terminal event
                        # if ALL reject
                        self._acct.trace(trace_id, "rejected", now,
                                         queued=len(self._queue))
                    raise QueueFullError(
                        f"admission queue full ({len(self._queue)} >= "
                        f"max_queue={self._max_queue}); retry after drain"
                    )
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(_Request(
            rid, prompt, max_new_tokens,
            deadline_t=now + deadline_s if deadline_s is not None else None,
            trace_id=trace_id,
            priority=int(priority),
        ))
        self.outputs[rid] = []
        self._acct.note_submit(
            rid, now, trace_id, len(self._queue),
            prompt_len=len(prompt), max_new_tokens=max_new_tokens,
        )
        return rid

    @property
    def active(self) -> int:
        return sum(1 for s in self._slots if s.rid >= 0) + len(self._queue)

    def _busy(self) -> bool:
        return any(s.rid >= 0 for s in self._slots)

    def reset_measurement(self) -> None:
        """Zero the counters, per-request records, accumulated outputs and
        the throughput-rate window. Bench harnesses call this after a
        warmup/compile request so recorded stats (and the
        ``serve/tokens_per_s`` gauge's window) cover only the timed
        window. Only valid while idle — live requests still need their
        ``request_stats`` records."""
        if self.active:
            raise RuntimeError(
                "reset_measurement() with requests queued or in flight"
            )
        self.stats.reset()
        self.request_stats.clear()
        self._finished_rids.clear()
        self.outputs.clear()
        self.done.clear()
        self.failed.clear()
        self._cache_mgr.reset_window()
        self._acct.reset_rate_window()

    # ------------------------------------------------------------------
    # live weight publish (docs/design/elasticity.md)

    def install_weights(
        self,
        params,
        *,
        version: Optional[int] = None,
        defer_to_idle: bool = False,
    ) -> int:
        """Stage a published parameter tree; the swap happens at the
        next dispatch boundary (a chunk boundary) — never mid-chunk, so
        chunks already in flight complete on the weights they were dispatched with.

        The tree must match the serving model's structure, shapes and
        placement (it is the same model, freshly trained): the jitted
        executables then keep their compiled signature and NO
        steady-state recompile happens. ``defer_to_idle`` holds the
        swap until no slot is busy, so requests in flight at install
        time finish wholly on the old generation (note: under sustained
        load this can defer indefinitely — it is a drain-style publish
        for low-traffic windows and deterministic tests). Returns the
        generation number the install will carry.
        """
        # generations are strictly monotonic PER BATCHER: two installs
        # before a boundary get distinct versions, and an external
        # version (a publisher whose own counter lags this batcher's)
        # is floored up rather than allowed to regress — otherwise two
        # different trees could share a stamp and the audit trail
        # couldn't tell which produced a request's tail
        staged = (
            self._pending_weights[1] if self._pending_weights is not None
            else self.weights_version
        )
        floor = max(self.weights_version, staged) + 1
        version = floor if version is None else max(int(version), floor)
        self._pending_weights = (
            normalize_params(params), int(version), time.perf_counter(),
            bool(defer_to_idle),
        )
        return int(version)

    def _apply_pending_weights(self) -> None:
        """Swap a staged publish in at a dispatch boundary. The old
        tree's device buffers stay alive exactly as long as an
        in-flight chunk references them (XLA holds the arguments), then
        free — device-side donation of nothing: the swap itself moves
        no data and dispatches nothing."""
        if self._pending_weights is None:
            return
        params, version, t0, defer = self._pending_weights
        if defer and self._busy():
            return  # in-flight requests finish on the old weights
        self._pending_weights = None
        self._params = params
        self.weights_version = int(version)
        # cached prefix KV was computed under the OLD weights: a
        # post-publish hit would silently attend stale pages and break
        # the token-identity contract — drop every entry (the next cold
        # fill re-caches under the new generation). In-flight rows are
        # untouched; like the contiguous path, they finish on the cache
        # they built.
        dropped = self._cache_mgr.invalidate_prefix_cache()
        if dropped is not None:
            if dropped:
                self._acct.counter_add(
                    "serve/prefix_cache_invalidated", dropped
                )
            # stamp the invalidation with the weights generation that
            # caused it: a canary rollback's re-invalidation is then
            # distinguishable from the publish invalidation it undoes
            # (both drop entries; only the stamp tells them apart)
            self._acct.gauge_set(
                "serve/prefix_cache_invalidated_version", version
            )
            self._note_pages()
        self._acct.counter_add("serve/weight_publish")
        self._acct.observe(
            "serve/weight_publish_s", time.perf_counter() - t0
        )
        self._acct.gauge_set("serve/weights_version", version)
        if is_quantized_tree(params):
            # generation stamp of the last QUANTIZED tree installed (a
            # rollback to full precision leaves it at the rolled-back
            # generation — the gauge answers "which quantizer output is
            # live / was last live", not "is the live tree quantized")
            self._acct.gauge_set("serve/weight_quant_version", version)

    # ------------------------------------------------------------------
    # fleet support (resilience/elastic.ServingFleet)

    def eject_queued(self) -> list[tuple[int, list, int, Optional[float]]]:
        """Remove every queued (never-admitted) request from the
        admission queue; returns ``[(rid, prompt, max_new_tokens,
        deadline_t)]``. The rids' outputs/stats records are left in
        place: the caller (``ServingFleet.shrink``) decides per request
        whether to migrate it (and drop this replica's records) or to
        retire it as an explicit failure — ejection must never make a
        request silently unobservable."""
        out = []
        while self._queue:
            req = self._queue.popleft()
            self._cache_mgr.drop_request(req.rid)
            out.append(
                (req.rid, list(req.prompt), req.max_new_tokens,
                 req.deadline_t)
            )
        if out:
            self._acct.gauge_set("serve/queued", 0)
        return out

    def fail_request(self, rid: int, reason: str) -> None:
        """Retire a not-yet-finished request as an explicit failure
        (``failed[rid] = reason``, partial output kept) — the fleet's
        surface for requests it cannot migrate."""
        if rid in self.done:
            return
        self._fail(rid, reason, time.perf_counter())

    def cancel_queued(self, rid: int, reason: str = "shed") -> bool:
        """Remove a still-QUEUED (never-admitted) request and retire it
        as an explicit failure (``failed[rid] = reason``, observable
        empty output) — the autopilot's shed surface. Returns False
        when ``rid`` is not in the queue (already admitted, finished,
        or unknown): an in-flight request is never yanked mid-decode;
        the caller decides what to do instead."""
        for req in self._queue:
            if req.rid == rid:
                self._queue.remove(req)
                self._fail(rid, reason, time.perf_counter())
                self._acct.gauge_set("serve/queued", len(self._queue))
                return True
        return False

    # ------------------------------------------------------------------
    # cross-replica KV page shipment (loop/serve_cache.py): only a clean
    # boundary has an exact pool view, and EVERY failure returns
    # None/False — the caller falls back to plain continuation
    # re-prefill

    def export_kv_pages(
        self,
        tokens: Sequence[int],
        *,
        transfer_budget_bytes: int = TRANSFER_BUDGET_BYTES,
    ) -> Optional[KVPageShipment]:
        """:meth:`CacheManager.export_pages` at a clean boundary. None
        when not paged, mid-chunk or nothing is cached."""
        if self._pending:
            return None
        # same boundary discipline as import: a staged publish means the
        # cache below is the OLD generation — apply it (invalidating the
        # stale entries) rather than stamping dead pages with a version
        # the importer would refuse anyway
        self._apply_pending_weights()
        ship = self._cache_mgr.export_pages(
            self._cache, tokens, weights_version=self.weights_version,
            transfer_budget_bytes=transfer_budget_bytes,
        )
        if ship is not None:
            self._acct.counter_add("serve/handoff_exports")
            self._acct.counter_add("serve/handoff_pages", ship.n_pages)
            self._acct.counter_add("serve/handoff_bytes", ship.nbytes)
            self._acct.counter_add("serve/handoff_chunks", ship.chunks)
        return ship

    def import_kv_pages(
        self,
        ship: KVPageShipment,
        *,
        transfer_budget_bytes: int = TRANSFER_BUDGET_BYTES,
    ) -> bool:
        """:meth:`CacheManager.import_pages` at a clean boundary. False
        on any rejection (mid-chunk, checksum, format or weights
        generation): the caller falls back to continuation re-prefill."""
        if self._pending:
            return False
        # an import IS a dispatch-boundary mutation: swap a staged
        # publish in first, exactly as the next _dispatch_chunk would —
        # otherwise a freshly-grown (idle) replica still reports the
        # pre-publish generation and refuses every current-gen shipment
        self._apply_pending_weights()
        cache, pages, refusal = self._cache_mgr.import_pages(
            self._cache, ship,
            # a publish still staged (deferred to idle): no generation
            # of a shipment matches
            weights_version=(
                self.weights_version if self._pending_weights is None
                else None
            ),
            transfer_budget_bytes=transfer_budget_bytes,
        )
        if cache is None:
            if refusal == "version_mismatch":
                self._acct.counter_add("serve/handoff_version_mismatch")
            elif refusal == "checksum":
                self._acct.counter_add("serve/handoff_checksum_failures")
            return False
        self._cache = cache
        self._acct.counter_add("serve/handoff_imports")
        self._acct.counter_add("serve/handoff_pages", pages)
        self._note_pages()
        return True

    # ------------------------------------------------------------------
    # residency (loop/serve_cache.py), told to the accounting

    def _release_row_pages(self, row: int, *, device_dead: bool) -> None:
        """Drop a retired row's page references: at once when the row
        died in-device or no chunk is in flight, deferred otherwise
        (:meth:`CacheManager.release_row`)."""
        self._cache_mgr.release_row(
            row, defer=not device_dead and bool(self._pending)
        )
        self._note_pages()

    def _note_pages(self) -> None:
        """Refresh the page-pool gauges (and the peak-concurrency
        accounting both modes share) — pure host arithmetic."""
        mgr = self._cache_mgr
        running = sum(1 for s in self._slots if s.rid >= 0)
        mgr.note_running(running)
        if not mgr.paged:
            return
        in_use = mgr.pages_in_use
        self._acct.gauge_set("serve/kv_pages_in_use", in_use)
        self._acct.gauge_set("serve/kv_pages_free", mgr.pages_free)
        self._acct.gauge_set(
            "serve/kv_hbm_bytes_per_request",
            in_use * mgr.page_bytes / max(1, running),
        )

    def hbm_bytes_per_request(self) -> float:
        """:meth:`CacheManager.hbm_bytes_per_request`."""
        return self._cache_mgr.hbm_bytes_per_request()

    def prefix_hit_rate(self) -> float:
        """:meth:`CacheManager.prefix_hit_rate`."""
        return self._cache_mgr.prefix_hit_rate()

    def _retire(self, rid: int) -> None:
        # bound the finished/failed-request retention (FIFO) — stats
        # record, output token list, done and failed flags together, so
        # host memory stays flat however many requests a long-lived
        # server processes; the aggregate histograms already captured
        # the latencies
        self._finished_rids.append(rid)
        while len(self._finished_rids) > self._MAX_FINISHED_STATS:
            old = self._finished_rids.popleft()
            self.request_stats.pop(old, None)
            self.outputs.pop(old, None)
            self.done.discard(old)
            self.failed.pop(old, None)

    # -- degraded mode: deadlines (docs/design/resilience.md) ----------

    def _fail(self, rid: int, reason: str, now: float) -> None:
        self.failed[rid] = reason
        self.done.add(rid)
        self._cache_mgr.drop_request(rid)
        self._acct.note_failed(rid, reason, now)
        self._retire(rid)

    def _expire_queued(self, now: float) -> None:
        """Drop queued requests whose deadline passed — an explicit
        failure the caller can observe, not a silent never-ran."""
        if not self._queue:
            return
        live = collections.deque()
        for req in self._queue:
            if req.deadline_t is not None and now >= req.deadline_t:
                self._fail(req.rid, "deadline", now)
            else:
                live.append(req)
        if len(live) != len(self._queue):
            self._queue = live
            self._acct.gauge_set("serve/queued", len(self._queue))

    def _expire_running(self, now: float) -> int:
        """Evict running rows past their deadline at a boundary; returns
        how many it evicted. The device row goes on decoding into the
        void until the slot is reused: emissions for a done rid are
        dropped at harvest."""
        evicted = 0
        for i, slot in enumerate(self._slots):
            if (
                slot.rid < 0
                or slot.deadline_t is None
                or now < slot.deadline_t
                or slot.rid in self.done
            ):
                continue
            self._fail(slot.rid, "deadline", now)
            self._slots[i] = _Slot()
            evicted += 1
            # the device twin may still be live: the free is deferred
            # when chunks are in flight
            self._release_row_pages(i, device_dead=False)
        return evicted

    # ------------------------------------------------------------------
    # the fused chunk: its program (the reader of the packed columns) and
    # its dispatch (their writer), one dispatch + one readback per K steps

    def _model_step(self, params, cache, tok, pos, held_rows=None):
        """One single-token decode call (trace-time helper of the fused
        executables). ``held_rows`` (the fused
        chunk's ``[2]`` int32 running sums, for a model that counts
        them) comes back with this step's ``rows_held`` and
        ``rows_routed`` added, summed over the layers that sow them.
        ``params`` is a TRACED
        argument, never a closure constant: that is what lets
        :meth:`install_weights` swap trees without retracing — the
        executable's signature (shapes/dtypes/placements) is identical
        across publishes, so ``tracked_jit`` sees the same fingerprint.

        A quantized tree (``loop/quantize.py``: int8 ``qvalue`` +
        per-channel ``scale`` sub-leaves) dequantizes HERE, inside the
        traced program: XLA streams the int8 bytes from HBM and widens
        per-tile at the matmul, which is the whole point — the weight
        stream halves while the compiled signature stays a pure
        function of the (quantized) tree's shapes/dtypes. On an
        unquantized tree this is a structural no-op."""
        params = dequantize_params(params)
        kwargs = {"mask": None}
        if self._step_pad is not None:
            kwargs["padding_mask"] = self._step_pad
        # submit() refuses what would pass decode_max_length and a step
        # takes one token, so the modules trace no debug check: its
        # effect would cost the program jax's C++ dispatch
        counted = held_rows is not None
        with caller_holds_bounds():
            logits, state = self._model.apply(
                {"params": params, "cache": cache},
                tok[:, None], pos[:, None],
                method=self._method,
                mutable=["cache", "moe_stats"] if counted else ["cache"],
                **kwargs,
            )
        row_logits = logits[:, -1].astype(jnp.float32)
        if not counted:
            return state["cache"], row_logits
        from flax.traverse_util import flatten_dict

        sown = flatten_dict(state["moe_stats"])
        step_rows = jnp.stack([
            jnp.asarray(sum(v for p, v in sown.items() if p[-1] == name))
            for name in _MOE_ROW_COUNTS
        ]).astype(jnp.int32)
        return state["cache"], row_logits, held_rows + step_rows

    def _sample(self, row_logits, key):
        if self._temp == 0.0:
            return jnp.argmax(row_logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, row_logits / self._temp, axis=-1
        ).astype(jnp.int32)

    def _build_fused(self, k: int, with_admit: bool):
        """Compile one fused K-step executable. ``with_admit`` variants
        open with the admitted rows' cache zeroing + carry resets fused
        into the same dispatch; the no-admit variant (every follow-up
        chunk, all speculative chunks) traces none of it. The zeroing
        (``decode_flags.zero_rows``) is a loop over the admitted rows,
        as many trips as the mask has rows set, counted on the device:
        each trip writes one zero row into each per-row leaf in place,
        the donated cache passes through it into the steps' scan without
        a copy, and one program serves any number of admitted rows.

        Everything the host decided for the chunk arrives as ONE int32
        array, ``packed`` (:func:`_chunk_columns`; built and staged by
        ``_dispatch_chunk``), and the program takes it apart: the forced
        prompt tokens (transposed to the scan's ``[K, B]``), how many of
        them a row has, the step it emits from, the admission's mask,
        budgets and first positions (read by the ``with_admit`` variant
        only; both take the same array, so there are two programs a
        ``K``), and in paged mode the host allocator's page table. The
        RNG key is a carry: the program splits it as the host used to,
        samples from the second half and hands the first back.

        What the program does to the cache pytree is
        ``loop/serve_cache.py``'s three functions: :func:`admit_rows`
        (paged, the rows' positions jump with their write index to
        ``admit_pos``, the first token past their prefix-cache hit),
        :func:`write_table` before the first step and
        :func:`pin_idle_rows` after every step."""
        eos = self._eos
        paged = self._cache_mgr.paged
        counted = self._cache_mgr.counts_held_rows
        cols = _chunk_columns(k)

        def fused_fn(params, cache, tok, pos, live, rem, key, packed):
            # forced_t: scan xs layout [K, B]
            forced_t = packed[:, cols.forced].T
            n_forced = packed[:, cols.n_forced]
            emit_from = packed[:, cols.emit_from]
            if with_admit:
                admit_mask = packed[:, cols.admit_mask] != 0
                admit_budget = packed[:, cols.admit_budget]
                # boundary work, fused into the same dispatch: zero
                # admitted rows' cache and reset their carries
                admit_pos = packed[:, cols.admit_pos] if paged else None
                cache = admit_rows(cache, admit_mask, admit_pos)
                pos = jnp.where(admit_mask, admit_pos if paged else 0, pos)
                live = jnp.where(admit_mask, True, live)
                rem = jnp.where(admit_mask, admit_budget, rem)
            if paged:
                cache = write_table(cache, packed[:, cols.table], live)
            # the split the host made before every chunk, same bits
            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, k)

            def body(carry, xs):
                (cache, tok, pos, live, rem), held_rows = carry[:5], carry[5:]
                j, kj, fj = xs
                # input: host-forced prompt token while any remain for
                # this row, else the previous step's sampled token
                inp = jnp.where((j < n_forced) & live, fj, tok)
                inp = jnp.where(live, inp, 0)
                pos_in = jnp.where(live, pos, 0)
                cache, row_logits, *held_rows = self._model_step(
                    params, cache, inp, pos_in, *held_rows
                )
                nxt = self._sample(row_logits, kj)
                emit = live & (j >= emit_from)
                out = jnp.where(emit, nxt, -1)
                # per-row stop masks, applied in-device: the finishing
                # emission itself goes out, then the row is dead for the
                # rest of the chunk (harvested at the boundary)
                rem = rem - emit.astype(jnp.int32)
                died = emit & (rem <= 0)
                if eos is not None:
                    died = died | (emit & (nxt == eos))
                live = live & jnp.logical_not(died)
                tok = jnp.where(live, nxt, tok)
                pos = jnp.where(live, pos + 1, pos)
                cache = pin_idle_rows(cache, live)
                return (cache, tok, pos, live, rem, *held_rows), out

            n_counts = len(_MOE_ROW_COUNTS)
            counts = (jnp.zeros((n_counts,), jnp.int32),) if counted else ()
            (cache, tok, pos, live, rem, *counts), toks = jax.lax.scan(
                body, (cache, tok, pos, live, rem, *counts),
                (jnp.arange(k, dtype=jnp.int32), keys, forced_t),
            )
            # toks [K, B] → the [B, K] device-side emission buffer the
            # host fetches in ONE readback per chunk
            toks = jnp.moveaxis(toks, 0, 1)
            if counted:
                # the held-rows counts ride the same buffer: a row
                # more for each, the count in its first column
                toks = jnp.concatenate([
                    toks,
                    jnp.zeros((n_counts, k), jnp.int32).at[:, 0].set(
                        counts[0]
                    ),
                ])
            return cache, tok, pos, live, rem, key, toks

        return tracked_jit(
            fused_fn,
            name=(
                f"serve/fused_k{k}" + ("_paged" if paged else "")
                + ("_admit" if with_admit else "")
            ),
            donate_argnums=(1, 2, 3, 4, 5, 6),
            # the page-table leaves come in only to hand their buffers to
            # the tables the program writes: unused, they would be pruned
            # and their donation dropped
            keep_unused=paged,
        )


    def _dispatch_chunk(self, k: int, admit: bool) -> None:
        """Build the host plan for one fused chunk and dispatch it.

        ``admit`` must only be True when no chunk is in flight (the
        host's slot view is then exact); speculative follow-up chunks
        dispatch with ``admit=False`` and a plan that is deterministic
        given the previous dispatch (prompt feeding advances host-side,
        everything else is a device carry).

        Everything the host decides crosses to the device in ONE
        staging: the plan, the admission and (paged) the allocator's
        page table are columns of one int32 array
        (:func:`_chunk_columns`) that ``_build_fused``'s program takes
        apart. The RNG key never comes back to the host: it is a
        carry the program splits. The table goes with EVERY chunk:
        whatever admission, release or a deferred release's zeroing did
        to the mirror is on the device before the chunk's first step.

        Phases (``serve/phase/*``, host clock, always on): ``admit`` is
        a pending weight swap, expiry, page allocation and slot filling;
        ``plan`` fills the array's columns and stages it; ``dispatch``
        is the call into the fused program (enqueue only) and the record
        of the plan. The profiler's annotations (on during a capture)
        cut the same code a little differently: ``serve.plan`` ends
        before the staging and ``serve.dispatch`` holds it and the call,
        but not the record of the plan.

        The chunk's closing ``serve/step`` span also says what the
        dispatch cost the host, whichever phase it fell in:
        ``dispatch_key_s``, ``dispatch_enqueue_s``, ``dispatch_arg_leaves``
        (the fused program's ``TrackedJit.last_call``) and ``stage_s``,
        ``stage_transfers``: the one staging of the packed array.
        """
        clock = self._clock or self._tele.phases(
            "serve", step=self.stats.chunks
        )
        self._apply_pending_weights()
        cols = _chunk_columns(k)
        packed = np.zeros(
            (self._b,
             cols.table.start + self._cache_mgr.pages_per_row),
            np.int32,
        )
        # views: the loops below write the array's columns in place
        admit_mask = packed[:, cols.admit_mask]
        admit_budget = packed[:, cols.admit_budget]
        admit_pos = packed[:, cols.admit_pos]
        if admit:
            with annotate("serve.admit"):
                now = time.perf_counter()
                self._expire_queued(now)
                self._expire_running(now)
                # admit=True ⇒ no chunks in flight: deferred zombie
                # pages free now; this chunk carries the zeroed table
                # rows
                self._cache_mgr.flush_deferred()
                for i, slot in enumerate(self._slots):
                    if slot.rid >= 0 or not self._queue:
                        continue
                    req = self._queue[0]
                    start_pos = self._cache_mgr.admit(
                        i, req.rid, req.prompt, req.total_tokens
                    )
                    if start_pos is None:
                        break  # head-of-line waits for pages
                    if self._cache_mgr.prefix_cache_enabled:
                        self._acct.note_prefix_lookup(start_pos)
                    self._queue.popleft()
                    self._slots[i] = _Slot(
                        rid=req.rid,
                        # a prefix-cache hit skips the cached tokens:
                        # feeding resumes at the first un-cached one
                        feed=list(req.prompt[start_pos:]),
                        pos=start_pos,
                        emitted=0,
                        budget=req.max_new_tokens,
                        deadline_t=req.deadline_t,
                    )
                    admit_mask[i] = 1
                    admit_budget[i] = req.max_new_tokens
                    admit_pos[i] = start_pos
                    self._acct.note_admit(req.rid, len(self._queue))
                self._note_pages()
        clock.mark("admit")

        with annotate("serve.plan"):
            forced = packed[:, cols.forced]
            n_forced = packed[:, cols.n_forced]
            emit_from = packed[:, cols.emit_from]
            emit_from[:] = k
            rids, pos = [], []
            for i, slot in enumerate(self._slots):
                rids.append(slot.rid)
                pos.append(slot.pos)
                if slot.rid < 0:
                    continue
                slot.pos += k
                m = len(slot.feed)
                nf = min(m, k)
                if nf:
                    forced[i, :nf] = slot.feed[:nf]
                n_forced[i] = nf
                emit_from[i] = max(m - 1, 0)
                slot.feed = slot.feed[k:]
            if self._cache_mgr.paged:
                packed[:, cols.table] = self._cache_mgr.table

            with_admit = bool(admit_mask.any())
            fused = self._fused.get((k, with_admit))
            if fused is None:
                fused = self._fused[(k, with_admit)] = self._build_fused(
                    k, with_admit
                )
        with annotate("serve.dispatch"):
            # the chunk's one host-to-device staging
            t = time.perf_counter()
            packed_d = jax.device_put(packed)
            stage_s = time.perf_counter() - t
            clock.mark("plan")
            (self._cache, self._tok_d, self._pos_d, self._live_d,
             self._rem_d, self._rng, toks) = fused(
                self._params, self._cache, self._tok_d, self._pos_d,
                self._live_d, self._rem_d, self._rng, packed_d,
            )
        if self._cache_mgr.prefix_cache_enabled:
            for slot in self._slots:
                if slot.rid >= 0 and not slot.feed:
                    # the whole prompt is now DISPATCHED
                    self._cache_mgr.mark_filled(slot.rid)
        self._pending.append(
            (toks,
             _ChunkPlan(k=k, rids=rids, emit_from=emit_from.tolist(),
                        pos=pos, version=self.weights_version,
                        index=self.stats.chunks))
        )
        self.stats.host_dispatches += 1
        self.stats.chunks += 1
        self.stats.device_steps += k
        rows_reset = int(admit_mask.sum())
        self.stats.rows_reset += rows_reset
        reset_bytes = rows_reset * self._cache_mgr.row_reset_bytes
        self.stats.rows_reset_device_bytes += reset_bytes
        recurrent_bytes = self._cache_mgr.recurrent_state_bytes
        window_bytes = self._cache_mgr.window_cache_bytes
        self.stats.recurrent_state_bytes = recurrent_bytes
        self.stats.window_cache_bytes = window_bytes
        # what the wrapper's own Python and the enqueue cost this chunk
        # (TrackedJit.last_call), beside the one staging timed above
        cost = fused.last_call
        clock.meta.update(
            recurrent_state_bytes=recurrent_bytes,
            window_cache_bytes=window_bytes,
            rows_reset=rows_reset,
            rows_reset_device_bytes=reset_bytes,
            dispatch_key_s=cost.key_s,
            dispatch_enqueue_s=cost.enqueue_s,
            dispatch_arg_leaves=cost.arg_leaves,
            stage_s=stage_s,
            stage_transfers=1,
        )
        if self._cache_mgr.paged:
            # one chunk boundary's pages in use into ServeStats, and on
            # the closing serve/step span too: ServeStats gives a caller
            # totals, the span timeline any window's peak
            in_use = self._cache_mgr.pages_in_use
            self.stats.pool_pages_total += in_use
            self.stats.pool_pages_peak = max(
                self.stats.pool_pages_peak, in_use
            )
            clock.meta.update(
                pool_pages=in_use,
                pool_pages_free=self._cache_mgr.pages_free,
            )
        self._progress_t = time.perf_counter()
        clock.mark("dispatch")

    def _harvest_one(self) -> dict[int, list[int]]:
        """Fetch the oldest in-flight chunk (ONE readback) and replay the
        device's emission/stop logic on it to commit host state."""
        toks_d, plan = self._pending.popleft()
        clock = self._clock or self._tele.phases("serve", step=plan.index)
        with annotate("serve.readback"):
            # d9d-lint: disable=D9D003 — the single [B, K] readback per chunk
            toks = np.asarray(toks_d)
        # the wait for the device plus the transfer; what follows replays
        # emission and stop logic on the host: serve/phase/commit
        clock.mark("readback")
        with annotate("serve.commit"):
            if self._cache_mgr.counts_held_rows:
                # the rows below the slots' (``_build_fused``), in
                # ``_MOE_ROW_COUNTS``' order
                held, routed, skipped = (int(n) for n in toks[self._b:, 0])
                self.stats.moe_rows_held += held
                self.stats.moe_rows_routed += routed
                self.stats.moe_rows_skipped += skipped
                toks = toks[:self._b]
            now = time.perf_counter()
            self._progress_t = now
            if self._first_readback_t is None:
                self._first_readback_t = now
            self.stats.readbacks += 1
            self.stats.slot_steps_total += self._b * plan.k
            chunk_busy = 0
            chunk_positions = 0
            chunk_tokens = 0
            row_spans = []  # (first position, busy steps) of each busy row
            ring_windows = self._cache_mgr.ring_windows
            emitted: dict[int, list[int]] = {}
            for i, rid in enumerate(plan.rids):
                if rid < 0 or rid in self.done:
                    # idle at dispatch, or finished in an earlier chunk
                    # that was harvested after this one was (speculatively)
                    # dispatched — the device masked it dead already
                    continue
                slot = self._slots[i]
                # exact occupancy, replayed like the device's stop masks:
                # a row is busy through the step it dies on, idle after
                busy_steps = plan.k
                for j in range(min(plan.emit_from[i], plan.k), plan.k):
                    tok = int(toks[i, j])
                    emitted.setdefault(rid, []).append(tok)
                    self.outputs[rid].append(tok)
                    slot.emitted += 1
                    self.stats.emitted_tokens += 1
                    chunk_tokens += 1
                    if slot.emitted >= slot.budget or (
                        self._eos is not None and tok == self._eos
                    ):
                        self.done.add(rid)
                        self._slots[i] = _Slot()
                        busy_steps = j + 1
                        # the device row died IN-DEVICE at this same step
                        # (its later writes are pinned to the garbage
                        # page), so the pages free immediately; reuse
                        # waits for the next admit boundary, whose chunk
                        # carries the new table
                        self._release_row_pages(i, device_dead=True)
                        break
                self.stats.slot_steps_busy += busy_steps
                # step j of the row writes position pos + j and attends
                # positions 0..pos + j
                chunk_positions += (
                    busy_steps * plan.pos[i]
                    + busy_steps * (busy_steps + 1) // 2
                )
                if ring_windows:
                    row_spans.append((plan.pos[i], busy_steps))
                # steps in which the row only consumed a prompt token,
                # from the plan the chunk was dispatched with; a row emits
                # before it dies, so these never pass the step it died on
                self.stats.slot_steps_prompt += min(
                    plan.emit_from[i], plan.k
                )
                chunk_busy += busy_steps
                if rid in emitted:
                    self._acct.note_tokens(rid, len(emitted[rid]), now)
                    if rid in self.done:
                        self._acct.note_finish(rid, now, plan.version)
                        self._retire(rid)
            self.stats.positions_attended += chunk_positions
            # this chunk's share of the two counters, for a reader of the
            # span timeline (a traced window's chunks, say)
            clock.meta.update(
                slot_steps_busy=chunk_busy, positions_attended=chunk_positions
            )
            # 0 without window layers: a reader of the attention layers'
            # work (the paged decode kernel's roofline) is told so
            chunk_window = sum(
                layers * positions_under(row_spans, window)
                for window, layers in ring_windows.items()
            )
            self.stats.window_positions_attended += chunk_window
            clock.meta.update(window_positions_attended=chunk_window)
            self._acct.observe(
                "serve/slot_util", chunk_busy / (self._b * plan.k),
                UTIL_EDGES,
            )
            self._acct.note_throughput(chunk_tokens, now)
            if self._clock is None:
                # the overlapped drain: this harvest's own clock, phases
                # only (inside step_chunk / step the chunk's clock ends
                # ``commit`` when it closes and emits ``serve/step``)
                clock.mark("commit")
        return emitted

    def _sync(self) -> dict[int, list[int]]:
        out: dict[int, list[int]] = {}
        while self._pending:
            for rid, toks in self._harvest_one().items():
                out.setdefault(rid, []).extend(toks)
        return out

    def _may_outlive_pending(self) -> bool:
        """Could any busy row still be live after the in-flight chunks?

        With no EOS, stopping is budget-only and fully host-predictable,
        so a speculative chunk that could only serve dead rows is never
        dispatched. With an EOS id any emission may stop a row — the
        host can't know until readback, so speculation proceeds (worst
        case: one wasted chunk at the tail of a drain).
        """
        if self._eos is not None:
            return True
        proj = {
            i: s.emitted for i, s in enumerate(self._slots) if s.rid >= 0
        }
        for _toks, plan in self._pending:
            for i in proj:
                if plan.rids[i] == self._slots[i].rid:
                    proj[i] += max(0, plan.k - plan.emit_from[i])
        return any(
            proj[i] < self._slots[i].budget for i in proj
        )

    def step_chunk(self) -> dict[int, list[int]]:
        """Admit waiting requests, advance every slot ``chunk_size``
        tokens in ONE dispatch; returns ``{rid: [tokens]}`` emitted
        (generation phase) during the chunk."""
        self._sync()
        if not self._busy() and not self._queue:
            return {}
        with self._chunk_clock():
            self._dispatch_chunk(self._k, admit=True)
            return self._sync()

    @contextlib.contextmanager
    def _chunk_clock(self):
        """One dispatch and one harvest under one phase clock: the
        ``serve/phase/*`` spans partition the chunk gap-free and
        ``serve/step`` closes it (see ``_dispatch_chunk`` and
        ``_harvest_one`` for what each phase holds)."""
        clock = self._clock = self._tele.phases(
            "serve", step=self.stats.chunks
        )
        try:
            yield
        except BaseException:
            clock.cancel()  # not a chunk: no spans for it
            raise
        finally:
            self._clock = None
            clock.close(tail_phase="commit")

    def step(self) -> dict[int, int]:
        """Admit waiting requests, advance every slot one token; returns
        ``{rid: token}`` for tokens emitted (generation phase) this
        step: a K=1 chunk (same one-dispatch boundary semantics), the
        single-token surface."""
        self._sync()
        if not self._busy() and not self._queue:
            return {}
        with self._chunk_clock():
            self._dispatch_chunk(1, admit=True)
            emitted = self._sync()
        return {rid: toks[0] for rid, toks in emitted.items() if toks}

    def drain(self, max_steps: int = 100_000) -> dict[int, list[int]]:
        """Run until every submitted request has finished.

        Chunks are pipelined double-buffered: while no admissions
        are waiting, the next chunk is dispatched BEFORE the previous
        chunk's tokens are fetched, overlapping the host readback with
        device compute (XLA async dispatch). Admission needs an exact
        slot view, so a non-empty queue forces a synchronous boundary.

        With ``stall_timeout_s`` set, a watchdog thread monitors
        dispatch/readback progress and converts a wedge into
        :class:`ServeStalledError`. (The interrupt lands between Python
        bytecodes: it catches host-visible stalls — a retry loop, a
        deadlocked lock, a sleeping fake — immediately; a readback
        hard-wedged inside the runtime's C++ is additionally covered by
        the process-level ``TimeoutManager`` watchdog.)
        """
        if self._stall_timeout_s is None:
            return self._drain_impl(max_steps)
        if threading.current_thread() is not threading.main_thread():
            # the watchdog interrupts via a signal to the MAIN thread; a
            # drain on a worker thread cannot be safely interrupted that
            # way (the exception would land in an unrelated thread)
            import warnings

            warnings.warn(
                "serve stall watchdog disabled: drain() is not on the "
                "main thread", stacklevel=2,
            )
            return self._drain_impl(max_steps)
        self._stalled = False
        self._progress_t = time.perf_counter()
        stop = threading.Event()

        main_ident = threading.main_thread().ident

        def watch():
            tick = min(0.05, self._stall_timeout_s / 4)
            fired = 0
            while not stop.wait(tick):
                if self.stats.readbacks == 0:
                    # nothing has ever round-tripped: the gap is almost
                    # certainly first-call XLA compilation, which can
                    # legitimately run minutes — interrupting it would
                    # fail a healthy cold start (and land the signal
                    # inside the compiler). A wedge this early is the
                    # process-level TimeoutManager's job.
                    continue
                if (
                    time.perf_counter() - self._progress_t
                    > self._stall_timeout_s * (1 + fired)
                ):
                    if stop.is_set():  # drain just finished: stand down
                        return
                    self._stalled = True
                    if fired == 0:
                        self._acct.counter_add("serve/stalls")
                    fired += 1
                    try:
                        # a real signal: wakes blocking C calls (sleeps,
                        # waits) via EINTR, unlike interrupt_main's
                        # between-bytecodes flag. Keep re-firing on a
                        # backoff rather than one-shot: an embedder's
                        # own SIGINT handler (graceful-shutdown servers,
                        # PreemptionGuard) swallows the first delivery
                        # without raising KeyboardInterrupt.
                        import signal

                        signal.pthread_kill(main_ident, signal.SIGINT)
                    except (OSError, AttributeError, ValueError):
                        _thread.interrupt_main()

        watchdog = threading.Thread(
            target=watch, name="d9d-serve-stall-watchdog", daemon=True
        )
        watchdog.start()
        try:
            return self._drain_impl(max_steps)
        except KeyboardInterrupt:
            if self._stalled:
                # black-box dump before surfacing the wedge: the recent
                # metric windows + span tail at the moment of the stall
                # (no-op unless a flight recorder is configured)
                self._tele.dump_flight_record(
                    "serve_stall",
                    extra={
                        "replica": self.replica_label,
                        "active": self.active,
                        "stall_timeout_s": self._stall_timeout_s,
                    },
                )
                raise ServeStalledError(
                    f"serving drain made no dispatch/readback progress "
                    f"for {self._stall_timeout_s}s with "
                    f"{self.active} request(s) outstanding"
                ) from None
            raise
        finally:
            stop.set()
            watchdog.join(timeout=1.0)

    def _drain_impl(self, max_steps: int) -> dict[int, list[int]]:
        steps = 0
        while self.active or self._pending:
            # admissions are waiting: sync so freed slots refill promptly
            # (and so the admit plan sees exact state)
            while self._pending and self._queue:
                self._harvest_one()
            if self._queue or (self._busy() and self._may_outlive_pending()):
                self._dispatch_chunk(self._k, admit=not self._pending)
                steps += self._k
                if steps > max_steps:
                    self._sync()
                    raise RuntimeError("drain exceeded max_steps")
                # keep at most one chunk in flight beyond the newest: the
                # harvest of chunk N overlaps chunk N+1's device compute
                while len(self._pending) > 1:
                    self._harvest_one()
            elif self._pending:
                self._harvest_one()
        return self.outputs
