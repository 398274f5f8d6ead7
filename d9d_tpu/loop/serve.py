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

``chunk_size=None`` selects the legacy per-token stepping path (one
dispatch + one readback per token) — kept as the oracle for the fused
path's exactness tests and for latency-critical single-token serving.

Static shapes are the law under XLA, so admission is TOKEN-LEVEL: the
loop always processes exactly one token per slot per device step. A
newly admitted request spends its first ``len(prompt)`` steps consuming
its prompt (teacher-forced through the same decode step — cache
contents and the final-position logits are bit-identical to a one-shot
prefill), then flips to generation. The price is prompt consumption at
one token per step; long prompts can instead be pre-filled out-of-band
with ``generate``'s chunked prefill and handed over — the primitives
compose, this loop stays shape-static.

Per-row cache state rides the decode modules unchanged: the serving
loop seeds the flax cache with a PER-ROW ``[B]`` ``cache_index``
(modules accept either rank — ``nn/attention.py``), the flash-decode
kernel takes per-row ``start`` offsets natively
(``ops/attention/pallas_decode.py``), and row admission resets just
that row's cache slice (every cache leaf leads with the batch dim).
Idle and dead rows have their ``cache_index`` pinned to 0 inside the
jitted step, so a slot left idle for arbitrarily many steps can never
overflow the capacity contract or defeat the flash-decode block skip.

Two kinds of cache live in one manager. Recurrent layers (GDN
``delta_state``, Mamba ``ssm_state``, the short convolutions'
``conv_tail``) keep state that is per-row already and is never paged:
the rule is ``nn/decode_flags.recurrent_leaves`` (any per-row leaf that
is neither pageable sequence content nor a write index), never a
model's name. Admission zeroes a row's leaves in the same dispatch that
starts it, so whatever an idle or dead row wrote there (it keeps
stepping on token 0 under static shapes) cannot reach the next
request; a model with such leaves serves with the prefix cache off
(its state summarizes the whole prefix and cannot be rebuilt from
shared KV pages) and ``prefix_cache=True`` raises. The zeroing
(``nn/decode_flags.zero_rows``, the ops under ``serve/reset_rows``)
writes a zero row into each per-row leaf at each admitted index, in
place: the device pays for the admitted rows' bytes and a launch a leaf
a row, never for the state (``ServeStats.recurrent_state_bytes``, on
the ``serve/step`` span and the ``serve/recurrent_state_bytes`` gauge
too). ``rows_reset`` counts the rows it cleared and
``rows_reset_device_bytes`` the bytes it wrote.

A third kind under paging: an attention layer that reads a window of
positions keeps a ring of pages a row (``nn/attention.py
_ring_page_table``; the leaves ``decode_flags.RING_CACHE_LEAVES``), the
window and one page of positions however long the context, beside the
full layers' pools: no allocator, no table leaf, no garbage page, no
zeroing at admission (what a ring still holds is behind the position
masks). ``ServeStats.window_cache_bytes`` is what they hold. A ring has
dropped what a shared prefix page would stand for, so such a model
serves with the prefix cache off by the rule that covers recurrent
state (``decode_flags.window_leaves``), ``prefix_cache=True`` raises,
and so does ``kv_quant``.

Parity contract: greedy serving of any admission schedule must emit,
per request, exactly the tokens ``generate(model, params, prompt)``
produces — ``tests/loop/test_serve.py`` drives staggered schedules
against that oracle, for both the fused and the per-token path.
(With ``temperature > 0`` the two paths consume the RNG stream in
different orders — per chunk vs per token — so sampled outputs are
both valid draws but not bitwise-identical across modes.)

Telemetry (docs/design/observability.md): per-request TTFT / TPOT /
queue-wait and per-chunk slot-occupancy histograms are derived from the
host clock at the SAME boundaries the token readbacks already happen at
— the fused path's host-interaction contract (one dispatch + one
readback per chunk) is untouched; ``tests/telemetry`` pins
``stats.readbacks`` against it. Every fused chunk is partitioned
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
The monitoring plane rides the same boundaries: every request carries
a fleet-stable trace id (``request_trace`` JSONL milestones),
``replica_label`` namespaces the serve instruments per replica
(``serve/r{i}/...`` with base-name rollups), and ``metrics_port``
serves live Prometheus ``/metrics`` + ``/healthz`` + ``/readyz`` from
a background thread — all pure host work, zero added readbacks (gated
by ``tools/bench_compare.py``'s exporter leg).

Paged KV cache + prefix cache (docs/design/generation.md): with
``page_size`` set, the sequence caches become device-resident page
POOLS (``[num_pages, ..., page_size, ...]``) indexed through a
static-shape per-row ``[B, max_pages]`` page table — HBM per request
is proportional to its actual length instead of ``decode_max_length``,
admission is bounded by free pages rather than batch rows, and a
content-hashed prefix cache maps a shared prompt's pages
copy-on-write into later requests so it prefills once per replica.
All policy (free lists, refcounts, hashing, LRU eviction —
``loop/kv_paging.py``) runs on the host at the SAME chunk boundaries
admission already owns; the page table is a traced cache leaf like
``cache_index``, so the host-interaction contract above and the
``tracked_jit`` fingerprints are untouched (``tools/bench_compare.py``
gates the paged leg's dispatch/readback/compile counts against the
contiguous leg's). The flash-decode kernel gathers a row's live pages
through the page table, a block of pages of each of a group of rows a
grid step (``ops/attention/pallas_decode.py paged_decode_geometry``);
the eager path gathers a contiguous per-row view and remains the
bitwise exactness reference — greedy paged serving is token-identical
to the contiguous layout, prefix hit or cold.

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
from jax.sharding import NamedSharding

from d9d_tpu.core.tracing import annotate
from d9d_tpu.core.tree_sharding import replicate_uncommitted
from d9d_tpu.core.types import Array
from d9d_tpu.loop.quantize import dequantize_params, is_quantized_tree
from d9d_tpu.nn.decode_flags import zero_rows
from d9d_tpu.telemetry import get_telemetry, tracked_jit

# what the expert layers that hold a range of their router's experts sow
# into ``moe_stats`` a step (nn/moe.py), in the order the fused chunk
# carries their sums out: ``ServeStats.moe_<name>``
_MOE_ROW_COUNTS = ("rows_held", "rows_routed", "rows_skipped")

# slot-occupancy fraction per chunk/step: 20 linear bins over [0, 1]
_UTIL_EDGES = tuple(i / 20 for i in range(21))

# tokens-per-completed-request distribution: 1 .. 4096 tokens, log bins.
# A generation-quality canary signal (docs/design/elasticity.md "SLO
# autopilot"): a bad weight publish that stops hitting EOS shows up as
# this distribution jumping to the budget ceiling on the canary replica
# long before any latency SLO moves.
_REQ_TOKENS_EDGES = tuple(
    1.0 * (4096.0 ** (i / 24)) for i in range(25)
)

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
    # legacy (per-token) mode: prompt tokens after the one in _tokens
    pending: list = dataclasses.field(default_factory=list)
    pos: int = 0             # next cache position this row writes
    # fused mode: prompt tokens not yet dispatched as step inputs
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


# default per-transfer staging bound for KV page shipments: the same
# order as elastic-restore's redistribute budget — big enough that a
# whole tiny-model prefix ships in one chunk, small enough that a long
# production prefix never stages the full run on the host at once
_TRANSFER_BUDGET_BYTES = 64 << 20


@dataclasses.dataclass
class KVPageShipment:
    """One cross-replica KV prefix shipment (host-side, self-checking).

    ``payload`` maps each paged pool leaf path (values AND int8 scale
    siblings) to a ``[n_pages, ...]`` host array stacked in block
    order; ``checksums[i]`` is a crc32 over page ``i``'s bytes across
    every leaf in sorted-path order, verified by the importer BEFORE
    any allocator or pool mutation — a flipped byte or truncated
    payload is detected, and the request falls back to re-prefill.
    ``weights_version`` pins the generation the pages were computed
    under: cached KV is weights-dependent, so an importer on any other
    generation must reject (same invariant as ``install_weights``
    prefix invalidation)."""

    page_size: int
    tokens: list            # the full-block token prefix the pages cover
    n_pages: int
    weights_version: int
    kv_quant: Optional[str]
    payload: dict
    checksums: list
    chunks: int = 0         # transfer chunks the export staged through

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.payload.values())


def _page_checksums(payload: dict) -> list:
    """Per-page crc32 across every payload leaf in sorted-path order."""
    import zlib

    if not payload:
        return []
    n = next(iter(payload.values())).shape[0]
    out = []
    for i in range(n):
        c = 0
        for name in sorted(payload):
            c = zlib.crc32(
                np.ascontiguousarray(payload[name][i]).tobytes(), c
            )
        out.append(c)
    return out


@dataclasses.dataclass
class RequestTelemetry:
    """Host-clock milestones for one request, harvested at the same
    boundaries the token readbacks already happen at (chunk boundaries
    on the fused path, per step on the legacy path) — deriving latency
    telemetry costs ZERO additional device readbacks.

    Granularity contract: on the fused path first-token and finish
    times are observed at chunk-boundary harvests, so TTFT/TPOT carry
    up-to-one-chunk quantization — exactly the latency a caller of
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


def _positions_under(row_spans, window: int) -> int:
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


def _normalize_params(params):
    """Pin uncommitted leaves of a handed-over param tree to the
    mesh-replicated placement of its committed leaves
    (``core/tree_sharding.replicate_uncommitted``); identity for trees
    with no committed NamedSharding to normalize against."""
    for leaf in jax.tree.leaves(params):
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding):
            return replicate_uncommitted(params, sh.mesh)
    return params


def _pin_cache_index(cache, live: Array):
    """Pin dead/idle rows' per-row write indices to 0: the jitted step
    advances every row's ``cache_index``, so without the pin a long-idle
    slot would climb past capacity (spurious checkify overflow under
    contract validation) and defeat the flash-decode whole-block skip
    (a huge start makes every block visible)."""
    from d9d_tpu.nn.decode_flags import map_cache_index

    return map_cache_index(cache, lambda idx: jnp.where(live, idx, 0))


def _pin_page_table(cache, live: Array):
    """Paged companion of :func:`_pin_cache_index`: pin dead/idle rows'
    page-table rows to the reserved garbage page (0). A row that dies
    mid-chunk keeps executing static-shape steps — with its write index
    pinned to 0 its writes land at logical slot 0, and WITHOUT this pin
    that is ``page_table[b, 0]``, which may be a freed page or (worse) a
    SHARED prefix page. With it, dead rows scribble harmlessly into the
    garbage page until the host reuses the slot."""
    from d9d_tpu.nn.decode_flags import map_page_table

    return map_page_table(
        cache, lambda pt: jnp.where(live[:, None], pt, 0)
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

    ``chunk_size``: decode steps fused per dispatch (default 8).
    ``None`` selects the legacy per-token stepping path. ``overlap``
    (fused mode) lets ``drain()`` keep one chunk in flight while the
    previous chunk's tokens are fetched.
    """

    def __init__(
        self,
        model,
        params,
        *,
        batch_size: int,
        eos_id: Optional[int] = None,
        temperature: float = 0.0,
        rng: Optional[jax.Array] = None,
        chunk_size: Optional[int] = 8,
        overlap: bool = True,
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

        Monitoring-plane knobs (docs/design/observability.md):
        ``replica_label`` (e.g. ``"r0"`` — ``ServingFleet.add_replica``
        assigns these) namespaces this batcher's serve instruments as
        ``serve/{label}/...`` so N same-process replicas stop blending
        into the shared ``serve/*`` names; counters and latency
        histograms additionally feed the base name as the fleet rollup.
        ``metrics_port`` (0 = ephemeral) starts a
        :class:`~d9d_tpu.telemetry.MetricsServer` for this batcher —
        ``/metrics`` in Prometheus text, ``/readyz`` not-ready until the
        first readback has round-tripped; call :meth:`close` (or use the
        fleet's endpoint instead) to shut it down.

        Paged KV knobs (docs/design/generation.md "Paged KV cache"):
        ``page_size`` switches the sequence caches to a device-resident
        page pool + per-row page tables — HBM per request becomes
        proportional to its ACTUAL length, admission is bounded by free
        pages (head-of-line waits, never rejects, when pages run
        short), and a content-hashed prefix cache lets a shared system
        prompt prefill once and be mapped copy-on-write into later
        requests. ``num_pages`` sizes the pool (default: enough for
        every slot at full ``decode_max_length`` + the reserved garbage
        page — no savings until you shrink it). ``prefix_cache`` —
        None (default) auto-enables when every sequence cache is
        pageable and disables for models with unpageable per-row
        recurrent state (GDN/conv tails: their state summarizes the
        whole prefix and cannot be restored from KV pages); True forces
        (raising if unsound), False disables. Greedy decoding is
        token-identical to the contiguous layout either way.

        ``kv_quant="int8"`` (paged mode only — the page is the
        quantization granule, docs/design/generation.md "Low-precision
        serving") stores the KV pools as int8 with f32
        per-(page, slot[, head]) scale pools riding next to them as
        sibling cache leaves. Writes quantize at the per-row scatter,
        reads dequantize in the decode-attention gather/kernel; the
        prefix cache and continuation handoff are unchanged (scale
        pages share the value pages' page table). Decoding is no longer
        bit-identical to bf16/f32 — it is drift-bounded, gated by the
        parity tests and the autopilot canary."""
        if temperature > 0.0 and rng is None:
            raise ValueError("temperature > 0 needs an rng key")
        if chunk_size is not None and chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if stall_timeout_s is not None and stall_timeout_s <= 0:
            raise ValueError(
                f"stall_timeout_s must be > 0, got {stall_timeout_s}"
            )
        self._model = model
        # latent-placement fix (same class as the PR 5 resume bug): a
        # param tree handed over from a restored checkpoint can carry
        # uncommitted scalar leaves whose single-device placement
        # conflicts with the mesh-placed majority at the first dispatch
        self._params = _normalize_params(params)
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
        self._overlap = overlap and chunk_size is not None
        self._dml = int(getattr(model, "decode_max_length", 0))
        if self._dml <= 0:
            raise ValueError("model must be built with decode_max_length > 0")

        # paged KV mode (docs/design/generation.md): fixed-size page
        # pools + per-row page tables instead of contiguous per-row
        # cache leaves; allocation/refcounting/prefix caching is host
        # work at the existing chunk boundaries (loop/kv_paging.py)
        self._paged = page_size is not None
        self._kv = None
        if self._paged:
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            self._page_size = int(page_size)
            self._pages_per_row = -(-self._dml // self._page_size)
            self._num_pages = (
                int(num_pages) if num_pages is not None
                # default: every slot can hold a full-length request
                # (+ the reserved garbage page) — paging then changes
                # accounting but strands nothing; shrink it to actually
                # overcommit HBM
                else batch_size * self._pages_per_row + 1
            )
        elif num_pages is not None or prefix_cache is not None:
            raise ValueError(
                "num_pages/prefix_cache need paged mode (set page_size)"
            )
        if kv_quant is not None and not self._paged:
            raise ValueError("kv_quant needs paged mode (set page_size)")
        if kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant must be None or 'int8', got {kv_quant!r}"
            )
        self._kv_quant = kv_quant

        self._slots = [_Slot() for _ in range(batch_size)]
        self._queue: collections.deque[_Request] = collections.deque()
        self._next_rid = 0
        self._tokens = np.zeros((batch_size,), np.int32)  # legacy inputs
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
        self.stats = ServeStats()
        # per-request latency telemetry (serve/* namespace): recorded into
        # the process hub unless an isolated hub is injected
        self._tele = telemetry if telemetry is not None else get_telemetry()
        # finished-request state (stats records, output token lists, done
        # flags) is retained bounded-FIFO (_MAX_FINISHED_STATS): a
        # long-lived server must not grow host memory linearly with total
        # requests served — read results within that retention horizon
        self.request_stats: dict[int, RequestTelemetry] = {}
        self._finished_rids: collections.deque[int] = collections.deque()
        # serve/tokens_per_s two-bucket rolling window, evaluated at
        # snapshot time via gauge_fn: a lifetime average would flatten
        # into a constant on a long-lived server, and a last-write-wins
        # gauge would freeze at the last healthy value through a stall —
        # this way an idle/stalled server's rate decays toward zero.
        # Registered through a weakref so the hub (whose gauge_fn
        # registrations are process-lifetime) never pins a discarded
        # batcher — and its device-resident cache — in memory.
        now = time.perf_counter()
        self._rate_win_t0 = now
        self._rate_win_tokens = 0
        self._rate_prev_t0 = now
        self._rate_prev_tokens = 0
        this = weakref.ref(self)
        self._rate_fn = (
            lambda: b._live_rate() if (b := this()) is not None
            else float("nan")
        )
        # label set BEFORE the first gauge_fn registration: a batcher
        # constructed with a label must never transiently claim (and on
        # labeling, delete) the base-name registration an earlier
        # unlabeled batcher may hold
        self._replica_label: Optional[str] = None
        if replica_label is not None:
            self._replica_label = self._validate_label(replica_label)
        self._tele.gauge_fn(self._rate_gauge_name(), self._rate_fn)
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

        # jitted executables are built lazily: the per-token step only
        # compiles if the legacy path (or a mode mix) is actually used,
        # and each distinct fused K compiles its own scan
        self._step = None
        self._fused: dict[tuple[int, bool], object] = {}  # (k, with_admit)
        if self._paged:
            from d9d_tpu.nn.decode_flags import map_cache_index

            def _reset_rows_paged(cache, row_mask, admit_pos):
                # page pools are shared (never row-zeroed — stale page
                # bytes are unreachable behind the slot mask) and table
                # rows come from the host mirror; per-row leaves reset,
                # write indices jump to the first un-cached position
                cache = zero_rows(cache, row_mask)
                return map_cache_index(
                    cache,
                    lambda idx: jnp.where(row_mask, admit_pos, idx),
                )

            self._reset = tracked_jit(
                _reset_rows_paged, name="serve/reset_row_paged",
                donate_argnums=0,
            )
        else:
            self._reset = tracked_jit(
                zero_rows, name="serve/reset_row", donate_argnums=0
            )
        self._cache = self._init_cache()
        # static per-batcher fact: what of the cache is per-row recurrent
        # state, beside the serve/kv_* gauges of the paged part
        self._gauge_set(
            "serve/recurrent_state_bytes", self._recurrent_state_bytes
        )
        self._gauge_set("serve/window_cache_bytes", self._window_cache_bytes)
        if self._paged:
            # static per-batcher fact, but exported so dashboards (and
            # the bench accounting) can tell quantized pools apart
            # without reverse-engineering bytes-per-page
            self._gauge_set(
                "serve/kv_quant_enabled", 0.0 if kv_quant is None else 1.0
            )
        # KV residency accounting (serve/kv_* gauges + the bench's
        # hbm_bytes_per_request): peaks over the measurement window
        self._peak_running = 0
        if self._paged:
            from d9d_tpu.loop.kv_paging import PagedKVAllocator

            if prefix_cache and self._unpageable_leaves:
                raise ValueError(
                    "prefix_cache=True is unsound for this model: cache "
                    f"leaves {self._unpageable_leaves} hold per-row "
                    "recurrent state that summarizes the whole prefix, "
                    "or a window layer's ring that has dropped it, and "
                    "cannot be restored from KV pages"
                )
            self._kv = PagedKVAllocator(
                num_pages=self._num_pages,
                page_size=self._page_size,
                rows=batch_size,
                max_pages_per_row=self._pages_per_row,
                enable_prefix_cache=(
                    prefix_cache if prefix_cache is not None
                    else not self._unpageable_leaves
                ),
            )
            self._kv_table_dirty = False  # seeded leaves match the mirror

        # live weight publish (docs/design/elasticity.md): staged tree
        # swapped in at the next dispatch boundary, generation-stamped
        self.weights_version = 0
        self._pending_weights: tuple | None = None

        # fused-mode device carries (one buffer each, donated through)
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

        # opt-in live metrics endpoint (telemetry/export.py); weakrefs so
        # the endpoint can never pin a discarded batcher's device cache
        self.metrics_server = None
        if metrics_port is not None:
            from d9d_tpu.telemetry import MetricsServer

            ref = weakref.ref(self)
            self.metrics_server = MetricsServer(
                self._tele,
                port=metrics_port,
                readiness=lambda: (
                    (b.ready, {"replica": b._replica_label})
                    if (b := ref()) is not None else (False, {})
                ),
                health=lambda: (
                    {
                        "replica": b._replica_label,
                        "active": b.active,
                        "ready": b.ready,
                        "stalled": b._stalled,
                    }
                    if (b := ref()) is not None else {"gone": True}
                ),
            ).start()

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
        self._tele.registry.unregister_gauge_fn(
            self._rate_gauge_name(), self._rate_fn
        )

    # -- instrument naming (replica namespacing, ISSUE satellite) ------

    def _rate_gauge_name(self) -> str:
        return (
            f"serve/{self._replica_label}/tokens_per_s"
            if self._replica_label else "serve/tokens_per_s"
        )

    @staticmethod
    def _validate_label(label: str) -> str:
        if not label or "/" in label:
            raise ValueError(f"replica_label must be path-free, got {label!r}")
        return str(label)

    def set_replica_label(self, label: str) -> None:
        """Namespace this batcher's serve instruments as
        ``serve/{label}/...`` (the fleet assigns ``r{i}``). Re-homes the
        live-rate callback gauge; subsequent records use the new name.
        Counters/histograms keep feeding the base ``serve/*`` name too —
        the fleet rollup the unlabeled world saw stays intact. (Prefer
        ``replica_label=`` at construction: an unlabeled batcher holds
        the base-name rate gauge until this call, with the pre-existing
        last-registration-wins semantics across unlabeled batchers.)"""
        label = self._validate_label(label)
        # fn-guarded: only tears down THIS batcher's registration
        self._tele.registry.unregister_gauge_fn(
            self._rate_gauge_name(), self._rate_fn
        )
        self._replica_label = label
        self._tele.gauge_fn(self._rate_gauge_name(), self._rate_fn)

    def _mname(self, name: str) -> str:
        # name always carries the "serve/" prefix at call sites
        return f"serve/{self._replica_label}/{name[6:]}"

    def _count(self, name: str, n: float = 1.0) -> None:
        self._tele.counter(name).add(n)
        if self._replica_label:
            self._tele.counter(self._mname(name)).add(n)

    def _observe(self, name: str, v: float, edges=None) -> None:
        # base name first: SLO digests key on the fleet-level metric
        self._tele.observe(name, v, edges)
        if self._replica_label:
            self._tele.observe(self._mname(name), v, edges)

    def _gauge_set(self, name: str, v: float) -> None:
        # gauges are last-write-wins: a shared base name would blend N
        # replicas (the conflation bug this satellite fixes), so labeled
        # batchers write ONLY their namespaced gauge; fleet-level gauges
        # are computed by ServingFleet as explicit rollups
        self._tele.gauge(
            self._mname(name) if self._replica_label else name
        ).set(v)

    # -- per-request trace events (schema v3, docs/design/observability.md)

    def _trace(
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
        if self._replica_label is not None:
            rec["replica"] = self._replica_label
        if rid is not None:
            rec["rid"] = rid
        if meta:
            rec["meta"] = meta
        self._tele.record_request_trace(rec)

    def _init_cache(self):
        import math

        from flax.traverse_util import flatten_dict, unflatten_dict

        from d9d_tpu.nn.decode_flags import (
            PAGE_TABLE_LEAF,
            PAGED_CACHE_LEAVES,
            PAGED_SCALE_SUFFIX,
            per_row_leaves,
            recurrent_leaves,
            ring_caches,
            window_leaves,
        )

        z = jnp.zeros((self._b, 1), jnp.int32)
        # eval_shape: cache SHAPES only — model.init would materialize
        # (and immediately discard) a full second copy of the parameters.
        # Paged, a window layer declares a ring of pages a row in place
        # of a context's worth of cache (nn/attention.py)
        with (
            ring_caches(self._page_size) if self._paged
            else contextlib.nullcontext(())
        ) as windows:
            shapes = jax.eval_shape(
                self._model.init, jax.random.PRNGKey(0), z, z, z
            )
        # window -> how many layers keep a ring of it
        self._ring_windows = collections.Counter(windows)
        flat = flatten_dict(shapes["cache"])
        # layers that hold a range of their router's experts count the
        # routed pairs that land here (nn/moe.py): the fused chunk
        # carries the counts out with its tokens
        self._counts_held_rows = any(
            p[-1] == "rows_held"
            for p in flatten_dict(shapes.get("moe_stats", {}))
        )
        # dense-layout byte total of the sequence caches: the paged
        # mode's savings denominator, and the contiguous mode's (static)
        # KV residency for the hbm-bytes-per-request accounting
        def nbytes(leaves) -> int:
            return sum(
                math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
                for s in leaves
            )

        self._kv_bytes_static = nbytes(
            s for p, s in flat.items() if p[-1] in PAGED_CACHE_LEAVES
        )
        # per-row cache leaves that are NOT pageable (GDN and Mamba
        # recurrent state, conv tails, toy memories): paging leaves them
        # per-row; their presence auto-disables the prefix cache (their
        # state can't be rebuilt from shared KV pages). An admission
        # zeroes the admitted rows of them (decode_flags.zero_rows).
        recurrent = recurrent_leaves(shapes["cache"])
        rings = window_leaves(shapes["cache"])
        if rings and self._kv_quant is not None:
            raise ValueError(
                "kv_quant does not cover a window layer's ring of pages "
                f"({sorted({p[-1] for p in rings})})"
            )
        self._unpageable_leaves = sorted(
            {p[-1] for p in recurrent} | {p[-1] for p in rings}
        )
        self._recurrent_state_bytes = nbytes(recurrent.values())
        self._window_cache_bytes = nbytes(rings.values())
        self._page_bytes = 0
        out = {}
        for p, s in flat.items():
            if p[-1] == "cache_index":
                # per-row write indices: seed [B] zeros in place of the
                # scalar — the decode modules accept either rank
                out[p] = jnp.zeros((self._b,), jnp.int32)
            elif self._paged and p[-1] in PAGED_CACHE_LEAVES:
                axis = PAGED_CACHE_LEAVES[p[-1]]
                if s.shape[axis] != self._dml:
                    raise ValueError(
                        f"cache leaf {'/'.join(p)} slot axis {axis} is "
                        f"{s.shape[axis]}, expected decode_max_length="
                        f"{self._dml}"
                    )
                pool_shape = (
                    (self._num_pages,) + s.shape[1:axis]
                    + (self._page_size,) + s.shape[axis + 1:]
                )
                if self._kv_quant is not None:
                    # int8 pool + f32 per-(page, slot[, head]) scale
                    # pool: the scale leaf drops only the trailing
                    # feature dim, so one scale covers one slot's
                    # feature vector (the finest granule the one-token
                    # scatter can maintain) and the scale pool indexes
                    # through the SAME page table as its value pool
                    pool = jnp.zeros(pool_shape, jnp.int8)
                    scale = jnp.zeros(pool_shape[:-1], jnp.float32)
                    out[p[:-1] + (p[-1] + PAGED_SCALE_SUFFIX,)] = scale
                    self._page_bytes += scale.nbytes // self._num_pages
                else:
                    pool = jnp.zeros(pool_shape, s.dtype)
                out[p] = pool
                # one table per module scope (identical contents; a few
                # ints per layer) so the module reads its own sibling
                out[p[:-1] + (PAGE_TABLE_LEAF,)] = jnp.zeros(
                    (self._b, self._pages_per_row), jnp.int32
                )
                self._page_bytes += pool.nbytes // self._num_pages
            else:
                out[p] = jnp.zeros(s.shape, s.dtype)
        cache = unflatten_dict(out)
        # what an admission writes: a zero row into each per-row leaf
        self._row_reset_bytes = (
            nbytes(per_row_leaves(cache).values()) // self._b
        )
        return cache

    # ------------------------------------------------------------------
    # jitted executables

    def _model_step(self, params, cache, tok, pos, held_rows=None):
        """One single-token decode call (trace-time helper shared by the
        per-token and fused executables). ``held_rows`` (the fused
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
        from d9d_tpu.nn.decode_flags import caller_holds_bounds

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

    def _build_step(self):
        paged = self._paged

        def step_fn(params, cache, tok, pos, key, live):
            cache, row_logits = self._model_step(params, cache, tok, pos)
            nxt = self._sample(row_logits, key)
            # idle rows ride through the static-shape step; pin their
            # write index so an arbitrarily long idle stretch can't
            # overflow capacity or defeat the flash block skip
            cache = _pin_cache_index(cache, live)
            if paged:
                cache = _pin_page_table(cache, live)
            return cache, nxt

        # donate the cache: XLA aliases input buffers to outputs, so the
        # per-step update is in place — no second cache residency or
        # full-cache memcpy per token. Params are NOT donated: the same
        # tree serves every following dispatch.
        return tracked_jit(step_fn, name="serve/step", donate_argnums=1)

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

        Paged mode differences, same dispatch structure: admitted rows
        reset only their PER-ROW leaves (``decode_flags.per_row_leaves``:
        pools are shared; stale page bytes sit behind the slot mask)
        and jump their write index /
        position to ``admit_pos`` — the first token past their prefix-
        cache hit; the host's table is written into every ``page_table``
        leaf and pinned by the device's own ``live`` (after the
        admission has set it) before the first step, so a row that died
        in-device while the host's mirror still holds its pages (a
        follow-up chunk dispatched with that death unread) goes on
        writing into the garbage page, and a row the host zeroed
        (released, or a zombie whose pages wait for a clean boundary)
        is rerouted there; each step additionally pins dead/idle rows'
        page tables to the garbage page (see :func:`_pin_page_table`)."""
        eos = self._eos
        paged = self._paged
        counted = self._counts_held_rows
        cols = _chunk_columns(k)
        if paged:
            from d9d_tpu.nn.decode_flags import (
                map_cache_index,
                map_page_table,
            )

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
                cache = zero_rows(cache, admit_mask)
                if paged:
                    admit_pos = packed[:, cols.admit_pos]
                    cache = map_cache_index(
                        cache,
                        lambda idx: jnp.where(admit_mask, admit_pos, idx),
                    )
                    pos = jnp.where(admit_mask, admit_pos, pos)
                else:
                    pos = jnp.where(admit_mask, 0, pos)
                live = jnp.where(admit_mask, True, live)
                rem = jnp.where(admit_mask, admit_budget, rem)
            if paged:
                # the host's table into every leaf, THEN the pin by the
                # device's live: the mirror still holds the pages of a
                # row whose death the host has not read yet
                table = packed[:, cols.table]
                cache = map_page_table(cache, lambda _pt: table)
                cache = _pin_page_table(cache, live)
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
                cache = _pin_cache_index(cache, live)
                if paged:
                    cache = _pin_page_table(cache, live)
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
        if need > self._dml:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens}"
                f" - 1 = {need} exceeds decode_max_length={self._dml}"
            )
        if self._paged and not self._kv.fits_ever(need):
            raise ValueError(
                f"request needs {self._kv.pages_needed(need)} pages but "
                f"the pool holds {self._num_pages - 1} allocatable "
                f"(num_pages={self._num_pages}, page_size="
                f"{self._page_size}); it could never be admitted"
            )
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
                freed = int(self._expire_running(now).sum())
                if freed and self._paged:
                    # paged admission is PAGE-bounded, not slot-bounded:
                    # the freed slot is only real capacity if the queue
                    # head can map onto pages by the next admit boundary
                    # — which flushes deferred frees first, so count
                    # those too (conservative beyond that: prefix hits
                    # and LRU eviction could only help)
                    head = self._queue[0]
                    if (
                        self._kv.pages_needed(head.total_tokens)
                        > self._kv.pages_free_after_flush()
                    ):
                        freed = 0
                if len(self._queue) - freed >= self._max_queue:
                    self.stats.rejected += 1
                    self._count("serve/rejected")
                    if minted_here:
                        # terminal only for a front-door submit: a fleet
                        # placement attempt (external trace id) that
                        # this replica rejects may still land on a
                        # survivor — the fleet emits the terminal event
                        # if ALL reject
                        self._trace(trace_id, "rejected", now,
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
        self.request_stats[rid] = RequestTelemetry(
            submit_t=now, trace_id=trace_id
        )
        self._gauge_set("serve/queued", len(self._queue))
        self._trace(
            trace_id, "submit", now, rid=rid,
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
        # KV residency window accounting; the prefix cache itself stays
        # warm deliberately (like compile warmth / _first_readback_t)
        self._peak_running = 0
        if self._paged:
            self._kv.peak_pages_in_use = self._kv.pages_in_use
            self._kv.prefix_hits = 0
            self._kv.prefix_misses = 0
            self._kv.prefix_hit_tokens = 0
        now = time.perf_counter()
        self._rate_win_t0 = now
        self._rate_win_tokens = 0
        self._rate_prev_t0 = now
        self._rate_prev_tokens = 0

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
        next dispatch boundary (chunk boundary in fused mode, step
        boundary in legacy mode) — never mid-chunk, so chunks already
        in flight complete on the weights they were dispatched with.

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
            _normalize_params(params), int(version), time.perf_counter(),
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
        if self._paged and self._kv.prefix_cache_enabled:
            # cached prefix KV was computed under the OLD weights: a
            # post-publish hit would silently attend stale pages and
            # break the token-identity contract — drop every entry (the
            # next cold fill re-caches under the new generation).
            # In-flight rows are untouched; like the contiguous path,
            # they finish on the cache they built.
            dropped = self._kv.invalidate_prefix_cache()
            if dropped:
                self._count("serve/prefix_cache_invalidated", dropped)
            # stamp the invalidation with the weights generation that
            # caused it: a canary rollback's re-invalidation is then
            # distinguishable from the publish invalidation it undoes
            # (both drop entries; only the stamp tells them apart)
            self._gauge_set("serve/prefix_cache_invalidated_version", version)
            self._note_pages()
        self._count("serve/weight_publish")
        self._observe(
            "serve/weight_publish_s", time.perf_counter() - t0
        )
        self._gauge_set("serve/weights_version", version)
        if is_quantized_tree(params):
            # generation stamp of the last QUANTIZED tree installed (a
            # rollback to full precision leaves it at the rolled-back
            # generation — the gauge answers "which quantizer output is
            # live / was last live", not "is the live tree quantized")
            self._gauge_set("serve/weight_quant_version", version)

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
            if self._paged:
                self._kv.forget(req.rid)  # drop any admission memo
            out.append(
                (req.rid, list(req.prompt), req.max_new_tokens,
                 req.deadline_t)
            )
        if out:
            self._gauge_set("serve/queued", 0)
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
                if self._paged:
                    self._kv.forget(rid)  # drop any admission memo
                self._fail(rid, reason, time.perf_counter())
                self._gauge_set("serve/queued", len(self._queue))
                return True
        return False

    # ------------------------------------------------------------------
    # cross-replica KV page shipment (docs/design/elasticity.md
    # "Disaggregated serving"): a prefill replica exports the READY
    # prefix pages covering a prompt; a decode replica imports them as
    # ready prefix entries and copies the payloads into its own pool.
    # Pure transfers at clean chunk boundaries — page pulls/pushes are
    # untracked device array ops, never tracked_jit dispatches, so the
    # steady-state executable census and the dispatch counts the bench
    # gates are untouched. EVERY failure (dirty boundary, version skew,
    # checksum mismatch, allocation shortfall) returns None/False and
    # the caller falls back to plain continuation re-prefill — fallback,
    # not failure, is the contract.

    def _pool_leaves(self) -> dict:
        """Paged pool leaves (values + int8 scale siblings) by path."""
        from flax.traverse_util import flatten_dict

        from d9d_tpu.nn.decode_flags import (
            PAGED_CACHE_LEAVES,
            PAGED_SCALE_SUFFIX,
        )

        return {
            "/".join(p): leaf
            for p, leaf in flatten_dict(self._cache).items()
            if p[-1] in PAGED_CACHE_LEAVES
            or p[-1].endswith(PAGED_SCALE_SUFFIX)
        }

    def export_kv_pages(
        self,
        tokens: Sequence[int],
        *,
        transfer_budget_bytes: int = _TRANSFER_BUDGET_BYTES,
    ) -> Optional["KVPageShipment"]:
        """Pull the READY prefix pages covering ``tokens``' leading
        full blocks off the device pool, chunk-by-chunk under
        ``transfer_budget_bytes`` (the ``_chunked_place`` discipline
        from ``resilience/elastic.py`` — bounded host staging however
        large the run). Returns None when not paged, mid-chunk (only a
        clean boundary has an exact pool view), or nothing is cached —
        the caller re-prefills instead."""
        if not self._paged or self._pending:
            return None
        # same boundary discipline as import: a staged publish means the
        # cache below is the OLD generation — apply it (invalidating the
        # stale entries) rather than stamping dead pages with a version
        # the importer would refuse anyway
        self._apply_pending_weights()
        tokens = [int(x) for x in tokens]
        pages = self._kv.export_prefix(tokens)
        if not pages:
            return None
        leaves = self._pool_leaves()
        chunk_len = max(
            1, int(transfer_budget_bytes) // max(1, self._page_bytes)
        )
        parts: dict[str, list] = {name: [] for name in leaves}
        chunks = 0
        for a in range(0, len(pages), chunk_len):
            idx = jnp.asarray(np.asarray(pages[a:a + chunk_len], np.int32))
            for name, pool in leaves.items():
                # d9d-lint: disable=D9D003 — bounded page-payload pull at
                # a clean boundary (a transfer, not a decode readback)
                parts[name].append(np.asarray(pool[idx]))
            chunks += 1
        payload = {
            name: np.concatenate(arrs, axis=0)
            for name, arrs in parts.items()
        }
        ship = KVPageShipment(
            page_size=self._page_size,
            tokens=tokens[: len(pages) * self._page_size],
            n_pages=len(pages),
            weights_version=self.weights_version,
            kv_quant=self._kv_quant,
            payload=payload,
            checksums=_page_checksums(payload),
            chunks=chunks,
        )
        self._count("serve/handoff_exports")
        self._count("serve/handoff_pages", len(pages))
        self._count("serve/handoff_bytes", ship.nbytes)
        self._count("serve/handoff_chunks", chunks)
        return ship

    def import_kv_pages(
        self,
        ship: "KVPageShipment",
        *,
        transfer_budget_bytes: int = _TRANSFER_BUDGET_BYTES,
    ) -> bool:
        """Install a shipment's pages as READY prefix entries and copy
        the payloads into this replica's pool (chunked under the same
        transfer budget). Checksums are verified BEFORE any allocator
        or pool mutation — a corrupt/truncated shipment is detected and
        rejected whole, never half-imported. A weights-generation
        mismatch (or a publish staged here) rejects too: cached KV is
        weights-dependent, the same invariant as ``install_weights``
        prefix invalidation. Returns False on any rejection — the
        caller falls back to continuation re-prefill."""
        if not self._paged or self._pending:
            return False
        # an import IS a dispatch-boundary mutation: swap a staged
        # publish in first, exactly as the next _dispatch_chunk would —
        # otherwise a freshly-grown (idle) replica still reports the
        # pre-publish generation and refuses every current-gen shipment
        self._apply_pending_weights()
        if (
            ship.page_size != self._page_size
            or ship.kv_quant != self._kv_quant
            or not self._kv.prefix_cache_enabled
        ):
            return False
        if (
            ship.weights_version != self.weights_version
            or self._pending_weights is not None
        ):
            self._count("serve/handoff_version_mismatch")
            return False
        leaves = self._pool_leaves()
        if set(ship.payload) != set(leaves) or any(
            ship.payload[n].shape[0] != ship.n_pages for n in ship.payload
        ):
            self._count("serve/handoff_checksum_failures")
            return False
        if _page_checksums(ship.payload) != list(ship.checksums):
            self._count("serve/handoff_checksum_failures")
            return False
        placed = self._kv.import_pages(ship.tokens, ship.n_pages)
        if placed is None:
            return False
        chunk_len = max(
            1, int(transfer_budget_bytes) // max(1, self._page_bytes)
        )
        flat = None
        for a in range(0, len(placed), chunk_len):
            part = placed[a:a + chunk_len]
            src = np.asarray([b for b, _ in part], np.int32)
            dest = jnp.asarray(np.asarray([p for _, p in part], np.int32))
            if flat is None:
                from flax.traverse_util import flatten_dict

                flat = flatten_dict(self._cache)
            for name in leaves:
                path = tuple(name.split("/"))
                flat[path] = flat[path].at[dest].set(
                    jnp.asarray(ship.payload[name][src])
                )
        if flat is not None:
            from flax.traverse_util import unflatten_dict

            self._cache = unflatten_dict(flat)
        self._count("serve/handoff_imports")
        self._count("serve/handoff_pages", len(placed))
        self._note_pages()
        return True

    # ------------------------------------------------------------------
    # paged KV bookkeeping (loop/kv_paging.py): all host work, all at
    # the existing chunk boundaries — the dispatch/readback contract and
    # the tracked_jit fingerprints are untouched

    def _try_alloc(self, row: int, req: _Request):
        """Map the queue head onto pages (prefix-cache walk + free-list
        allocation); None leaves it queued — admission is bounded by
        free pages, not rows."""
        alloc = self._kv.admit(row, req.rid, req.prompt, req.total_tokens)
        if alloc is None:
            return None
        self._kv_table_dirty = True
        if self._kv.prefix_cache_enabled:
            if alloc.hit_tokens:
                self._count("serve/prefix_cache_hits")
                self._count(
                    "serve/prefix_cache_hit_tokens", alloc.hit_tokens
                )
            else:
                self._count("serve/prefix_cache_misses")
        return alloc

    def _push_page_table(self) -> None:
        """Sync the device page tables from the host mirror (a tiny
        host→device transfer between dispatches — NOT a tracked
        dispatch): the single-step path's way, one transfer per
        page-table leaf while ``_kv_table_dirty``, none while clean.
        Only ever called at clean boundaries, so a zeroed row reroutes
        any still-live zombie row's writes to the garbage page before
        its next step. A fused chunk makes no such transfer: the table
        is columns of its one packed argument and the program writes
        every leaf itself (``_build_fused``)."""
        if not self._kv_table_dirty:
            return
        self._kv_table_dirty = False
        from d9d_tpu.nn.decode_flags import map_page_table

        table = self._kv.table
        # one fresh buffer PER leaf: the cache is donated into the
        # step, and donating one shared buffer through N layer scopes
        # trips XLA's double-donation check
        self._cache = map_page_table(
            self._cache, lambda _pt: jnp.asarray(table)
        )

    def _release_row_pages(self, row: int, *, device_dead: bool) -> None:
        """Drop a retired row's page references. ``device_dead`` rows
        (finished in-device: their writes are already pinned to the
        garbage page) free immediately; host-side kills with chunks in
        flight DEFER — the device twin may still be live and writing
        into these pages, so they stay held until the zeroed table row
        has been pushed at a clean boundary (``flush_deferred``)."""
        if device_dead or not self._pending:
            self._kv.release(row)
        else:
            self._kv.defer_release(row)
        self._kv_table_dirty = True
        self._note_pages()

    def _note_pages(self) -> None:
        """Refresh the page-pool gauges (and the peak-concurrency
        accounting both modes share) — pure host arithmetic."""
        running = sum(1 for s in self._slots if s.rid >= 0)
        self._peak_running = max(self._peak_running, running)
        if not self._paged:
            return
        in_use = self._kv.pages_in_use
        self._gauge_set("serve/kv_pages_in_use", in_use)
        self._gauge_set("serve/kv_pages_free", self._kv.pages_free)
        self._gauge_set(
            "serve/kv_hbm_bytes_per_request",
            in_use * self._page_bytes / max(1, running),
        )

    def _count_pool_pages(self) -> int:
        """One chunk boundary's pages in use into ``ServeStats`` (paged
        mode only)."""
        in_use = self._kv.pages_in_use
        self.stats.pool_pages_total += in_use
        self.stats.pool_pages_peak = max(self.stats.pool_pages_peak, in_use)
        return in_use

    def hbm_bytes_per_request(self) -> float:
        """Peak resident KV bytes over peak concurrent running requests
        for the current measurement window — deterministic given the
        schedule, so the bench gate can pin it exactly. Contiguous mode
        charges the full static allocation (every row's
        decode_max_length is resident whether used or not); paged mode
        charges pages actually mapped."""
        if self._paged:
            resident = (
                self._kv.peak_pages_in_use * self._page_bytes
                + self._window_cache_bytes
            )
        else:
            resident = self._kv_bytes_static
        return resident / max(1, self._peak_running)

    def prefix_hit_rate(self) -> float:
        """Admissions served (partly) from the prefix cache over all
        admissions in the window; 0.0 when disabled or idle."""
        if self._kv is None:
            return 0.0
        total = self._kv.prefix_hits + self._kv.prefix_misses
        return self._kv.prefix_hits / total if total else 0.0

    # ------------------------------------------------------------------
    # request latency telemetry (host clock only; see RequestTelemetry)

    def _note_admit(self, rid: int) -> None:
        rec = self.request_stats[rid]
        rec.admit_t = time.perf_counter()
        self._observe("serve/queue_wait_s", rec.queue_wait_s)
        self._gauge_set("serve/queued", len(self._queue))
        self._trace(rec.trace_id, "admit", rec.admit_t, rid=rid)

    def _note_tokens(self, rid: int, n: int, now: float) -> None:
        rec = self.request_stats[rid]
        if rec.first_tok_t is None:
            rec.first_tok_t = now
            self._observe("serve/ttft_s", rec.ttft_s)
            self._trace(rec.trace_id, "first_token", now, rid=rid)
        rec.tokens += n

    def _note_finish(
        self, rid: int, now: float, version: Optional[int] = None
    ) -> None:
        rec = self.request_stats[rid]
        rec.finish_t = now
        rec.weights_version = (
            version if version is not None else self.weights_version
        )
        tpot = rec.tpot_s
        if tpot is not None:
            self._observe("serve/tpot_s", tpot)
        self._observe(
            "serve/request_tokens", float(rec.tokens), _REQ_TOKENS_EDGES
        )
        self._count("serve/requests_finished")
        self._trace(
            rec.trace_id, "finish", now, rid=rid,
            tokens=rec.tokens, weights_version=rec.weights_version,
        )
        self._retire(rid)

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
        if self._paged:
            # a request that failed mid-prompt-fill must not leave its
            # half-written pages hit-eligible in the prefix cache
            self._kv.abort_filling(rid)
        # accounting keyed on the reason: "expired" means deadline
        # expiry and nothing else (the degraded-mode signal operators
        # alert on); "shed" is the autopilot's deliberate load-shedding
        # (its own alertable signal — shed traffic is policy, not a
        # fault); other retirements (fleet shrink) count serve/failed
        if reason == "deadline":
            self.stats.expired += 1
            self._count("serve/expired")
        elif reason == "shed":
            self.stats.shed += 1
            self._count("serve/shed")
        else:
            self._count("serve/failed")
        rec = self.request_stats.get(rid)
        if rec is not None and rec.finish_t is None:
            rec.finish_t = now
        if rec is not None:
            self._trace(
                rec.trace_id,
                "expired" if reason == "deadline" else "failed",
                now, rid=rid, reason=reason, tokens=rec.tokens,
            )
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
            self._gauge_set("serve/queued", len(self._queue))

    def _expire_running(self, now: float) -> np.ndarray:
        """Evict running rows past their deadline at a boundary; returns
        the evicted-row mask (legacy mode resets those cache rows; fused
        mode leaves the device row decoding into the void until the slot
        is reused — emissions for a done rid are dropped at harvest)."""
        evict = np.zeros((self._b,), bool)
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
            self._tokens[i] = 0
            evict[i] = True
            if self._paged:
                # the device twin may still be live: defer the free
                # when chunks are in flight (see _release_row_pages)
                self._release_row_pages(i, device_dead=False)
        return evict

    # rolling-window span for the live throughput gauge: long enough to
    # average over scheduling noise, short enough that a collapse shows
    # within seconds on an operator's console/dashboard
    _RATE_WINDOW_S = 10.0
    # finished RequestTelemetry records retained for the host stats API
    _MAX_FINISHED_STATS = 50_000

    def _live_rate(self) -> float:
        """Tokens over the current + previous window, against the age of
        the older one — evaluated at flush/snapshot time, so it reflects
        'now' even when no harvest has run since the last flush."""
        dt = time.perf_counter() - self._rate_prev_t0
        if dt <= 0:
            return float("nan")
        return (self._rate_win_tokens + self._rate_prev_tokens) / dt

    def _note_throughput(self, new_tokens: int, now: float) -> None:
        self._count("serve/tokens", new_tokens)
        self._gauge_set(
            "serve/slot_utilization", self.stats.slot_utilization
        )
        self._rate_win_tokens += new_tokens
        if now - self._rate_win_t0 >= self._RATE_WINDOW_S:
            self._rate_prev_t0 = self._rate_win_t0
            self._rate_prev_tokens = self._rate_win_tokens
            self._rate_win_t0 = now
            self._rate_win_tokens = 0

    # ------------------------------------------------------------------
    # legacy per-token path (chunk_size=None): the exactness oracle for
    # the fused path and the latency-critical single-token mode

    def _admit_legacy(self):
        with annotate("serve.admit"):
            now = time.perf_counter()
            self._expire_queued(now)
            reset_mask = self._expire_running(now)
            admit_pos = np.zeros((self._b,), np.int32)
            if self._paged and self._kv.flush_deferred():
                self._kv_table_dirty = True  # legacy: always clean
            for i, slot in enumerate(self._slots):
                if slot.rid >= 0 or not self._queue:
                    continue
                req = self._queue[0]
                start_pos = 0
                if self._paged:
                    alloc = self._try_alloc(i, req)
                    if alloc is None:
                        break  # head-of-line waits for pages to free
                    start_pos = alloc.start_pos
                self._queue.popleft()
                self._slots[i] = _Slot(
                    rid=req.rid,
                    pending=list(req.prompt[start_pos + 1:]),
                    pos=start_pos,
                    emitted=0,
                    budget=req.max_new_tokens,
                    deadline_t=req.deadline_t,
                )
                self._tokens[i] = req.prompt[start_pos]
                reset_mask[i] = True
                admit_pos[i] = start_pos
                self._note_admit(req.rid)
                self.stats.rows_reset += 1
                self.stats.rows_reset_device_bytes += self._row_reset_bytes
            if reset_mask.any():
                if self._paged:
                    self._cache = self._reset(
                        self._cache, jnp.asarray(reset_mask),
                        jnp.asarray(admit_pos),
                    )
                else:
                    self._cache = self._reset(
                        self._cache, jnp.asarray(reset_mask)
                    )
                self.stats.host_dispatches += 1
            if self._paged:
                self._push_page_table()
            self._note_pages()

    def _step_legacy(self) -> dict[int, int]:
        self._apply_pending_weights()
        self._admit_legacy()
        if not self._busy():
            return {}
        if self._step is None:
            self._step = self._build_step()
        pos = np.asarray([s.pos for s in self._slots], np.int32)
        live = np.asarray([s.rid >= 0 for s in self._slots], bool)
        self._rng, sub = jax.random.split(self._rng)
        with annotate("serve.dispatch"):
            self._cache, nxt = self._step(
                self._params, self._cache, jnp.asarray(self._tokens),
                jnp.asarray(pos), sub, jnp.asarray(live),
            )
        with annotate("serve.readback"):
            # d9d-lint: disable=D9D003 — the one [B] readback per legacy token step
            nxt = np.asarray(nxt)
        now = time.perf_counter()
        self._progress_t = now
        if self._first_readback_t is None:
            self._first_readback_t = now
        self.stats.host_dispatches += 1
        self.stats.readbacks += 1
        self.stats.device_steps += 1
        self.stats.slot_steps_total += self._b
        self.stats.slot_steps_busy += int(live.sum())
        self.stats.positions_attended += int((pos[live] + 1).sum())
        self.stats.recurrent_state_bytes = self._recurrent_state_bytes
        self.stats.window_cache_bytes = self._window_cache_bytes
        if self._paged:
            self._count_pool_pages()
        self._observe("serve/slot_util", live.sum() / self._b, _UTIL_EDGES)

        emitted: dict[int, int] = {}
        evict_mask = np.zeros((self._b,), bool)
        for i, slot in enumerate(self._slots):
            if slot.rid < 0:
                continue
            slot.pos += 1
            if self._paged and not slot.pending:
                # the whole prompt has been dispatched: this rid's
                # prefix-cache entries become hit-eligible (idempotent)
                self._kv.mark_filled(slot.rid)
            if slot.pending:  # still consuming the prompt
                self._tokens[i] = slot.pending.pop(0)
                self.stats.slot_steps_prompt += 1
                continue
            tok = int(nxt[i])  # sampled from the row's latest position
            emitted[slot.rid] = tok
            self.outputs[slot.rid].append(tok)
            slot.emitted += 1
            self.stats.emitted_tokens += 1
            self._note_tokens(slot.rid, 1, now)
            finished = slot.emitted >= slot.budget or (
                self._eos is not None and tok == self._eos
            )
            if finished:
                self._note_finish(slot.rid, now)
                self.done.add(slot.rid)
                self._slots[i] = _Slot()
                self._tokens[i] = 0
                evict_mask[i] = True
                if self._paged:
                    # legacy rows only step under a host live mask, so
                    # a cleared slot can never write again: free now
                    self._release_row_pages(i, device_dead=True)
            else:
                self._tokens[i] = tok
        self._note_throughput(len(emitted), now)
        if evict_mask.any():
            # reset at EVICTION, not just admission, so the freed row's
            # cache contents can't leak into a same-rid-free diagnostic
            # view; the overflow/block-skip concern itself is handled by
            # the in-step cache_index pin
            if self._paged:
                self._cache = self._reset(
                    self._cache, jnp.asarray(evict_mask),
                    jnp.zeros((self._b,), jnp.int32),
                )
            else:
                self._cache = self._reset(
                    self._cache, jnp.asarray(evict_mask)
                )
            self.stats.host_dispatches += 1
        return emitted

    # ------------------------------------------------------------------
    # fused path: one dispatch + one readback per K-step chunk

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
        carry the program splits. The table goes with EVERY chunk, so
        ``_kv_table_dirty`` decides nothing here (the single-step path's
        ``_push_page_table`` still reads it): whatever admission,
        release or a deferred release's zeroing did to the mirror is on
        the device before the chunk's first step.

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
             cols.table.start + (self._pages_per_row if self._paged else 0)),
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
                if self._paged:
                    # admit=True ⇒ no chunks in flight: deferred zombie
                    # pages free now; this chunk carries the zeroed
                    # table rows
                    self._kv.flush_deferred()
                for i, slot in enumerate(self._slots):
                    if slot.rid >= 0 or not self._queue:
                        continue
                    req = self._queue[0]
                    start_pos = 0
                    if self._paged:
                        alloc = self._try_alloc(i, req)
                        if alloc is None:
                            break  # head-of-line waits for pages
                        start_pos = alloc.start_pos
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
                    self._note_admit(req.rid)
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
            if self._paged:
                packed[:, cols.table] = self._kv.table

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
        if self._paged:
            for slot in self._slots:
                if slot.rid >= 0 and not slot.feed:
                    # the whole prompt is now DISPATCHED: this rid's
                    # prefix-cache entries become hit-eligible — later
                    # admits dispatch after, so their reads see the
                    # writes (idempotent across chunks)
                    self._kv.mark_filled(slot.rid)
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
        reset_bytes = rows_reset * self._row_reset_bytes
        self.stats.rows_reset_device_bytes += reset_bytes
        self.stats.recurrent_state_bytes = self._recurrent_state_bytes
        self.stats.window_cache_bytes = self._window_cache_bytes
        # what the wrapper's own Python and the enqueue cost this chunk
        # (TrackedJit.last_call), beside the one staging timed above
        cost = fused.last_call
        clock.meta.update(
            recurrent_state_bytes=self._recurrent_state_bytes,
            window_cache_bytes=self._window_cache_bytes,
            rows_reset=rows_reset,
            rows_reset_device_bytes=reset_bytes,
            dispatch_key_s=cost.key_s,
            dispatch_enqueue_s=cost.enqueue_s,
            dispatch_arg_leaves=cost.arg_leaves,
            stage_s=stage_s,
            stage_transfers=1,
        )
        if self._paged:
            # on the closing serve/step span too: ServeStats gives a
            # caller totals, the span timeline any window's peak
            clock.meta.update(
                pool_pages=self._count_pool_pages(),
                pool_pages_free=self._kv.pages_free,
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
            if self._counts_held_rows:
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
                        if self._paged:
                            # the device row died IN-DEVICE at this same
                            # step (its later writes are pinned to the
                            # garbage page), so the pages free
                            # immediately; reuse waits for the next admit
                            # boundary, whose chunk carries the new table
                            self._release_row_pages(i, device_dead=True)
                        break
                self.stats.slot_steps_busy += busy_steps
                # step j of the row writes position pos + j and attends
                # positions 0..pos + j
                chunk_positions += (
                    busy_steps * plan.pos[i]
                    + busy_steps * (busy_steps + 1) // 2
                )
                if self._ring_windows:
                    row_spans.append((plan.pos[i], busy_steps))
                # steps in which the row only consumed a prompt token,
                # from the plan the chunk was dispatched with; a row emits
                # before it dies, so these never pass the step it died on
                self.stats.slot_steps_prompt += min(
                    plan.emit_from[i], plan.k
                )
                chunk_busy += busy_steps
                if rid in emitted:
                    self._note_tokens(rid, len(emitted[rid]), now)
                    if rid in self.done:
                        self._note_finish(rid, now, version=plan.version)
            self.stats.positions_attended += chunk_positions
            # this chunk's share of the two counters, for a reader of the
            # span timeline (a traced window's chunks, say)
            clock.meta.update(
                slot_steps_busy=chunk_busy, positions_attended=chunk_positions
            )
            # 0 without window layers: a reader of the attention layers'
            # work (the paged decode kernel's roofline) is told so
            chunk_window = sum(
                layers * _positions_under(row_spans, window)
                for window, layers in self._ring_windows.items()
            )
            self.stats.window_positions_attended += chunk_window
            clock.meta.update(window_positions_attended=chunk_window)
            self._observe(
                "serve/slot_util", chunk_busy / (self._b * plan.k),
                _UTIL_EDGES,
            )
            self._note_throughput(chunk_tokens, now)
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
        (generation phase) during the chunk. Fused mode only."""
        if self._k is None:
            raise RuntimeError(
                "step_chunk() needs a fused batcher (chunk_size not None)"
            )
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
        ``{rid: token}`` for tokens emitted (generation phase) this step.

        In fused mode this runs a K=1 chunk (same one-dispatch boundary
        semantics); with ``chunk_size=None`` it is the legacy per-token
        path.
        """
        if self._k is None:
            return self._step_legacy()
        self._sync()
        if not self._busy() and not self._queue:
            return {}
        with self._chunk_clock():
            self._dispatch_chunk(1, admit=True)
            emitted = self._sync()
        return {rid: toks[0] for rid, toks in emitted.items() if toks}

    def drain(self, max_steps: int = 100_000) -> dict[int, list[int]]:
        """Run until every submitted request has finished.

        Fused mode pipelines chunks double-buffered: while no admissions
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
                        self._count("serve/stalls")
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
                        "replica": self._replica_label,
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
        if self._k is None:
            steps = 0
            while self.active:
                self._step_legacy()
                steps += 1
                if steps > max_steps:
                    raise RuntimeError("drain exceeded max_steps")
            return self.outputs

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
                while len(self._pending) > (1 if self._overlap else 0):
                    self._harvest_one()
            elif self._pending:
                self._harvest_one()
        return self.outputs
