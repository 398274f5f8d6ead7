"""From a profiler trace to numbers: the benchmark's own reduction.

Two halves. ``capture`` and ``load_xplane`` need jax and a run; they turn
the profiler's ``.xplane.pb`` into a plain ``dict`` of events (the
*normalised trace*, JSON-serialisable). Everything else is arithmetic on
that dict and imports nothing but the standard library, so it is tested
on a small recorded trace (``tests/benchmarks/data``) with no TPU.

Normalised trace (times in seconds on the profiler's clock)::

    {"devices": {"0": {"ops":     [[text, start, dur], ...],   # XLA Ops
                       "async":   [[text, start, dur], ...],   # Async XLA Ops
                       "modules": [[name, start, dur, run_id], ...]}},
     "host":    [[thread, name, start, dur, run_id_or_null], ...]}

An op's ``text`` is the HLO instruction as the trace names it
(``%fusion.3 = bf16[..] fusion(..)``), cut to ``TEXT_LIMIT`` characters.
"""

import bisect
import glob
import os
import re
import typing

TEXT_LIMIT = 400
LABEL_LIMIT = 96
LEDGER_KEEPS = 64  # characters of a label

COLLECTIVE_OPCODES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "ragged-all-to-all", "collective-permute", "collective-broadcast",
)
# data movement the compiler schedules beside compute: neither compute
# nor a collective when exposure is reckoned
TRANSFER_OPCODES = ("copy-start", "copy-done")
# ops that only contain other ops: their own time is what their
# children leave over
CONTAINER_OPCODES = ("while", "conditional", "call")
# host spans idle gaps are attributed to: the program's annotations
# (serve.*, loop.*, pp*) and the benchmark's own (bench/*)
SPAN_PREFIXES = ("serve.", "bench/", "loop.", "train/", "pp")


# -- capture (needs jax) ----------------------------------------------------


def capture(logdir: str):
    """Profile the body into ``logdir`` through the program's one control
    for the profiler (``core/tracing.trace``: Python tracer off, host
    tracer level 2, annotations on, a clock anchor at both ends)."""
    from d9d_tpu.core.tracing import trace

    return trace(logdir)


def span(name: str):
    """A host span of the benchmark's own, written into the profiler's
    trace while one is being captured (a null context otherwise)."""
    from d9d_tpu.core.tracing import annotate

    return annotate(name)


def newest_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """The normalised trace of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    def stat(event, key):
        for k, v in event.stats:
            if k == key:
                return v
        return None

    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        device = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if device:
            lanes = {"ops": [], "async": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    lanes["ops"] = [
                        [e.name[:TEXT_LIMIT], e.start_ns / 1e9,
                         e.duration_ns / 1e9] for e in line.events
                    ]
                elif line.name == "Async XLA Ops":
                    lanes["async"] = [
                        [e.name[:TEXT_LIMIT], e.start_ns / 1e9,
                         e.duration_ns / 1e9] for e in line.events
                    ]
                elif line.name == "XLA Modules":
                    lanes["modules"] = [
                        [e.name, e.start_ns / 1e9, e.duration_ns / 1e9,
                         stat(e, "run_id")] for e in line.events
                    ]
            out["devices"][device[1]] = lanes
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    run_id = (
                        stat(e, "run_id") if e.name == "DoEnqueueProgram"
                        else None
                    )
                    out["host"].append([
                        line.name, e.name, e.start_ns / 1e9,
                        e.duration_ns / 1e9, run_id,
                    ])
    return out


# -- names ------------------------------------------------------------------


def instruction(text: str) -> tuple[str, str, str]:
    """``(instruction name, result type, opcode)`` of an HLO
    instruction's text, as a trace or a compiled program prints it."""
    m = re.match(r"%?(\S+) = (.*)", text, re.S)
    if not m:
        return text.strip().lstrip("%"), "", ""
    name, rest = m[1], m[2]
    if rest.startswith("("):  # tuple-shaped result: up to its close
        depth, close = 0, len(rest)
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                close = i + 1
                break
        result, rest = rest[:close], rest[close:]
    else:
        result, _, rest = rest.partition(" ")
    opcode = re.match(r"\s*([\w\-]+)\(", rest)
    return name, result, opcode[1] if opcode else ""


def parse_op(text: str) -> tuple[str, str]:
    """``(instruction name, opcode)`` of an HLO instruction's text."""
    name, _, opcode = instruction(text)
    return name, opcode


# what every op of a fused serving chunk carries ahead of its own scope:
# the chunk's loop and the model's class and entry method
_WRAPPERS = re.compile(r"^(?:jit\([^)]*\)/)+")
_CHUNK_HEAD = re.compile(r"^while/body/closed_call/[A-Z]\w*\.\w+/")


def label(text: str, scope: str | None = None) -> str:
    """``opcode:scope-or-name`` as the breakdown prints it. ``scope`` is
    the ``op_name`` of the event's own instruction (``layers.own_scope``):
    the leading ``jit(...)/`` wrappers go from it and the layers of a
    stack add up under one label. A label over ``LABEL_LIMIT`` keeps both
    ends, the scope's first 36 characters and the op. A serving chunk's
    also loses the head that all its ops share, and where it is still
    over what the ledger keeps, keeps its tail alone: the tail names the
    op."""
    name, opcode = parse_op(text)
    chunk = 0
    if scope:
        scope = re.sub(r"layers_\d+", "layers_*", _WRAPPERS.sub("", scope))
        scope, chunk = _CHUNK_HEAD.subn("", scope)
    head = f"{opcode or 'op'}:"
    full = head + (scope or name)
    kept, limit = (len(head), LEDGER_KEEPS) if chunk else (36, LABEL_LIMIT)
    if len(full) > limit:
        full = full[:kept] + ".." + full[kept + 2 - limit:]
    return full


# -- intervals --------------------------------------------------------------


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint, sorted union of ``(start, end)`` pairs."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def measure(intervals) -> float:
    return sum(end - start for start, end in intervals)


def clip(intervals, lo: float, hi: float):
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals
        if min(e, hi) > max(s, lo)
    ]


def subtract(a, b):
    """Parts of union ``a`` not covered by union ``b`` (both disjoint,
    sorted)."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def _spans(events):
    return [(e[1], e[1] + e[2]) for e in events]


def window_of(trace: dict) -> tuple[float, float]:
    """First device op's start to the last one's end, over all devices."""
    starts, ends = [], []
    for lanes in trace["devices"].values():
        events = lanes["ops"] or lanes["modules"]
        if events:
            starts.append(min(e[1] for e in events))
            ends.append(max(e[1] + e[2] for e in events))
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_by_device(trace: dict, window=None) -> dict[str, float]:
    """Seconds in which an operation ran, per device: the union of the
    device's op intervals (nested ops count once), inside ``window``."""
    lo, hi = window or window_of(trace)
    return {
        ordinal: measure(clip(union(_spans(
            lanes["ops"] or lanes["modules"]
        )), lo, hi))
        for ordinal, lanes in trace["devices"].items()
    }


def busy_and_window(trace: dict, window=None) -> tuple[float, float]:
    """(busy seconds averaged over the devices, window seconds)."""
    lo, hi = window or window_of(trace)
    busy = busy_by_device(trace, (lo, hi))
    return sum(busy.values()) / len(busy), hi - lo


def idle_share(trace: dict, window=None) -> float:
    busy, length = busy_and_window(trace, window)
    return 1.0 - busy / length


# -- per-operation time -----------------------------------------------------


def self_times_at(events) -> list[tuple[str, float, float]]:
    """``(text, start, self seconds)`` per event of one device lane: an
    event's duration less what the events nested inside it cover."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [text, start, end, self]
    for text, start, dur in order:
        while stack and stack[-1][2] <= start + 1e-12:
            done = stack.pop()
            out.append((done[0], done[1], max(done[3], 0.0)))
        if stack:
            stack[-1][3] -= dur
        stack.append([text, start, start + dur, dur])
    while stack:
        done = stack.pop()
        out.append((done[0], done[1], max(done[3], 0.0)))
    return out


def events_in_modules(trace: dict, window=None):
    """``(device, module, text, self seconds)`` for every device op of
    the window. ``module`` is the execution the op started in, as the
    trace names it (the program's name with its fingerprint:
    ``jit_step(1444..)``), or ``None`` outside any: a device runs one
    program at a time, so an op belongs to the execution that covers
    its start."""
    lo, hi = window or window_of(trace)
    for ordinal, lanes in trace["devices"].items():
        runs = sorted((m[1], m[1] + m[2], m[0]) for m in lanes["modules"])
        starts = [r[0] for r in runs]
        inside = [e for e in lanes["ops"] if lo <= e[1] < hi]
        for text, start, seconds in self_times_at(inside):
            i = bisect.bisect_right(starts, start) - 1
            covered = i >= 0 and start < runs[i][1]
            yield ordinal, runs[i][2] if covered else None, text, seconds


class Ops(typing.NamedTuple):
    """A window's device ops, grouped once a trace: what every reader of
    device time by instruction sums over."""

    devices: int
    by_instruction: dict  # (module, text) -> [self seconds, events], summed


def grouped(trace: dict, window=None) -> Ops:
    """``events_in_modules`` by the execution's module and the op's text:
    one instruction of one program, however often it ran."""
    out: dict = {}
    for _, module, text, own in events_in_modules(trace, window):
        row = out.setdefault((module, text), [0.0, 0])
        row[0] += own
        row[1] += 1
    return Ops(max(len(trace["devices"]), 1), out)


def event_seconds(ops: Ops, take) -> dict:
    """Self time of the device ops that ``take(text, module)`` accepts,
    averaged over devices; with the event count per device."""
    seconds, events = 0.0, 0
    for (module, text), (own, count) in ops.by_instruction.items():
        if take(text, module):
            seconds, events = seconds + own, events + count
    return {"seconds": seconds / ops.devices, "events": events / ops.devices}


def top_ops(ops: Ops, scope_of=None, n: int = 10):
    """The ``n`` device operations with most self time, by label, summed
    over the window and averaged over devices. ``scope_of(text, module)``
    gives an event's scope (``layers.own_scope``: from its own
    instruction in the program that ran its module); without it, or where
    it gives none, the label is the instruction's name."""
    totals: dict[str, float] = {}
    for (module, text), (seconds, _) in ops.by_instruction.items():
        key = label(text, scope_of(text, module) if scope_of else None)
        totals[key] = totals.get(key, 0.0) + seconds
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / ops.devices] for k, v in ranked]


def module_seconds(trace: dict, pattern: str, window=None) -> list[float]:
    """Device durations of the executions of the modules (executables)
    whose name matches, over all devices, in start order."""
    rx = re.compile(pattern)
    lo, hi = window or window_of(trace)
    runs = [
        (m[1], m[2]) for lanes in trace["devices"].values()
        for m in lanes["modules"] if rx.search(m[0]) and lo <= m[1] < hi
    ]
    return [dur for _, dur in sorted(runs)]


# -- host spans and idle gaps ------------------------------------------------


def clock_offset(trace: dict) -> float:
    """Seconds to ADD to device times so that no program starts on the
    device before the host enqueued it. The two clocks are synchronised
    by the profiler to within a millisecond or so; this shifts the
    device timeline by the smallest amount that restores causality and
    leaves it alone when causality already holds."""
    enqueued = {
        h[4]: h[2] for h in trace["host"]
        if h[1] == "DoEnqueueProgram" and h[4] is not None
    }
    lead = [
        enqueued[m[3]] - m[1]
        for lanes in trace["devices"].values() for m in lanes["modules"]
        if m[3] in enqueued
    ]
    return max(max(lead), 0.0) if lead else 0.0


def program_spans(
    trace: dict, prefixes=SPAN_PREFIXES
) -> list[tuple[str, float, float]]:
    """Host spans ``(name, start, end)`` whose name starts with one of
    ``prefixes``: the program's and the benchmark's annotations."""
    return [
        (h[1], h[2], h[2] + h[3]) for h in trace["host"]
        if h[1].startswith(prefixes)
    ]


def idle_gaps(trace: dict, spans, n: int = 10, window=None,
              unattributed: str = "(no span)") -> list[list]:
    """Device idle time by the innermost host span that covers it.

    Idle is the window less the union of op intervals on the first
    device (with several devices they idle together in an SPMD program);
    each idle piece is cut at span boundaries and each cut goes to the
    shortest span that contains it."""
    lo, hi = window or window_of(trace)
    first = sorted(trace["devices"])[0]
    lanes = trace["devices"][first]
    shift = clock_offset(trace)
    busy = union(
        (s + shift, e + shift)
        for s, e in _spans(lanes["ops"] or lanes["modules"])
    )
    gaps = subtract([(lo + shift, hi + shift)], busy)
    totals: dict[str, float] = {}
    ordered = sorted(spans, key=lambda s: s[2] - s[1])  # shortest first
    for g_start, g_end in gaps:
        cuts = sorted({g_start, g_end, *(
            t for _, s, e in spans for t in (s, e) if g_start < t < g_end
        )})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            owner = next(
                (name for name, s, e in ordered if s <= mid < e),
                unattributed,
            )
            totals[owner] = totals.get(owner, 0.0) + (b - a)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def idle_seconds_in(trace: dict, spans, names, window=None) -> float:
    """Idle seconds attributed to the spans called ``names``."""
    return sum(
        seconds for name, seconds in idle_gaps(
            trace, spans, n=10**6, window=window
        ) if name in names
    )


# -- collectives -------------------------------------------------------------


def _is(opcode: str, family) -> bool:
    return any(
        opcode == f or opcode.startswith(f + "-") for f in family
    )


def collective_exposed_share(trace: dict, window=None) -> float:
    """Share of the window in which a collective runs on a device and no
    compute does, averaged over devices. A collective's interval is its
    event on the op lane (a synchronous collective, or the start and the
    waiting done of an asynchronous one) and its start-to-done span on
    the async lane; compute is every other op except scheduled copies."""
    lo, hi = window or window_of(trace)
    shares = []
    for lanes in trace["devices"].values():
        collective, compute = [], []
        for text, start, dur in lanes["ops"]:
            opcode = parse_op(text)[1]
            if _is(opcode, COLLECTIVE_OPCODES):
                collective.append((start, start + dur))
            elif opcode in CONTAINER_OPCODES or _is(opcode, TRANSFER_OPCODES):
                continue
            else:
                compute.append((start, start + dur))
        for text, start, dur in lanes["async"]:
            if _is(parse_op(text)[1], COLLECTIVE_OPCODES):
                collective.append((start, start + dur))
        exposed = subtract(
            clip(union(collective), lo, hi), clip(union(compute), lo, hi)
        )
        shares.append(measure(exposed) / (hi - lo))
    return sum(shares) / len(shares)


# -- roofline ----------------------------------------------------------------


def roofline_share(least_seconds: float, measured_seconds: float) -> float:
    """The least time the chip could take over the time it took. Never
    clamped: above 1 the operations or bytes are counted too high, or
    the time leaves out part of the work."""
    if measured_seconds <= 0:
        raise ValueError("no measured time for the kernel")
    return least_seconds / measured_seconds
