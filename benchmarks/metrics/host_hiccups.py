"""What the process hub's hiccup probe saw (``Telemetry.watch_hiccups``),
for the three metrics that read it: ``host/hiccup`` spans, the late
wake-ups of a thread that only sleeps (``t0`` when it was due, ``dur_s``
how late it came), and ``host/probe`` spans, one a second with the
wake-ups' count and lateness, the witness that the probe ran.

A hiccup says the host stood still and not why. What it overlaps does:
inside a ``host/gc`` span the collector held the interpreter; inside a
``serve/phase/readback`` span the main thread was blocked in the runtime
with the interpreter released, so the pause is the machine's; anywhere
else the interpreter was held or the machine paused. A stretch with no
``host/probe`` had no probe and gives nothing to read, never 0."""

from benchmarks.harness import layers
from benchmarks.harness import trace as tr

HICCUP, PROBE, GC = "host/hiccup", "host/probe", "host/gc"
READBACK = "serve/phase/readback"


def extent(span) -> tuple:
    return (span.t0, span.t0 + span.dur_s)


def inside(spans, lo: float, hi: float, name: str) -> list:
    """The parts of the spans called ``name`` between ``lo`` and ``hi``,
    as a disjoint union: a pause that straddles an edge counts as far as
    it reaches in."""
    return tr.clip(
        tr.union(extent(s) for s in spans if s.name == name), lo, hi)


def common(a, b) -> list:
    """What two disjoint unions share."""
    return tr.subtract(a, tr.subtract(a, b))


def ms(intervals) -> float:
    return 1e3 * tr.measure(intervals)


def witness(spans, lo: float, hi: float):
    """``{"probe_wakes", "mean_wake_late_us"}`` over the ``host/probe``
    seconds that reach into ``[lo, hi]``, each by the share of it that
    lies inside; ``None`` when none does: no probe ran there."""
    wakes = late = 0.0
    for s in spans:
        if s.name != PROBE or not s.dur_s:
            continue
        share = tr.measure(tr.clip([extent(s)], lo, hi)) / s.dur_s
        wakes += share * s.meta["wakes"]
        late += share * s.meta["late_sum_s"]
    if not wakes:
        return None
    return {"probe_wakes": wakes, "mean_wake_late_us": 1e6 * late / wakes}


def window_hiccups(run):
    """The parts of the ``host/hiccup`` spans inside the run's measured
    window and, of those, the parts inside a collection, both as
    disjoint unions, with the notes every reader of their sum gives;
    ``None`` where the window holds no witness."""
    o = run.observed
    spans = layers.program_spans()
    seen = witness(spans, o.opened_at, o.closed_at)
    if seen is None:
        return None
    hiccups = inside(spans, o.opened_at, o.closed_at, HICCUP)
    in_gc = common(hiccups, inside(spans, o.opened_at, o.closed_at, GC))
    run.note("count", len(hiccups))
    run.note("longest_ms", 1e3 * max(
        (end - start for start, end in hiccups), default=0.0))
    run.note("gc_ms", ms(in_gc))
    for key, value in seen.items():
        run.note(key, value)
    return hiccups, in_gc
