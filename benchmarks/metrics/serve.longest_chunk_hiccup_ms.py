"""Of the measured window's longest chunk (the ``serve/step`` that
``serve.longest_chunk_ms`` reports), the milliseconds in which the host
stood still: the ``host/hiccup`` spans that overlap it.

The note ``chunk`` says which chunk, its seconds and the window's
median, the overlap by phase, the ``host/gc`` milliseconds inside it and
a ``verdict`` on the chunk's excess over the median chunk: ``"gc"`` when
collections cover at least half of it, ``"host"`` when the hiccups
outside any collection do (a collection holds the probe's thread up too,
so its hiccup is the collector's), ``"device_or_runtime"`` when the
probe was alive and neither does: the device or the runtime answered
late and the host was awake to see it. A window with no ``host/probe``
witness gives nothing to read."""

import statistics

from benchmarks.harness import layers
from benchmarks.harness import trace as tr
from benchmarks.metrics import host_hiccups as hh

PHASES = "serve/phase/"


def read(run):
    o = run.observed
    spans = layers.program_spans()
    chunks = layers.window_spans(run, {"serve/step"})
    if not chunks or hh.witness(spans, o.opened_at, o.closed_at) is None:
        return None
    longest = max(chunks, key=lambda s: s.dur_s)
    lo, hi = hh.extent(longest)
    median = statistics.median(s.dur_s for s in chunks)
    hiccups = hh.inside(spans, lo, hi, hh.HICCUP)
    collections = hh.inside(spans, lo, hi, hh.GC)
    outside_gc = tr.subtract(hiccups, collections)
    half = (longest.dur_s - median) / 2
    if tr.measure(collections) >= half > 0:
        verdict = "gc"
    elif tr.measure(outside_gc) >= half > 0:
        verdict = "host"
    else:
        verdict = "device_or_runtime"
    run.note("chunk", {
        "chunk": longest.step, "seconds": longest.dur_s,
        "median_seconds": median,
        "by_phase_ms": {
            s.name[len(PHASES):]: hh.ms(tr.clip(hiccups, *hh.extent(s)))
            for s in layers.spans_between(spans, lo, hi)
            if s.name.startswith(PHASES) and s.step == longest.step
        },
        "gc_ms": hh.ms(collections),
        "outside_gc_ms": hh.ms(outside_gc),
        "verdict": verdict,
    })
    return hh.ms(hiccups)
