"""Milliseconds the cyclic collector held the interpreter inside the
measured window (``host/gc``: every collection of generation 2 and every
one longer than 1 ms, recorded by the process hub's collector hook)."""

from benchmarks.harness import layers


def read(run):
    return 1e3 * sum(
        s.dur_s for s in layers.window_spans(run, {"host/gc"})
    )
