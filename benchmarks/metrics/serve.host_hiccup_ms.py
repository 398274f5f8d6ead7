"""Milliseconds the host stood still inside the measured window: the
sum of the ``host/hiccup`` spans there (the process hub's probe, a
thread that only sleeps 10 ms and notes every wake-up 10 ms or more
late). No chunk's own clock can say this of a long chunk: it runs on
the thread that was itself frozen or blocked.

The notes split the sum by what each hiccup overlaps (``gc_ms`` a
collection, ``readback_ms`` the main thread's blocking wait for the
device: the machine's, ``other_ms`` the interpreter held or the machine
paused), give the count and the longest, and from the window's
``host/probe`` witnesses the wake-ups counted and their mean lateness,
the one reading that may name a host slow throughout a window that held
no pause. A traced run adds ``traced_idle_ms``: the device's idle time
of the traced seconds under those seconds' hiccups, so that an idle gap
can be put down to a pause and not only to a phase. A window with no
witness had no probe and gives nothing to read."""

from benchmarks.harness import layers
from benchmarks.harness import trace as tr
from benchmarks.metrics import host_hiccups as hh


def read(run):
    found = hh.window_hiccups(run)
    if found is None:
        return None
    hiccups, in_gc = found
    o, spans = run.observed, layers.program_spans()
    rest = tr.subtract(hiccups, in_gc)
    blocked = hh.common(
        rest, hh.inside(spans, o.opened_at, o.closed_at, hh.READBACK))
    run.note("readback_ms", hh.ms(blocked))
    run.note("other_ms", hh.ms(rest) - hh.ms(blocked))
    traced = getattr(o, "traced", None)
    if (traced and run.trace is not None and run.trace["devices"]
            and hh.witness(spans, *traced) is not None):
        under = layers.spans_on_trace(run.trace, layers.spans_between(
            spans, *traced, names={hh.HICCUP}))
        run.note("traced_idle_ms", 1e3 * tr.idle_seconds_in(
            run.trace, under, {hh.HICCUP}))
    return hh.ms(hiccups)
