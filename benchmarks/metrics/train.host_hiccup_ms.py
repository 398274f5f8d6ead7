"""Milliseconds the host stood still inside a training window: the sum
of the ``host/hiccup`` spans there (the process hub's probe, a thread
that only sleeps 10 ms and notes every wake-up 10 ms or more late). The
Trainer dispatches ahead of the device, so a short pause costs the rate
nothing; one at a metric fetch, where the loop waits for the device with
nothing queued behind it, does.

The notes give the count, the longest, the collector's share
(``gc_ms``), the ``host/probe`` witnesses' wake-ups and mean lateness,
and ``longest_period``: the longest stretch from one ``train/step`` to
the next in the window (the long fetch period of PERF.md, PR 37 and
PR 27) with the hiccup milliseconds inside it. A window with no witness
had no probe and gives nothing to read."""

from benchmarks.harness import layers
from benchmarks.harness import trace as tr
from benchmarks.metrics import host_hiccups as hh


def read(run):
    found = hh.window_hiccups(run)
    if found is None:
        return None
    hiccups, _ = found
    steps = sorted(
        layers.window_spans(run, {"train/step"}), key=lambda s: s.t0)
    periods = [
        (after.t0 - before.t0, before)
        for before, after in zip(steps, steps[1:])
    ]
    if periods:
        seconds, step = max(periods, key=lambda p: p[0])
        run.note("longest_period", {
            "step": step.step, "seconds": seconds,
            "hiccup_ms": hh.ms(
                tr.clip(hiccups, step.t0, step.t0 + seconds)),
        })
    return hh.ms(hiccups)
