"""Routed pairs that landed on the experts this chip holds, as a share
of all routed pairs: ``moe/rows_held`` over ``moe/rows_routed`` on the
measured window's ``train/step`` spans (the Trainer puts a fetched
step's counts there; each is a sum over the expert layers). Spans
without the counts (a program whose layers hold every expert) give
nothing to read."""

from benchmarks.harness import layers


def read(run):
    counted = [
        s.meta for s in layers.window_spans(run, {"train/step"})
        if s.meta and s.meta.get("moe/rows_routed")
    ]
    if not counted:
        return None
    held = sum(m["moe/rows_held"] for m in counted)
    return 100.0 * held / sum(m["moe/rows_routed"] for m in counted)
