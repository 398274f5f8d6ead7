"""Of the (q block, kv block) pairs the window layers' flash grids
visit, those that compute, forward and backward: ``flash/window/<pass>/
blocks_computed`` over ``blocks_visited`` on the measured window's
``train/step`` spans (the kernel's wrapper notes both from static shapes
where the step is traced, a kind and a pass; the Trainer puts them on a
fetched step's span). Spans without the counts (a program without a
window layer, or one whose attention is not the Pallas kernel) give
nothing to read."""

from benchmarks.harness import layers

PASSES = ("fwd", "bwd")


def read(run):
    counted = [
        s.meta for s in layers.window_spans(run, {"train/step"})
        if s.meta and s.meta.get("flash/window/fwd/blocks_visited")
    ]
    if not counted:
        return None
    last = counted[-1]  # a level, the same on every fetched step
    visited = sum(last[f"flash/window/{p}/blocks_visited"] for p in PASSES)
    computed = sum(last[f"flash/window/{p}/blocks_computed"] for p in PASSES)
    return 100.0 * computed / visited
