"""How full the dropless exchange's receive buffers were: rows needed
over rows of the buffers taken, ``moe/ep_buffer_fill`` on the measured
window's ``train/step`` spans (the Trainer puts a fetched step's
``moe/*`` scalars there). Mean over the fetched steps; spans without it
(a program with no expert-parallel exchange) give nothing to read."""

from benchmarks.harness import layers


def read(run):
    fills = [
        s.meta["moe/ep_buffer_fill"]
        for s in layers.window_spans(run, {"train/step"})
        if s.meta and "moe/ep_buffer_fill" in s.meta
    ]
    return 100.0 * sum(fills) / len(fills) if fills else None
