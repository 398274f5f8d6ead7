"""How full the dropless exchange's receive buffers were: rows needed
over rows of the buffers taken, ``moe/ep_buffer_fill`` on the measured
window's ``train/step`` spans (the Trainer puts a fetched step's
``moe/*`` scalars there). Mean over the fetched steps; spans without it
(a program with no expert-parallel exchange) give nothing to read."""

from benchmarks.metrics import span_meta


def read(run):
    return span_meta.mean(run, "train/step", "moe/ep_buffer_fill", 100.0)
