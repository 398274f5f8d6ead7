"""Layer-steps of the dropless exchange that fell back to the worst-case
receive buffer, over all its dispatches: ``moe/ep_fallback_share`` on
the measured window's ``train/step`` spans (the Trainer puts a fetched
step's ``moe/*`` scalars there). Mean over the fetched steps; spans
without it give nothing to read."""

from benchmarks.metrics import span_meta


def read(run):
    return span_meta.mean(run, "train/step", "moe/ep_fallback_share", 100.0)
