"""Layer-steps of the dropless exchange that fell back to the worst-case
receive buffer, over all its dispatches: ``moe/ep_fallback_share`` on
the measured window's ``train/step`` spans (the Trainer puts a fetched
step's ``moe/*`` scalars there). Mean over the fetched steps; spans
without it give nothing to read."""

from benchmarks.harness import layers


def read(run):
    shares = [
        s.meta["moe/ep_fallback_share"]
        for s in layers.window_spans(run, {"train/step"})
        if s.meta and "moe/ep_fallback_share" in s.meta
    ]
    return 100.0 * sum(shares) / len(shares) if shares else None
