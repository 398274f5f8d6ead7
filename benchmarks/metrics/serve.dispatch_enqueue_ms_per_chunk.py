"""The enqueue of the fused program per chunk: the seconds inside
``compiled(*args)``, which the serving loop puts on each chunk's
``serve/step`` span as ``dispatch_enqueue_s``. Mean over the measured
window's chunks; spans without the key give nothing to read."""

from benchmarks.harness import layers


def read(run):
    seconds = [
        s.meta["dispatch_enqueue_s"]
        for s in layers.window_spans(run, {"serve/step"})
        if s.meta and "dispatch_enqueue_s" in s.meta
    ]
    return 1e3 * sum(seconds) / len(seconds) if seconds else None
