"""The enqueue of the fused program per chunk: the seconds inside
``compiled(*args)``, which the serving loop puts on each chunk's
``serve/step`` span as ``dispatch_enqueue_s``. Mean over the measured
window's chunks; spans without the key give nothing to read."""

from benchmarks.metrics import span_meta


def read(run):
    return span_meta.mean(run, "serve/step", "dispatch_enqueue_s", 1e3)
