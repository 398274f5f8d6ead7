"""The jit wrapper's own Python per chunk: what ``TrackedJit`` spends
before the compiled call of the fused program (flatten, signature,
lookup), which the serving loop puts on each chunk's ``serve/step`` span
as ``dispatch_key_s``. Mean over the measured window's chunks; spans
without the key (a program from before it) give nothing to read."""

from benchmarks.metrics import span_meta


def read(run):
    return span_meta.mean(run, "serve/step", "dispatch_key_s", 1e3)
