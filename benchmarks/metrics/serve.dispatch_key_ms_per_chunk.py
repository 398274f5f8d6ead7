"""The jit wrapper's own Python per chunk: what ``TrackedJit`` spends
before the compiled call of the fused program (flatten, signature,
lookup), which the serving loop puts on each chunk's ``serve/step`` span
as ``dispatch_key_s``. Mean over the measured window's chunks; spans
without the key (a program from before it) give nothing to read."""

from benchmarks.harness import layers


def read(run):
    seconds = [
        s.meta["dispatch_key_s"]
        for s in layers.window_spans(run, {"serve/step"})
        if s.meta and "dispatch_key_s" in s.meta
    ]
    return 1e3 * sum(seconds) / len(seconds) if seconds else None
