"""The chunk's host-to-device stagings, wherever they sit (the
page-table pushes in ``admit``, the RNG split and the admission arrays
in ``plan``, the three plan arrays): ``stage_s`` on each chunk's
``serve/step`` span. Mean over the measured window's chunks; spans
without the key give nothing to read."""

from benchmarks.harness import layers


def read(run):
    seconds = [
        s.meta["stage_s"]
        for s in layers.window_spans(run, {"serve/step"})
        if s.meta and "stage_s" in s.meta
    ]
    return 1e3 * sum(seconds) / len(seconds) if seconds else None
