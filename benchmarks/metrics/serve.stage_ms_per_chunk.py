"""The chunk's host-to-device stagings, wherever they sit (the
page-table pushes in ``admit``, the RNG split and the admission arrays
in ``plan``, the three plan arrays): ``stage_s`` on each chunk's
``serve/step`` span. Mean over the measured window's chunks; spans
without the key give nothing to read."""

from benchmarks.metrics import span_meta


def read(run):
    return span_meta.mean(run, "serve/step", "stage_s", 1e3)
