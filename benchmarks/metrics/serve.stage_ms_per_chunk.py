"""The chunk's host-to-device stagings, wherever they sit (since PR 42
one a chunk: the packed array of plan, admission and page table, which
carries the RNG key the program splits): ``stage_s`` on each chunk's
``serve/step`` span. Mean over the measured window's chunks; spans
without the key give nothing to read."""

from benchmarks.metrics import span_meta


def read(run):
    return span_meta.mean(run, "serve/step", "stage_s", 1e3)
