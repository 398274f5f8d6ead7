"""What an admission's reset of the rows it admits costs the device, in
milliseconds an admitting chunk: the device time of the ops under the
scope ``serve/reset_rows`` (``nn/decode_flags.zero_rows``: a loop of
in-place row writes into every per-row recurrent leaf) over the traced
chunks whose ``serve/step`` span carries ``rows_reset`` above 0. A
masked pass over every leaf in its place took 7 to 15 ms on these cells
(PERF.md, PR 52).

Traced seconds in which no chunk admitted, a program whose spans carry
no such count, or a trace with no op under that scope (a program with no
per-row state to clear), give nothing to read."""

from benchmarks.harness import layers

RESET = r"serve/reset_rows"


def read(run):
    traced = getattr(run.observed, "traced", None)
    if not traced:
        return None
    admitting = [
        s for s in layers.spans_between(
            layers.program_spans(), *traced, names={"serve/step"}
        ) if s.meta and s.meta.get("rows_reset", 0) > 0
    ]
    taken = layers.own_seconds(run, scope=RESET)
    seconds = taken and taken["seconds"]
    if not admitting or not seconds:
        return None
    run.note("admitting_chunks", len(admitting))
    run.note("device_s", seconds)
    return 1e3 * seconds / len(admitting)
