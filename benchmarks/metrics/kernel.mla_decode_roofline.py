"""The absorbed latent decode's share of its roofline, over the traced
chunks: the least time the chip could take for the work those chunks did
(``mla_decode_cost``: the rows of the positions the serving loop says it
attended, the absorption matmuls) over the device time of the ops under
``mla/cache_append`` (the new token's scatter and the page gather, which
is where the rows are read from HBM), ``mla/absorb_q``,
``mla/latent_attend`` and ``mla/fold_v``. Without the gather the three
stages read rows that are already on the chip and the share passes what a
memory roofline allows (85 and 91 % on the v5e; PERF.md, PR 27).

The traced chunks' own counts ride on their ``serve/step`` spans; a
program whose spans carry none, or a trace with no op under those scopes,
gives nothing to read."""

from benchmarks.harness import costs, layers
from benchmarks.harness import trace as tr
from benchmarks.metrics import mla_decode_cost

STAGES = r"mla/(cache_append|absorb_q|latent_attend|fold_v)(/|$)"


def read(run):
    traced = getattr(run.observed, "traced", None)
    if run.trace is None or not run.trace["devices"] or not traced:
        return None
    chunks = [
        s.meta for s in layers.spans_between(
            layers.program_spans(), *traced, names={"serve/step"}
        ) if s.meta and "positions_attended" in s.meta
    ]
    taken = layers.own_seconds(run, scope=STAGES)
    seconds = taken and taken["seconds"]
    if not chunks or not seconds:
        return None
    work = mla_decode_cost.mla_decode_work(
        run.hf,
        positions_attended=sum(c["positions_attended"] for c in chunks),
        slot_steps=sum(c["slot_steps_busy"] for c in chunks),
        steps=len(chunks) * run.observed.chunk_k,
    )
    least, bound = costs.roofline_seconds(work, run.peak)
    run.note("bound", bound)
    run.note("traced_chunks", len(chunks))
    run.note("device_s", seconds)
    return 100.0 * tr.roofline_share(least, seconds)
