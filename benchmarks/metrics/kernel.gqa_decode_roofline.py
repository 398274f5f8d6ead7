"""The paged grouped-query decode kernel's share of its roofline, over
the traced chunks: the least time the chip could take for the key and
value rows those chunks' steps had to read (``gqa_decode_cost``: in a
full layer the positions the serving loop says it attended, in a window
layer its own count of them, the window at most) over the device time of
the kernel's custom calls, each taken by its own instruction's scope
(``paged_decode_p<pages a block>/pallas_call``) in the compiled program
that ran it.

The traced chunks' own counts ride on their ``serve/step`` spans; a
program whose spans carry no ``window_positions_attended``, or a trace
with no such call, gives nothing to read."""

from benchmarks.harness import costs, layers
from benchmarks.harness import trace as tr
from benchmarks.metrics import gqa_decode_cost

KERNEL = r"paged_decode_p\d+/pallas_call"


def read(run):
    traced = getattr(run.observed, "traced", None)
    if not traced:
        return None
    chunks = [
        s.meta for s in layers.spans_between(
            layers.program_spans(), *traced, names={"serve/step"}
        ) if s.meta and "window_positions_attended" in s.meta
        and "positions_attended" in s.meta
    ]
    if not chunks:
        return None
    measured = layers.own_seconds(run, "fused", scope=KERNEL)
    if not measured or not measured["events"]:
        return None
    work = gqa_decode_cost.gqa_decode_work(
        run.hf,
        positions_attended=sum(c["positions_attended"] for c in chunks),
        window_positions_attended=sum(
            c["window_positions_attended"] for c in chunks
        ),
    )
    least, bound = costs.roofline_seconds(work, run.peak)
    run.note("bound", bound)
    run.note("traced_chunks", len(chunks))
    run.note("device_s", measured["seconds"])
    return 100.0 * tr.roofline_share(least, measured["seconds"])
