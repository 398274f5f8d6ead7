"""The Kimi delta attention mixers' decode step as a share of its
roofline, over the traced chunks: the least time the chip could take for
the state (a matrix a head, float32, read and written), the convolution
tails, the step's operands and the mixers' projections of every KDA
layer (``kda_decode_cost``: all slots, every traced step) over the
device time of the ops under the mixers' module scope (``kda``), the
scope ``model.decode_kda_device_pct`` takes.

The whole mixer and not the step's Pallas call alone, as
``kernel.ssm_decode_roofline`` is built and for the reason its docstring
gives: the compiler brings operands into VMEM under the neighbouring
projections, and the time of the call alone would leave out part of the
work.

The traced chunks are counted from their ``serve/step`` spans, which
carry ``recurrent_state_bytes`` in a program that has such layers; a
program whose spans carry none, a configuration without the family's
keys (``linear_attn_config``), or a trace with no op under that scope,
gives nothing to read."""

from benchmarks.harness import costs, layers
from benchmarks.harness import trace as tr
from benchmarks.metrics import kda_decode_cost

MIXER = r"/kda/"


def read(run):
    traced = getattr(run.observed, "traced", None)
    if not traced:
        return None
    if "linear_attn_config" not in run.hf:
        return None
    chunks = [
        s for s in layers.spans_between(
            layers.program_spans(), *traced, names={"serve/step"}
        ) if s.meta and s.meta.get("recurrent_state_bytes")
    ]
    taken = layers.own_seconds(run, scope=MIXER)
    seconds = taken and taken["seconds"]
    if not chunks or not seconds:
        return None
    work = kda_decode_cost.kda_decode_work(
        run.hf, slots=run.observed.slots,
        steps=len(chunks) * run.observed.chunk_k,
    )
    least, bound = costs.roofline_seconds(work, run.peak)
    run.note("bound", bound)
    run.note("traced_chunks", len(chunks))
    run.note("device_s", seconds)
    return 100.0 * tr.roofline_share(least, seconds)
