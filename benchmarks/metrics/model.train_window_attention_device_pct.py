"""Device time of the window layers' attention as a share of busy time,
from the traced steps: the train step's ops whose own instruction is
scoped under ``attn_window`` (the decoder wraps a window layer's
attention module in that scope), each looked up in the compiled program
that ran it (``layers.programs_that_ran``). A program with no such scope
has no op there and gives nothing to read."""

from benchmarks.harness import layers
from benchmarks.harness import trace as tr

WINDOW_ATTENTION = r"/attn_window/"
STEP = "train_step|jit_step"


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    take = layers.own_instruction(
        layers.programs_that_ran(run.trace, run.programs),
        STEP, scope=WINDOW_ATTENTION,
    )
    try:
        seconds = tr.event_seconds(run.trace, take)["seconds"]
    except layers.Ambiguous as which:
        run.notes["train_window_attention.ambiguous"] = str(which)
        return None
    busy, _ = tr.busy_and_window(run.trace)
    if not seconds or not busy:
        return None
    return 100.0 * seconds / busy
