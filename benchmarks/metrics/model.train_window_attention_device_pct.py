"""Device time of the window layers' attention as a share of busy time,
from the traced steps: the train step's ops whose own instruction is
scoped under ``attn_window`` (the decoder wraps a window layer's
attention module in that scope). A program with no such scope has no op
there and gives nothing to read."""

from benchmarks.harness import layers

WINDOW_ATTENTION = r"/attn_window/"
STEP = "train_step|jit_step"


def read(run):
    return layers.scope_share(
        run, WINDOW_ATTENTION, module_pattern=STEP) or None
