"""The window layers' flash kernels as a share of their roofline, over
the traced steps: the Pallas custom calls under ``attn_window/self_attn``
(the decoder wraps a window layer's attention module in ``attn_window``),
forward, rematerialised forward and backward, each by its own instruction
in the program that ran it (``readers.kernel_roofline``), against the
work of those layers at their own query heads and the keys a query sees
under the window (``attention_kinds_train_cost.flash_train``). A file
without ``layer_types``, or a program with no such call, gives nothing
to read."""

from benchmarks.metrics import attention_kinds_train_cost as by_kind

SCOPE = r"attn_window/self_attn.*pallas_call"


def read(run):
    return by_kind.flash_roofline(
        run, by_kind.SLIDING, "flash_window_train", SCOPE)
