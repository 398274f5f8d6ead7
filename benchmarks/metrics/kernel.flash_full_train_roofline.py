"""The full-attention layers' flash kernels as a share of their roofline
in a stack that mixes attention kinds: the Pallas custom calls under a
``self_attn`` scope that no ``attn_window`` encloses, against the work of
those layers at the full kind's query heads and the causal half of the
sequence. The reading is ``kernel.flash_window_train_roofline``'s, of the
other kind."""

from benchmarks.metrics import attention_kinds_train_cost as by_kind

# an op name holds ``self_attn`` twice (the module's scope, then the
# method's: ``.../self_attn/self_attn._sdpa_padded/pallas_call``), so the
# whole name is held free of ``attn_window``, not one occurrence's prefix
SCOPE = r"^(?!.*attn_window/).*self_attn.*pallas_call"


def read(run):
    return by_kind.flash_roofline(run, by_kind.FULL, "flash_full_train", SCOPE)
