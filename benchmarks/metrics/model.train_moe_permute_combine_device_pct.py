"""Device time of the row movement around the experts as a share of busy
time, from the traced steps: ops under ``moe/permute`` and
``moe/combine``, forward and transposed (the sort's index work, the
gathers, a held range's spread and fold, whose Pallas call keeps the
scope in its ``op_name``). The expert FFN under ``moe/experts/`` is
``model.train_experts_device_pct``'s. A program with no MoE layer has no
op there and gives nothing to read."""

from benchmarks.harness import layers

SCOPE = r"moe/(permute|combine)"


def read(run):
    return layers.scope_share(run, SCOPE) or None
