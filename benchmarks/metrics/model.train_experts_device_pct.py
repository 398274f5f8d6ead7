"""Device time of the expert FFN as a share of busy time, from the traced
seconds: ops under ``moe/experts/`` and the ``ragged-dot`` custom calls,
to which the compiler leaves no scope."""

from benchmarks.harness import layers


def read(run):
    return layers.scope_share(run, layers.EXPERTS, layers.RAGGED_CALL)
