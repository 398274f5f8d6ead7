"""Device time of ops under ``lm_head`` (the output head and its linear
cross-entropy) as a share of busy time, from the traced seconds."""

from benchmarks.harness import layers


def read(run):
    return layers.scope_share(run, layers.HEAD_LOSS)
