"""Device time of ops under ``train/optimizer`` as a share of busy time,
from the traced seconds."""

from benchmarks.harness import layers


def read(run):
    return layers.scope_share(run, layers.OPTIMIZER)
