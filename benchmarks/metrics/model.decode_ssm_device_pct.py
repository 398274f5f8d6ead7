"""Device time of ops under the Mamba mixers' module scope (``mamba``)
as a share of busy time, from the traced seconds. A program with no such
module has no op there and gives nothing to read."""

from benchmarks.harness import layers

MAMBA = r"/mamba/"


def read(run):
    share = layers.scope_share(run, MAMBA)
    return share or None
