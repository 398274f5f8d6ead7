"""Device time of ops under the multi-token-prediction module's scope
(``mtp``) as a share of busy time, from the traced steps. It overlaps
the attention, expert and residual-mix shares, whose scopes stay inside
the module. A program without the module has no op there and gives
nothing to read."""

from benchmarks.harness import layers

MTP = r"/mtp/"


def read(run):
    return layers.scope_share(run, MTP) or None
