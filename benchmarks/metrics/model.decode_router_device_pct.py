"""Device time of the ops under the expert layers' router scopes
(``moe/router/``: the down-projection, the depth carry, the MLP, the
scores, the selection; for a one-matrix router its scores and selection)
as a share of busy time, from the traced seconds. A trace with no op
there gives nothing to read."""

from benchmarks.harness import layers

ROUTER = r"/moe/router/"


def read(run):
    return layers.scope_share(run, ROUTER) or None
