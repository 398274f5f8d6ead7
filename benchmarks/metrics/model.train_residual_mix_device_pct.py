"""Device time of ops under an ``mhc`` scope (the n-stream residual
path: ``attn_mhc`` and ``mlp_mhc`` modules, ``mhc/*`` scopes) as a share
of busy time, from the traced steps. A program without the path has no
op there and gives nothing to read."""

from benchmarks.harness import layers
from benchmarks.metrics import mhc_train_cost


def read(run):
    return layers.scope_share(run, mhc_train_cost.SCOPE) or None
