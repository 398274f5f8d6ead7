"""Device time of ops under the attention modules' scopes (``self_attn``)
as a share of busy time, from the traced seconds."""

from benchmarks.harness import layers


def read(run):
    return layers.scope_share(run, layers.ATTENTION)
