"""Device time of ops under the Kimi delta attention mixers' module scope
(``kda``) as a share of busy time, from the traced seconds: the scope
``kernel.kda_decode_roofline`` takes. A program with no such module has
no op there and gives nothing to read."""

from benchmarks.harness import layers

KDA = r"/kda/"


def read(run):
    return layers.scope_share(run, KDA) or None
