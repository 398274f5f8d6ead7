"""Device time of what compressed convolutional attention adds around the
paged decode kernel and the output projection, as a share of busy time,
from the traced seconds: the ops under the module's scopes
``cca/{qk_proj, conv, qk_mean, v_shift, norm_temp, rope}`` (the narrow
projections, both convolutions with their tails, the q-k mean, the value
shift, the l2 norms with the temperature, the partial rotation). A
program with no such module has no op there and gives nothing to read."""

from benchmarks.harness import layers

CCA_MIX = r"/cca/(qk_proj|conv|qk_mean|v_shift|norm_temp|rope)/"


def read(run):
    return layers.scope_share(run, CCA_MIX) or None
