"""The lowering half of ``entry.compile_s``: tracing the step in Python
and lowering it, which a hit in the compile cache does not skip."""


def read(run):
    return sum(r.lower_s for r in run.inventory)
