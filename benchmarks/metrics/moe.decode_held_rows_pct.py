"""Routed pairs that landed on the experts this chip holds, as a share
of all routed pairs, over the measured window's decode steps:
``ServeStats.moe_rows_held`` over ``moe_rows_routed`` (the layers' own
counts, which the fused chunk carries out with its tokens). A program
without the counters, or one whose layers hold every expert (it counts
nothing), gives nothing to read."""


def read(run):
    stats = run.observed.stats_window
    routed = stats.get("moe_rows_routed")
    if not routed:
        return None
    return 100.0 * stats["moe_rows_held"] / routed
