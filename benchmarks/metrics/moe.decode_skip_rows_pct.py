"""Routed pairs a router with a skip sent to the skip, as a share of all
routed pairs, over the measured window's decode steps:
``ServeStats.moe_rows_skipped`` over ``moe_rows_routed`` (the layers' own
counts, the last entry of their ``tokens_per_expert``, which the fused
chunk carries out with its tokens). A program without the counter, or
one that routed nothing, gives nothing to read."""


def read(run):
    stats = run.observed.stats_window
    routed = stats.get("moe_rows_routed")
    if not routed or "moe_rows_skipped" not in stats:
        return None
    return 100.0 * stats["moe_rows_skipped"] / routed
