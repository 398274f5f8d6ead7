"""Peak of the page pool's pages in use at the measured window's chunk
boundaries over the pages of the pool (in use plus free: every page a
request can be given), both from the counts each ``serve/step`` span
carries. Spans without the counts give nothing to read."""

from benchmarks.harness import layers


def read(run):
    counts = [
        s.meta for s in layers.window_spans(run, {"serve/step"})
        if s.meta and "pool_pages_free" in s.meta
    ]
    if not counts:
        return None
    pool = counts[0]["pool_pages"] + counts[0]["pool_pages_free"]
    return 100.0 * max(c["pool_pages"] for c in counts) / pool
