"""Bytes of the window layers' rings of pages the serving loop holds, in
GB: the largest ``window_cache_bytes`` the measured window's
``serve/step`` spans carry (it is a level: every chunk says the same).
Spans without the count (a program without the counter) give nothing to
read."""

from benchmarks.harness import layers


def read(run):
    counts = [
        s.meta["window_cache_bytes"]
        for s in layers.window_spans(run, {"serve/step"})
        if s.meta and "window_cache_bytes" in s.meta
    ]
    return max(counts) / 1e9 if counts else None
