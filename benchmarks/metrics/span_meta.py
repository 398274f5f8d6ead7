"""What the program put on its spans' ``meta``, for the metrics that
read one key of it: the values over the measured window's spans of one
name, and their mean. Spans without the key (a program from before it,
a step that fetched nothing) give nothing to read."""

from benchmarks.harness import layers


def values(run, span: str, key: str) -> list:
    return [
        s.meta[key] for s in layers.window_spans(run, {span})
        if s.meta and key in s.meta
    ]


def mean(run, span: str, key: str, scale: float = 1.0):
    found = values(run, span, key)
    return scale * sum(found) / len(found) if found else None
