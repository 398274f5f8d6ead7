"""Argument leaves of the fused chunk program: the largest
``dispatch_arg_leaves`` among the measured window's ``serve/step``
spans (the program with admission takes two or three arrays more than
the one without). Spans without the key give nothing to read."""

from benchmarks.harness import layers


def read(run):
    leaves = [
        s.meta["dispatch_arg_leaves"]
        for s in layers.window_spans(run, {"serve/step"})
        if s.meta and "dispatch_arg_leaves" in s.meta
    ]
    return max(leaves) if leaves else None
