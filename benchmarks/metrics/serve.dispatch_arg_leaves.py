"""Argument leaves of the fused chunk program: the largest
``dispatch_arg_leaves`` among the measured window's ``serve/step``
spans (the program with admission takes two or three arrays more than
the one without). Spans without the key give nothing to read."""

from benchmarks.metrics import span_meta


def read(run):
    leaves = span_meta.values(run, "serve/step", "dispatch_arg_leaves")
    return max(leaves) if leaves else None
