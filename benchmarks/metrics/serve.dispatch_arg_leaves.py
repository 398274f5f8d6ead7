"""Argument leaves of the fused chunk program: the largest
``dispatch_arg_leaves`` among the measured window's ``serve/step``
spans (since PR 42 the program with admission and the one without take
the same arguments: plan, admission and page table are one packed
array). Spans without the key give nothing to read."""

from benchmarks.metrics import span_meta


def read(run):
    leaves = span_meta.values(run, "serve/step", "dispatch_arg_leaves")
    return max(leaves) if leaves else None
