"""Shared timing helper for the benchmark harnesses.

Dispatch is asynchronous, so a timed region has to end in
``jax.block_until_ready``: the call returns when the device has finished
everything the outputs depend on. ``chip_smoke.py``'s sync check shows on
the chip that it really waits (a chain of large matmuls timed around it
comes out at or below the chip's peak FLOP/s), so nothing is subtracted
from a reading.

One module so the methodology cannot drift between harnesses.
"""

import time


def timeit(fn, *args, reps: int = 50, warmup: int = 3) -> float:
    """Mean ms/call over ``reps`` back-to-back dispatches, timed around
    one ``block_until_ready`` on the last output (a device executes its
    queue in order, so the last output's readiness covers every call)."""
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3
