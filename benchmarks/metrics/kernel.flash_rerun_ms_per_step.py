"""Device time a step spends in flash kernels that run a second time
because a layer is rematerialised: the step program's Pallas custom calls
under a ``self_attn`` scope whose own instruction's op name lies under
``rematted_computation`` (``jax.checkpoint`` puts the recomputed forward
there), in milliseconds a traced step and device. A layer that keeps the
call's output and log-sum-exp has no such call and reads 0; so does a
program in which the compiler merged the repeat with the first forward. A
program with no flash call under ``self_attn`` gives nothing to read."""

from benchmarks.harness import layers
from benchmarks.harness import trace as tr

STEP = "train_step|jit_step"
FLASH = r"self_attn.*pallas_call"
RERUN = r"rematted_computation.*" + FLASH


def read(run):
    flash = layers.own_seconds(run, STEP, scope=FLASH)
    rerun = layers.own_seconds(run, STEP, scope=RERUN)
    if not flash or not rerun:
        return None
    steps = len(tr.module_seconds(run.trace, STEP))
    steps /= len(run.trace["devices"])
    if not flash["events"] or not steps:
        return None
    return 1e3 * rerun["seconds"] / steps
