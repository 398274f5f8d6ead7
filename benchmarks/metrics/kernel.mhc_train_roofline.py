"""The n-stream residual path as a share of its roofline, over the
traced steps: the least time the chip could take for the stream's passes
(``mhc_train_cost``: per sublayer and token the stream read twice and
written once, the sublayer's input written and its output read, and
twice that backward) over the device time of the ops under an ``mhc``
scope (the path's module scopes ``attn_mhc`` and ``mlp_mhc`` with
``mhc/{norm,coef,sinkhorn,pre,post}`` under them, and the stream's
``mhc/{expand,readout}``): the scope
``model.train_residual_mix_device_pct`` takes.

A configuration without ``hc_mult`` above 1, or a trace with no op under
that scope (a program without the path), gives nothing to read."""

from benchmarks.harness import costs, layers
from benchmarks.harness import trace as tr
from benchmarks.metrics import mhc_train_cost

STEP = r"train_step|jit_step"


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    if mhc_train_cost.streams(run.hf) < 2:
        return None
    taken = layers.own_seconds(run, scope=mhc_train_cost.SCOPE)
    seconds = taken and taken["seconds"]
    steps = len(tr.module_seconds(run.trace, STEP))
    steps /= max(len(run.trace["devices"]), 1)
    if not seconds or not steps:
        return None
    o = run.observed
    work = mhc_train_cost.mhc_train_work(run.hf, o.tokens_per_step // o.chips)
    least, bound = costs.roofline_seconds(work, run.peak)
    run.note("bound", bound)
    run.note("traced_steps", steps)
    run.note("device_s", seconds)
    return 100.0 * tr.roofline_share(least * steps, seconds)
