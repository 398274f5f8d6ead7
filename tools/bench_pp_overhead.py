"""PP executor dispatch-overhead microbench: mitigations vs naive VM.

The single-controller executor's per-action dispatch tax got real
mitigations — the pre-compiled dispatch plan (no
isinstance chains or label formatting on the step path), windowed
first-use kwargs staging, and the fused end-of-step loss-stat jit
(``pipelining/runtime/executor.py``). This harness runs the
SAME schedule program through (a) the production executor and (b) a
``NaiveExecutor`` subclass that deliberately re-creates the
pre-mitigation interpretation loop — per-action type dispatch + label
formatting, kwargs staged at first use on the action path, and one tiny
jitted add per microbatch for the loss statistics — and reports
steady-state step time for both. Device compute is identical (same
jitted stage executables), so the delta isolates host dispatch cost.

The fused MPMD rewrite made the whole ladder three rungs: a third
``fused`` row runs the same schedule through the compiled-run executor
(``runtime/fused.py``, its own engine — the naive VM shares the legacy
executor's internals, so the legacy pair pins ``runtime="legacy"``),
and the summary adds ``precompiled_over_fused`` /
``dispatch_tax_removed_pct`` — the tax the schedule compiler removes
on top of the per-action mitigations.

Smoke on CPU mesh:  JAX_PLATFORMS=cpu python tools/bench_pp_overhead.py --tiny
CPU rig number:     python tools/bench_pp_overhead.py --cpu
TPU chip:           python tools/bench_pp_overhead.py

Prints one JSON line per executor plus a "summary" line. No pipeline
runtime has a benchmark cell yet, so nothing here is in the ledger
(ROADMAP R-P, `pp-tools-without-a-cell`).
"""

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def build_naive(executor):
    """Wrap a built executor's state in the pre-mitigation step loop.

    Reuses the production action handlers (device work identical) but
    interprets the raw program order with per-action ``isinstance``
    chains + f-string labels, stages every microbatch's kwargs on the
    action path (no bounded first-use window), and sums per-microbatch
    loss stats with one tiny jitted dispatch per microbatch.
    """
    import jax

    from d9d_tpu.core.tracing import annotate
    from d9d_tpu.pipelining.program.actions import (
        BackwardFull,
        BackwardInput,
        BackwardRecv,
        BackwardSend,
        BackwardWeight,
        Compose,
        ForwardCompute,
        ForwardRecv,
        ForwardSend,
    )
    from d9d_tpu.pipelining.runtime.executor import (
        PipelineExecutionResult,
        PipelineScheduleExecutor,
        _StepState,
    )

    # built ONCE: the pre-mitigation loop paid one tiny jitted DISPATCH
    # per microbatch, not a retrace — a per-step jax.jit wrapper would
    # recompile the add every step and overstate the mitigation
    naive_add = jax.jit(
        lambda a, b: jax.tree.map(lambda x, y: x + y, a, b)
    )

    class NaiveExecutor(PipelineScheduleExecutor):
        def step(self, microbatches):
            first = self.stages[0]
            last = self._last
            st = _StepState(self.num_microbatches)
            with annotate("pp.stage_inputs"):
                for mb, micro in enumerate(microbatches):
                    carry, kw, state = first.task.split_microbatch(micro)
                    st.carries[mb] = self._put(carry, first.carry_sharding)
                    st.kwargs_h.append(kw)
                    st.states[mb] = self._put(state, last.state_sharding)
            # make every kwargs lookup stage on demand (no window)
            st.kwargs_next = len(self._kwargs_first_use)

            def run(action):
                # the pre-mitigation interpretation loop: type dispatch +
                # label formatting per action, every step
                if isinstance(action, Compose):
                    for member in action.actions:
                        run(member)
                    return
                if isinstance(action, (ForwardRecv, BackwardRecv)):
                    return
                if isinstance(action, ForwardCompute):
                    name, handler = "fwd", self._act_forward
                elif isinstance(action, ForwardSend):
                    name, handler = "fwd_send", self._act_forward_send
                elif isinstance(action, BackwardFull):
                    name, handler = "bwd", self._act_backward_full
                elif isinstance(action, BackwardInput):
                    name, handler = "bwd_dI", self._act_backward_input
                elif isinstance(action, BackwardWeight):
                    name, handler = "bwd_dW", self._act_backward_weight
                elif isinstance(action, BackwardSend):
                    name, handler = "bwd_send", self._act_backward_send
                else:  # pragma: no cover
                    raise TypeError(f"unknown action {action!r}")
                label = f"pp.{name}.s{action.stage}.mb{action.microbatch}"
                with annotate(label):
                    handler(st, action)

            for _rank, action in self.order:
                run(action)

            loss_sum = weight_sum = None
            metrics_sum = {}
            if st.aux:
                # one tiny jitted dispatch per microbatch (the
                # pre-mitigation loss accumulation)
                with annotate("pp.loss_sum"), last._scoped():
                    acc = st.aux[0]
                    for aux in st.aux[1:]:
                        acc = naive_add(acc, aux)
                    loss_sum, weight_sum, metrics_sum = acc
            return PipelineExecutionResult(
                grads=st.grads if self.train else None,
                loss_sum=loss_sum,
                weight_sum=weight_sum,
                metrics=dict(metrics_sum),
                outputs=st.outputs if not self.train else None,
            )

    naive = object.__new__(NaiveExecutor)
    naive.__dict__ = executor.__dict__
    return naive


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="CPU smoke config")
    ap.add_argument("--cpu", action="store_true",
                    help="CPU rig measurement config (bigger model)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--schedule", default="1f1b")
    args = ap.parse_args()

    if args.tiny or args.cpu:
        import os

        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2"
            ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")

    from d9d_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp

    from d9d_tpu.models.qwen3 import Qwen3DenseConfig
    from d9d_tpu.pipelining.factory import (
        Interleaved1F1BScheduleConfig,
        ZeroBubble1PScheduleConfig,
    )
    from tools.bench_pp import build_engine, measure

    if args.tiny:
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 256),), hidden_size=64, num_layers=2,
            num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128,
            remat=False,
        )
        seq_len, microbatch = 64, 1
        warmup, steps = 1, args.steps or 2
        dtype = jnp.float32
    elif args.cpu:
        # big enough that compute dominates: the overhead shows as a
        # few-percent delta
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 4096),), hidden_size=256,
            num_layers=4, num_heads=8, num_kv_heads=4, head_dim=32,
            intermediate_size=1024, remat=False,
        )
        seq_len, microbatch = 256, 2
        warmup, steps = 2, args.steps or 5
        dtype = jnp.float32
    else:
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 32_768),), hidden_size=1024,
            num_layers=12, num_heads=16, num_kv_heads=8, head_dim=64,
            intermediate_size=4096, remat=True,
        )
        seq_len, microbatch = 2048, 1
        warmup, steps = 3, args.steps or 10
        dtype = jnp.bfloat16

    batch = microbatch * args.microbatches
    # the naive VM shares the LEGACY executor's internals (handlers +
    # _StepState), so this engine must pin runtime="legacy"; the fused
    # compiled-run engine is measured as its own third row below
    if args.schedule == "1f1b":
        schedule_cfg = Interleaved1F1BScheduleConfig(
            stages_per_rank=2, runtime="legacy"
        )
    elif args.schedule == "zb1p":
        schedule_cfg = ZeroBubble1PScheduleConfig(
            stages_per_rank=2, residual_policy="cache_full",
            runtime="legacy",
        )
    else:
        raise SystemExit(f"unknown --schedule {args.schedule!r}")
    engine = build_engine(
        schedule_cfg, cfg=cfg, seq_len=seq_len, batch=batch,
        microbatch=microbatch, dtype=dtype,
    )
    fused_engine = build_engine(
        schedule_cfg.model_copy(update={"runtime": "fused"}),
        cfg=cfg, seq_len=seq_len, batch=batch,
        microbatch=microbatch, dtype=dtype,
    )

    # label -> (engine that owns the params, executor to install)
    legs = {
        "precompiled": (engine, engine.executor),
        "naive": (engine, build_naive(engine.executor)),
        "fused": (fused_engine, fused_engine.executor),
    }
    rows = {}
    # two passes per executor, first discarded: the first measured pass
    # carries compilation and code-path warmup (an A/B/A probe on the
    # tiny config showed the first round inflated ~2x for both sides);
    # only the warm second pass is recorded
    for recorded in (False, True):
        for label, (eng, executor) in legs.items():
            eng.executor = executor
            s = measure(
                eng, batch=batch, microbatch=microbatch,
                seq_len=seq_len, vocab=cfg.vocab_size, warmup=warmup,
                steps=steps,
            )
            if recorded:
                rows[label] = s
                row = {"executor": label, "step_s": round(s, 4),
                       "schedule": args.schedule,
                       "microbatches": args.microbatches}
                if label == "fused":
                    row["fused_programs"] = executor.num_fused_programs
                print(json.dumps(row), flush=True)

    print(json.dumps({"summary": {
        "naive_over_precompiled": round(
            rows["naive"] / rows["precompiled"], 4
        ),
        "overhead_removed_pct": round(
            100.0 * (rows["naive"] - rows["precompiled"]) / rows["naive"], 2
        ),
        "precompiled_over_fused": round(
            rows["precompiled"] / rows["fused"], 4
        ),
        "dispatch_tax_removed_pct": round(
            100.0 * (rows["precompiled"] - rows["fused"])
            / rows["precompiled"], 2
        ),
    }}), flush=True)


if __name__ == "__main__":
    main()
