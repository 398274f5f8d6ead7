"""Pipeline-schedule microbenchmark: 1F1B vs ZB1P × residual policies.

Zero-bubble schedules pay forward recomputes for the
dI/dW split ("remat" policy) or give up the deferred-W bubble filler
("cache_full"); whether either beats plain 1F1B is an empirical question,
and the single-controller executor's per-action dispatch cost needs a
number. This harness runs 2 virtual stages on ONE chip (pp=1,
stages_per_rank=2 — every schedule's action stream, no cross-chip
transfers) and measures steady-state optimizer-step time for each
(schedule, policy) combination.

Run on the TPU chip:  python tools/bench_pp.py
Smoke on CPU mesh:    JAX_PLATFORMS=cpu python tools/bench_pp.py --tiny

Prints one JSON line per combination plus a "winner" line.
"""

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def build_engine(schedule_cfg, *, cfg, seq_len, batch, microbatch, dtype,
                 pp=1):
    import jax
    import jax.numpy as jnp

    from d9d_tpu.core import MeshParameters
    from d9d_tpu.loop import CausalLMTask, ModelProvider
    from d9d_tpu.loop.components.batch_maths import BatchMaths
    from d9d_tpu.loop.pipeline_driver import PipelineTrainEngine
    from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM
    from d9d_tpu.nn.sdpa import build_sdpa_backend
    from d9d_tpu.parallel import replicate_plan

    class Provider(ModelProvider):
        def build_module(self, stage):
            return Qwen3DenseCausalLM(
                config=cfg, sdpa=build_sdpa_backend(), stage=stage, dtype=dtype
            )

        def build_plan(self, c):
            return replicate_plan(c)

        def sample_inputs(self, b, t):
            z = jnp.zeros((b, t), jnp.int32)
            return (z, z, z)

    # pp=1: virtual stages share one device (no bubbles, measures dispatch
    # overhead). pp>1: one device group per stage — real warmup/drain
    # bubbles, the regime zero-bubble schedules exist for.
    ctx = MeshParameters(pp=pp).build(jax.devices()[:pp])
    import optax

    engine = PipelineTrainEngine(
        ctx=ctx,
        schedule=schedule_cfg,
        model_provider=Provider(),
        task=CausalLMTask(),
        optimizer=optax.adamw(1e-4, b1=0.9, b2=0.95),
        batch_maths=BatchMaths(
            global_batch_size=batch,
            microbatch_size=microbatch,
            dp_size=1,
        ),
        seq_len=seq_len,
        init_rng=jax.random.PRNGKey(0),
    )
    return engine


def measure(engine, *, batch, microbatch, seq_len, vocab, warmup, steps,
            trace_dir=None):

    import jax
    import numpy as np

    from d9d_tpu.loop import CausalLMTask
    from d9d_tpu.loop.components.batch_staging import split_microbatches

    task = CausalLMTask()
    rng = np.random.RandomState(0)

    def make_microbatches():
        prepared = task.prepare_batch(
            {"input_ids": rng.randint(0, vocab, size=(batch, seq_len + 1))}
        )
        return split_microbatches(
            prepared,
            num_microbatches=batch // microbatch,
            microbatch_size=microbatch,
        )

    # warmup (incl. compilation) first. A pipeline step is many
    # executables: the loss is ready before the last step's optimizer
    # update (W-phase work trails it in the queue), so a timed region
    # waits for every stage's updated params as well as the metrics.
    def drain(m):
        jax.block_until_ready(
            (m, [rt.params for rt in engine.stages.values()])
        )

    for _ in range(warmup):
        m = engine.step(make_microbatches())
    drain(m)

    # timed loop runs UNPROFILED — per-op trace collection would inflate
    # the step times this harness records
    t0 = time.perf_counter()
    for _ in range(steps):
        m = engine.step(make_microbatches())
    drain(m)
    dt = time.perf_counter() - t0

    if trace_dir:
        # separate short traced pass: steady-state dispatch gaps only,
        # with per-action host annotations on (tools/trace_summary.py
        # groups by them)
        from d9d_tpu.core.tracing import trace

        with trace(trace_dir):
            for _ in range(min(steps, 3)):
                m = engine.step(make_microbatches())
            drain(m)
    return dt / steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="CPU smoke config")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument(
        "--pp", type=int, default=1,
        help="pipeline stages on SEPARATE devices (default 1 = virtual "
        "stages on one device; --pp 4 on the four-chip host, or on the "
        "CPU rig under JAX_PLATFORMS=cpu, has real warmup/drain bubbles "
        "per schedule — the zero-bubble regime)",
    )
    ap.add_argument(
        "--microbatches", type=int, default=None,
        help="override the microbatch COUNT (must divide the global batch)",
    )
    ap.add_argument(
        "--only", default=None,
        help="comma-separated schedule/policy filters, e.g. "
        "'1f1b/remat,zb1p/cache_acts' (substring match on schedule alone "
        "also works)",
    )
    ap.add_argument(
        "--profile", default=None, metavar="DIR",
        help="capture a jax.profiler trace per combination into DIR/<name> "
        "(inspect executor dispatch gaps / overlap in xprof)",
    )
    args = ap.parse_args()

    import os

    if args.tiny or os.environ.get("JAX_PLATFORMS") == "cpu":
        # CPU rig (--tiny always runs there; --pp N does under
        # JAX_PLATFORMS=cpu, and takes N real chips otherwise): the
        # virtual device count must be set before the backend starts
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{max(args.pp, 2)}"
            ).strip()

    import jax

    if args.tiny:
        jax.config.update("jax_platforms", "cpu")

    from d9d_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp

    from d9d_tpu.models.qwen3 import Qwen3DenseConfig
    from d9d_tpu.pipelining.factory import (
        DualPipeVScheduleConfig,
        Interleaved1F1BScheduleConfig,
        ZeroBubble1PScheduleConfig,
        ZeroBubbleVScheduleConfig,
    )

    if args.pp > 1:
        # real-bubble rig: one device group per stage, enough layers for
        # the V schedules' 2 stages/rank, microbatch count small enough
        # that warmup/drain bubbles are a visible fraction of the step
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 1024),), hidden_size=256,
            num_layers=2 * args.pp, num_heads=4, num_kv_heads=2,
            head_dim=64, intermediate_size=1024, remat=False,
        )
        seq_len, batch, microbatch = 256, 16, 2
        warmup, steps = 2, 5
        dtype = jnp.float32
    elif args.tiny:
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 256),), hidden_size=64, num_layers=2,
            num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128,
            remat=False,
        )
        seq_len, batch, microbatch = 64, 8, 2
        warmup, steps = 1, 2
        dtype = jnp.float32
    else:
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 32_768),), hidden_size=1024,
            num_layers=12, num_heads=16, num_kv_heads=8, head_dim=64,
            intermediate_size=4096, remat=True,
        )
        seq_len, batch, microbatch = 2048, 8, 1
        warmup, steps = 3, 8
        dtype = jnp.bfloat16
    if args.steps:
        steps = args.steps
    if args.microbatches:
        if batch % args.microbatches:
            raise SystemExit(
                f"--microbatches {args.microbatches} does not divide the "
                f"global batch {batch}"
            )
        microbatch = batch // args.microbatches

    spr = 2 if args.pp == 1 else 1  # virtual stages only on the 1-device rig
    combos = [
        ("1f1b", "remat",
         Interleaved1F1BScheduleConfig(stages_per_rank=spr)),
        ("zb1p", "remat",
         ZeroBubble1PScheduleConfig(
             stages_per_rank=spr, residual_policy="remat")),
        ("zb1p", "cache_full",
         ZeroBubble1PScheduleConfig(
             stages_per_rank=spr, residual_policy="cache_full")),
        # the true zero-bubble split (r4): dW deferred at 1F1B FLOPs
        ("zb1p", "cache_acts",
         ZeroBubble1PScheduleConfig(
             stages_per_rank=spr, residual_policy="cache_acts")),
        # V-style schedules are fixed at 2 stages/rank
        ("zbv", "cache_full", ZeroBubbleVScheduleConfig()),
        ("zbv", "cache_acts",
         ZeroBubbleVScheduleConfig(residual_policy="cache_acts")),
        ("dualpipev", "cache_full", DualPipeVScheduleConfig()),
        ("dualpipev", "cache_acts",
         DualPipeVScheduleConfig(residual_policy="cache_acts")),
    ]
    if args.only:
        wanted = [w.strip() for w in args.only.split(",")]
        combos = [
            (n, p, s) for n, p, s in combos
            if any(w == n or w == f"{n}/{p}" or w in n for w in wanted)
        ]
        if not combos:
            raise SystemExit(
                f"--only {args.only!r} matched nothing; valid: "
                "gpipe 1f1b zb1p zbv dualpipev (optionally /<policy>)"
            )
    results = []
    for name, policy, sched in combos:
        engine = build_engine(
            sched, cfg=cfg, seq_len=seq_len, batch=batch,
            microbatch=microbatch, dtype=dtype, pp=args.pp,
        )
        dt = measure(
            engine, batch=batch, microbatch=microbatch, seq_len=seq_len,
            vocab=cfg.vocab_size, warmup=warmup, steps=steps,
            trace_dir=f"{args.profile}/{name}_{policy}" if args.profile else None,
        )
        tok_s = batch * seq_len / dt
        row = {
            "schedule": name,
            "residual_policy": policy,
            "step_time_s": round(dt, 4),
            "tokens_per_sec": round(tok_s, 1),
        }
        results.append(row)
        print(json.dumps(row), flush=True)

    best = min(results, key=lambda r: r["step_time_s"])
    print(json.dumps({"winner": f"{best['schedule']}/{best['residual_policy']}",
                      "step_time_s": best["step_time_s"]}))


if __name__ == "__main__":
    main()
