"""Trace the registered hot executables on the CPU rig and collect
their compile-time audit facts.

This is the standing certification harness the tier-1 gate
(tests/tools/test_audit_clean.py) and the ``d9d-audit`` CLI both run:
every executable shape the repo dispatches in production is compiled
here once, at tiny config, with artifact capture on — non-PP train
step, ZeRO dp_replicate>1 train step, the serving fused-K path, the
disaggregated prefill->decode fleet (whose handoff
must add zero executables), the speculative-decode round, the
PipelinedOptimizer per-stage update programs, and the fused MPMD
pipeline runs
(``pp_fused/r{R}/run{K}``). Each leg runs under its own capture context
so the manifest can pre-register per-configuration contracts (the same
``train_step`` name carries "no collectives" plain and the exact
reduce-scatter/all-gather schedule under ZeRO).

Facts are harvested at compile time only (telemetry/audit_capture.py):
the legs below dispatch a handful of steps merely to force each
wrapper's lower→compile, and the gate pins that capture added zero
runtime dispatches/readbacks.

Every leg asserts it captured at least one fact block — a silently
disabled capture (or a renamed executable) must fail the gate, not
read as clean.
"""

import contextlib
from typing import Callable

__all__ = ["LEGS", "trace_registered_executables"]


def _collect(leg_name: str, fn: Callable[[], None]) -> list[dict]:
    from d9d_tpu.telemetry import audit_capture, introspect

    mark = len(introspect.inventory())
    with audit_capture.context(leg_name):
        fn()
    facts = [
        r.audit
        for r in introspect.inventory()[mark:]
        if r.audit is not None
    ]
    if not facts:
        raise RuntimeError(
            f"audit leg {leg_name!r} captured no facts — either capture "
            "was not enabled or the leg compiled nothing; the gate "
            "cannot certify what it did not see"
        )
    return facts


# -- toy fixtures (the tests/parallel/test_zero.py shapes) ---------------


def _toy_train(dp: int, zero_on: bool, steps: int = 2) -> None:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from d9d_tpu.core.mesh import MeshParameters
    from d9d_tpu.core.tree_sharding import replicate_uncommitted
    from d9d_tpu.loop.control.task import TrainTask
    from d9d_tpu.loop.train_step import build_train_step
    from d9d_tpu.parallel.zero import (
        ZeroShardedOptimizer,
        build_zero_sharding,
        place_tree,
    )
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    class ToyTask(TrainTask):
        def prepare_batch(self, batch):
            return batch

        def loss_fn(self, module, params, mb, rng):
            y = module.apply(params, mb["x"])
            return (
                jnp.sum((y - mb["y"]) ** 2),
                jnp.float32(mb["x"].shape[0]),
                {},
            )

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.Dense(16)(x)
            return nn.Dense(4)(jax.nn.relu(h))

    ctx = MeshParameters(dp_replicate=dp).build(jax.devices()[:dp])
    module = Net()
    x = jnp.ones((2, 4, 8)) * jnp.arange(8)
    y = jnp.linspace(0, 1, 2 * 4 * 4).reshape(2, 4, 4)
    params = jax.device_put(
        module.init(jax.random.PRNGKey(0), x[0]),
        NamedSharding(ctx.mesh, P()),
    )
    opt = optax.adamw(1e-2)
    opt_state = replicate_uncommitted(jax.jit(opt.init)(params), ctx.mesh)
    zero = None
    if zero_on:
        zero = build_zero_sharding(
            params=params, opt_state=opt_state, mesh=ctx.mesh
        )
        opt_state = place_tree(opt_state, zero.state_shardings)
        opt = ZeroShardedOptimizer(opt, zero)
    step = build_train_step(
        module=module, task=ToyTask(), optimizer=opt,
        num_microbatches=2, zero=zero,
    )
    rng = jax.random.PRNGKey(1)
    for _ in range(steps):
        params, opt_state, metrics = step(
            params, opt_state, {"x": x, "y": y}, rng
        )
    jax.block_until_ready(metrics["loss"])


def leg_train() -> None:
    """Non-PP train step on a 1-chip mesh: zero collectives."""
    _toy_train(dp=1, zero_on=False)


def leg_train_zero() -> None:
    """ZeRO dp_replicate=2 train step: the reduce-scatter/all-gather
    schedule (expressed as all-reduce + all-gather on the CPU SPMD
    backend) pre-registered in the manifest."""
    import jax

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "the ZeRO audit leg needs >= 2 devices — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "(the d9d-audit CLI sets this automatically)"
        )
    _toy_train(dp=2, zero_on=True)


def leg_serve() -> None:
    """The fused-K serving path (fused_k4[_admit], the row reset inside
    the admitting program)."""
    from tools.bench_serve import build_model

    from d9d_tpu.loop.serve import ContinuousBatcher

    model, params, cfg = build_model()
    fused = ContinuousBatcher(model, params, batch_size=2, chunk_size=4)
    fused.submit([1, 2, 3], max_new_tokens=10)
    fused.drain()


def leg_serve_quant() -> None:
    """The low-precision serving path: int8 weight stream (per-channel
    qvalue+scale tree dequantized inside the traced step) over int8
    paged KV pools with sibling scale pages. The manifest requires the
    fused decode programs' dtype census to carry BOTH int8 (pool/weight
    loads actually narrow) and float32 (accumulation stays wide) —
    certifying no silent bf16/f32 pool resurrection — on top of the
    serve plane's zero-collective and donation contracts."""
    from tools.bench_serve import build_model

    from d9d_tpu.loop.quantize import quantize_for_serving
    from d9d_tpu.loop.serve import ContinuousBatcher

    model, params, cfg = build_model()
    qparams = quantize_for_serving(params)
    fused = ContinuousBatcher(
        model, qparams, batch_size=2, chunk_size=4,
        page_size=4, num_pages=33, kv_quant="int8",
    )
    fused.submit([1, 2, 3], max_new_tokens=10)
    fused.drain()


def leg_serve_disagg() -> None:
    """The disaggregated prefill->decode fleet: the handoff plane is
    host-side page shipment (export -> checksum -> import via device
    transfer), so the contract this leg certifies is mostly negative —
    a fleet round that hands a request off compiles exactly the same
    serving executables as a unified paged replica (serve/fused_k*,
    zero collectives), and a steady-state handed-off request adds NO
    tracked executables: the transfer never grows the dispatch set."""
    from tools.bench_serve import build_model

    from d9d_tpu.loop.serve import ContinuousBatcher
    from d9d_tpu.resilience import ServingFleet
    from d9d_tpu.telemetry import get_telemetry, introspect

    model, params, cfg = build_model()

    def make() -> ContinuousBatcher:
        return ContinuousBatcher(
            model, dict(params), batch_size=2, chunk_size=4,
            page_size=4, num_pages=33,
        )

    fleet = ServingFleet()
    fleet.add_replica(make(), role="prefill")
    fleet.add_replica(make(), role="decode")
    prompt = [1, 2, 3, 4, 5, 6]  # spans a full page: a real handoff
    fleet.submit(prompt, max_new_tokens=10)
    fleet.drain()
    snap = get_telemetry().registry.snapshot()["counters"]
    if not snap.get("serve/fleet_handoffs", 0):
        raise RuntimeError(
            "disagg audit leg fell back to re-prefill instead of "
            "shipping pages — it certified nothing; counters: "
            f"handoffs={snap.get('serve/fleet_handoffs', 0)} "
            f"fallbacks={snap.get('serve/fleet_handoff_fallbacks', 0)}"
        )

    # steady state: a second handed-off request must hit the compiled
    # set — the page shipment itself is not allowed to introduce (or
    # recompile) a single tracked executable
    mark = len(introspect.inventory())
    fleet.submit(prompt, max_new_tokens=10)
    fleet.drain()
    added = [r.name for r in introspect.inventory()[mark:]]
    if added:
        raise RuntimeError(
            "the steady-state handoff round compiled new tracked "
            f"executables {added} — page transfer must stay host-side"
        )
    fleet.close()


def leg_spec_decode() -> None:
    """The fused speculative round (serve/spec_round): draft + verify
    as one executable, zero collectives."""
    import jax
    import jax.numpy as jnp

    from d9d_tpu.loop.speculative import speculative_generate
    from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
    from d9d_tpu.ops.attention.eager import eager_sdpa

    def dense(seed: int):
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 64),),
            hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2,
            head_dim=8, intermediate_size=64, remat=False,
        )
        model = Qwen3DenseCausalLM(
            config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
            decode_max_length=24,
        )
        z = jnp.zeros((2, 4), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32), (2, 4))
        params = model.clone(decode_max_length=0).init(
            jax.random.PRNGKey(seed), z, pos, z
        )["params"]
        return model, params

    model, params = dense(0)
    draft, draft_params = dense(7)
    prompt = jnp.ones((2, 4), jnp.int32)
    out = speculative_generate(
        model, params, draft, draft_params, prompt,
        max_new_tokens=6, speculate_k=2,
    )
    jax.block_until_ready(out)


def leg_pp_opt() -> None:
    """PipelinedOptimizer per-stage device programs under ZeRO
    (pp_opt/s{S}/update_guarded + combine_guarded + sq_norm): the
    per-stage pairs the MPMD runtime will inherit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from d9d_tpu.core.mesh import AXIS_DP_REPLICATE
    from d9d_tpu.pipelining.training import PipelinedOptimizer

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "the pp_opt audit leg needs >= 2 devices — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    mesh = Mesh(np.array(jax.devices()[:2]), (AXIS_DP_REPLICATE,))
    sh = NamedSharding(mesh, P())
    popt = PipelinedOptimizer(
        optimizer=optax.adamw(1e-2),
        scalar_shardings={0: sh, 1: sh},
        anomaly_freeze=True,
        zero_axis=AXIS_DP_REPLICATE,
    )
    params = {
        s: {"w": jax.device_put(
            jnp.linspace(s, s + 1, 16).reshape(4, 4), sh
        )}
        for s in (0, 1)
    }
    states = popt.init(params)
    guard = popt.init_guard_state()
    for i in range(2):
        grads = {
            s: {"w": jnp.full((4, 4), 0.1 * (i + 1))} for s in (0, 1)
        }
        params, states, _, _gm, guard = popt.step_guarded(
            params, states, grads, jnp.float32(1.0), jnp.float32(1.0),
            guard,
        )
    jax.block_until_ready(guard)


def leg_pp_fused() -> None:
    """The fused MPMD pipeline runtime (pipelining/runtime/fused.py):
    every compiled run (``pp_fused/r{R}/run{K}``) certified for the
    zero-collective contract and donation coverage. Two partitions:
    the tiny single-program 1F1B config (the bench_compare acceptance
    row) and the zero-bubble cache_acts pp=2 schedule,
    whose dI/dW split plus cross-rank run boundaries produce the
    richest run structure the partitioner emits."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from d9d_tpu.pipelining import (
        FusedPipelineExecutor,
        PipelineStageInfo,
        PipelineStageRuntime,
    )
    from d9d_tpu.pipelining.program import add_communication_ops
    from d9d_tpu.pipelining.program.builders import (
        Interleaved1F1BProgramBuilder,
    )

    hid = 8

    class _Stage(nn.Module):
        @nn.compact
        def __call__(self, x):
            return jnp.tanh(nn.Dense(hid, use_bias=True)(x))

    class _Task:
        def split_microbatch(self, micro):
            return micro["x"], {}, {"y": micro["y"], "w": micro["w"]}

        def stage_forward(self, module, params, carry, kwargs):
            return module.apply(params, carry)

        def last_stage_loss(self, module, params, carry, kwargs, state):
            out = module.apply(params, carry)
            err = ((out - state["y"]) ** 2).sum(-1)
            return (err * state["w"]).sum(), state["w"].sum(), {}

    def run(builder, m, residual_policy):
        key = jax.random.PRNGKey(0)
        stages = {}
        for s in range(builder.num_stages):
            key, sub = jax.random.split(key)
            module = _Stage()
            stages[s] = PipelineStageRuntime(
                info=PipelineStageInfo(
                    stage_index=s, num_stages=builder.num_stages
                ),
                module=module,
                params=module.init(sub, jnp.zeros((1, hid))),
                task=_Task(),
                residual_policy=residual_policy,
            )
        program = add_communication_ops(
            builder.compose(m), num_stages=builder.num_stages,
            stage_owner=builder.stage_owner,
        )
        ex = FusedPipelineExecutor(
            stages=stages, program=program,
            stage_owner=builder.stage_owner, num_microbatches=m,
        )
        mb_key = jax.random.PRNGKey(1)
        mbs = []
        for _ in range(m):
            mb_key, k1, k2 = jax.random.split(mb_key, 3)
            mbs.append({
                "x": jax.random.normal(k1, (4, hid)),
                "y": jax.random.normal(k2, (4, hid)),
                "w": jnp.ones((4,)),
            })
        res = ex.step(list(mbs))
        jax.block_until_ready(res.loss_sum)

    run(Interleaved1F1BProgramBuilder(1, 2), 4, "remat")
    run(
        Interleaved1F1BProgramBuilder(2, zero_bubble=True), 4,
        "cache_acts",
    )


LEGS: dict[str, Callable[[], None]] = {
    "train": leg_train,
    "train_zero": leg_train_zero,
    "serve": leg_serve,
    "serve_quant": leg_serve_quant,
    "serve_disagg": leg_serve_disagg,
    "spec_decode": leg_spec_decode,
    "pp_opt": leg_pp_opt,
    "pp_fused": leg_pp_fused,
}


def trace_registered_executables(
    legs: list[str] | None = None,
) -> list[dict]:
    """Run the requested legs (default: all) with capture forced on;
    returns every captured fact block. The caller owns telemetry-hub
    hygiene (the gate test installs a fresh hub around this)."""
    names = list(LEGS) if legs is None else list(legs)
    unknown = [n for n in names if n not in LEGS]
    if unknown:
        raise ValueError(
            f"unknown audit leg(s) {unknown}; available: {list(LEGS)}"
        )
    facts: list[dict] = []
    with _capture_forced_on():
        for name in names:
            facts.extend(_collect(name, LEGS[name]))
    return facts


@contextlib.contextmanager
def _capture_forced_on():
    from d9d_tpu.telemetry import audit_capture

    audit_capture.enable(True)
    try:
        yield
    finally:
        audit_capture.enable(None)  # back to env-var control
