"""Fused-executor exactness: the compiled-run runtime must be BIT-identical
to the legacy per-action interpreter (runtime/fused.py's parity contract).

The fused runtime replays the legacy handlers symbolically and traces the
same raw stage impls into per-rank programs — so equality here is exact
(``assert_array_equal``), not tolerance-based: any divergence means the
schedule compiler reordered or rewired the math. (Known boundary of the
bitwise contract, documented in fused.py: ``cache_acts`` W-slot grads on
graphs XLA compiles differently once the replayed jaxpr shares a program
with its I slot — on real models the long f32 dW reductions can
reassociate at ~1e-4 relative; on these toy stages both contexts compile
identically and the pins below hold exactly.) The suite pins loss,
weight, every metric, per-stage grads, eval outputs, and the
``pp_numerics/s{S}`` stats vector against the legacy oracle across 1F1B
and zero-bubble schedules; the tiny 1F1B config additionally pins the
structural acceptance: the whole step fuses into ONE program and real
dispatches drop ≥5× (the ISSUE 16 gate, also enforced continuously by
``tools/bench_compare.py``).

Compile-heavy schedule×policy sweeps live in the ``slow`` tier; tier-1
keeps one representative per contract.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# unlike test_e2e's SPMD tier this suite is mesh-free by construction
# (single-device stages, shardings None), so it runs — and the parity
# contract is enforced — on the legacy-jax CPU rig too
pytestmark = [pytest.mark.e2e]


from d9d_tpu.pipelining import (
    FusedPipelineExecutor,
    PipelineScheduleExecutor,
    PipelineStageInfo,
    PipelineStageRuntime,
)
from d9d_tpu.pipelining.program import add_communication_ops
from d9d_tpu.pipelining.program.builders import (
    DualPipeVProgramBuilder,
    GPipeProgramBuilder,
    Interleaved1F1BProgramBuilder,
    InferenceProgramBuilder,
    ZeroBubbleVProgramBuilder,
)
from d9d_tpu.telemetry.introspect import TrackedJit

HID = 8


class StageBlock(nn.Module):
    """One pipeline stage: dense + tanh (nonlinear so dI/dW split is honest)."""

    n_layers: int = 1

    @nn.compact
    def __call__(self, x):
        for _ in range(self.n_layers):
            x = jnp.tanh(nn.Dense(HID, use_bias=True)(x))
        return x


class TinyTask:
    """StageTask impl: carry = activations; loss = masked square error."""

    def split_microbatch(self, micro):
        return micro["x"], {}, {"y": micro["y"], "w": micro["w"]}

    def stage_forward(self, module, params, carry, kwargs):
        return module.apply(params, carry)

    def last_stage_loss(self, module, params, carry, kwargs, state):
        out = module.apply(params, carry)
        err = ((out - state["y"]) ** 2).sum(-1)
        loss_sum = (err * state["w"]).sum()
        weight = state["w"].sum()
        return loss_sum, weight, {"examples": weight}


def make_stages(num_stages, key, residual_policy="remat"):
    task = TinyTask()
    stages = {}
    for s in range(num_stages):
        info = PipelineStageInfo(stage_index=s, num_stages=num_stages)
        module = StageBlock()
        key, sub = jax.random.split(key)
        params = module.init(sub, jnp.zeros((1, HID)))
        stages[s] = PipelineStageRuntime(
            info=info, module=module, params=params, task=task,
            residual_policy=residual_policy,
        )
    return stages


def make_microbatches(m, key, mb_size=4):
    out = []
    for _ in range(m):
        key, k1, k2 = jax.random.split(key, 3)
        out.append({
            "x": jax.random.normal(k1, (mb_size, HID)),
            "y": jax.random.normal(k2, (mb_size, HID)),
            "w": jnp.ones((mb_size,)),
        })
    return out


def build_pair(builder, m, residual_policy="remat", train=True,
               fused_numerics=False):
    """Legacy + fused executors over independently-built but identical
    stage sets (same PRNG seed → identical params; separate objects so
    neither runtime can lean on the other's caches)."""
    stages_l = make_stages(
        builder.num_stages, jax.random.PRNGKey(0), residual_policy
    )
    stages_f = make_stages(
        builder.num_stages, jax.random.PRNGKey(0), residual_policy
    )
    program = add_communication_ops(
        builder.compose(m), num_stages=builder.num_stages,
        stage_owner=builder.stage_owner,
    )
    legacy = PipelineScheduleExecutor(
        stages=stages_l, program=program, stage_owner=builder.stage_owner,
        num_microbatches=m, train=train,
    )
    fused = FusedPipelineExecutor(
        stages=stages_f, program=program, stage_owner=builder.stage_owner,
        num_microbatches=m, train=train, numerics=fused_numerics,
    )
    return legacy, fused, stages_l, stages_f


def tree_equal(a, b):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)
        ),
        a, b,
    )


def assert_results_identical(rl, rf, train=True):
    if train:
        assert set(rl.grads) == set(rf.grads)
        for s in rl.grads:
            tree_equal(rl.grads[s], rf.grads[s])
    else:
        assert len(rl.outputs) == len(rf.outputs)
        for a, b in zip(rl.outputs, rf.outputs):
            tree_equal(a, b)
    np.testing.assert_array_equal(
        np.asarray(rl.loss_sum), np.asarray(rf.loss_sum)
    )
    np.testing.assert_array_equal(
        np.asarray(rl.weight_sum), np.asarray(rf.weight_sum)
    )
    assert set(rl.metrics) == set(rf.metrics)
    for k in rl.metrics:
        np.testing.assert_array_equal(
            np.asarray(rl.metrics[k]), np.asarray(rf.metrics[k])
        )


def run_parity(builder, m, residual_policy="remat", train=True):
    legacy, fused, _, _ = build_pair(
        builder, m, residual_policy=residual_policy, train=train
    )
    mbs = make_microbatches(m, jax.random.PRNGKey(1))
    rl = legacy.step(list(mbs))
    rf = fused.step(list(mbs))
    assert_results_identical(rl, rf, train=train)
    # a second step reuses the compiled runs: donation / buffer
    # lifetime bugs surface as deleted-buffer errors or drift here
    rf2 = fused.step(list(mbs))
    np.testing.assert_array_equal(
        np.asarray(rl.loss_sum), np.asarray(rf2.loss_sum)
    )
    return fused


class _DispatchCounter:
    """Counts real executable dispatches through TrackedJit.__call__ —
    the single dispatch point both runtimes share, so the ratio is
    measured symmetrically."""

    def __init__(self, monkeypatch):
        self.count = 0
        orig = TrackedJit.__call__

        def counting(tj, *args, **kwargs):
            self.count += 1
            return orig(tj, *args, **kwargs)

        monkeypatch.setattr(TrackedJit, "__call__", counting)

    def take(self):
        n, self.count = self.count, 0
        return n


# -- tier-1: one representative per contract ---------------------------


def test_1f1b_bitwise():
    run_parity(Interleaved1F1BProgramBuilder(2), 4)


def test_zb1p_cache_acts_bitwise():
    run_parity(
        Interleaved1F1BProgramBuilder(2, zero_bubble=True), 4,
        residual_policy="cache_acts",
    )


def test_single_stage_zero_bubble_bitwise():
    """pp=1 zero-bubble: the stage is first AND last; loss statistics
    must surface identically from the fused BackwardInput slot."""
    fused = run_parity(Interleaved1F1BProgramBuilder(1, zero_bubble=True), 3)
    assert fused.num_fused_programs == 1


def test_tiny_1f1b_fuses_and_drops_dispatches(monkeypatch):
    """The ISSUE 16 acceptance config (tools/bench_pp_overhead.py --tiny:
    one rank, two virtual stages, m=8): the whole step must fuse into a
    single device program, and real dispatches must drop ≥5×."""
    builder = Interleaved1F1BProgramBuilder(1, 2)
    m = 8
    legacy, fused, _, _ = build_pair(builder, m)
    mbs = make_microbatches(m, jax.random.PRNGKey(1))
    counter = _DispatchCounter(monkeypatch)
    rl = legacy.step(list(mbs))
    legacy_dispatches = counter.take()
    rf = fused.step(list(mbs))
    fused_dispatches = counter.take()
    assert_results_identical(rl, rf)
    assert fused.num_fused_programs == 1
    assert fused_dispatches == 1
    assert legacy_dispatches >= 5 * fused_dispatches, (
        f"dispatch reduction {legacy_dispatches}/{fused_dispatches} < 5x"
    )


def test_numerics_stats_vector_bitwise():
    """The in-program pp_numerics/s{S} fold must reproduce the
    PipelinedOptimizer.stage_numerics oracle bit-for-bit on cadence and
    NaN-fill off cadence — from the SAME fused program (the traced flag
    flips a cond branch, never the signature)."""
    import optax

    from d9d_tpu.pipelining.training import PipelinedOptimizer
    from d9d_tpu.telemetry import numerics as numerics_mod

    builder = Interleaved1F1BProgramBuilder(2)
    m = 4
    legacy, fused, stages_l, stages_f = build_pair(
        builder, m, fused_numerics=True
    )
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    scalar = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    opt = PipelinedOptimizer(
        optimizer=optax.adam(1e-3),
        scalar_shardings={s: scalar for s in stages_l},
    )
    opt_states = opt.init({s: rt.params for s, rt in stages_l.items()})

    mbs = make_microbatches(m, jax.random.PRNGKey(1))
    rl = legacy.step(list(mbs))
    moments = {
        s: numerics_mod.find_second_moments(opt_states[s], rt.params)
        for s, rt in stages_f.items()
    }
    rf_on = fused.step(list(mbs), numerics_on=True, numerics_moments=moments)
    rf_off = fused.step(list(mbs), numerics_on=False, numerics_moments=moments)
    assert_results_identical(rl, rf_on)
    for s in stages_l:
        oracle = opt.stage_numerics(
            s, stages_l[s].params, rl.grads[s], opt_states[s]
        )
        np.testing.assert_array_equal(
            np.asarray(rf_on.numerics[s]), np.asarray(oracle)
        )
        assert np.all(np.isnan(np.asarray(rf_off.numerics[s])))


def test_inference_outputs_bitwise():
    run_parity(InferenceProgramBuilder(2), 4, train=False)


def test_timeline_attribution_parity_tiny_1f1b():
    """ISSUE 19 acceptance: on the tiny 1F1B config a timeline-cadence
    fused step populates pp/s{S}/busy_s|bubble_s|bubble_frac for EVERY
    stage, and the busy vector is each run's own reported wall
    apportioned by the kind-weighted op shares of the RunManifest the
    executor persists on the run's ExecutableRecord: what an offline
    consumer re-derives from the inventory is what the gauges say. The
    comparison is between two readings of one clock, so it holds to
    float tolerance; which stage the schedule loads more is a count
    (stage 0 runs a forward and a full backward a microbatch, the last
    stage folds its forward into the backward).
    """
    from d9d_tpu.telemetry import Telemetry, introspect, set_telemetry

    tele = set_telemetry(Telemetry())
    builder = Interleaved1F1BProgramBuilder(1, 2)
    m = 8
    _, fused, _, _ = build_pair(builder, m)
    mbs = make_microbatches(m, jax.random.PRNGKey(1))
    # the warm step compiles the runs, which is when their manifests
    # land in the inventory
    mark = len(introspect.inventory())
    fused.step(list(mbs))
    (man,) = [
        r.manifest for r in introspect.inventory()[mark:]
        if r.name.startswith("pp_fused/") and r.manifest is not None
    ]
    assert fused.num_fused_programs == 1

    fused.step(list(mbs), timeline=True)
    gauges = tele.registry.snapshot()["gauges"]
    num_stages = builder.num_stages
    # the acceptance surface: every stage's gauge triple on the cadence
    # step, plus the rollup and the per-run wall
    for s in range(num_stages):
        assert gauges[f"pp/s{s}/busy_s"] > 0
        assert gauges[f"pp/s{s}/bubble_s"] >= 0
        assert 0 <= gauges[f"pp/s{s}/bubble_frac"] <= 1
    assert 0 <= gauges["pp/bubble_frac"] <= 1
    assert gauges["pp/run/r0/k0/wall_s"] > 0

    weights = [0.0] * num_stages
    for op in man["ops"]:
        if op["stage"] >= 0:
            weights[op["stage"]] += op["weight"]
    wall = gauges[f"pp/run/r{man['rank']}/k{man['index']}/wall_s"]
    want_busy = [wall * w / sum(weights) for w in weights]
    busy = [gauges[f"pp/s{s}/busy_s"] for s in range(num_stages)]
    np.testing.assert_allclose(busy, want_busy, rtol=1e-9)
    # m forwards (1.0) and m full backwards (2.0) on stage 0 against m
    # full backwards on the last stage: 3m of 5m
    np.testing.assert_allclose(
        [b / sum(busy) for b in busy], [0.6, 0.4], rtol=1e-9
    )


def test_timeline_off_by_default_no_gauges():
    """Without timeline=True the fused step must emit NO pp/s{S}/* or
    pp/run/* gauges (the off-cadence byte-identical contract's
    telemetry face)."""
    from d9d_tpu.telemetry import Telemetry, set_telemetry

    tele = set_telemetry(Telemetry())
    legacy, fused, _, _ = build_pair(Interleaved1F1BProgramBuilder(1, 2), 8)
    del legacy
    fused.step(make_microbatches(8, jax.random.PRNGKey(1)))
    gauges = tele.registry.snapshot()["gauges"]
    assert not any(
        k.startswith("pp/s") or k.startswith("pp/run/") for k in gauges
    ), sorted(gauges)


# -- slow tier: the compile-heavy schedule × policy sweep ---------------


@pytest.mark.slow
@pytest.mark.parametrize("residual_policy", ["remat", "cache_full", "cache_acts"])
@pytest.mark.parametrize("m", [4, 7])
def test_zb1p_policies_bitwise_slow(residual_policy, m):
    run_parity(
        Interleaved1F1BProgramBuilder(2, zero_bubble=True), m,
        residual_policy=residual_policy,
    )


@pytest.mark.slow
@pytest.mark.parametrize("residual_policy", ["cache_full", "cache_acts"])
def test_zbv_bitwise_slow(residual_policy):
    run_parity(
        ZeroBubbleVProgramBuilder(2), 4, residual_policy=residual_policy
    )


@pytest.mark.slow
def test_dual_pipe_v_bitwise_slow():
    run_parity(DualPipeVProgramBuilder(2), 4, residual_policy="cache_full")


@pytest.mark.slow
@pytest.mark.parametrize("pp,m", [(2, 4), (4, 8)])
def test_gpipe_bitwise_slow(pp, m):
    run_parity(GPipeProgramBuilder(pp), m)


@pytest.mark.slow
def test_interleaved_virtual_stages_bitwise_slow():
    run_parity(Interleaved1F1BProgramBuilder(2, 2), 8)
