"""Trainer-driven pipeline parallelism: a 4-stage Qwen3-Dense must
reproduce the no-PP loss trajectory (VERDICT r1 item 2; reference
d9d/loop/run/train.py:251 steps *through* schedules).

The baseline runs the identical model/data/optimizer on a flat dp mesh;
the PP runs use pp=4 × dp_s=2 with stage submeshes. Loss histories must
match to float tolerance — same sum-then-scale grad semantics, same
clipping, same adamw math, just different execution geometry.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.core.compat import HAS_MODERN_JAX

# the SPMD/multiprocess e2e tier needs the modern jax runtime
# (core/compat.py emulates only ambient-mesh bookkeeping)
requires_modern_jax = pytest.mark.skipif(
    not HAS_MODERN_JAX, reason="needs the modern-jax SPMD runtime"
)
# slow tier: full training/IO flows
pytestmark = [pytest.mark.e2e, requires_modern_jax]


from d9d_tpu.core import MeshParameters
from d9d_tpu.loop import (
    AdamWProvider,
    CausalLMTask,
    DatasetProvider,
    Trainer,
    TrainerConfig,
)
from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
from d9d_tpu.nn.sdpa import build_sdpa_backend
from d9d_tpu.parallel import fsdp_plan, replicate_plan
from tests.loop.conftest import LMProvider, SeededBatches
from tests.loop.conftest import sync_stage_params as _sync_stage_params

VOCAB = 64
CFG = Qwen3DenseConfig(
    vocab_ranges=(("default", VOCAB),),
    hidden_size=32,
    num_layers=4,
    num_heads=4,
    num_kv_heads=2,
    head_dim=8,
    intermediate_size=64,
    remat=False,
)
STEPS = 4


def _dense(stage):
    return Qwen3DenseCausalLM(
        config=CFG, sdpa=build_sdpa_backend(), stage=stage, dtype=jnp.float32,
    )


def Provider(fsdp: bool, build=_dense):
    return LMProvider(build, fsdp_plan if fsdp else replicate_plan)


def Data():
    return SeededBatches((16, 17), VOCAB, seed=7, steps=STEPS)


def train_history(ctx, pipeline=None, fsdp=False, build_only=False):
    trainer = Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=16,
            microbatch_size=4,
            seq_len=16,
            total_steps=STEPS,
            log_every=1,
            pipeline=pipeline,
            learning_rate=1e-2,
        ),
        model_provider=Provider(fsdp),
        dataset_provider=Data(),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(),
    )
    if build_only:
        return trainer
    return trainer, trainer.train()


@pytest.fixture(scope="module")
def baseline(devices):
    ctx = MeshParameters(dp_shard=2).build(devices[:2])
    trainer = train_history(ctx, fsdp=True, build_only=True)
    init_params = jax.tree.map(np.asarray, trainer.params)
    hist = trainer.train()
    return init_params, [h["loss"] for h in hist]


@pytest.mark.parametrize(
    "schedule",
    [
        {"kind": "gpipe"},
        {"kind": "interleaved_1f1b"},
        {"kind": "zero_bubble_1p"},
    ],
    ids=lambda s: s["kind"],
)
def test_pp_matches_flat_loss_trajectory(devices, baseline, schedule):
    init_params, base_losses = baseline
    ctx = MeshParameters(pp=4, dp_shard=2).build(devices)
    trainer = train_history(ctx, pipeline=schedule, fsdp=True, build_only=True)
    _sync_stage_params(trainer.pp_engine, init_params)
    hist = trainer.train()
    losses = [h["loss"] for h in hist]
    assert len(losses) == len(base_losses)
    np.testing.assert_allclose(losses, base_losses, rtol=2e-4, atol=2e-5)


def test_pp_virtual_stages_and_export(devices):
    """looped_bfs with 2 virtual stages per rank (8 stages on pp=4) +
    merged_params covers the whole model param tree."""
    ctx = MeshParameters(pp=4, dp_shard=2).build(devices)
    trainer, hist = train_history(
        ctx, pipeline={"kind": "looped_bfs", "stages_per_rank": 2}
    )
    assert all(np.isfinite(h["loss"]) for h in hist)

    merged = trainer.merged_params()
    leaves = jax.tree_util.tree_leaves_with_path(merged)
    names = {"/".join(str(k) for k in path) for path, _ in leaves}
    # embeddings (stage 0), every global layer, final norm + head (last)
    assert any("embed_tokens" in n for n in names)
    for layer in range(CFG.num_layers):
        assert any(f"layers_{layer}" in n for n in names), f"layer {layer}"
    assert any("lm_head" in n for n in names)


def test_pp_timeline_cadence_populates_stage_gauges(devices):
    """`pp_timeline_every_steps` wires trainer → driver → fused executor
    (docs/design/observability.md "Pipeline timeline & profiling"):
    cadence steps populate every per-stage busy/bubble gauge, the
    `pp/bubble_frac` rollup, and per-run walls."""
    from d9d_tpu.telemetry import Telemetry, get_telemetry, set_telemetry

    set_telemetry(Telemetry())  # executors cache the hub at build time
    ctx = MeshParameters(pp=4, dp_shard=2).build(devices)
    trainer = Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=16,
            microbatch_size=4,
            seq_len=16,
            total_steps=STEPS,
            log_every=1,
            pipeline={"kind": "interleaved_1f1b"},
            pp_timeline_every_steps=2,
            learning_rate=1e-2,
        ),
        model_provider=Provider(False),
        dataset_provider=Data(),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(),
    )
    hist = trainer.train()
    assert all(np.isfinite(h["loss"]) for h in hist)
    gauges = get_telemetry().registry.snapshot()["gauges"]
    for s in range(4):
        assert gauges[f"pp/s{s}/busy_s"] > 0.0, f"stage {s}"
        assert gauges[f"pp/s{s}/bubble_s"] >= 0.0
        assert 0.0 <= gauges[f"pp/s{s}/bubble_frac"] <= 1.0
    assert 0.0 <= gauges["pp/bubble_frac"] <= 1.0
    assert any(
        k.startswith("pp/run/") and k.endswith("/wall_s") for k in gauges
    )


def test_pp_checkpoint_resume_bitwise(devices, tmp_path):
    """Mid-run crash + resume reproduces the uninterrupted run exactly."""
    from d9d_tpu.loop import StatefulDataLoader

    ctx = MeshParameters(pp=2, dp_shard=2).build(devices[:4])

    class Items:
        def __len__(self):
            return 64

        def __getitem__(self, i):
            rng = np.random.default_rng(i)
            return {"input_ids": rng.integers(0, VOCAB, (17,))}

    class Loader(DatasetProvider):
        def build(self):
            return StatefulDataLoader(Items(), 16, shuffle=True, seed=7,
                                      num_epochs=None)

    def make(total, ckpt_dir):
        return Trainer(
            ctx=ctx,
            config=TrainerConfig(
                global_batch_size=16,
                microbatch_size=8,
                seq_len=16,
                total_steps=total,
                log_every=1,
                pipeline={"kind": "gpipe"},
                checkpoint_dir=str(ckpt_dir),
                checkpoint_every_steps=2,
                learning_rate=1e-2,
            ),
            model_provider=Provider(False),
            dataset_provider=Loader(),
            task=CausalLMTask(),
            optimizer_provider=AdamWProvider(),
        )

    full = make(STEPS, tmp_path / "a")
    hist_full = full.train()
    full.close()

    part = make(2, tmp_path / "b")
    part.train()
    part.close()
    resumed = make(STEPS, tmp_path / "b")
    hist_resumed = resumed.train()
    resumed.close()

    np.testing.assert_array_equal(
        [h["loss"] for h in hist_full[2:]],
        [h["loss"] for h in hist_resumed],
    )


@pytest.mark.parametrize("pipeline", [
    None,
    {"kind": "zero_bubble_1p", "residual_policy": "cache_acts"},
], ids=["default", "zb1p-cache_acts"])
def test_pp_lora_trains_adapters_only(devices, pipeline):
    """PEFT × PP (VERDICT r2 item 8): pp=2 LoRA training leaves every
    stage's base params bit-identical, trains only adapters, and
    merged_params folds the delta in — under the default schedule AND the
    r4 cache_acts split (base params ride the recorded VJP's residual
    consts; adapters are the differentiated leaves)."""
    from d9d_tpu.peft import LoRA

    ctx = MeshParameters(pp=2, dp_shard=2).build(devices[:4])
    trainer = Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=16,
            microbatch_size=4,
            seq_len=16,
            total_steps=STEPS,
            log_every=1,
            learning_rate=1e-2,
            pipeline=pipeline,
        ),
        model_provider=Provider(fsdp=True),
        dataset_provider=Data(),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(),
        peft_method=LoRA(rank=2, alpha=4.0,
                         target_patterns=(r".*self_attn.*kernel",)),
    )
    engine = trainer.pp_engine
    base_before = {
        s: jax.tree.map(np.asarray, rt.task.base)
        for s, rt in engine.stages.items()
    }
    adapters_before = {
        s: jax.tree.map(np.asarray, rt.params)
        for s, rt in engine.stages.items()
    }
    hist = trainer.train()
    assert all(np.isfinite(h["loss"]) for h in hist)
    # loss moves (adapters receive grads; B starts at zero so step 0 output
    # equals the base model and training changes it)
    assert hist[-1]["loss"] != hist[0]["loss"]

    for s, rt in engine.stages.items():
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
            rt.task.base,
            base_before[s],
        )
        changed = jax.tree.leaves(
            jax.tree.map(
                lambda a, b: bool(np.any(np.asarray(a) != b)),
                rt.params,
                adapters_before[s],
            )
        )
        assert any(changed), f"stage {s}: no adapter moved"

    # optimizer state exists only for adapters: adamw keeps mu/nu trees
    # mirroring the param tree, so its array leaves are bounded by
    # 2x adapter leaves + a few scalars — base-sized state would blow this
    for s, rt in engine.stages.items():
        adapter_leaves = len(jax.tree.leaves(rt.params))
        base_leaves = len(jax.tree.leaves(rt.task.base))
        opt_leaves = len(jax.tree.leaves(engine.opt_states[s]))
        assert adapter_leaves > 0
        assert opt_leaves <= 2 * adapter_leaves + 4
        assert opt_leaves < 2 * base_leaves

    # merged export covers the full model and differs from the pure base
    merged = trainer.merged_params()
    names = {
        "/".join(str(k) for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(merged)
    }
    assert any("embed_tokens" in n for n in names)
    assert any("lm_head" in n for n in names)
    for layer in range(CFG.num_layers):
        assert any(f"layers_{layer}" in n for n in names)


def test_pp_hybrid_linear_attention_trains(devices):
    """Hybrid GDN:attention stacks compose with pipeline parallelism: the
    stage splitter assigns whole layers, so GDN layers pipeline like any
    other (beyond-reference family; BASELINE config 5)."""
    from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig

    ctx = MeshParameters(pp=2, dp_shard=2).build(devices[:4])

    def hybrid(stage):
        return Qwen3MoeCausalLM(
            config=Qwen3MoeConfig.hybrid_tiny(vocab_size=VOCAB),
            sdpa=build_sdpa_backend(),
            stage=stage,
            dtype=jnp.float32,
        )

    trainer = Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=16,
            microbatch_size=4,
            seq_len=16,
            total_steps=3,
            log_every=1,
            learning_rate=5e-3,
        ),
        model_provider=Provider(fsdp=True, build=hybrid),
        dataset_provider=Data(),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(),
    )
    hist = trainer.train()
    assert len(hist) == 3
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert hist[-1]["loss"] < hist[0]["loss"]
    # both param families present across the merged stages
    names = {
        "/".join(str(k) for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(
            trainer.merged_params()
        )
    }
    assert any("linear_attn" in n for n in names)
    assert any("self_attn" in n for n in names)


def test_pp_sleep_wake_roundtrip(devices):
    """sleep() offloads every stage's params/opt state and wake() restores
    them bitwise with the same shardings (the Trainer's PP branches,
    train.py sleep/wake; reference train_sleeper.py:22)."""
    ctx = MeshParameters(pp=2, dp_shard=2).build(devices[:4])
    trainer = train_history(
        ctx, pipeline={"kind": "gpipe"}, build_only=True
    )
    trainer.train()
    engine = trainer.pp_engine
    before = {
        s: jax.tree.map(lambda x: np.asarray(x).copy(), rt.params)
        for s, rt in engine.stages.items()
    }
    shard_before = {
        s: jax.tree.map(lambda x: x.sharding, rt.params)
        for s, rt in engine.stages.items()
    }
    trainer.sleep()
    assert all(rt.params is None for rt in engine.stages.values())
    assert engine.opt_states is None
    trainer.wake()
    for s, rt in engine.stages.items():
        for a, b in zip(
            jax.tree.leaves(before[s]), jax.tree.leaves(rt.params)
        ):
            np.testing.assert_array_equal(a, np.asarray(b))
        for sa, sb in zip(
            jax.tree.leaves(shard_before[s], is_leaf=lambda x: x is None),
            jax.tree.leaves(
                jax.tree.map(lambda x: x.sharding, rt.params),
                is_leaf=lambda x: x is None,
            ),
        ):
            assert sa == sb
    # the woken trainer keeps training
    more = trainer.run_step({"input_ids": np.zeros((16, 17), np.int64)})
    assert np.isfinite(float(more["loss"]))


def test_pp_zero_sharding_matches_unsharded(devices):
    """ZeRO optimizer-state sharding over dp_r under PP
    (docs/design/zero_sharding.md): pp=2 x dp_r=4 with
    zero_sharding=True must reproduce the unsharded PP trajectory at
    float tolerance, with every stage's moments actually sharded."""
    from d9d_tpu.parallel.zero import tree_bytes_per_device

    def run(zero):
        ctx = MeshParameters(pp=2, dp_replicate=4).build(devices)
        trainer = Trainer(
            ctx=ctx,
            config=TrainerConfig(
                global_batch_size=16,
                microbatch_size=4,
                seq_len=16,
                total_steps=STEPS,
                log_every=1,
                pipeline={"kind": "gpipe"},
                learning_rate=1e-2,
                zero_sharding=zero,
                telemetry_console=False,
            ),
            model_provider=Provider(fsdp=False),
            dataset_provider=Data(),
            task=CausalLMTask(),
            optimizer_provider=AdamWProvider(),
        )
        hist = trainer.train()
        return trainer, [h["loss"] for h in hist]

    base_trainer, base_losses = run(False)
    zero_trainer, zero_losses = run(True)
    np.testing.assert_allclose(zero_losses, base_losses, rtol=2e-4,
                               atol=2e-5)
    # the per-stage tables exist and the state is genuinely 1/N
    engine = zero_trainer.pp_engine
    assert set(engine.optimizer.zero_shardings) == set(engine.stages)
    for s, state in engine.opt_states.items():
        replicated = tree_bytes_per_device(
            jax.tree.map(np.asarray, state)
        )
        assert tree_bytes_per_device(state) < 0.5 * replicated
    assert (
        zero_trainer.opt_state_bytes_per_chip()
        < 0.5 * base_trainer.opt_state_bytes_per_chip()
    )
