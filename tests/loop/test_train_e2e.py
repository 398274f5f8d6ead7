"""End-to-end: tiny dense LM trains with loss going down (BASELINE config 1).

Mirrors the reference's full-model task-centric harness pattern
(SURVEY §4.3) at minimum scale: 8-device DP mesh, grad accumulation,
weighted-loss semantics.
"""

import jax
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
    ModelProvider,
    Trainer,
    TrainerConfig,
)
from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu.parallel import fsdp_plan, replicate_plan

VOCAB = 64


class TinyModelProvider(ModelProvider):
    def __init__(self, plan="replicate"):
        self.cfg = Qwen3DenseConfig.tiny(vocab_size=VOCAB)
        self.plan_name = plan

    def build_module(self, stage):
        import jax.numpy as jnp

        return Qwen3DenseCausalLM(
            config=self.cfg,
            sdpa=eager_sdpa,
            stage=stage,
            dtype=jnp.float32,
        )

    def build_plan(self, ctx):
        return replicate_plan(ctx) if self.plan_name == "replicate" else fsdp_plan(ctx)

    def sample_inputs(self, batch_size, seq_len):
        import jax.numpy as jnp

        tokens = jnp.zeros((batch_size, seq_len), jnp.int32)
        positions = jnp.zeros((batch_size, seq_len), jnp.int32)
        return (tokens, positions, tokens)


class ShiftPatternDataset(DatasetProvider):
    """Next token = (token + 3) % VOCAB — a perfectly learnable pattern."""

    def __init__(self, num_batches, batch_size, seq_len, seed=0):
        self.num_batches = num_batches
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.seed = seed

    def build(self):
        rng = np.random.RandomState(self.seed)
        for _ in range(self.num_batches):
            start = rng.randint(0, VOCAB, size=(self.batch_size, 1))
            steps = np.arange(self.seq_len + 1)[None, :]
            yield {"input_ids": (start + 3 * steps) % VOCAB}


@pytest.mark.parametrize("plan", ["replicate", "fsdp"])
def test_tiny_lm_loss_goes_down(plan):
    ctx = MeshParameters(
        dp_replicate=4 if plan == "replicate" else 1,
        dp_shard=2 if plan == "replicate" else 8,
    ).build(jax.devices())
    config = TrainerConfig(
        global_batch_size=16,
        microbatch_size=8,
        seq_len=16,
        total_steps=30,
        learning_rate=1e-2,
        log_every=5,
        seed=0,
    )
    trainer = Trainer(
        ctx=ctx,
        config=config,
        model_provider=TinyModelProvider(plan),
        dataset_provider=ShiftPatternDataset(40, 16, 16),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(weight_decay=0.01),
    )
    history = trainer.train()
    assert len(history) >= 3
    first, last = history[0]["loss"], history[-1]["loss"]
    assert np.isfinite(first) and np.isfinite(last)
    # the pattern is deterministic: loss must collapse
    assert last < first * 0.5, f"loss did not improve: {first} -> {last}"
    assert history[-1]["grad_norm"] >= 0


def test_weighted_loss_ignores_masked_tokens():
    ctx = MeshParameters(dp_replicate=8).build(jax.devices())
    config = TrainerConfig(
        global_batch_size=8,
        microbatch_size=8,
        seq_len=8,
        total_steps=1,
        log_every=1,
    )
    provider = TinyModelProvider()

    class MaskedDataset(DatasetProvider):
        def build(self):
            ids = np.arange(8 * 9).reshape(8, 9) % VOCAB
            mask = np.zeros((8, 9), np.int32)
            mask[:, :4] = 1
            yield {"input_ids": ids, "loss_mask": mask}

    trainer = Trainer(
        ctx=ctx,
        config=config,
        model_provider=provider,
        dataset_provider=MaskedDataset(),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(),
    )
    history = trainer.train()
    # 8 rows x 3 valid label positions (mask shifts by 1) = 24
    assert history[-1]["loss_weight"] == 24.0


@pytest.mark.parametrize("expert_parallel", [False, True], ids=["local", "ep"])
def test_moe_training_reports_expert_load_balance(devices, expert_parallel):
    """MoE runs surface the tokens_per_expert load statistic (reference
    buffer, module/block/moe/layer.py:16) as task/moe_load_max_frac —
    the heaviest expert's share of routed assignments. Under dropless
    expert parallelism the metric flush also says how the receive
    buffers were used: the share of layer-steps that fell back to the
    worst-case rung, and the fill of the buffers taken."""
    import dataclasses

    import jax.numpy as jnp

    from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig
    from d9d_tpu.parallel import fsdp_ep_plan

    mesh_kw = {"ep_shard": 4} if expert_parallel else {}
    ctx = MeshParameters(dp_shard=4, **mesh_kw).build(devices[:4])
    cfg = Qwen3MoeConfig.tiny(vocab_size=VOCAB)
    if expert_parallel:
        cfg = dataclasses.replace(
            cfg, ep_axes=ctx.ep_shard_axes,
            moe_token_axes=(ctx.batch_axes, ctx.sequence_axes),
        )

    class MoEProvider(ModelProvider):
        def build_module(self, stage):
            return Qwen3MoeCausalLM(
                config=cfg,
                sdpa=eager_sdpa,
                stage=stage,
                act_sharding=ctx.batch_sharding() if expert_parallel else None,
                dtype=jnp.float32,
            )

        def build_plan(self, ctx):
            return fsdp_ep_plan(ctx) if expert_parallel else replicate_plan(ctx)

        def sample_inputs(self, batch_size, seq_len):
            z = np.zeros((batch_size, seq_len), np.int32)
            return (z, z, z)

    class Data(DatasetProvider):
        def build(self):
            rng = np.random.RandomState(0)
            for _ in range(2):
                yield {"input_ids": rng.randint(0, VOCAB, size=(8, 17))}

    trainer = Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=8, microbatch_size=4, seq_len=16,
            total_steps=2, log_every=1,
        ),
        model_provider=MoEProvider(),
        dataset_provider=Data(),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(),
    )
    hist = trainer.train()
    frac = hist[-1]["task/moe_load_max_frac"]
    # 8 experts, top-2 routing: heaviest share ∈ [1/8, 1]
    assert 1.0 / 8 - 1e-6 <= frac <= 1.0
    # dense runs must NOT carry the metric
    assert "task/moe_load_max_frac" not in _dense_history(devices)[-1]
    # the raw sums never reach the history, the shares only under EP
    assert not [k for k in hist[-1] if k.startswith("task/moe_ep_")]
    if expert_parallel:
        for entry in hist:
            # two microbatches x the tiny preset's expert layers a step
            assert entry["moe/ep_fallback_share"] in (0.0, 0.25, 0.5, 0.75, 1.0)
            assert 0.0 < entry["moe/ep_buffer_fill"] <= 1.0
    else:
        assert "moe/ep_fallback_share" not in hist[-1]
        assert "moe/ep_buffer_fill" not in hist[-1]


def _dense_history(devices):
    ctx = MeshParameters(dp_shard=4).build(devices[:4])
    trainer = Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=8, microbatch_size=8, seq_len=8,
            total_steps=1, log_every=1,
        ),
        model_provider=TinyModelProvider(),
        dataset_provider=_OneBatch(),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(),
    )
    return trainer.train()


class _OneBatch(DatasetProvider):
    def build(self):
        yield {"input_ids": np.arange(8 * 9).reshape(8, 9) % VOCAB}
