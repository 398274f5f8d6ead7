"""PP x CP composition: ring attention over cp_s inside pipeline stages.

Completes the composition matrix (PPxEP and PPxFSDPxTPxEP live in
test_pp_ep_train.py; CP alone in test_cp_train.py): sequence-parallel
ring attention must work when each pipeline stage runs it on its own
submesh."""
import pytest

from d9d_tpu.core.compat import HAS_MODERN_JAX

# the SPMD/multiprocess e2e tier needs the modern jax runtime
# (core/compat.py emulates only ambient-mesh bookkeeping)
requires_modern_jax = pytest.mark.skipif(
    not HAS_MODERN_JAX, reason="needs the modern-jax SPMD runtime"
)

# slow tier: full training/IO flows
pytestmark = [pytest.mark.e2e, requires_modern_jax]

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from d9d_tpu.core import MeshParameters
from d9d_tpu.loop import (
    AdamWProvider,
    CausalLMTask,
    Trainer,
    TrainerConfig,
)
from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
from d9d_tpu.nn.sdpa import SdpaRingConfig, build_sdpa_backend
from d9d_tpu.parallel import fsdp_plan
from tests.loop.conftest import LMProvider, SeededBatches

VOCAB = 64


def test_dense_ring_attention_trains_under_pp(devices):
    ctx = MeshParameters(pp=2, dp_shard=2, cp_shard=2).build(devices)
    ring = build_sdpa_backend(
        SdpaRingConfig(
            seq_axis="cp_s", batch_axes=("dp_r", "dp_s"), head_axes=()
        )
    )
    cfg = Qwen3DenseConfig(
        vocab_ranges=(("default", VOCAB),),
        hidden_size=32,
        num_layers=4,
        num_heads=2,
        num_kv_heads=1,
        head_dim=16,
        intermediate_size=64,
        remat=False,
    )

    def build_module(stage):
        return Qwen3DenseCausalLM(
            config=cfg,
            sdpa=ring,
            stage=stage,
            act_sharding=NamedSharding(
                ctx.stage_mesh(stage.stage_index),
                P(ctx.batch_axes, ctx.sequence_axes),
            ),
            dtype=jnp.float32,
        )

    trainer = Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=8,
            microbatch_size=4,
            seq_len=32,
            total_steps=8,
            log_every=1,
            learning_rate=3e-3,
            pipeline={"kind": "interleaved_1f1b"},
        ),
        model_provider=LMProvider(build_module, fsdp_plan),
        dataset_provider=SeededBatches((8, 33), VOCAB, fresh=False),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(),
    )
    hist = trainer.train()
    l0, l1 = float(hist[0]["loss"]), float(hist[-1]["loss"])
    assert l1 < l0 - 0.3, (l0, l1)
