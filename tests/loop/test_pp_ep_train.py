"""PP x EP composition: Qwen3-MoE with expert-parallel experts training
through the pipeline engine — the reference example's headline layout
(pretrain.json: PP=4 x DP_r=2 x EP=2) shrunk to the CPU mesh: a 4-device
pp=2 x dp_s=2 leg with ep=2 overlaying dp_s, and the full 8-device
pp=2 x dp_s=2 x tp=2 leg with ep=4 overlaying dp_s x tp. The multichip
dryrun covers EP and PP separately; these are the composed paths."""

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

from jax.sharding import NamedSharding, PartitionSpec as P

from d9d_tpu.core import MeshParameters
from d9d_tpu.loop import (
    AdamWProvider,
    CausalLMTask,
    Trainer,
    TrainerConfig,
)
from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig
from d9d_tpu.nn.sdpa import build_sdpa_backend
from d9d_tpu.parallel import fsdp_ep_plan
from tests.loop.conftest import LMProvider, SeededBatches

VOCAB = 128


def _train_pp_ep(ctx, *, with_tp: bool, seed: int) -> list[dict]:
    cfg = Qwen3MoeConfig(
        vocab_ranges=(("default", VOCAB),),
        hidden_size=64,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        moe_intermediate_size=64,
        num_experts=8,
        num_experts_per_tok=2,
        remat=False,
        ep_axes=ctx.ep_shard_axes,
        moe_token_axes=(ctx.batch_axes, ctx.sequence_axes),
    )

    def build_module(stage):
        return Qwen3MoeCausalLM(
            config=cfg,
            sdpa=build_sdpa_backend(),
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
        model_provider=LMProvider(
            build_module, lambda c: fsdp_ep_plan(c, with_tp=with_tp)),
        dataset_provider=SeededBatches((8, 33), VOCAB, seed, fresh=False),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(),
    )
    hist = trainer.train()
    # forward-only path (inference program) with EP inside the stages:
    # eval loss on the training batch must sit near the last train loss
    raw = {"input_ids": np.random.RandomState(seed).randint(
        0, VOCAB, size=(8, 33))}
    eval_loss = trainer.loss_on_batch(raw)
    assert abs(eval_loss - float(hist[-1]["loss"])) < 0.5, (
        eval_loss, float(hist[-1]["loss"]))
    return hist


@pytest.mark.parametrize("layout", ["pp_dp_ep", "pp_dp_tp_ep"])
def test_moe_ep_trains_under_pp(devices, layout):
    if layout == "pp_dp_ep":
        ctx = MeshParameters(pp=2, dp_shard=2, ep_shard=2).build(devices[:4])
        with_tp = False
    else:
        ctx = MeshParameters(pp=2, dp_shard=2, tp=2, ep_shard=4).build(devices)
        with_tp = True
    hist = _train_pp_ep(ctx, with_tp=with_tp, seed=1 if with_tp else 0)
    l0, l1 = float(hist[0]["loss"]), float(hist[-1]["loss"])
    assert l1 < l0 - 0.3, (layout, l0, l1)
