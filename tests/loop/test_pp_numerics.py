"""PP-path numerics parity (ISSUE 14 tentpole (c)): the trainer-driven
pipeline engine surfaces per-stage numerics windows under ``pp/s{S}/``
row prefixes, their union covers every model parameter leaf exactly
once, and the per-leaf gradient statistics match the flat (no-PP) run's
window up to the backends' global grad scaling — the cross-stage
numerics-skew evidence ROADMAP item 2's MPMD rebuild wants.

Slow tier: two whole-model trainer builds (flat + pp=2) compile-bound
on the CPU rig, like the test_pp_train parity legs this mirrors.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.core.compat import HAS_MODERN_JAX

requires_modern_jax = pytest.mark.skipif(
    not HAS_MODERN_JAX, reason="needs the modern-jax SPMD runtime"
)
pytestmark = [pytest.mark.e2e, pytest.mark.slow, requires_modern_jax]


from d9d_tpu.core import MeshParameters
from d9d_tpu.loop import (
    AdamWProvider,
    CausalLMTask,
    Trainer,
    TrainerConfig,
)
from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
from d9d_tpu.nn.sdpa import build_sdpa_backend
from tests.loop.conftest import LMProvider, SeededBatches
from tests.loop.conftest import sync_stage_params as _sync_stage_params

VOCAB = 64
CFG = Qwen3DenseConfig(
    vocab_ranges=(("default", VOCAB),),
    hidden_size=32,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=8,
    intermediate_size=64,
    remat=False,
)
STEPS = 2


def _dense(stage):
    return Qwen3DenseCausalLM(
        config=CFG, sdpa=build_sdpa_backend(), stage=stage, dtype=jnp.float32,
    )


def _make(ctx, pipeline=None):
    return Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=16,
            microbatch_size=4,
            seq_len=16,
            total_steps=STEPS,
            log_every=1,
            pipeline=pipeline,
            learning_rate=1e-2,
            numerics_every_steps=1,
        ),
        model_provider=LMProvider(_dense),
        dataset_provider=SeededBatches((16, 17), VOCAB, seed=7, steps=STEPS),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(),
    )


def test_pp_stage_windows_cover_and_match_flat_grads(devices):
    flat = _make(MeshParameters(dp_shard=2).build(devices[:2]))
    init_params = jax.tree.map(np.asarray, flat.params)
    flat_hist = flat.train()
    flat_report = flat.numerics_monitor.last
    assert flat_report is not None and flat_report.step == STEPS

    pp = _make(
        MeshParameters(pp=2, dp_shard=2).build(devices[:4]),
        pipeline={"kind": "gpipe"},
    )
    _sync_stage_params(pp.pp_engine, init_params)
    pp_hist = pp.train()
    pp_report = pp.numerics_monitor.last
    assert pp_report is not None and pp_report.step == STEPS

    # losses track the flat run (the existing parity contract, here just
    # a sanity anchor that the two runs saw the same trajectory)
    np.testing.assert_allclose(
        [h["loss"] for h in pp_hist], [h["loss"] for h in flat_hist],
        rtol=2e-4, atol=2e-5,
    )
    # numerics scalars rode the PP history too
    assert all("numerics/grad_rms_max" in h for h in pp_hist)

    flat_params = {
        n: r for n, r in flat_report.rows.items() if r["kind"] == "param"
    }
    # every PP row is stage-prefixed, finite, and param-kind
    by_leaf = {}
    for name, r in pp_report.rows.items():
        assert name.startswith("pp/s"), name
        stage, leaf = name.split("/", 2)[1], name.split("/", 2)[2]
        assert r["kind"] == "param" and r["finite_ok"], name
        assert leaf not in by_leaf, f"{leaf} owned by two stages"
        by_leaf[leaf] = r
    # union of the stage windows covers the flat model's leaves exactly
    assert set(by_leaf) == set(flat_params)

    # grad-RMS parity up to the backends' global scaling: the flat step
    # stats see sum-then-scale(+clip)ed grads, the PP stats dispatch on
    # raw stage sums before the fused clip — a single global factor, so
    # the per-leaf profile normalized by its max must match
    leaves = sorted(by_leaf)
    flat_v = np.array([flat_params[n]["rms"] for n in leaves])
    pp_v = np.array([by_leaf[n]["rms"] for n in leaves])
    np.testing.assert_allclose(
        flat_v / flat_v.max(), pp_v / pp_v.max(), rtol=5e-3, atol=1e-6,
    )
