"""Trainer telemetry e2e on the CPU micro config: the JSONL phase
timeline must account for (>=95% of) each step's wall time, throughput
must be reported with the batch-maths token count, and the emitted
events must schema-validate."""

import collections
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.e2e  # full (micro) training flow

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
from d9d_tpu.parallel import replicate_plan
from d9d_tpu.telemetry import Telemetry, iter_events, set_telemetry

VOCAB = 64
BATCH, SEQ, STEPS = 4, 16, 5


class _Provider(ModelProvider):
    cfg = Qwen3DenseConfig.tiny(vocab_size=VOCAB)

    def build_module(self, stage):
        return Qwen3DenseCausalLM(
            config=self.cfg, sdpa=eager_sdpa, stage=stage, dtype=jnp.float32
        )

    def build_plan(self, ctx):
        return replicate_plan(ctx)

    def sample_inputs(self, batch_size, seq_len):
        z = jnp.zeros((batch_size, seq_len), jnp.int32)
        return (z, z, z)


class _Data(DatasetProvider):
    def build(self):
        rng = np.random.RandomState(0)
        for _ in range(STEPS + 2):
            yield {"input_ids": rng.randint(0, VOCAB, size=(BATCH, SEQ + 1))}


def _train(tmp_path):
    # fresh hub: isolate this run's registry from other tests' residue
    set_telemetry(Telemetry())
    ctx = MeshParameters().build(jax.devices()[:1])
    trainer = Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=BATCH,
            microbatch_size=BATCH,
            seq_len=SEQ,
            total_steps=STEPS,
            log_every=2,
            prefetch_batches=0,
            telemetry_dir=str(tmp_path),
            telemetry_every_steps=2,
            telemetry_console=False,
        ),
        model_provider=_Provider(),
        dataset_provider=_Data(),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(weight_decay=0.0),
    )
    history = trainer.train()
    (path,) = pathlib.Path(tmp_path).glob("*.jsonl")
    return history, list(iter_events(path))  # iter_events schema-validates


@pytest.mark.slow  # >10s compile-bound on the 2-core rig (full tiny train run)
def test_phase_timeline_covers_wall_and_reports_throughput(tmp_path):
    history, events = _train(tmp_path)

    # -- the acceptance criterion: per-step phase spans account for
    # >= 95% of the step's measured wall time, no unattributed gaps
    phase_sum = collections.defaultdict(float)
    step_wall = {}
    for e in events:
        if e["kind"] != "span":
            continue
        if e["name"].startswith("train/phase/"):
            phase_sum[e["step"]] += e["dur_s"]
        elif e["name"] == "train/step":
            step_wall[e["step"]] = e["dur_s"]
    assert len(step_wall) == STEPS
    for step, wall in step_wall.items():
        assert phase_sum[step] >= 0.95 * wall, (
            f"step {step}: phases cover {phase_sum[step]:.6f}s "
            f"of {wall:.6f}s wall"
        )
    # the per-step timelines in turn account for the loop's wall_s
    # (compile rides inside step 0's host_dispatch phase)
    assert sum(step_wall.values()) <= history[-1]["wall_s"] * 1.001

    # -- every step emits the expected phase set
    names = {e["name"] for e in events if e["kind"] == "span"}
    for phase in ("data_wait", "host_dispatch", "device_block",
                  "metric_flush", "checkpoint", "other"):
        assert f"train/phase/{phase}" in names

    # -- satellite: tokens_per_s rides next to wall_s in history rows,
    # from the batch-maths token count
    for row in history:
        assert row["tokens_per_s"] == pytest.approx(
            row["step"] * BATCH * SEQ / row["wall_s"], rel=1e-6
        )

    # -- flush events on the telemetry cadence carry the live gauges
    flushes = [e for e in events if e["kind"] == "flush"]
    assert flushes, "no flush events on the telemetry cadence"
    last = flushes[-1]
    assert last["counters"]["train/tokens"] == STEPS * BATCH * SEQ
    assert last["counters"]["train/steps"] == STEPS
    assert last["gauges"]["train/tokens_per_s"] > 0
    # the CPU rig has no peak FLOP/s to divide by, so no utilisation
    # gauge (telemetry/flops.device_peak_flops returns None off the TPU)
    assert "train/mfu" not in last["gauges"]
    # io spans from the data loader side are absent (generator dataset),
    # but the histogram summaries must be well-formed where present
    for name, h in last["histograms"].items():
        assert h["count"] >= 0, name
