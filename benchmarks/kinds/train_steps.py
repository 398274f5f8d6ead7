"""A training cell: a ``Trainer`` stepping for ``--seconds`` seconds.

The Trainer runs its own loop (``train()``) with its defaults: steps are
dispatched without waiting for the device and the metrics are fetched
every ``log_every`` steps. The benchmark only feeds it batches through a
``DatasetProvider`` and listens to its spans. When the last warm-up step
has been dispatched the benchmark waits for the device
(``block_until_ready``) and opens the window; the batches stop once
``--seconds`` have passed; when ``train()`` has returned it waits for
the device again and closes the window. Tokens per second is every step
dispatched in between over that whole interval: all the work and all
the time of the window.
"""

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from d9d_tpu.loop.tasks import CausalLMTask
from d9d_tpu.loop.train_step import build_eval_step
from d9d_tpu.parallel import replicate_plan
from d9d_tpu.core import MeshParameters
from d9d_tpu.pipelining import PipelineStageInfo
from d9d_tpu.telemetry import get_telemetry, introspect
from d9d_tpu.telemetry.sinks import TelemetrySink

from benchmarks.harness import build, correct, traffic
from benchmarks.harness import trace as tr

# steps the traced run records from the window's start
TRACE_STEPS = 6


class WindowSink(TelemetrySink):
    """Collects the Trainer's spans and opens the window when the last
    warm-up step has been dispatched and has finished on the device. In
    a traced run it records the first ``TRACE_STEPS`` steps after that
    and opens the window anew once the capture has been written, so that
    no rate or share includes the profiler's own stop."""

    def __init__(self, warmup_steps: int, seconds: float, trace_dir):
        self.warmup_steps = warmup_steps
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.trainer = None
        self.spans = []
        self.opened_at = None
        self.first_step = None  # first step counted in the window
        self.deadline = math.inf
        self.inventory_mark = None
        self._capture = None

    def _open(self, next_step: int) -> None:
        jax.block_until_ready(self.trainer.params)
        self.opened_at = time.perf_counter()
        self.first_step = next_step
        self.deadline = self.opened_at + self.seconds
        self.inventory_mark = len(introspect.inventory())

    def on_span(self, span) -> None:
        self.spans.append(span)
        if span.name != "train/step":
            return
        if span.step == self.warmup_steps - 1:
            self._open(span.step + 1)
            if self.trace_dir is not None:
                self._capture = tr.capture(self.trace_dir)
                self._capture.__enter__()
        elif (
            self._capture is not None
            and span.step == self.warmup_steps - 1 + TRACE_STEPS
        ):
            jax.block_until_ready(self.trainer.params)
            self.stop_capture()
            self._open(span.step + 1)

    def stop_capture(self) -> None:
        if self._capture is not None:
            self._capture.__exit__(None, None, None)
            self._capture = None


def windowed(batches, sink: WindowSink):
    """The generator's batches until the window's deadline has passed."""
    for batch in batches:
        if time.perf_counter() >= sink.deadline:
            return
        yield batch


@dataclasses.dataclass
class TrainObserved:
    kind: str
    chips: int
    steps_in_window: int
    tokens_per_step: int
    seq_len: int
    spans: list
    opened_at: float
    closed_at: float
    first_step: int
    marks: dict
    compiles_in_window: int
    step_hbm_bytes: int
    losses: list
    hlo_texts: list
    checks: dict

    @property
    def window_s(self) -> float:
        return self.closed_at - self.opened_at


def sample_tokens(mix: dict, seed: int, vocab: int, rows: int):
    """``sample_tokens`` seeded ids in all: one row on one chip, one row a
    chip across chips (the batch dimension is what the mesh shards)."""
    rng = traffic.rng_for(seed, "correctness_sample")
    return rng.integers(
        0, vocab, size=(rows, mix["sample_tokens"] // rows + 1)
    )


def one_chip_loss(config, mix, tiny, seed, batch, device):
    """``(loss, weights)``: the forward loss of the same seeded model, at
    the cell's own depth, and the same batch on one chip through the
    local expert path: what the sharded run is held to. Forward only, so
    one chip holds the weights and no optimizer state; built and freed
    before the sharded trainer exists."""
    ctx = MeshParameters().build([device])
    cfg = build.model_config(config, tiny)
    provider = build.BenchModel(
        {**config, "plan": "replicate"}, cfg, ctx, sharded=False
    )
    module = provider.build_module(PipelineStageInfo())
    params = build.seeded_params(
        module, provider.sample_inputs(mix["sequences"], mix["seq_len"]),
        seed, ctx.mesh, replicate_plan(ctx),
    )
    task = CausalLMTask()
    prepared = task.prepare_batch(batch)
    staged = {
        k: jnp.asarray(v)[None] for k, v in prepared.items()
    }  # one microbatch
    loss = build_eval_step(module=module, task=task, num_microbatches=1)(
        params, staged, jax.random.PRNGKey(0)  # no dropout: unused
    )
    return float(loss), params


def run(cell, seed: int, seconds: float, trace_dir, tiny: bool,
        devices) -> TrainObserved:
    config = cell.config
    mix = traffic.sized(cell.traffic, tiny)
    local_cfg, hf = build.sizes(config, tiny)
    vocab = local_cfg.vocab_size
    reference = build.reference_module(config)
    checks, marks = {}, {"cell_start": time.perf_counter()}

    batches = traffic.train_batches(mix, seed, vocab)
    first_batch = next(batches)
    sample = sample_tokens(mix, seed, vocab, rows=len(devices))

    # Across chips: the one-chip value and the reference's answer first,
    # on one chip and under its mesh, freed before the sharded mesh is
    # built (MeshParameters.build sets the ambient mesh).
    expected = None
    if len(devices) > 1:
        loss_1, plain_params = one_chip_loss(
            config, mix, tiny, seed, first_batch, devices[0]
        )
        checks["one_chip_loss"] = loss_1
        marks["one_chip_loss"] = time.perf_counter()
        expected = correct.training_reference(
            reference, plain_params, hf, sample
        )
        del plain_params
        marks["one_chip_reference"] = time.perf_counter()

    ctx = build.mesh_context(config, devices)
    cfg = build.sharded_model_config(config, ctx, tiny)
    sink = WindowSink(mix["warmup_steps"], seconds, trace_dir)

    def stream():
        yield first_batch
        yield from batches

    trainer = build.build_trainer(
        config, mix, cfg, ctx, seed, windowed(stream(), sink),
        total_steps=10**9,
    )
    sink.trainer = trainer
    marks["trainer_built"] = time.perf_counter()
    if expected is None:
        expected = correct.training_reference(
            reference, trainer.params, hf, sample
        )
    checks.update(correct.compare_training(
        correct.training_system(trainer.module, trainer.params, sample),
        expected,
    ))
    del expected
    marks["sample_compared"] = time.perf_counter()
    if len(devices) > 1:
        checks["sharded_loss"] = trainer.loss_on_batch(first_batch)

    marks["compared"] = time.perf_counter()
    tele = get_telemetry()
    tele.add_sink(sink)
    try:
        history = trainer.train()
    finally:
        sink.stop_capture()
        tele.remove_sink(sink, close=False)
    jax.block_until_ready(trainer.params)
    closed_at = time.perf_counter()

    if sink.opened_at is None:
        raise RuntimeError("the window never opened: warm-up never ended")
    steps = [
        s for s in sink.spans
        if s.name == "train/step" and s.step >= sink.first_step
    ]
    records = [r for r in introspect.inventory() if r.name == "train_step"]
    observed = TrainObserved(
        kind="train_steps", chips=len(devices),
        steps_in_window=len(steps),
        tokens_per_step=mix["sequences"] * mix["seq_len"],
        seq_len=mix["seq_len"], spans=sink.spans,
        opened_at=sink.opened_at, closed_at=closed_at,
        first_step=sink.first_step, marks=marks,
        compiles_in_window=len(introspect.inventory()) - sink.inventory_mark,
        step_hbm_bytes=int(records[-1].hbm_peak_bytes) if records else 0,
        losses=[h["loss"] for h in history],
        hlo_texts=(
            list(introspect.compiled_hlo("train_step"))
            if trace_dir is not None else []
        ),
        checks=checks,
    )
    trainer.close()
    return observed


def verdict(observed: TrainObserved) -> dict:
    """What decides ``correct`` for a training cell."""
    c = observed.checks
    failures = list(correct.sample_failures(c))
    if not observed.losses:
        failures.append("no loss was fetched in the window")
    if not all(np.isfinite(observed.losses)):
        failures.append(f"losses not finite: {observed.losses}")
    if observed.compiles_in_window:
        failures.append(
            f"{observed.compiles_in_window} compiles inside the window"
        )
    if "one_chip_loss" in c:
        gap = abs(c["sharded_loss"] - c["one_chip_loss"])
        if not gap <= correct.MULTICHIP_LOSS_TOL:
            failures.append(
                f"sharded loss {c['sharded_loss']} against one chip "
                f"{c['one_chip_loss']}: beyond {correct.MULTICHIP_LOSS_TOL}"
            )
    return {"failures": failures, **c}


def attempts(observed: TrainObserved) -> dict:
    bad = sum(1 for v in observed.losses if not math.isfinite(v))
    return {"attempted": observed.steps_in_window, "failed": bad}


def samples(observed: TrainObserved) -> dict:
    """Sample counts and where set-up went, for the line before the last."""
    durations = sorted(
        s.dur_s for s in observed.spans
        if s.name == "train/step" and s.step >= observed.first_step
    )
    return {
        "steps": observed.steps_in_window, "window_s": observed.window_s,
        "losses_fetched": len(observed.losses),
        "host_step_s_min_med_max": [
            durations[0], durations[len(durations) // 2], durations[-1]],
    }
