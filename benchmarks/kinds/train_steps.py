"""A training cell: a ``Trainer`` stepping for ``--seconds`` seconds.

The Trainer runs its own loop (``train()``) with its defaults: steps are
dispatched without waiting for the device and the metrics are fetched
every ``log_every`` steps. The benchmark only feeds it batches through a
``DatasetProvider`` and listens to its spans. When the last warm-up step
has been dispatched the benchmark waits for the device
(``block_until_ready``) and opens the window; at the first step that
ends after ``--seconds`` have passed it waits for the device again and
closes the window, inside ``train()``, and the batches stop. Tokens per
second is every step dispatched in between over that whole interval: all
the work and all the time of the window.

A traced run (``--trace 2``) is that run and then ``TRACE_STEPS`` more
steps of the same ``train()`` under the profiler, started once the
window has closed and its numbers are taken. ``--trace 1`` records them
first, from the window's start, and opens the window anew afterwards.
"""

import dataclasses
import math
import os
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

# steps a traced run records
TRACE_STEPS = 6


class WindowSink(TelemetrySink):
    """Collects the Trainer's spans, opens the window when the last
    warm-up step has been dispatched and has finished on the device, and
    closes it, in the loop's own thread, at the first step that ends
    past the deadline: it waits for the device, takes the time and the
    last step counted, and the batches stop (``windowed``).

    ``--trace 1`` (``trace_dir`` alone) records the first ``TRACE_STEPS``
    steps after the window's start and opens the window anew once the
    capture has been written. ``--trace 2`` (``trace_after``) starts the
    capture when the window has closed and lets ``TRACE_STEPS`` more
    steps through. Either way no rate or share includes the profiler's
    own start or stop."""

    def __init__(self, warmup_steps: int, seconds: float, trace_dir,
                 trace_after: bool = False):
        self.warmup_steps = warmup_steps
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.trace_after = trace_after
        self.trainer = None
        self.spans = []
        self.opened_at = None
        self.first_step = None  # first step counted in the window
        self.deadline = math.inf
        self.inventory_mark = None
        self.closed_at = None
        self.compiles_in_window = None
        self.stop_after = None  # no batch is handed out for a later step
        self.traced = None  # (start, end) of the capture, host clock
        self._capture = self._capture_from = None

    def _open(self, next_step: int) -> None:
        jax.block_until_ready(self.trainer.params)
        self.opened_at = time.perf_counter()
        self.first_step = next_step
        self.deadline = self.opened_at + self.seconds
        self.inventory_mark = len(introspect.inventory())

    def _close(self, step: int) -> None:
        jax.block_until_ready(self.trainer.params)
        self.closed_at = time.perf_counter()
        self.stop_after = step  # the last step counted
        self.compiles_in_window = (
            len(introspect.inventory()) - self.inventory_mark
        )
        if self.trace_after:
            # the profiler's first start costs seconds: it falls into a
            # capture of nothing, which is thrown away
            with tr.capture(os.path.join(self.trace_dir, "first_start")):
                pass
            self.stop_after = step + TRACE_STEPS
            self._start_capture()

    def _start_capture(self) -> None:
        self._capture = tr.capture(self.trace_dir)
        self._capture.__enter__()
        self._capture_from = time.perf_counter()

    def on_span(self, span) -> None:
        if self.closed_at is not None:
            return  # the window's spans are taken
        self.spans.append(span)
        if span.name != "train/step":
            return
        if span.step == self.warmup_steps - 1:
            self._open(span.step + 1)
            if self.trace_dir is not None and not self.trace_after:
                self._start_capture()
        elif self._capture is not None:
            if span.step == self.warmup_steps - 1 + TRACE_STEPS:
                jax.block_until_ready(self.trainer.params)
                self.stop_capture()
                self._open(span.step + 1)
        elif span.t0 + span.dur_s >= self.deadline:
            self._close(span.step)

    def stop_capture(self) -> None:
        if self._capture is not None:
            self._capture.__exit__(None, None, None)
            self._capture = None
            self.traced = (self._capture_from, time.perf_counter())


def windowed(batches, sink: WindowSink):
    """The generator's batches, one per step, until the sink has closed
    the window (and, in a ``--trace 2`` run, ``TRACE_STEPS`` more). It
    may run in the prefetch thread, ahead of the loop: it never waits,
    and the few batches it has handed out beyond the last step run as
    steps that nothing counts."""
    for step, batch in enumerate(batches):
        if sink.stop_after is not None and step > sink.stop_after:
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
    traced: tuple | None = None  # (start, end) of the capture, host clock

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
        devices, trace_after: bool = False) -> TrainObserved:
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
    sink = WindowSink(mix["warmup_steps"], seconds, trace_dir, trace_after)

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
        correct.training_system(
            trainer.module, trainer.params, sample, trainer.task
        ),
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
        jax.block_until_ready(trainer.params)
    finally:
        sink.stop_capture()
        tele.remove_sink(sink, close=False)

    if sink.closed_at is None:
        raise RuntimeError("the window never opened, or never closed")
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
        opened_at=sink.opened_at, closed_at=sink.closed_at,
        first_step=sink.first_step, marks=marks,
        compiles_in_window=sink.compiles_in_window,
        step_hbm_bytes=int(records[-1].hbm_peak_bytes) if records else 0,
        losses=[h["loss"] for h in history],
        hlo_texts=(
            list(introspect.compiled_hlo("train_step"))
            if trace_dir is not None else []
        ),
        checks=checks, traced=sink.traced,
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
