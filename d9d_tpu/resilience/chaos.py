"""Deterministic fault injectors driving ``tests/resilience/``.

Every injector is reproducible from explicit indices — no randomness, no
timing races — so a chaos test asserts exact recovery behavior, not
"usually survives". The catalogue (docs/design/resilience.md):

- :class:`ChaosScaleTask` — multiply the training loss of chosen host
  batches by a factor (``float("nan")`` ⇒ NaN loss AND NaN grads through
  the whole backward; ``1000.0`` ⇒ a finite loss spike). Works through
  both step backends: the factor rides the batch pytree as a
  ``chaos_scale`` leaf, so the jitted step stays trace-stable and the
  injection point is an ordinary host decision.
- :class:`FlakyDataset` — raise on chosen ``__getitem__`` *call
  indices* (retries advance the call counter, so transient-vs-fatal is
  expressed exactly), or permanently from a call index on
  (``dead_from`` ⇒ prefetch-producer death once retries exhaust).
- :func:`truncate_latest_checkpoint` — physically truncate the largest
  payload file of a finalized save directory (the on-disk state of a
  machine that died mid-write after the finalize rename).
- :func:`sigterm_at_step` — deliver a real SIGTERM to this process when
  a chosen trainer step begins (event-bus hook).
- :func:`wedge_batcher` — replace a serving batcher's harvest with a
  long sleep: a deterministic stand-in for a wedged device readback.
- :func:`shrink_at_step` — arm a :class:`~d9d_tpu.resilience.elastic.
  ServingFleet` to shrink a chosen replica at an exact scheduling
  round (the deterministic form of a preemption landing mid-traffic).
- :func:`kill_replica_mid_drain` — make a replica die partway through
  its shrink drain (after an exact number of grace chunks): the fleet
  must recover its unfinished requests onto survivors.
- :func:`kill_prefill_mid_handoff` — make a prefill replica die at its
  next handoff with pages exported but not yet imported: the shipment
  is lost in flight, the request must recover via continuation.
- :func:`corrupt_handoff_payload` — flip a byte of the next handoff
  shipment so the per-page checksum must catch it: the import is
  refused wholesale and the request re-prefills, token-identically.
- :func:`ramp_arrivals` — a scripted arrival-rate ramp: phases of
  (steps, arrivals-per-step) compiled into an exact arrival schedule.
  Arrival *times* carry zero randomness (fractional rates are spread
  by an error accumulator), so an overload ramp reproduces the same
  queue depths, rejections and autopilot decisions on every run.

Queue overflow needs no injector: submit past ``max_queue`` and assert
:class:`~d9d_tpu.loop.serve.QueueFullError`.

This module imports the loop task surface; import it on demand (tests,
harnesses), not from ``d9d_tpu.resilience.__init__``.
"""

import os
import signal
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from d9d_tpu.core.types import PyTree
from d9d_tpu.loop.control.task import PipelineTrainTask
from d9d_tpu.resilience.manifest import MANIFEST_NAME

CHAOS_SCALE_KEY = "chaos_scale"


class ChaosScaleTask(PipelineTrainTask):
    """Wrap a task; scale the loss of chosen prepared batches.

    ``scale_at`` maps *prepared-batch call index* (0-based, counted on
    the host in ``prepare_batch`` — under prefetch that is the
    producer's order, which equals consumption order) to a loss factor.
    Unlisted batches are untouched (factor 1). The factor is injected as
    a per-sample ``chaos_scale`` batch leaf and applied as
    ``loss_sum * mean(scale)`` inside the jitted loss — NaN propagates
    into every gradient leaf, a finite factor spikes the loss and scales
    grads without breaking finiteness.

    Implements the full :class:`PipelineTrainTask` surface by
    delegation, routing the leaf through the last stage's ``state``
    tree, so the same injector drives the non-PP and the PP step
    backends. (PP note: ``state`` leaves are staged with the last
    stage's [batch, seq] sharding — the [B, 1] scale leaf requires the
    context-parallel axis to be trivial, which chaos rigs satisfy.)
    """

    def __init__(self, inner, scale_at: dict[int, float]):
        self.inner = inner
        self.scale_at = {int(k): float(v) for k, v in scale_at.items()}
        self.calls = 0

    # -- non-PP surface ------------------------------------------------

    def prepare_batch(self, batch: PyTree) -> PyTree:
        prepared = dict(self.inner.prepare_batch(batch))
        n = np.shape(jax.tree.leaves(prepared)[0])[0]
        factor = self.scale_at.get(self.calls, 1.0)
        self.calls += 1
        prepared[CHAOS_SCALE_KEY] = np.full((n, 1), factor, np.float32)
        return prepared

    def loss_fn(self, module, params, mb, rng):
        mb = dict(mb)
        scale = mb.pop(CHAOS_SCALE_KEY)
        loss_sum, weight, metrics = self.inner.loss_fn(
            module, params, mb, rng
        )
        return loss_sum * jnp.mean(scale), weight, metrics

    def metrics_postprocess(self, metrics):
        return self.inner.metrics_postprocess(metrics)

    def metrics(self):
        return self.inner.metrics()

    def update_metrics(self, metric_objs, stats):
        return self.inner.update_metrics(metric_objs, stats)

    # -- PP surface (delegated; the scale leaf rides `state`) ----------

    def sample_microbatch(self, microbatch_size: int, seq_len: int):
        mb = dict(self.inner.sample_microbatch(microbatch_size, seq_len))
        mb[CHAOS_SCALE_KEY] = np.ones((microbatch_size, 1), np.float32)
        return mb

    def split_microbatch(self, microbatch):
        mb = dict(microbatch)
        scale = mb.pop(CHAOS_SCALE_KEY)
        carry, kwargs, state = self.inner.split_microbatch(mb)
        state = dict(state)
        state[CHAOS_SCALE_KEY] = scale
        return carry, kwargs, state

    def stage_forward(self, module, params, carry, kwargs):
        return self.inner.stage_forward(module, params, carry, kwargs)

    def last_stage_loss(self, module, params, carry, kwargs, state):
        state = dict(state)
        scale = state.pop(CHAOS_SCALE_KEY)
        loss_sum, weight, metrics = self.inner.last_stage_loss(
            module, params, carry, kwargs, state
        )
        return loss_sum * jnp.mean(scale), weight, metrics

    def stage_init(self, module, rng, carry, kwargs, state, is_last):
        state = dict(state)
        state.pop(CHAOS_SCALE_KEY, None)
        return self.inner.stage_init(
            module, rng, carry, kwargs, state, is_last
        )


class FlakyDataset:
    """Map-style dataset wrapper that fails on exact fetch-call indices.

    ``fail_calls`` — the global ``__getitem__`` call indices that raise
    (a retry is a new call, so ``fail_calls={3, 4}`` with
    ``retry_attempts>=2`` is a transient fault the loader survives);
    ``dead_from`` — every call at/after this index raises (a permanent
    source outage: retries exhaust, the error must surface cleanly).
    """

    def __init__(
        self,
        inner,
        *,
        fail_calls=frozenset(),
        dead_from: int | None = None,
        exc_type: type[Exception] = ConnectionError,
    ):
        self.inner = inner
        self.fail_calls = frozenset(int(c) for c in fail_calls)
        self.dead_from = dead_from
        self.exc_type = exc_type
        self.calls = 0
        self.failures = 0

    def __len__(self) -> int:
        return len(self.inner)

    def __getitem__(self, i):
        call = self.calls
        self.calls += 1
        if (self.dead_from is not None and call >= self.dead_from) or (
            call in self.fail_calls
        ):
            self.failures += 1
            raise self.exc_type(
                f"chaos: injected fetch failure (call {call}, item {i})"
            )
        return self.inner[i]


def checkpoint_steps(directory: str | Path) -> list[int]:
    """Finalized ``save_{N}`` steps under a checkpoint dir, ascending."""
    steps = []
    for p in Path(directory).glob("save_*"):
        tail = p.name.split("_", 1)[1]
        if p.is_dir() and tail.isdigit():
            steps.append(int(tail))
    return sorted(steps)


def truncate_latest_checkpoint(
    directory: str | Path, *, step: int | None = None
) -> tuple[int, Path]:
    """Truncate the largest payload file of the newest (or given) save
    directory to half its size — the post-crash disk state of an
    interrupted array write. Returns (step, truncated file path).

    The step's integrity manifest (written before the damage) now
    records the original size, so restore-time validation must reject
    the step and fall back.
    """
    steps = checkpoint_steps(directory)
    if not steps:
        raise FileNotFoundError(f"no save_* directories under {directory}")
    target = steps[-1] if step is None else step
    step_dir = Path(directory) / f"save_{target}"
    files = [
        p for p in step_dir.rglob("*")
        if p.is_file() and p.name != MANIFEST_NAME and p.stat().st_size > 0
    ]
    victim = max(files, key=lambda p: p.stat().st_size)
    size = victim.stat().st_size
    with open(victim, "r+b") as fh:
        fh.truncate(size // 2)
    return target, victim


def sigterm_at_step(
    event_bus, step: int, *, signum: int = signal.SIGTERM
) -> None:
    """Deliver ``signum`` to this process when trainer step ``step``
    begins (EVENT_STEP.pre hook) — a real mid-run preemption, raced
    against nothing: the flag is checked at the same step's boundary."""
    from d9d_tpu.loop import event as ev

    def hook(**payload):
        if payload.get("step") == step:
            os.kill(os.getpid(), signum)

    event_bus.subscribe(ev.EVENT_STEP.pre, hook)


def shrink_at_step(fleet, replica_idx: int, step: int) -> None:
    """Shrink ``replica_idx`` out of ``fleet`` when its scheduling-round
    counter reaches ``step`` — a preemption arriving mid-traffic, raced
    against nothing (the trigger is consumed at the exact round, before
    that round's chunk dispatches)."""
    fleet._chaos_shrink = (int(replica_idx), int(step))


def kill_replica_mid_drain(
    fleet, replica_idx: int, *, after_chunks: int = 1
) -> None:
    """Make ``replica_idx`` die after ``after_chunks`` grace chunks of
    its shrink drain: the fleet must resubmit the replica's unfinished
    requests to survivors as continuation prompts (prompt + tokens
    already emitted), losing no committed work."""
    fleet._chaos_kill = (int(replica_idx), int(after_chunks))


def kill_prefill_mid_handoff(fleet, replica_idx: int) -> None:
    """Make ``replica_idx`` die at its NEXT prefill→decode handoff, at
    the worst instant: pages exported but not yet imported anywhere.
    The shipment is lost with the replica; the fleet must recover every
    in-flight request (including the one mid-handoff) via the
    continuation fallback — token-identically, with zero leaked pages
    on every survivor (``check_invariants``)."""
    fleet._chaos_kill_handoff = int(replica_idx)


def corrupt_handoff_payload(fleet) -> None:
    """Flip one byte of the NEXT handoff shipment's page payload after
    export. The importer's per-page checksum must detect it and refuse
    the import wholesale (no partially-written pool pages); the request
    falls back to continuation re-prefill — fallback, not failure."""
    fleet._chaos_corrupt_handoff = True


def ramp_arrivals(
    schedule,
    *,
    vocab: int,
    seed: int = 0,
    prompt_lo: int = 1,
    prompt_hi: int = 4,
    gen_lo: int = 2,
    gen_hi: int = 8,
    start_step: int = 0,
) -> list[tuple[int, list[int], int]]:
    """Compile a scripted arrival-rate ramp into an exact workload.

    ``schedule`` is a sequence of ``(steps, rate)`` phases: for
    ``steps`` scheduling steps, ``rate`` requests arrive per step
    (fractional rates are spread deterministically by an error
    accumulator — rate 0.5 lands one arrival every second step, never a
    random draw). Returns ``[(arrival_step, prompt, max_new_tokens)]``
    in the exact tuple shape ``tools/bench_serve.py`` workloads use.
    Prompt contents and budgets come from the
    seeded RNG (``prompt_hi``/``gen_hi`` exclusive, matching
    ``make_workload``); arrival *times* carry no randomness at all.
    """
    rng = np.random.RandomState(seed)
    arrivals: list[tuple[int, list[int], int]] = []
    step = int(start_step)
    acc = 0.0
    for steps, rate in schedule:
        if steps < 0 or rate < 0:
            raise ValueError(
                f"schedule phases need steps >= 0 and rate >= 0, got "
                f"({steps}, {rate})"
            )
        for s in range(int(steps)):
            acc += float(rate)
            while acc >= 1.0 - 1e-9:
                acc -= 1.0
                prompt = rng.randint(
                    0, vocab, rng.randint(prompt_lo, prompt_hi)
                ).tolist()
                arrivals.append(
                    (step + s, prompt, int(rng.randint(gen_lo, gen_hi)))
                )
        step += int(steps)
    return arrivals


def wedge_batcher(batcher, *, seconds: float = 3600.0) -> None:
    """Make the batcher's next harvest block for ``seconds`` — a
    deterministic stand-in for a device/runtime wedge, used to prove the
    drain stall watchdog converts a hang into ``ServeStalledError``."""

    def wedged_harvest():
        time.sleep(seconds)
        return {}

    batcher._harvest_one = wedged_harvest
