"""Trainer: wires providers into the compiled step and runs the loop.

Reference: d9d/loop/run/train.py:71,251 (TrainingConfigurator/Trainer).
The configure step builds mesh→model→optimizer→step-fn; ``train()`` is a
thin host loop around the jitted step — data staging and metric readback
are the only per-step host work (hot path is one XLA program). Around it
sit the reference's loop components: event bus, tracker-backed logger,
orbax job-state checkpointer with resume, jax.profiler cycles, manual GC,
hang watchdog, and sleep/wake host offload.
"""

import logging
import time
import warnings

import jax
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from d9d_tpu.core.mesh import MeshContext
from d9d_tpu.core.offload import SleepTag, offload_tree, onload_tree
from d9d_tpu.core.types import PyTree
from d9d_tpu.loop import event as ev
from d9d_tpu.loop.components.batch_maths import BatchMaths
from d9d_tpu.loop.components.batch_staging import (
    make_batch_stager,
    split_microbatches,
)
from d9d_tpu.loop.components.checkpointer import StateCheckpointer
from d9d_tpu.loop.components.garbage_collector import ManualGarbageCollector
from d9d_tpu.loop.components.job_profiler import JobProfiler
from d9d_tpu.loop.components.metric_collector import MetricCollector
from d9d_tpu.loop.components.prefetch import BatchPrefetcher
from d9d_tpu.loop.components.stepper import Stepper
from d9d_tpu.loop.components.timeout_manager import TimeoutManager
from d9d_tpu.loop.config import TrainerConfig
from d9d_tpu.loop.control.providers import (
    DatasetProvider,
    ModelProvider,
    OptimizerProvider,
)
from d9d_tpu.loop.control.task import TrainTask
from d9d_tpu.loop.event import EventBus
from d9d_tpu.loop.model_factory import init_sharded_params
from d9d_tpu.loop.train_step import build_eval_step, build_train_step
from d9d_tpu.pipelining import PipelineStageInfo
from d9d_tpu.resilience import (
    HostAnomalyGuard,
    PreemptionGuard,
    TrainingPreempted,
)
from d9d_tpu.telemetry import (
    ConsoleSink,
    JsonlSink,
    TrackerBridge,
    get_telemetry,
    recompile_guard,
)
from d9d_tpu.telemetry.numerics import (
    NumericsMonitor,
    TrainDriftMonitor,
    default_drift_policies,
)
from d9d_tpu.telemetry.introspect import executable_flops
from d9d_tpu.telemetry.flops import (
    active_param_count,
    device_peak_flops,
    model_flops_per_token,
)
from d9d_tpu.tracker import NullTracker, Tracker

logger = logging.getLogger("d9d_tpu.trainer")


class Trainer:
    def __init__(
        self,
        *,
        ctx: MeshContext,
        config: TrainerConfig,
        model_provider: ModelProvider,
        dataset_provider: DatasetProvider,
        task: TrainTask,
        optimizer_provider: OptimizerProvider,
        learning_rate: optax.ScalarOrSchedule | None = None,
        peft_method=None,
        tracker: Tracker | None = None,
        event_bus: EventBus | None = None,
    ):
        self.ctx = ctx
        self.config = config
        self.task = task
        self.events = event_bus if event_bus is not None else EventBus()
        self.tracker = tracker if tracker is not None else NullTracker()
        self.events.emit(ev.EVENT_TRAIN_CONFIG_STARTED, trainer=self)

        self.batch_maths = BatchMaths.from_context(
            ctx, config.global_batch_size, config.microbatch_size
        )
        self.stepper = Stepper(total_steps=config.total_steps)

        rng = jax.random.PRNGKey(config.seed)
        self.init_rng, self.step_rng = jax.random.split(rng)
        self.peft_method = peft_method
        self.base_params = None
        self.pp_engine = None
        self.module = None
        self.params = self.param_shardings = None
        self.opt_state = None
        self.step_fn = None

        self.optimizer = optimizer_provider.build(
            learning_rate if learning_rate is not None else config.learning_rate
        )

        if ctx.pp_size > 1:
            from d9d_tpu.loop.pipeline_driver import PipelineTrainEngine

            self.zero = None  # PP: the per-stage optimizer owns the tables
            self.pp_engine = PipelineTrainEngine(
                ctx=ctx,
                schedule=config.pipeline,
                model_provider=model_provider,
                task=task,
                optimizer=self.optimizer,
                batch_maths=self.batch_maths,
                seq_len=config.seq_len,
                init_rng=self.init_rng,
                max_grad_norm=config.max_grad_norm,
                peft_method=peft_method,
                anomaly_policy=config.anomaly_policy,
                zero_sharding=config.zero_sharding,
                numerics=config.numerics_every_steps is not None,
            )
            self.events.emit(ev.EVENT_MODEL_READY, trainer=self)
            self.events.emit(ev.EVENT_OPTIMIZER_READY, trainer=self)
        else:
            self.module = model_provider.build_module(PipelineStageInfo())
            plan = model_provider.build_plan(ctx)
            sample = model_provider.sample_inputs(
                self.batch_maths.microbatch_size, config.seq_len
            )
            self.params, self.param_shardings = init_sharded_params(
                self.module, sample, self.init_rng, ctx, plan
            )

            if peft_method is not None:
                # engine "params" become the adapter tree; base stays frozen
                from d9d_tpu.peft import PeftTask

                inject_rng = jax.random.fold_in(self.init_rng, 1)
                self.base_params, adapters = peft_method.inject(
                    self.params, inject_rng
                )
                self.params = adapters
                self.task = task = PeftTask(task, peft_method, self.base_params)
            self.events.emit(ev.EVENT_MODEL_READY, trainer=self)

            # Place the state where the step will keep it: every
            # param-shaped leaf (the moments) on its parameter's sharding,
            # the riders (step counters, RNG keys) replicated on the mesh.
            # Left to the compiler, the moments — constant zeros to it —
            # come out replicated: N full copies per chip at step 0, and a
            # second compile of the train step when they come back out of
            # it sharded (seen on the FSDP x EP mesh, PR 21). Committing
            # the riders to the mesh also keeps a checkpoint round trip
            # from pinning them to one device.
            replicated = NamedSharding(ctx.mesh, P())
            state_shardings = optax.tree_utils.tree_map_params(
                self.optimizer,
                lambda _, sharding: sharding,
                jax.eval_shape(self.optimizer.init, self.params),
                jax.tree.map(lambda x: x.sharding, self.params),
                transform_non_params=lambda _: replicated,
            )
            # d9d-lint: disable=D9D001 — one-shot optimizer-state init
            self.opt_state = jax.jit(
                self.optimizer.init, out_shardings=state_shardings
            )(self.params)
            self.zero = None
            if config.zero_sharding:
                # ZeRO optimizer-state sharding (parallel/zero.py): move
                # the live state onto its 1/N-per-chip layout and wrap
                # the optimizer with the reduce-scatter/all-gather
                # annotations around the update seam
                from d9d_tpu.parallel.zero import (
                    ZeroShardedOptimizer,
                    build_zero_sharding,
                    place_tree,
                )

                self.zero = build_zero_sharding(
                    params=self.params,
                    opt_state=self.opt_state,
                    mesh=ctx.mesh,
                )
                self.opt_state = place_tree(
                    self.opt_state, self.zero.state_shardings
                )
                self.optimizer = ZeroShardedOptimizer(
                    self.optimizer, self.zero
                )
            self.events.emit(ev.EVENT_OPTIMIZER_READY, trainer=self)

            self.step_fn = build_train_step(
                module=self.module,
                task=self.task,
                optimizer=self.optimizer,
                num_microbatches=self.batch_maths.num_microbatches,
                max_grad_norm=config.max_grad_norm,
                anomaly_policy=config.anomaly_policy,
                zero=self.zero,
                split_update=config.split_optimizer_update,
                numerics=config.numerics_every_steps is not None,
            )

        self.dataset_provider = dataset_provider
        self.data_loader = None  # built fresh per train() call

        self.checkpointer = (
            StateCheckpointer(
                config.checkpoint_dir,
                save_every_steps=config.checkpoint_every_steps,
                num_to_keep=config.checkpoints_to_keep,
                async_save=config.checkpoint_async,
            )
            if config.checkpoint_dir is not None
            else None
        )
        self.profiler = JobProfiler(
            config.profile_dir,
            every_steps=config.profile_every_steps,
            active_steps=config.profile_active_steps,
            wait_steps=config.profile_wait_steps,
        )
        self.timeout = TimeoutManager(
            init_timeout_s=config.init_timeout_s,
            step_timeout_s=config.step_timeout_s,
            exit_code=config.watchdog_exit_code,
        )
        # resilience (docs/design/resilience.md): host half of the step
        # anomaly guard + the preemption signal flag; both no-ops unless
        # their config knobs enable them
        self.anomaly_guard = (
            HostAnomalyGuard(
                policy=config.anomaly_policy,
                rollback_after=config.anomaly_rollback_after,
                spike_factor=config.anomaly_spike_factor,
                spike_window=config.anomaly_spike_window,
            )
            if config.anomaly_policy is not None
            else None
        )
        self.preemption = PreemptionGuard(enabled=config.handle_preemption)
        # training numerics plane (telemetry/numerics.py): host half —
        # decodes the cadence windows the metric fetch already carried,
        # names the first non-finite layer for the anomaly guard, feeds
        # numerics/* gauges + the schema-v4 JSONL event; drift policies
        # gauge train_slo/* over the same host metric dicts
        self.numerics_monitor = (
            NumericsMonitor(telemetry=get_telemetry())
            if config.numerics_every_steps is not None
            else None
        )
        self.drift_monitor = (
            TrainDriftMonitor(
                default_drift_policies(), telemetry=get_telemetry()
            )
            if config.numerics_every_steps is not None and config.numerics_drift
            else None
        )
        self.gc = ManualGarbageCollector(config.gc_every_steps)
        self.metric_collector = MetricCollector(self.task)
        self.run = None  # tracker run, opened in train()
        self._sleep_store: dict[SleepTag, tuple[PyTree, PyTree]] = {}
        self._prefetcher = None  # BatchPrefetcher, live only inside train()

        self._stage = make_batch_stager(
            ctx,
            num_microbatches=self.batch_maths.num_microbatches,
            microbatch_size=self.batch_maths.microbatch_size,
            seq_len=config.seq_len,
        )
        self._eval_fn = None
        self._merge_fn = None

        # always-on runtime telemetry (docs/design/observability.md):
        # recording happens regardless; config knobs only attach sinks
        # (JSONL event log / tracker bridge / console) inside train()
        self.telemetry = get_telemetry()
        self._tokens_per_step = config.global_batch_size * config.seq_len
        self._flops_per_token = model_flops_per_token(
            self._active_param_count(), seq_len=config.seq_len,
            config=self._model_config(),
        )
        # tok_s is whole-mesh throughput, so MFU normalizes by the whole
        # mesh's peak (per-chip peak x mesh size), the single-chip
        # convention at mesh size 1. None off the TPU: the
        # CPU rig has no peak, so it emits no train/mfu gauge
        chip_peak = device_peak_flops()
        self._peak_flops = (
            chip_peak * int(ctx.mesh.devices.size)
            if chip_peak is not None else None
        )
        # per-chip optimizer-state footprint (docs/design/zero_sharding.md):
        # under ZeRO this reads ~1/dp_replicate of the replicated value —
        # the executable claim the bench column mirrors
        self.telemetry.gauge("opt/state_bytes_per_chip").set(
            self.opt_state_bytes_per_chip()
        )
        # once-per-process flag for the model-vs-XLA FLOPs cross-check
        # (telemetry/introspect.py inventory vs the roofline convention)
        self._flops_divergence_checked = False
        # monitoring plane (docs/design/observability.md): steps run by
        # the CURRENT train() session — the /readyz warmup contract;
        # the metrics endpoint itself is started/stopped inside train()
        self._session_steps = 0
        self.metrics_server = None
        # anomaly flight recorder: with a telemetry dir configured, the
        # guard/watchdog failure paths dump flight_recorder_{event}.json
        # NEXT TO that dir (its parent) — one black box per job dir
        if config.telemetry_dir is not None:
            from pathlib import Path

            self.telemetry.configure_flight_recorder(
                Path(config.telemetry_dir).parent
            )
            # flight-recorder capture hook: an SLO-burn/anomaly dump
            # kicks off a 1s on-demand profile (device trace + folded
            # host stacks) into the telemetry dir's captures/ next to
            # the metric windows — JobProfiler.capture degrades to None
            # when the profiler is busy, and the recorder treats that
            # as "no capture", never a failed dump
            captures_dir = Path(config.telemetry_dir) / "captures"
            recorder = self.telemetry.flight_recorder
            if recorder is not None:
                recorder.capture_hook = (
                    lambda event: self.profiler.capture(1.0, captures_dir)
                )
        # saving-mesh block for checkpoint manifests (elastic restore);
        # built lazily at the first save — placement is stable by then
        self._mesh_spec = None
        self.events.emit(ev.EVENT_TRAIN_READY, trainer=self)

    # -- live-MFU inputs (telemetry/flops.py roofline convention) ------

    def _active_param_count(self) -> float:
        """Params that compute per token, via the shared accounting in
        telemetry/flops.py (MoE experts scaled by top_k/num_experts) —
        so the live MFU gauge and the bench-reported MFU agree."""
        if self.pp_engine is not None:
            trees = [rt.params for rt in self.pp_engine.stages.values()]
        else:
            trees = [self.params]
        if self.base_params is not None:  # PEFT: frozen base still computes
            trees.append(self.base_params)
        return active_param_count(trees, self._model_config())

    def opt_state_bytes_per_chip(self) -> int:
        """Per-chip bytes of the live optimizer state (shard-aware).

        Under PP each chip belongs to exactly one stage, so the honest
        per-chip number is the worst stage's footprint, not the sum.
        """
        from d9d_tpu.parallel.zero import tree_bytes_per_device

        if self.pp_engine is not None:
            per_rank: dict[int, int] = {}
            for s, state in self.pp_engine.opt_states.items():
                rank = self.pp_engine.stage_owner[s]
                per_rank[rank] = per_rank.get(rank, 0) + tree_bytes_per_device(
                    state
                )
            return max(per_rank.values(), default=0)
        return tree_bytes_per_device(self.opt_state)

    def _model_config(self):
        if self.pp_engine is not None:
            rt = self.pp_engine.stages.get(0)
            return getattr(rt.module, "config", None) if rt else None
        return getattr(self.module, "config", None)

    def _note_flops_divergence(self) -> None:
        """Cross-check the roofline FLOPs inventory (telemetry/flops.py,
        the live-MFU convention) against XLA's own cost analysis of the
        compiled train step. A large gap means the MFU gauge is lying —
        the model inventory drifted from what actually runs (missed
        attention term, uncounted recompute) — so it gets a gauge
        (``flops/model_vs_xla_divergence``, signed, relative) and a
        warning past the configured tolerance. Non-PP only: under PP
        the step is many per-action executables, not one program."""
        if self._flops_divergence_checked or self.pp_engine is not None:
            return
        xla = executable_flops("train_step")
        if xla is None or xla <= 0:
            return  # backend declined cost analysis, or tracked_jit degraded
        # Two normalizations to compare like with like: cost_analysis
        # describes the PER-DEVICE SPMD program (the model inventory
        # counts the whole mesh), and XLA's static analysis counts the
        # microbatch lax.scan body ONCE, not x trip-count — so the
        # comparable model term is per-device, per-microbatch.
        model = (
            self._flops_per_token * self._tokens_per_step
            / max(int(self.ctx.mesh.devices.size), 1)
            / max(self.batch_maths.num_microbatches, 1)
        )
        if model <= 0:
            return
        divergence = (xla - model) / model
        self.telemetry.gauge("flops/model_vs_xla_divergence").set(divergence)
        self._flops_divergence_checked = True
        if abs(divergence) > self.config.flops_divergence_tolerance:
            logger.warning(
                "model-FLOPs inventory diverges from XLA cost analysis by "
                "%+.1f%% (model %.3e vs XLA %.3e FLOPs/step): the MFU "
                "gauge inherits this error — check telemetry/flops.py's "
                "inventory against the model geometry",
                100 * divergence, model, xla,
            )

    # ------------------------------------------------------------------

    def _stage_batch(self, raw_batch: PyTree) -> PyTree:
        """prepare → microbatch-reshape → device_put (dp + cp sharding).

        Pipeline mode returns the host microbatch *list* instead — the
        executor places each carry/kwargs/state on its stage's submesh.
        """
        prepared = self.task.prepare_batch(raw_batch)
        if self.pp_engine is not None:
            return self._split_microbatches(prepared)
        return self._stage(prepared)

    def _split_microbatches(self, prepared: PyTree) -> list[PyTree]:
        return split_microbatches(
            prepared,
            num_microbatches=self.batch_maths.num_microbatches,
            microbatch_size=self.batch_maths.microbatch_size,
        )

    def run_step(self, raw_batch: PyTree) -> dict:
        """Public single-step API: stage ``raw_batch``, run one optimizer
        step and advance the step counter.

        Returns the step's metric dict with values still on device (call
        ``jax.block_until_ready`` to synchronize). This is the stable hook
        for benchmarks and external drivers; ``train()`` runs through the
        same staging/step internals.
        """
        metrics = self._optimizer_step(self._stage_batch(raw_batch))
        self.stepper.advance()
        return metrics

    def _fetches_metrics(self, step: int) -> bool:
        """Will the loop fetch ``step``'s metrics? Log cadence, final
        step, or a guard-forced checkpoint fetch (a checkpoint step only
        forces a fetch when the anomaly guard must examine the state
        being saved). THE predicate — shared by the loop's fetch site
        and :meth:`_numerics_on`, so a computed numerics window is
        always one the host actually decodes and vice versa."""
        return (
            step % self.config.log_every == 0
            or step >= self.config.total_steps
            or (
                self.anomaly_guard is not None
                and self.checkpointer is not None
                and self.checkpointer.should_checkpoint(step)
            )
        )

    def _numerics_on(self) -> bool:
        """Whether THIS step computes its numerics window: the config
        cadence, plus every step whose metrics the loop will fetch
        anyway (:meth:`_fetches_metrics`) — the window the host decodes
        is always the fetched step's own, at zero extra fetches."""
        k = self.config.numerics_every_steps
        if k is None:
            return False
        nxt = self.stepper.step + 1
        return nxt % k == 0 or self._fetches_metrics(nxt)

    def _pp_timeline_on(self) -> bool:
        """Whether THIS step runs the fused pipeline timeline cadence
        (``pp_timeline_every_steps``). Strictly the config cadence — NOT
        folded with :meth:`_fetches_metrics` like numerics, because a
        timeline step serializes the fused dispatch loop and that cost
        should land only where the user asked for it."""
        k = self.config.pp_timeline_every_steps
        if k is None or self.pp_engine is None:
            return False
        return (self.stepper.step + 1) % k == 0

    def _optimizer_step(self, batch: PyTree) -> dict:
        if self.pp_engine is not None:
            return self.pp_engine.step(
                batch,
                numerics=self._numerics_on(),
                timeline=self._pp_timeline_on(),
            )
        rng = jax.random.fold_in(self.step_rng, self.stepper.step)
        self.step_fn.numerics_next = self._numerics_on()
        self.params, self.opt_state, metrics = self.step_fn(
            self.params, self.opt_state, batch, rng
        )
        return metrics

    # -- checkpoint/resume ---------------------------------------------

    def _job_arrays(self) -> PyTree:
        if self.pp_engine is not None:
            return self.pp_engine.job_arrays()
        return {"params": self.params, "opt_state": self.opt_state}

    def _job_mesh_spec(self) -> dict:
        """The saving-topology record for checkpoint manifests
        (docs/design/elasticity.md): MeshParameters axes incl.
        dp_replicate, the zero_sharding setting, per-leaf shardings."""
        if self._mesh_spec is None:
            from d9d_tpu.resilience.elastic import job_mesh_spec

            self._mesh_spec = job_mesh_spec(
                ctx=self.ctx,
                zero_sharding=self.config.zero_sharding,
                arrays=self._job_arrays(),
            )
        return self._mesh_spec

    def _job_meta(self) -> dict:
        meta = {"step": self.stepper.step}
        if self.data_loader is not None:
            # under prefetch the loader runs ahead of the trainer; the
            # checkpoint must record the position of the last CONSUMED
            # batch, not the producer's run-ahead position
            if (
                self._prefetcher is not None
                and self._prefetcher.consumed_position is not None
                and hasattr(self.data_loader, "state_dict_at")
            ):
                meta["data_loader"] = self.data_loader.state_dict_at(
                    self._prefetcher.consumed_position
                )
            elif hasattr(self.data_loader, "state_dict"):
                meta["data_loader"] = self.data_loader.state_dict()
        if self.run is not None:
            meta["tracker"] = self.run.state_dict()
        return meta

    def _save_checkpoint(self, *, last: bool = False) -> None:
        if self.checkpointer is None:
            return
        step = self.stepper.step
        if not self.checkpointer.should_checkpoint(step, last=last):
            return
        with self.events.bounded(ev.EVENT_CHECKPOINT, trainer=self, step=step):
            if self.checkpointer.last_saved_step != step:
                self.checkpointer.save(
                    step, self._job_arrays(), self._job_meta(),
                    mesh_spec=self._job_mesh_spec(),
                )
            if last:
                # intermediate saves overlap training (async write-back);
                # the FINAL one must be durable when train() returns — the
                # process may exit right after, and auto-resume contracts
                # on the last step's checkpoint existing
                self.checkpointer.wait_until_finished()

    def _restore_state(self) -> int | None:
        """Restore the newest intact checkpoint into the live job state
        (arrays, stepper, loader position, tracker run); returns the
        restored step or None. Shared by resume and anomaly rollback."""
        if self.checkpointer is None:
            return None
        budget_mb = self.config.reshard_hbm_budget_mb
        restored = self.checkpointer.restore(
            self._job_arrays(),
            reshard_hbm_budget_bytes=(
                int(budget_mb * 2**20) if budget_mb is not None else None
            ),
        )
        if restored is None:
            return None
        step, arrays, meta = restored
        if self.pp_engine is not None:
            self.pp_engine.load_job_arrays(arrays)
        else:
            self.params = arrays["params"]
            self.opt_state = arrays["opt_state"]
        self.stepper.load_state_dict({"step": meta["step"]})
        if (
            "data_loader" in meta
            and self.data_loader is not None
            and hasattr(self.data_loader, "load_state_dict")
        ):
            self.data_loader.load_state_dict(meta["data_loader"])
        if "tracker" in meta and self.run is not None:
            self.run.load_state_dict(meta["tracker"])
        return step

    def _try_resume(self) -> None:
        if self.checkpointer is None or not self.config.resume:
            return
        step = self._restore_state()
        if step is not None:
            logger.info("resumed from checkpoint at step %d", step)

    def _reset_guard_state(self) -> None:
        """Zero both halves of the anomaly guard (post-rollback), plus
        the numerics/drift windows the restored state invalidates."""
        if self.anomaly_guard is not None:
            self.anomaly_guard.reset()
        if self.pp_engine is not None:
            self.pp_engine.reset_guard()
        elif self.step_fn is not None:
            self.step_fn.reset_guard()
        if self.numerics_monitor is not None:
            self.numerics_monitor.reset()
        if self.drift_monitor is not None:
            self.drift_monitor.reset()

    def _numerics_windows(self, vecs: dict) -> list:
        """(prefix, spec, host vector) windows for the monitor: the
        single-program step's ``numerics/stats``, or one ``pp/s{S}/``-
        prefixed window per stage under PP."""
        windows = []
        if self.pp_engine is not None:
            for s, spec in sorted(self.pp_engine.numerics_specs.items()):
                vec = vecs.get(f"numerics/s{s}")
                if vec is not None:
                    windows.append((f"pp/s{s}/", spec, vec))
            return windows
        spec = self.step_fn.numerics_spec
        vec = vecs.get("numerics/stats")
        if spec is not None and vec is not None:
            windows.append(("", spec, vec))
        return windows

    # -- the loop ------------------------------------------------------

    def train(self) -> list[dict]:
        """Run until total_steps or data exhaustion; returns metric history."""
        history: list[dict] = []
        self.run = None
        tele = self.telemetry
        tele_sinks = []
        if self.config.telemetry_dir:
            tele_sinks.append(tele.add_sink(JsonlSink(
                self.config.telemetry_dir,
                run_name=self.config.run_name or "train",
                process_index=jax.process_index(),
            )))
        if self.config.telemetry_console:
            tele_sinks.append(tele.add_sink(ConsoleSink(
                min_interval_s=self.config.telemetry_console_interval_s,
            )))
        flush_every = (
            self.config.telemetry_every_steps
            if self.config.telemetry_every_steps is not None
            else self.config.log_every
        )
        last_tele_flush = None  # step of the loop's most recent flush
        self._session_steps = 0
        # silent-recompile guard: re-arm for this session — every
        # legitimate signature compiles within the warmup steps, after
        # which any compile is a flagged steady-state recompile
        guard = recompile_guard()
        guard.configure(self.config.introspect_warmup_steps)
        try:
            # live metrics endpoint for the duration of this train()
            # session (telemetry/export.py): ready once past the
            # introspection warmup. Started INSIDE the try: a bind
            # failure (port taken) must still run the finally that
            # detaches the sinks attached above
            if self.config.metrics_port is not None:
                from pathlib import Path

                from d9d_tpu.telemetry import MetricsServer

                # /debug/profile backend: one-shot captures land in the
                # telemetry dir's captures/ (falling back to the profile
                # dir when no telemetry dir is configured); None when
                # neither exists — the endpoint then answers 404
                cap_base = (
                    self.config.telemetry_dir or self.config.profile_dir
                )
                profile_backend = (
                    (lambda d: self.profiler.capture(
                        d, Path(cap_base) / "captures"
                    ))
                    if cap_base is not None
                    else None
                )
                self.metrics_server = MetricsServer(
                    tele,
                    port=self.config.metrics_port,
                    readiness=lambda: (
                        self._session_steps
                        >= self.config.introspect_warmup_steps,
                        {"session_steps": self._session_steps},
                    ),
                    health=lambda: {"step": self.stepper.step},
                    profile=profile_backend,
                ).start()
            self.data_loader = self.dataset_provider.build()
            self.events.emit(ev.EVENT_DATA_LOADER_READY, trainer=self)
            self.run = self.tracker.new_run(self.config.run_name)
            # resume BEFORE hparams: restoring the tracker run hash re-points
            # output at the original run
            self._try_resume()
            self.run.track_hparams(self.config.model_dump())
            tele_sinks.append(tele.add_sink(TrackerBridge(self.run)))
            t0 = time.perf_counter()
            session_steps = 0  # steps run by THIS call (excludes resume)
            tele_sync_t0 = t0  # last host/device sync point (log cadence)
            steps_since_sync = 0
            data_iter = iter(self.data_loader)
            use_prefetch = self.config.prefetch_batches > 0
            if (
                use_prefetch
                and hasattr(self.data_loader, "state_dict")
                and not hasattr(self.data_loader, "position")
            ):
                # a stateful loader we cannot snapshot per-fetch would get
                # checkpointed at the producer's run-ahead position — keep
                # resume exact by staying on the step path instead
                warnings.warn(
                    "data loader has state_dict() but no position(); "
                    "disabling batch prefetch to keep checkpoint resume "
                    "exact (add position()/state_dict_at() to re-enable)",
                    stacklevel=2,
                )
                use_prefetch = False
            def spawn_prefetcher(batch_iter):
                # producer thread runs fetch + prepare (+ device staging
                # when that is collective-free) prefetch_batches ahead;
                # must start AFTER _try_resume (and restart after an
                # anomaly rollback) so it iterates from the restored
                # loader position. Multi-process non-PP staging
                # device_puts onto multi-process shardings — a hidden
                # collective — so it moves to the consumer thread
                # (finish_fn); PP staging is host-only and stays in the
                # producer either way.
                if self.pp_engine is None and jax.process_count() > 1:
                    produce, finish = self.task.prepare_batch, self._stage
                else:
                    produce, finish = self._stage_batch, None
                self._prefetcher = BatchPrefetcher(
                    batch_iter,
                    produce,
                    depth=self.config.prefetch_batches,
                    position_fn=getattr(self.data_loader, "position", None),
                    finish_fn=finish,
                )

            if use_prefetch:
                spawn_prefetcher(data_iter)
            rollbacks = 0
            last_rollback_trigger: int | None = None
            with self.timeout, self.gc, self.preemption:
                while not self.stepper.finished:
                    step = self.stepper.step
                    tele.set_step(step)
                    # contiguous phase timeline: data_wait / host_dispatch /
                    # device_block / metric_flush / checkpoint / other
                    # partition the step's wall time gap-free (the JSONL
                    # timeline accounts for the whole step by construction)
                    clock = tele.phases("train", step=step)
                    try:
                        if self._prefetcher is not None:
                            raw, batch = None, next(self._prefetcher)
                        else:
                            raw = next(data_iter)
                    except StopIteration:
                        # no step ran — discard the timeline rather than
                        # emit a phantom train/step span for this step
                        clock.cancel()
                        break
                    clock.mark("data_wait")
                    self.profiler.step_begin(step)
                    with self.events.bounded(ev.EVENT_STEP, trainer=self, step=step):
                        if raw is not None:
                            batch = self._stage_batch(raw)
                        with self.events.bounded(
                            ev.EVENT_FORWARD_BACKWARD, trainer=self, step=step
                        ):
                            metrics = self._optimizer_step(batch)
                        self.metric_collector.collect(metrics)
                    step = self.stepper.advance()
                    session_steps += 1
                    self._session_steps = session_steps
                    steps_since_sync += 1
                    guard.note_step(session_steps)
                    self.profiler.step_end(step - 1)
                    self.gc.step(step)
                    clock.mark("host_dispatch")
                    if self.timeout.step_timeout_s is not None:
                        # async dispatch lets the host run ahead of the device;
                        # a heartbeat only counts once this step really finished,
                        # so a hung collective trips the watchdog within one step
                        jax.block_until_ready(metrics)
                    clock.mark("device_block")
                    self.timeout.set_periodic()
                    guard_action = "ok"
                    # _fetches_metrics: log cadence, final step, or a
                    # guard-forced checkpoint fetch (anomalous state
                    # must never be persisted unexamined; the fetch
                    # costs nothing extra — the save itself snapshots
                    # device state anyway). The SAME predicate gates the
                    # step's numerics window (_numerics_on), so every
                    # fetched step decodes its own fresh window.
                    if self._fetches_metrics(step):
                        # postprocess sees everything (it may derive scalars
                        # from vector stats, e.g. expert-load counts); only
                        # scalars survive into history/tracker — remaining
                        # vectors (e.g. per-class confusion counts) are
                        # metric-collector fodder
                        host_metrics = {
                            k: float(arr) if (arr := np.asarray(v)).ndim == 0
                            else arr
                            for k, v in metrics.items()
                        }
                        # numerics windows ride the same fetch (the
                        # np.asarray above IS their readback); peel them
                        # off before task postprocess sees the dict
                        numerics_vecs = {
                            k: host_metrics.pop(k)
                            for k in [
                                k for k in host_metrics
                                if k.startswith("numerics/")
                            ]
                        }
                        host_metrics = self.task.metrics_postprocess(host_metrics)
                        host_metrics = {
                            k: float(v)
                            for k, v in host_metrics.items()
                            if np.ndim(v) == 0
                        }
                        # the step's routing counts ride its span too
                        # (``train/step`` meta): a listener of spans reads
                        # them without the metric history
                        clock.meta.update({
                            k: v for k, v in host_metrics.items()
                            if k.startswith("moe/")
                        })
                        # and what the flash kernels' grids visit and
                        # compute, noted where the step was traced
                        # (ops/attention/pallas_flash.py _note_grid)
                        clock.meta.update({
                            k: g.value
                            for k, g in list(tele.registry.gauges.items())
                            if k.startswith("flash/")
                        })
                        host_metrics.update(
                            self.metric_collector.flush(self.run, step)
                        )
                        host_metrics["step"] = step
                        if self.numerics_monitor is not None and numerics_vecs:
                            report = self.numerics_monitor.ingest(
                                step, self._numerics_windows(numerics_vecs)
                            )
                            if report is not None:
                                host_metrics.update(report.scalars())
                        # drift policies gauge BEFORE the guard acts: a
                        # rollback this cadence must still record what
                        # was drifting when it fired
                        if self.drift_monitor is not None:
                            self.drift_monitor.observe(step, host_metrics)
                        # anomaly guard, host half: the metrics are on
                        # host anyway at this cadence, so inspecting the
                        # device guard's flags (and the loss for spikes)
                        # costs no extra sync (docs/design/resilience.md)
                        if self.anomaly_guard is not None:
                            guard_action = self.anomaly_guard.observe(
                                step, host_metrics,
                                context=(
                                    self.numerics_monitor.guard_context()
                                    if self.numerics_monitor is not None
                                    else None
                                ),
                            )
                        host_metrics["wall_s"] = time.perf_counter() - t0
                        # throughput from the batch-maths token count — live
                        # even before any telemetry sink is attached
                        host_metrics["tokens_per_s"] = (
                            session_steps * self._tokens_per_step
                            / max(host_metrics["wall_s"], 1e-9)
                        )
                        history.append(host_metrics)
                        for k, v in host_metrics.items():
                            if k != "step":
                                self.run.track_scalar(
                                    f"train/{k}", v, step=step,
                                    context={"subset": "train"},
                                )
                        logger.info("step %d: %s", step, host_metrics)
                        # live throughput + MFU gauges (roofline FLOPs
                        # inventory, telemetry/flops.py), averaged since
                        # the previous sync point: the metric fetch above
                        # just drained the device, so the window is an
                        # honest device-time average — a single step's
                        # host wall under async dispatch is not
                        now = time.perf_counter()
                        window = now - tele_sync_t0
                        if window > 0 and steps_since_sync:
                            tok_s = (
                                steps_since_sync * self._tokens_per_step
                                / window
                            )
                            tele.gauge("train/tokens_per_s").set(tok_s)
                            if self._peak_flops is not None:
                                tele.gauge("train/mfu").set(
                                    tok_s * self._flops_per_token
                                    / self._peak_flops
                                )
                        tele_sync_t0 = now
                        steps_since_sync = 0
                        self._note_flops_divergence()
                    clock.mark("metric_flush")
                    if guard_action == "ok":
                        # never persist state the guard flagged: under a
                        # spike streak the params keep updating (finite
                        # losses never trip the device freeze), so a
                        # cadence save during "warn" steps would hand a
                        # later rollback the exploded checkpoint it was
                        # meant to discard
                        self._save_checkpoint()
                    clock.mark("checkpoint")
                    clock.close()
                    tele.counter("train/tokens").add(self._tokens_per_step)
                    tele.counter("train/steps").add(1)
                    if step % flush_every == 0 or self.stepper.finished:
                        tele.flush(step)
                        last_tele_flush = step
                    if guard_action == "rollback":
                        # "consecutive" semantics: progressing PAST the
                        # previous rollback's trigger step means that
                        # fault was cleared — a later, independent fault
                        # starts a fresh streak instead of inheriting a
                        # month of unrelated history
                        if (
                            last_rollback_trigger is not None
                            and step > last_rollback_trigger
                        ):
                            rollbacks = 0
                        last_rollback_trigger = step
                        rollbacks += 1
                        tele.counter("resilience/rollbacks").add(1)
                        if rollbacks > self.config.anomaly_max_rollbacks:
                            raise RuntimeError(
                                "anomaly guard: rollback triggered "
                                f"{rollbacks} times (anomaly_max_rollbacks="
                                f"{self.config.anomaly_max_rollbacks}); the "
                                "fault survives restores — failing fast"
                            )
                        # the producer thread must not race the restore's
                        # loader-state mutation; rewinding makes its
                        # run-ahead batches moot anyway
                        if self._prefetcher is not None:
                            self._prefetcher.close()
                            self._prefetcher = None
                        # a large restore can take longer than the tight
                        # per-step watchdog window — recovery must not be
                        # hard-killed as a hang
                        self.timeout.disarm()
                        restored_step = self._restore_state()
                        self.timeout.set_periodic()
                        self._reset_guard_state()
                        if restored_step is None:
                            logger.error(
                                "anomaly rollback requested at step %d but "
                                "no restorable checkpoint exists; continuing "
                                "under skip/warn semantics (prefetched "
                                "batches in flight were dropped)", step,
                            )
                        else:
                            logger.warning(
                                "anomaly rollback: restored step %d state "
                                "(anomalies began before step %d)",
                                restored_step, step,
                            )
                            data_iter = iter(self.data_loader)
                        if use_prefetch:
                            spawn_prefetcher(data_iter)
                        continue
                    if self.preemption.triggered:
                        # step boundary reached with the flag set: write
                        # the emergency checkpoint (synchronous — durable
                        # before the raise) and exit with the documented
                        # code; resume picks this checkpoint up unchanged
                        logger.warning(
                            "preemption: emergency checkpoint at step %d, "
                            "exiting with code %d",
                            step, self.config.preemption_exit_code,
                        )
                        tele.counter("resilience/preemptions").add(1)
                        # the emergency save's durability barrier can
                        # outlast the per-step watchdog window; exiting
                        # with the watchdog code mid-save would waste the
                        # preemption grace period
                        self.timeout.disarm()
                        self._save_checkpoint(last=True)
                        raise TrainingPreempted(
                            self.config.preemption_exit_code, step=step
                        )
                self.timeout.disarm()  # final durable save, same reason
                self._save_checkpoint(last=True)
            self.events.emit(ev.EVENT_TRAIN_FINISHED, trainer=self)
        finally:
            # release the profiler trace and flush/close the tracker run even
            # when a step raises (a dangling trace breaks the next train())
            if self._prefetcher is not None:
                self._prefetcher.close()
                self._prefetcher = None
            if self.metrics_server is not None:
                # the endpoint serves THIS session; a crashed step must
                # not leave the port bound (the next train() rebinds it)
                self.metrics_server.close()
                self.metrics_server = None
            self.profiler.close()
            # final telemetry flush (short runs still get one flush event,
            # and early exits flush the tail steps) unless the loop already
            # flushed at this exact step; then detach this run's sinks —
            # the registry itself stays live
            try:
                if last_tele_flush != self.stepper.step:
                    tele.flush(self.stepper.step)
            finally:
                for sink in tele_sinks:
                    tele.remove_sink(sink)
                tele.set_step(None)
            if self.run is not None:
                self.run.close()
            if self.checkpointer is not None:
                # a step raising must not strand an in-flight async save as
                # an unfinalized tmp dir — the crashed job's restart resumes
                # from this checkpoint (the old sync default was durable at
                # every save; keep that property on the exception path)
                self.checkpointer.wait_until_finished()
        return history

    def close(self) -> None:
        """Release held resources (checkpoint manager IO threads)."""
        if self.checkpointer is not None:
            self.checkpointer.close()

    # -- sleep/wake (reference component/train_sleeper.py:22) ----------

    def sleep(self, tags: set[SleepTag] = frozenset(SleepTag)) -> None:
        """Offload model/optimizer state to host, freeing device HBM."""
        with self.events.bounded(ev.EVENT_SLEEP, trainer=self):
            if SleepTag.MODEL in tags and SleepTag.MODEL not in self._sleep_store:
                if self.pp_engine is not None:
                    store = {}
                    for s, rt in self.pp_engine.stages.items():
                        store[s] = offload_tree(rt.params)
                        rt.params = None
                    self._sleep_store[SleepTag.MODEL] = store
                else:
                    self._sleep_store[SleepTag.MODEL] = offload_tree(self.params)
                    self.params = None
            if (
                SleepTag.OPTIMIZER in tags
                and SleepTag.OPTIMIZER not in self._sleep_store
            ):
                if self.pp_engine is not None:
                    store = {
                        s: offload_tree(v)
                        for s, v in self.pp_engine.opt_states.items()
                    }
                    self.pp_engine.opt_states = None
                    self._sleep_store[SleepTag.OPTIMIZER] = store
                else:
                    self._sleep_store[SleepTag.OPTIMIZER] = offload_tree(
                        self.opt_state
                    )
                    self.opt_state = None

    def wake(self) -> None:
        """Restore everything offloaded by :meth:`sleep`."""
        with self.events.bounded(ev.EVENT_WAKE, trainer=self):
            if SleepTag.MODEL in self._sleep_store:
                stored = self._sleep_store.pop(SleepTag.MODEL)
                if self.pp_engine is not None:
                    for s, (host, sh) in stored.items():
                        self.pp_engine.stages[s].params = onload_tree(host, sh)
                else:
                    self.params = onload_tree(*stored)
            if SleepTag.OPTIMIZER in self._sleep_store:
                stored = self._sleep_store.pop(SleepTag.OPTIMIZER)
                if self.pp_engine is not None:
                    self.pp_engine.opt_states = {
                        s: onload_tree(host, sh)
                        for s, (host, sh) in stored.items()
                    }
                else:
                    self.opt_state = onload_tree(*stored)

    # -- export (reference component/model_stage_exporter.py:11) -------

    def export(self, out_dir, mapper=None, shard_size_gb: float = 4.0) -> None:
        """Write the (merged) model weights as sharded safetensors via the
        model_state mapper system."""
        from d9d_tpu.model_state.io.module import save_params

        save_params(
            out_dir, self.merged_params(), mapper=mapper,
            shard_size_gb=shard_size_gb,
        )

    def merged_params(self) -> PyTree:
        """Full parameter tree for export: identity without PEFT, adapters
        folded into the frozen base with it; stage trees merged under PP."""
        if self.pp_engine is not None:
            return self.pp_engine.merged_params()
        if self.peft_method is None:
            return self.params
        if self._merge_fn is None:
            # d9d-lint: disable=D9D001 — one-shot export-time PEFT merge
            self._merge_fn = jax.jit(self.peft_method.merge)
        return self._merge_fn(self.base_params, self.params)

    # convenience for tests / evaluation -------------------------------

    def loss_on_batch(self, raw_batch: PyTree) -> float:
        if self.pp_engine is not None:
            # forward-only pipeline program over the same stages
            return float(self.pp_engine.eval_loss(self._stage_batch(raw_batch)))
        if self._eval_fn is None:
            self._eval_fn = build_eval_step(
                module=self.module,
                task=self.task,
                num_microbatches=self.batch_maths.num_microbatches,
            )
        batch = self._stage_batch(raw_batch)
        rng = jax.random.fold_in(self.step_rng, 10**9)
        return float(self._eval_fn(self.params, batch, rng))
