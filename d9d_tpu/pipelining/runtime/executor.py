"""Pipeline schedule executor: interprets a compiled action program.

Reference: d9d/pipelining/runtime/executor.py:16 (PipelineScheduleExecutor)
— a VM iterating ``program[rank]`` per process, with NCCL P2P at Send/Recv
actions. Under JAX's single controller one executor interprets the *merged*
program (the dependency-proven global linearization from
``validate_program``): every rank's compute is dispatched from one Python
loop, device-to-device transfers happen at Send actions via
``jax.device_put`` onto the consuming stage's sharding, and XLA's async
dispatch provides the overlap the reference gets from per-process
execution — the host races ahead enqueuing work for all stage device
groups while earlier computations are still running.

Because every action costs host dispatch time, the interpretation loop is
pre-compiled at construction: the program is flattened once into a list of
(bound handler, action, trace label) triples — no isinstance chains or
label formatting on the step path — microbatch kwargs are staged onto
stage submeshes through a bounded sliding window ordered by the
schedule's first use (async puts that overlap compute instead of
splitting dispatch gaps mid-schedule, refilled as entries are consumed,
so total staged residency stays O(window + in-flight) rather than
O(stages x microbatches)), and per-microbatch loss statistics are summed
in ONE fused jit at step end instead of one tiny dispatch per microbatch. Each action dispatch is wrapped in a gated
``TraceAnnotation`` (core/tracing.py) mirroring the reference's
``record_function`` per action (runtime/executor.py:96).

Buffer lifecycle (reference computations.py:29,121): the executor stores
per (stage, microbatch) only the input carry (the remat residual) and the
output cotangent between its producing backward and consuming
weight-backward; entries are freed at last use, which bounds pipeline
memory exactly like the reference's per-microbatch caches.
"""

import dataclasses
import time
from typing import Any

import jax

from d9d_tpu.core.tracing import annotate
from d9d_tpu.telemetry import get_telemetry, tracked_jit
from d9d_tpu.core.types import PyTree
from d9d_tpu.pipelining.program.actions import (
    Action,
    BackwardFull,
    BackwardInput,
    BackwardRecv,
    BackwardSend,
    BackwardWeight,
    Compose,
    ForwardCompute,
    ForwardRecv,
    ForwardSend,
    PipelineProgram,
)
from d9d_tpu.pipelining.program.validate import validate_program
from d9d_tpu.pipelining.runtime.stage import PipelineStageRuntime
from d9d_tpu.pipelining.runtime.transfer import put_compat

__all__ = ["PipelineExecutionResult", "PipelineScheduleExecutor"]


@dataclasses.dataclass
class PipelineExecutionResult:
    """Per-step outcome: unscaled per-stage grad sums + loss statistics."""

    grads: dict[int, PyTree] | None  # stage id → Σ_mb grads (unscaled)
    loss_sum: Any
    weight_sum: Any
    metrics: dict[str, Any]
    outputs: list[PyTree] | None = None  # forward-only: last-stage aux per mb
    # fused runtime only: stage id → pp_numerics/s{S} stats vector (NaN
    # off cadence — the traced flag flips a cond branch, not the program)
    numerics: dict[int, Any] | None = None


class _StepState:
    """Per-step mutable buffers (fresh per ``step`` call)."""

    __slots__ = (
        "carries", "states", "inputs", "kwargs_d", "kwargs_h", "kwargs_next",
        "kwargs_staged", "cots", "grad_in", "fwd_out", "grads", "aux",
        "outputs", "weight_done", "saved",
    )

    def __init__(self, num_microbatches: int):
        self.carries: dict[int, PyTree] = {}  # mb → first-stage carry
        self.states: dict[int, PyTree] = {}  # mb → last-stage task state
        # per-(stage, mb) device buffers
        self.inputs: dict[tuple[int, int], PyTree] = {}  # carry in (residual)
        self.kwargs_d: dict[tuple[int, int], PyTree] = {}  # kwargs on submesh
        self.kwargs_h: list[PyTree] = []  # mb → host kwargs tree
        self.kwargs_next: int = 0  # index into the first-use staging order
        self.kwargs_staged: set[tuple[int, int]] = set()  # ever staged
        self.cots: dict[tuple[int, int], PyTree] = {}  # cot wrt stage output
        self.grad_in: dict[tuple[int, int], PyTree] = {}  # dI awaiting send
        self.fwd_out: dict[tuple[int, int], PyTree] = {}  # out awaiting send
        self.grads: dict[int, PyTree] = {}
        self.aux: list[Any] = []  # (loss, weight, metrics) per microbatch
        self.outputs: list[PyTree | None] = [None] * num_microbatches
        # (stage, mb) whose weight grads were already produced at the I slot
        self.weight_done: set[tuple[int, int]] = set()
        # cache_acts: (stage, mb) → backward residuals awaiting the W slot
        self.saved: dict[tuple[int, int], Any] = {}


class PipelineScheduleExecutor:
    """Executes one train/eval step per call.

    ``stages`` maps *global stage id* → runtime. The executor owns no
    parameters — it reads ``stage.params`` at each action, so optimizer
    updates between steps are picked up automatically.
    """

    def __init__(
        self,
        *,
        stages: dict[int, PipelineStageRuntime],
        program: PipelineProgram,
        stage_owner: dict[int, int],
        num_microbatches: int,
        train: bool = True,
    ):
        self.stages = stages
        self.num_stages = len(stages)
        self.num_microbatches = num_microbatches
        self.stage_owner = stage_owner
        self.train = train
        sim = validate_program(
            program,
            num_stages=self.num_stages,
            num_microbatches=num_microbatches,
            stage_owner=stage_owner,
            train=train,
        )
        self.order: tuple[tuple[int, Action], ...] = sim.order
        self._last = self.stages[self.num_stages - 1]
        self._sum_aux = None  # built lazily (jit over the aux list pytree)
        self._plan = self._compile_plan()
        self._tele = get_telemetry()

    # ------------------------------------------------------------------
    # plan compilation: one (handler, action, label) triple per action,
    # Compose flattened — the step loop does zero type dispatch

    _HANDLERS = {
        ForwardCompute: "_act_forward",
        ForwardSend: "_act_forward_send",
        BackwardFull: "_act_backward_full",
        BackwardInput: "_act_backward_input",
        BackwardWeight: "_act_backward_weight",
        BackwardSend: "_act_backward_send",
    }

    _LABELS = {
        ForwardCompute: "fwd",
        ForwardSend: "fwd_send",
        BackwardFull: "bwd",
        BackwardInput: "bwd_dI",
        BackwardWeight: "bwd_dW",
        BackwardSend: "bwd_send",
    }

    def _compile_plan(self):
        plan = []

        def add(action: Action) -> None:
            if isinstance(action, Compose):
                for member in action.actions:
                    add(member)
                return
            if isinstance(action, (ForwardRecv, BackwardRecv)):
                return  # transfers already target the consumer at the Send
            name = self._HANDLERS.get(type(action))
            if name is None:  # pragma: no cover
                raise TypeError(f"unknown action {action!r}")
            label = (
                f"pp.{self._LABELS[type(action)]}"
                f".s{action.stage}.mb{action.microbatch}"
            )
            plan.append((getattr(self, name), action, label))

        for _rank, action in self.order:
            add(action)
        # kwargs staging order: (stage, mb) pairs by FIRST use in the plan
        # (sends never read kwargs) — the sliding window stages whatever
        # the schedule needs soonest, regardless of stage
        seen: set[tuple[int, int]] = set()
        first_use: list[tuple[int, int]] = []
        for _h, action, _l in plan:
            if isinstance(action, (ForwardSend, BackwardSend)):
                continue
            key = (action.stage, action.microbatch)
            if key not in seen:
                seen.add(key)
                first_use.append(key)
        self._kwargs_first_use = tuple(first_use)
        return tuple(plan)

    # ------------------------------------------------------------------

    @staticmethod
    def _put(tree: PyTree, sharding) -> PyTree:
        return put_compat(tree, sharding)

    def step(self, microbatches: list[PyTree]) -> PipelineExecutionResult:
        """Run the program over ``microbatches`` (list of host/device pytrees)."""
        if len(microbatches) != self.num_microbatches:
            raise ValueError(
                f"program compiled for {self.num_microbatches} microbatches, "
                f"got {len(microbatches)}"
            )
        first = self.stages[0]
        last = self._last

        t_step0 = time.perf_counter()
        # per-stage busy seconds, host-attributed: time this single
        # controller spends dispatching each stage's actions. Under XLA
        # async dispatch this measures the dispatch loop (the quantity the
        # trace-annotation tables attribute); the residual
        # ``step − busy`` is that stage's per-step bubble from the host's
        # point of view — the observable MPMD-pipeline schedule tuning
        # actually optimizes (docs/design/observability.md).
        busy = [0.0] * self.num_stages

        st = _StepState(self.num_microbatches)
        with annotate("pp.stage_inputs"):
            for mb, micro in enumerate(microbatches):
                carry, kw, state = first.task.split_microbatch(micro)
                st.carries[mb] = self._put(carry, first.carry_sharding)
                st.kwargs_h.append(kw)
                st.states[mb] = self._put(state, last.state_sharding)
            # pre-stage a bounded window of kwargs in the schedule's
            # first-use order: the puts are async and overlap the first
            # computes instead of splitting dispatch gaps mid-schedule,
            # while TOTAL staged residency stays O(window + in-flight)
            # instead of O(stages x microbatches) — each consumed entry
            # refills the window (_drop_kwargs)
            window = min(
                len(self._kwargs_first_use), 2 * self.num_stages + 2
            )
            for key in self._kwargs_first_use[:window]:
                self._stage_kwargs(st, *key)
            st.kwargs_next = window

        for handler, action, label in self._plan:
            with annotate(label):
                t_act = time.perf_counter()
                handler(st, action)
                busy[action.stage] += time.perf_counter() - t_act

        loss_sum = weight_sum = None
        metrics_sum: dict[str, Any] = {}
        if st.aux:
            # one fused jit sums every microbatch's (loss, weight, metrics)
            # on the last stage's devices — replaces per-microbatch scalar
            # dispatches on the action path. Fusable only when every
            # microbatch produced the same aux structure; a task emitting
            # different metric keys per microbatch falls back to the
            # key-unioning merge.
            with annotate("pp.loss_sum"), last._scoped():
                structures = {jax.tree.structure(a) for a in st.aux}
                if len(structures) == 1:
                    if self._sum_aux is None:
                        self._sum_aux = tracked_jit(
                            lambda auxes: jax.tree.reduce(
                                lambda a, b: jax.tree.map(
                                    lambda x, y: x + y, a, b
                                ),
                                auxes,
                                is_leaf=lambda t: isinstance(t, tuple)
                                and len(t) == 3,
                            ),
                            name="pp/loss_sum",
                        )
                    loss_sum, weight_sum, metrics_sum = self._sum_aux(st.aux)
                else:
                    for loss, weight, metrics in st.aux:
                        loss_sum = loss if loss_sum is None else loss_sum + loss
                        weight_sum = (
                            weight if weight_sum is None
                            else weight_sum + weight
                        )
                        for k, v in metrics.items():
                            metrics_sum[k] = (
                                v if k not in metrics_sum
                                else metrics_sum[k] + v
                            )

        total = time.perf_counter() - t_step0
        tele = self._tele
        tele.registry.record_span(
            "pp/step", t_step0, total,
            meta={"stages": self.num_stages, "train": self.train},
        )
        for s in range(self.num_stages):
            bubble = max(total - busy[s], 0.0)
            tele.gauge(f"pp/s{s}/busy_s").set(busy[s])
            tele.gauge(f"pp/s{s}/bubble_s").set(bubble)
            tele.gauge(f"pp/s{s}/bubble_frac").set(
                bubble / total if total > 0 else 0.0
            )
            tele.counter(f"pp/s{s}/busy_total_s").add(busy[s])
            tele.counter(f"pp/s{s}/bubble_total_s").add(bubble)

        return PipelineExecutionResult(
            grads=st.grads if self.train else None,
            loss_sum=loss_sum,
            weight_sum=weight_sum,
            metrics=metrics_sum,
            outputs=st.outputs if not self.train else None,
        )

    # ------------------------------------------------------------------
    # shared helpers

    def _stage_kwargs(self, st: _StepState, s: int, mb: int) -> None:
        st.kwargs_staged.add((s, mb))
        st.kwargs_d[(s, mb)] = self._put(
            st.kwargs_h[mb], self.stages[s].kwargs_sharding
        )

    def _kwargs(self, st: _StepState, s: int, mb: int) -> PyTree:
        kw = st.kwargs_d.get((s, mb))
        if kw is None:  # outside the pre-staged window: stage on demand
            self._stage_kwargs(st, s, mb)
            kw = st.kwargs_d[(s, mb)]
        return kw

    def _drop_kwargs(self, st: _StepState, s: int, mb: int) -> None:
        """Free a consumed kwargs buffer and refill the staging window
        with the next first-use entry not already staged."""
        st.kwargs_d.pop((s, mb), None)
        order = self._kwargs_first_use
        nxt = st.kwargs_next
        while nxt < len(order) and order[nxt] in st.kwargs_staged:
            nxt += 1
        if nxt < len(order):
            self._stage_kwargs(st, *order[nxt])
            nxt += 1
        st.kwargs_next = nxt

    def _add_grads(self, st: _StepState, s: int, gp: PyTree) -> None:
        stage = self.stages[s]
        if s not in st.grads:
            # d9d-lint: disable=D9D008 — legacy parity oracle: the per-action interpreter stays one release as the fused runtime's bit-exactness reference
            st.grads[s] = stage.cast_grads(gp)
        else:
            # d9d-lint: disable=D9D008 — legacy parity oracle (see cast_grads above)
            st.grads[s] = stage.accumulate(st.grads[s], gp)

    def _route_input_grad(
        self, st: _StepState, s: int, mb: int, gc: PyTree
    ) -> None:
        """Store dI for the downstream (stage-1) consumer."""
        if s == 0:
            return
        if self.stage_owner[s - 1] == self.stage_owner[s]:
            st.cots[(s - 1, mb)] = gc  # local edge: no send action exists
        else:
            st.grad_in[(s, mb)] = gc  # cross-rank: BackwardSend moves it

    # ------------------------------------------------------------------
    # action handlers (one per action type, bound into the plan)

    def _act_forward(self, st: _StepState, action: Action) -> None:
        s, mb = action.stage, action.microbatch
        stage = self.stages[s]
        if s == 0:
            st.inputs[(0, mb)] = st.carries.pop(mb)
        elif (s, mb) not in st.inputs:
            # same-rank edge: pull directly from the producing stage
            st.inputs[(s, mb)] = st.fwd_out.pop((s - 1, mb))
        carry = st.inputs[(s, mb)]
        kw = self._kwargs(st, s, mb)
        if stage.info.is_last:
            if not self.train:
                if stage.has_output_fn:
                    st.outputs[mb] = stage.forward_outputs(
                        carry, kw, st.states[mb]
                    )
                else:
                    # d9d-lint: disable=D9D008 — legacy parity oracle (one dispatch per action is this interpreter's contract)
                    aux = stage.forward_loss(carry, kw, st.states[mb])
                    st.aux.append(aux)
                    st.outputs[mb] = aux
                st.inputs.pop((s, mb), None)
                self._drop_kwargs(st, s, mb)  # eval: forward is last use
            # train: forward is folded into the backward's
            # value_and_grad (remat), nothing to run here
        else:
            # d9d-lint: disable=D9D008 — legacy parity oracle (one dispatch per action is this interpreter's contract)
            st.fwd_out[(s, mb)] = stage.forward(carry, kw)
            if not self.train:
                st.inputs.pop((s, mb), None)
                self._drop_kwargs(st, s, mb)  # eval: forward is last use

    def _act_forward_send(self, st: _StepState, action: Action) -> None:
        s, mb = action.stage, action.microbatch
        out = st.fwd_out.pop((s, mb))
        nxt = self.stages[s + 1]
        st.inputs[(s + 1, mb)] = self._put(out, nxt.carry_sharding)

    def _act_backward_full(self, st: _StepState, action: Action) -> None:
        s, mb = action.stage, action.microbatch
        stage = self.stages[s]
        cot = None if stage.info.is_last else st.cots.pop((s, mb))
        state = st.states.get(mb) if stage.info.is_last else None
        # d9d-lint: disable=D9D008 — legacy parity oracle (one dispatch per action is this interpreter's contract)
        gp, gc, aux = stage.backward_full(
            st.inputs.pop((s, mb)), self._kwargs(st, s, mb), cot, state
        )
        self._drop_kwargs(st, s, mb)
        if aux is not None:
            st.aux.append(aux)
        self._add_grads(st, s, gp)
        self._route_input_grad(st, s, mb, gc)

    def _act_backward_input(self, st: _StepState, action: Action) -> None:
        s, mb = action.stage, action.microbatch
        stage = self.stages[s]
        if stage.residual_policy == "cache_acts":
            # true zero-bubble split: dI + residual capture now, dW at the
            # deferred W slot from the captured residuals
            cot = None if stage.info.is_last else st.cots.pop((s, mb), None)
            state = st.states.get(mb) if stage.info.is_last else None
            # d9d-lint: disable=D9D008 — legacy parity oracle (one dispatch per action is this interpreter's contract)
            gc, aux, saved = stage.backward_input_acts(
                st.inputs.pop((s, mb)), self._kwargs(st, s, mb), cot, state
            )
            self._drop_kwargs(st, s, mb)  # residuals replace kwargs reuse
            st.saved[(s, mb)] = saved
            if aux is not None:
                st.aux.append(aux)
            if gc is not None:
                self._route_input_grad(st, s, mb, gc)
            return
        if stage.residual_policy == "cache_full":
            # fused backward at the I slot: weight grads accumulate
            # now, the deferred BackwardWeight becomes a no-op
            cot = None if stage.info.is_last else st.cots.pop((s, mb), None)
            state = st.states.get(mb) if stage.info.is_last else None
            # d9d-lint: disable=D9D008 — legacy parity oracle (one dispatch per action is this interpreter's contract)
            gp, gc, aux = stage.backward_full(
                st.inputs.pop((s, mb)), self._kwargs(st, s, mb), cot, state
            )
            self._drop_kwargs(st, s, mb)
            if aux is not None:
                st.aux.append(aux)
            self._add_grads(st, s, gp)
            self._route_input_grad(st, s, mb, gc)
            st.weight_done.add((s, mb))
            return
        cot = None if stage.info.is_last else st.cots.get((s, mb))
        state = st.states.get(mb) if stage.info.is_last else None
        # d9d-lint: disable=D9D008 — legacy parity oracle (one dispatch per action is this interpreter's contract)
        gc, aux = stage.backward_input(
            st.inputs[(s, mb)], self._kwargs(st, s, mb), cot, state
        )
        if aux is not None:
            st.aux.append(aux)
        if gc is not None:
            self._route_input_grad(st, s, mb, gc)
        # inputs/cot stay alive for the deferred weight backward

    def _act_backward_weight(self, st: _StepState, action: Action) -> None:
        s, mb = action.stage, action.microbatch
        stage = self.stages[s]
        if stage.residual_policy == "cache_acts":
            # d9d-lint: disable=D9D008 — legacy parity oracle (one dispatch per action is this interpreter's contract)
            gp = stage.backward_weight_acts(st.saved.pop((s, mb)))
            self._add_grads(st, s, gp)
            return
        if (s, mb) in st.weight_done:
            st.weight_done.discard((s, mb))
            return
        kw = self._kwargs(st, s, mb)
        cot = None if stage.info.is_last else st.cots.pop((s, mb), None)
        state = st.states.get(mb) if stage.info.is_last else None
        # d9d-lint: disable=D9D008 — legacy parity oracle (one dispatch per action is this interpreter's contract)
        gp = stage.backward_weight(st.inputs.pop((s, mb)), kw, cot, state)
        self._drop_kwargs(st, s, mb)
        self._add_grads(st, s, gp)

    def _act_backward_send(self, st: _StepState, action: Action) -> None:
        s, mb = action.stage, action.microbatch
        g = st.grad_in.pop((s, mb))
        prev = self.stages[s - 1]
        st.cots[(s - 1, mb)] = self._put(g, prev.carry_sharding)
