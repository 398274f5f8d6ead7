"""Per-stage compiled compute: forward + full/split backward.

Reference: d9d/pipelining/infra/stage/stage.py:13 (PipelineStage) and
splitgrad.py (autograd-graph surgery for the zero-bubble dI/dW split).

TPU redesign: there is no autograd graph to mutate. Instead each stage gets
four jitted pure functions — forward, fused backward, input-only backward,
weight-only backward — derived from the stage's forward with ``jax.vjp``.
Residual policy is *rematerialization*: the executor stores only the
stage's small input carry per in-flight microbatch; every backward variant
recomputes the stage forward inside its own jit (XLA fuses it with the
cotangent math). That is the memory-optimal choice for deep pipelines on
TPU (the reference reaches the same point via activation checkpointing),
costs one extra forward per backward direction, and makes the dI/dW split
exact rather than approximated: input-backward computes only the carry
cotangent chain, weight-backward only the parameter grads, matching the
compute split that zero-bubble schedules rely on (splitgrad.py:220,290).
"""

import contextlib
import dataclasses
from typing import Any, Protocol

import flax.linen as nn
import jax
import jax.numpy as jnp

from d9d_tpu.core import compat
from d9d_tpu.core.types import PyTree
from d9d_tpu.pipelining.stage_info import PipelineStageInfo
from d9d_tpu.telemetry import tracked_jit

__all__ = ["PipelineStageRuntime", "StageTask"]


class StageTask(Protocol):
    """How the executor drives one stage of a model for a task.

    Split of responsibilities mirroring the reference's TrainTask +
    LossComputer pair (loop/control/task.py:180,
    component/pipeline_result_processing.py:18): the task defines what a
    microbatch looks like and how the last stage turns activations into a
    weighted loss; the engine owns everything else.
    """

    def split_microbatch(
        self, microbatch: PyTree
    ) -> tuple[PyTree, PyTree, PyTree]:
        """→ (first_stage_carry, per_stage_kwargs, last_stage_state)."""
        ...

    def stage_forward(
        self, module: nn.Module, params: PyTree, carry: PyTree, kwargs: PyTree
    ) -> PyTree:
        """Non-last stage: carry in → carry out."""
        ...

    def last_stage_loss(
        self,
        module: nn.Module,
        params: PyTree,
        carry: PyTree,
        kwargs: PyTree,
        state: PyTree,
    ) -> tuple[jax.Array, jax.Array, dict[str, jax.Array]]:
        """Last stage: → (loss_sum, weight, metrics)."""
        ...

    # Optional — forward-only programs (inference): when a task defines
    # ``last_stage_outputs(module, params, carry, kwargs, state) -> PyTree``
    # the eval executor returns its value per microbatch instead of loss
    # statistics (reference InferenceProcessor,
    # component/pipeline_result_processing.py:79).


def _tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(lambda x, y: x + y.astype(x.dtype), a, b)


@dataclasses.dataclass
class PipelineStageRuntime:
    """One pipeline stage: module + params + the four compiled functions.

    ``carry_sharding``/``state_sharding`` describe where this stage's
    activations and task state live (its pp submesh); the executor uses
    them as transfer targets.
    """

    info: PipelineStageInfo
    module: nn.Module
    params: PyTree
    task: StageTask
    carry_sharding: Any | None = None
    kwargs_sharding: Any | None = None
    state_sharding: Any | None = None
    grad_dtype: Any | None = None
    # the stage's submesh; scoped ambient during compute so an outer full
    # mesh (jax.set_mesh in MeshParameters.build) never conflicts with this
    # stage's device group, and shard_map-based modules resolve it
    mesh: Any | None = None
    # How zero-bubble schedules pay for the dI/dW split:
    # - "remat": dI and dW are independent vjps, each recomputing the stage
    #   forward (2 extra forwards per microbatch vs 1F1B's one). Memory-
    #   minimal: only the input carry persists between I and W actions.
    # - "cache_full": the BackwardInput action runs the fused backward once
    #   (one forward recompute, same FLOPs as 1F1B) and the weight grads
    #   accumulate immediately; the deferred BackwardWeight action becomes
    #   a no-op. Trades the zero-bubble property (the dW slot no longer
    #   holds compute to fill the bubble) for one forward less per mb.
    # - "cache_acts": the TRUE zero-bubble split (arXiv 2401.10241
    #   semantics, r4): the I slot runs one forward + ONLY the carry-
    #   cotangent half of the backward and hands the backward's residuals
    #   to the deferred W slot, which computes the weight grads from them —
    #   same total FLOPs as a fused backward, with dW genuinely off the
    #   inter-stage critical path. Implemented by closure-converting the
    #   stage VJP into a pure jaxpr + residual arrays: the I-slot jit keeps
    #   forward+dI (XLA dead-code-eliminates the dW half), the W-slot jit
    #   keeps dW alone. Costs residual memory between the I and W actions
    #   (what the ZB schedules' memory model budgets for).
    # The better default is workload-dependent — tools/bench_pp.py measures
    # all three; none measured on the chip yet (ROADMAP R-P,
    # `zb-residual-policies`).
    residual_policy: str = "remat"

    def __post_init__(self) -> None:
        # device-side attribution: every op a stage function emits carries a
        # "pp_s{k}/<phase>" named-scope prefix in captured traces (reference
        # wraps the same regions in record_function — executor.py:96)
        def scoped(name, fn):
            sid = self.info.stage_index

            def wrapped(*args):
                with jax.named_scope(f"pp_s{sid}/{name}"):
                    return fn(*args)

            return wrapped

        # tracked_jit: each per-action executable gets compile-span /
        # recompile-guard / HBM-inventory accounting under its stage-
        # scoped name (telemetry/introspect.py); dispatch count per
        # action is unchanged
        sid = self.info.stage_index

        def tjit(label, fn, **kw):
            return tracked_jit(fn, name=f"pp_s{sid}/{label}", **kw)

        self._fwd = tjit("fwd", scoped("fwd", self._fwd_impl))
        self._fwd_loss = tjit("fwd_loss", scoped("fwd_loss", self._fwd_loss_impl))
        self._fwd_out = tjit("fwd_out", scoped("fwd_out", self._fwd_out_impl))
        self._bwd_full = tjit("bwd", scoped("bwd", self._bwd_full_impl))
        self._bwd_input = tjit("bwd_dI", scoped("bwd_dI", self._bwd_input_impl))
        self._bwd_weight = tjit("bwd_dW", scoped("bwd_dW", self._bwd_weight_impl))
        self._acc = tjit(
            "grad_acc", scoped("grad_acc", _tree_add), donate_argnums=(0,)
        )
        self._cast = tjit(
            "cast_grads",
            lambda g: jax.tree.map(lambda x: x.astype(self.grad_dtype), g),
        )
        if self.residual_policy not in ("remat", "cache_full", "cache_acts"):
            raise ValueError(
                f"unknown residual_policy {self.residual_policy!r}"
            )
        # cache_acts split: VJP jaxprs recorded while tracing the I-slot
        # jit, keyed by residual signature, replayed by the W-slot jit (the
        # executor always runs I before W for a (stage, mb), so the first
        # W trace for any signature finds its record)
        self._acts_records = {}
        self._bwd_input_acts = tjit(
            "bwd_dI_acts", scoped("bwd_dI_acts", self._bwd_input_acts_impl)
        )
        self._bwd_weight_acts = tjit(
            "bwd_dW_acts", scoped("bwd_dW_acts", self._bwd_weight_acts_impl)
        )

    # ---- forward ---------------------------------------------------------

    def _fwd_impl(self, params, carry, kwargs):
        return self.task.stage_forward(self.module, params, carry, kwargs)

    def _fwd_loss_impl(self, params, carry, kwargs, state):
        return self.task.last_stage_loss(self.module, params, carry, kwargs, state)

    def _fwd_out_impl(self, params, carry, kwargs, state):
        return self.task.last_stage_outputs(
            self.module, params, carry, kwargs, state
        )

    @property
    def has_output_fn(self) -> bool:
        return getattr(self.task, "last_stage_outputs", None) is not None

    def _scoped(self):
        return compat.set_mesh(self.mesh) if self.mesh is not None else (
            contextlib.nullcontext()
        )

    def forward(self, carry, kwargs):
        with self._scoped():
            return self._fwd(self.params, carry, kwargs)

    def forward_loss(self, carry, kwargs, state):
        """Last stage forward → (loss_sum, weight, metrics)."""
        with self._scoped():
            return self._fwd_loss(self.params, carry, kwargs, state)

    def forward_outputs(self, carry, kwargs, state):
        """Last stage forward → task outputs (inference programs)."""
        with self._scoped():
            return self._fwd_out(self.params, carry, kwargs, state)

    # ---- backward (remat: recompute fwd inside each jit) ----------------

    def _loss_of(self, params, carry, kwargs, state):
        loss, weight, metrics = self.task.last_stage_loss(
            self.module, params, carry, kwargs, state
        )
        return loss, (weight, metrics)

    def _bwd_full_impl(self, params, carry, kwargs, cot, state):
        """→ (grad_params, grad_carry, aux). ``cot``/``state`` exclusive."""
        if self.info.is_last:
            grad_fn = jax.value_and_grad(
                self._loss_of, argnums=(0, 1), has_aux=True
            )
            (loss, (weight, metrics)), (gp, gc) = grad_fn(
                params, carry, kwargs, state
            )
            return gp, gc, (loss, weight, metrics)
        _, vjp = jax.vjp(
            lambda p, c: self.task.stage_forward(self.module, p, c, kwargs),
            params,
            carry,
        )
        gp, gc = vjp(cot)
        return gp, gc, None

    def _bwd_input_impl(self, params, carry, kwargs, cot, state):
        """Input-only backward → (grad_carry, aux)."""
        if self.info.is_last:
            if self.info.is_first:
                # single-stage pipeline: tokens are not differentiable, but
                # the loss statistics must still surface from this action
                loss, (weight, metrics) = self._loss_of(
                    params, carry, kwargs, state
                )
                return None, (loss, weight, metrics)
            grad_fn = jax.value_and_grad(
                self._loss_of, argnums=1, has_aux=True
            )
            (loss, (weight, metrics)), gc = grad_fn(params, carry, kwargs, state)
            return gc, (loss, weight, metrics)
        if self.info.is_first:
            # tokens are not differentiable; dI is a structural no-op
            return None, None
        _, vjp = jax.vjp(
            lambda c: self.task.stage_forward(self.module, params, c, kwargs),
            carry,
        )
        (gc,) = vjp(cot)
        return gc, None

    def _bwd_weight_impl(self, params, carry, kwargs, cot, state):
        """Weight-only backward → grad_params."""
        if self.info.is_last:
            gp = jax.grad(
                lambda p: self._loss_of(p, carry, kwargs, state)[0]
            )(params)
            return gp
        _, vjp = jax.vjp(
            lambda p: self.task.stage_forward(self.module, p, carry, kwargs),
            params,
        )
        (gp,) = vjp(cot)
        return gp

    # ---- backward (cache_acts: residual-cached true zero-bubble split) --

    @staticmethod
    def _acts_sig(saved):
        """Shape/dtype signature of a residual payload — the key tying a
        W-slot evaluation to the jaxpr its I slot traced (a retrace for
        different shapes, e.g. a ragged last microbatch, records its own
        entry instead of clobbering shared state)."""
        consts, cot = saved
        return (
            tuple((tuple(x.shape), str(x.dtype)) for x in consts),
            tuple(
                (tuple(x.shape), str(x.dtype)) for x in jax.tree.leaves(cot)
            ),
        )

    def _record_acts(self, vjp, cot, params):
        """Trace the stage VJP once and file it under its residual
        signature. Residual consts that are literally the parameter arrays
        (the dI half's weight references) are NOT carried in ``saved`` —
        the W slot rebuilds them from ``self.params``, so the payload holds
        activations only, not a duplicate copy of the stage weights per
        in-flight microbatch."""
        closed, out_shape = jax.make_jaxpr(vjp, return_shape=True)(cot)
        param_ids = {
            id(leaf): i for i, leaf in enumerate(jax.tree.leaves(params))
        }
        param_slots = {}  # const position → param leaf index
        saved_consts = []
        for pos, const in enumerate(closed.consts):
            j = param_ids.get(id(const))
            if j is None:
                saved_consts.append(const)
            else:
                param_slots[pos] = j
        record = (
            closed.jaxpr,
            jax.tree.structure(out_shape),
            len(closed.consts),
            param_slots,
        )
        saved = (saved_consts, cot)
        self._acts_records[self._acts_sig(saved)] = record
        return saved

    def _bwd_input_acts_impl(self, params, carry, kwargs, cot, state):
        """I slot: forward + carry-cotangent half → (gc, aux, saved).

        ``gc`` comes from a direct vjp call whose weight-grad outputs are
        unused — XLA dead-code-eliminates the dW half from THIS jit. The
        same vjp is traced into a jaxpr filed by residual signature; the
        W slot replays it with only the dW outputs live."""
        if self.info.is_last:
            if self.info.is_first:
                loss, vjp, (weight, metrics) = jax.vjp(
                    lambda p: self._loss_of(p, carry, kwargs, state),
                    params, has_aux=True,
                )
            else:
                loss, vjp, (weight, metrics) = jax.vjp(
                    lambda p, c: self._loss_of(p, c, kwargs, state),
                    params, carry, has_aux=True,
                )
            seed = jnp.ones_like(loss)
            saved = self._record_acts(vjp, seed, params)
            gc = None if self.info.is_first else vjp(seed)[1]
            return gc, (loss, weight, metrics), saved
        if self.info.is_first:
            _, vjp = jax.vjp(
                lambda p: self.task.stage_forward(
                    self.module, p, carry, kwargs
                ),
                params,
            )
            return None, None, self._record_acts(vjp, cot, params)
        _, vjp = jax.vjp(
            lambda p, c: self.task.stage_forward(self.module, p, c, kwargs),
            params, carry,
        )
        saved = self._record_acts(vjp, cot, params)
        gc = vjp(cot)[1]
        return gc, None, saved

    def _bwd_weight_acts_impl(self, params, saved):
        """W slot: weight grads alone, from the I slot's residuals."""
        record = self._acts_records.get(self._acts_sig(saved))
        if record is None:  # pragma: no cover — executor ordering
            raise RuntimeError(
                "cache_acts weight backward before a matching input backward"
            )
        jaxpr, out_tree, n_consts, param_slots = record
        consts_iter = iter(saved[0])
        params_flat = jax.tree.leaves(params)
        consts = [
            params_flat[param_slots[pos]]
            if pos in param_slots else next(consts_iter)
            for pos in range(n_consts)
        ]
        out = jax.core.eval_jaxpr(
            jaxpr, consts, *jax.tree.leaves(saved[1])
        )
        flat_out = jax.tree.unflatten(out_tree, out)
        return flat_out[0]

    def backward_input_acts(self, carry, kwargs, cot=None, state=None):
        with self._scoped():
            return self._bwd_input_acts(self.params, carry, kwargs, cot, state)

    def backward_weight_acts(self, saved):
        with self._scoped():
            return self._bwd_weight_acts(self.params, saved)

    def backward_full(self, carry, kwargs, cot=None, state=None):
        with self._scoped():
            return self._bwd_full(self.params, carry, kwargs, cot, state)

    def backward_input(self, carry, kwargs, cot=None, state=None):
        with self._scoped():
            return self._bwd_input(self.params, carry, kwargs, cot, state)

    def backward_weight(self, carry, kwargs, cot=None, state=None):
        with self._scoped():
            return self._bwd_weight(self.params, carry, kwargs, cot, state)

    # ---- gradient accumulator -------------------------------------------

    def cast_grads(self, grads: PyTree) -> PyTree:
        """First microbatch: adopt grads as the accumulator (cast to
        ``grad_dtype``); preserves the vjp output sharding, so no separate
        zero-init is needed. No-dispatch identity when no cast is wanted."""
        if self.grad_dtype is None:
            return grads
        with self._scoped():
            return self._cast(grads)

    def accumulate(self, acc: PyTree, grads: PyTree) -> PyTree:
        with self._scoped():
            return self._acc(acc, grads)
