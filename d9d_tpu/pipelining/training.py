"""Per-stage optimizer aggregate for pipeline-parallel training.

Reference: d9d/pipelining/training/optimizer.py:10 (``PipelinedOptimizer``)
and scheduler.py (``PipelinedLRScheduler``) — one logical optimizer over
the disjoint per-stage parameter groups a pipeline rank owns.

TPU redesign: stages live on *different submeshes*, so there is no single
jit spanning them. Instead each stage gets its own jitted update, and the
cross-stage scalars (gradient norm, loss-weight scale) flow as tiny device
arrays: per-stage squared norms hop to the last stage's devices, one fused
jit there computes the global clip/scale factor (sum-then-scale semantics +
reference's ND grad-norm contract, internals/grad_norm/norm.py:99), and the
factor hops back to each stage. Everything stays in XLA's async stream —
no host sync on the step path.
"""

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import optax

from d9d_tpu.core import compat
from d9d_tpu.core.protocol import OptimizerProtocol
from d9d_tpu.core.tracing import annotate
from d9d_tpu.core.types import PyTree
from d9d_tpu.pipelining.runtime.transfer import put_compat
from d9d_tpu.telemetry import numerics as numerics_mod
from d9d_tpu.telemetry import tracked_jit

__all__ = ["PipelinedOptimizer"]


@dataclasses.dataclass
class PipelinedOptimizer:
    """One optimizer instance per pipeline stage, stepped as a unit.

    ``shardings`` maps stage id → a NamedSharding on that stage's submesh
    used to place the broadcast scale factor (any fully-replicated sharding
    on the stage's devices works).
    """

    optimizer: "optax.GradientTransformation | OptimizerProtocol"
    scalar_shardings: dict[int, Any]
    max_grad_norm: float | None = 1.0
    # step anomaly guard (docs/design/resilience.md): when True,
    # step_guarded() freezes every stage's param/moment update on a
    # non-finite global grad norm or loss via an in-device select —
    # the ok flag rides the same scalar hops as the clip factor, so the
    # guard adds no dispatches and no readbacks to the step
    anomaly_freeze: bool = False
    # ZeRO optimizer-state sharding (parallel/zero.py): shard each
    # stage's fp32 masters/moments over this axis of its submesh —
    # grads reduce-scattered at the update entry, the update computed
    # on the 1/N shard, new params all-gathered back. The per-stage
    # sharding tables are computed in init() from the concrete states,
    # so the jitted updates are built lazily per stage. None = off.
    zero_axis: str | None = None

    def __post_init__(self) -> None:
        def sq_norm(grads):
            with jax.named_scope("pp_opt/sq_norm"):
                return optax.global_norm(grads) ** 2

        def combine(sq_norms, weight_sum, max_norm):
            # grads are Σ_mb sums: scale by 1/Σweight, then clip the norm of
            # the *scaled* grads — norm(g/w) = sqrt(Σ sq)/w
            with jax.named_scope("pp_opt/combine"):
                inv_w = 1.0 / jnp.maximum(weight_sum, 1e-8)
                norm = jnp.sqrt(sum(sq_norms)) * inv_w
                clip = (
                    jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
                    if max_norm is not None
                    else 1.0
                )
                return norm, inv_w * clip

        def combine_guarded(sq_norms, weight_sum, loss_sum, guard, max_norm):
            # the unguarded combine, plus finiteness of the two scalars
            # the step already materializes and a [streak, total] device
            # carry — nothing here forces a host sync
            with jax.named_scope("pp_opt/combine_guarded"):
                norm, factor = combine(sq_norms, weight_sum, max_norm)
                ok = jnp.isfinite(norm) & jnp.isfinite(loss_sum)
                anomaly = jnp.logical_not(ok).astype(jnp.int32)
                streak = jnp.where(ok, 0, guard[0] + 1)
                total = guard[1] + anomaly
                new_guard = jnp.stack([streak, total])
                # metric copies come out of the same jit: the guard adds
                # zero eager op dispatches to the engine's step
                metrics = {
                    "resilience/anomaly": anomaly.astype(jnp.float32),
                    "resilience/anomaly_streak": streak.astype(jnp.float32),
                    "resilience/anomaly_total": total.astype(jnp.float32),
                }
                return norm, factor, ok, new_guard, metrics

        # tracked_jit (telemetry/introspect.py): these run EVERY step —
        # per-stage sq_norm/update plus the anchor-stage combine — so
        # their compiles/recompiles must be visible to the guard like
        # the rest of the step path. Per-stage executables get per-stage
        # names (pp_opt/s{S}/...) because the hbm/{name}/* gauges are
        # set per compile: one shared name across stages of different
        # sizes would last-write-wins blend their claims (the PR 9
        # gauge-conflation class). The combine runs only on the anchor
        # stage, so one name suffices.
        self._sq_norm_impl = sq_norm
        self._sq_norm_fns: dict[int, Any] = {}
        self._combine = tracked_jit(
            functools.partial(combine, max_norm=self.max_grad_norm),
            name="pp_opt/combine",
        )
        self._combine_guarded = tracked_jit(
            functools.partial(combine_guarded, max_norm=self.max_grad_norm),
            name="pp_opt/combine_guarded",
        )
        # per-stage jitted update pairs, built lazily on first use;
        # zero-enabled stages get theirs swapped in by init() (per-stage
        # sharding tables baked into the traced program)
        self._stage_fns: dict[int, tuple] = {}
        # per-stage numerics stats executables (telemetry/numerics.py):
        # built lazily like the update pairs, dispatched by the engine
        # ONLY on cadence steps — off-cadence PP steps add zero
        # dispatches to the single-controller loop
        self._numerics_fns: dict[int, Any] = {}
        self.zero_shardings: dict[int, Any] = {}

    def _stage_sq_norm(self, stage: int):
        fn = self._sq_norm_fns.get(stage)
        if fn is None:
            fn = self._sq_norm_fns[stage] = tracked_jit(
                self._sq_norm_impl, name=f"pp_opt/s{stage}/sq_norm"
            )
        return fn

    def _build_update_fns(self, opt, scope: str) -> tuple:
        """(update, update_guarded) jits closed over ``opt`` — one pair
        per stage (the ZeRO wrapper bakes its per-stage sharding tables
        into the traced program). ``scope`` (``pp_opt/s{S}``) keys the
        tracked names so each stage's ``hbm/*`` gauges stay distinct."""
        accepts_fp32 = getattr(opt, "accepts_fp32_grads", False)
        apply_updates = getattr(opt, "apply_updates", optax.apply_updates)
        freeze = self.anomaly_freeze

        def update(params, opt_state, grads, factor):
            with jax.named_scope("pp_opt/update"):
                grads = jax.tree.map(lambda g: g * factor, grads)
                if not accepts_fp32:
                    grads = jax.tree.map(
                        lambda g, p: g.astype(p.dtype), grads, params
                    )
                updates, opt_state = opt.update(grads, opt_state, params)
                return apply_updates(params, updates), opt_state

        def update_guarded(params, opt_state, grads, factor, ok):
            with jax.named_scope("pp_opt/update_guarded"):
                new_params, new_state = update(
                    params, opt_state, grads, factor
                )
                if freeze:
                    new_params = jax.tree.map(
                        lambda new, old: jnp.where(ok, new, old),
                        new_params, params,
                    )
                    new_state = jax.tree.map(
                        lambda new, old: jnp.where(ok, new, old),
                        new_state, opt_state,
                    )
                return new_params, new_state

        return (
            tracked_jit(
                update, name=f"{scope}/update", donate_argnums=(0, 1, 2)
            ),
            tracked_jit(
                update_guarded, name=f"{scope}/update_guarded",
                donate_argnums=(0, 1, 2),
            ),
        )

    def _stage_update_fns(self, stage: int) -> tuple:
        fns = self._stage_fns.get(stage)
        if fns is None:
            fns = self._stage_fns[stage] = self._build_update_fns(
                self.optimizer, scope=f"pp_opt/s{stage}"
            )
        return fns

    def _scoped(self, stage: int):
        return compat.set_mesh(self.scalar_shardings[stage].mesh)

    # -- per-stage numerics (docs/design/observability.md) -------------

    def stage_numerics(self, stage: int, params, grads, opt_state):
        """One stage's per-leaf numerics rows as a flat f32 device
        array (``telemetry/numerics.py`` layout, param rows only).

        Dispatched BEFORE the update (the update executables donate
        params/opt_state/grads, so post-update those buffers are gone);
        the update:param ratio column is therefore NaN under PP —
        cross-stage *grad/param/moment* skew is the signal this surface
        exists for. One ``pp_numerics/s{S}/stats`` executable per stage:
        per-stage names keep the ``hbm/*`` gauges distinct, like the
        update pairs.
        """
        fn = self._numerics_fns.get(stage)
        if fn is None:
            def stats(params, grads, opt_state):
                nu = numerics_mod.find_second_moments(opt_state, params)
                return numerics_mod.stacked_param_rows(
                    grads, params=None, new_params=params, nu=nu
                ).reshape(-1)

            fn = self._numerics_fns[stage] = tracked_jit(
                stats, name=f"pp_numerics/s{stage}/stats"
            )
        with self._scoped(stage):
            return fn(params, grads, opt_state)

    def init(self, stage_params: dict[int, PyTree]) -> dict[int, PyTree]:
        from d9d_tpu.core.tree_sharding import replicate_uncommitted

        out = {}
        for s, p in stage_params.items():
            with self._scoped(s):
                # replicate constraint-free scalars (step counters) onto
                # the stage submesh so their placement survives a
                # checkpoint round-trip (see trainer init note)
                out[s] = replicate_uncommitted(
                    # d9d-lint: disable=D9D001 — one-shot per-stage init, not steady-state
                    jax.jit(self.optimizer.init)(p),
                    self.scalar_shardings[s].mesh,
                )
            if self.zero_axis is not None:
                out[s] = self._enable_zero(s, p, out[s])
        return out

    def _enable_zero(self, stage: int, params: PyTree, state: PyTree):
        """Shard ``stage``'s optimizer state over ``zero_axis`` and swap
        in a ZeRO-wrapped update pair for that stage. The anomaly-guard
        freeze select stays elementwise, so frozen moments freeze
        shard-local — PR 5 semantics preserved on sharded state."""
        from d9d_tpu.parallel.zero import (
            ZeroShardedOptimizer,
            build_zero_sharding,
            place_tree,
        )

        mesh = self.scalar_shardings[stage].mesh
        if self.zero_axis not in mesh.shape:
            raise ValueError(
                f"zero_axis {self.zero_axis!r} not in stage {stage}'s "
                f"submesh axes {tuple(mesh.shape)}"
            )
        zero = build_zero_sharding(
            params=params, opt_state=state, mesh=mesh, axis=self.zero_axis
        )
        self.zero_shardings[stage] = zero
        self._stage_fns[stage] = self._build_update_fns(
            ZeroShardedOptimizer(self.optimizer, zero),
            scope=f"pp_opt/s{stage}",
        )
        return place_tree(state, zero.state_shardings)

    def step(
        self,
        stage_params: dict[int, PyTree],
        opt_states: dict[int, PyTree],
        stage_grads: dict[int, PyTree],
        weight_sum: jax.Array,
    ) -> tuple[dict[int, PyTree], dict[int, PyTree], jax.Array]:
        """→ (new_params, new_opt_states, grad_norm_of_scaled_grads)."""
        last = max(self.scalar_shardings)
        anchor = self.scalar_shardings[last]
        with annotate("pp_opt.sq_norms"):
            sq_local = []
            for s in sorted(stage_grads):
                with self._scoped(s):
                    sq_local.append(self._stage_sq_norm(s)(stage_grads[s]))
            # batched hop: all per-stage scalars move to the anchor stage
            # from one call site
            sq_norms = put_compat(sq_local, anchor)
        with annotate("pp_opt.combine"), self._scoped(last):
            norm, factor = self._combine(sq_norms, weight_sum)

        new_params: dict[int, PyTree] = {}
        new_states: dict[int, PyTree] = {}
        with annotate("pp_opt.update"):
            for s in sorted(stage_params):
                f = put_compat(factor, self.scalar_shardings[s])
                update, _ = self._stage_update_fns(s)
                with self._scoped(s):
                    new_params[s], new_states[s] = update(
                        stage_params[s], opt_states[s], stage_grads[s], f
                    )
        return new_params, new_states, norm

    # -- anomaly-guarded stepping (docs/design/resilience.md) ----------

    def init_guard_state(self) -> jax.Array:
        """Fresh device-resident [streak, total] carry on the anchor
        (last) stage's devices."""
        last = max(self.scalar_shardings)
        with self._scoped(last):
            return jnp.zeros((2,), jnp.int32)

    def step_guarded(
        self,
        stage_params: dict[int, PyTree],
        opt_states: dict[int, PyTree],
        stage_grads: dict[int, PyTree],
        weight_sum: jax.Array,
        loss_sum: jax.Array,
        guard_state: jax.Array,
    ) -> tuple[
        dict[int, PyTree], dict[int, PyTree], jax.Array, dict, jax.Array
    ]:
        """:meth:`step` with the step anomaly guard threaded through:
        → (new_params, new_opt_states, grad_norm, guard_metrics,
        guard_state).

        ``guard_metrics`` (``resilience/*`` f32 scalars on the anchor
        stage) and the carry stay on device; the engine folds them into
        its metric dict for the trainer's cadence-rate host inspection.
        """
        last = max(self.scalar_shardings)
        anchor = self.scalar_shardings[last]
        with annotate("pp_opt.sq_norms"):
            sq_local = []
            for s in sorted(stage_grads):
                with self._scoped(s):
                    sq_local.append(self._stage_sq_norm(s)(stage_grads[s]))
            sq_norms = put_compat(sq_local, anchor)
        with annotate("pp_opt.combine"), self._scoped(last):
            norm, factor, ok, guard_state, guard_metrics = (
                self._combine_guarded(
                    sq_norms, weight_sum, loss_sum, guard_state
                )
            )

        new_params: dict[int, PyTree] = {}
        new_states: dict[int, PyTree] = {}
        with annotate("pp_opt.update"):
            for s in sorted(stage_params):
                # the ok flag rides the same hop as the clip factor: one
                # put per stage either way, no extra dispatches
                f, ok_s = put_compat((factor, ok), self.scalar_shardings[s])
                _, update_guarded = self._stage_update_fns(s)
                with self._scoped(s):
                    new_params[s], new_states[s] = update_guarded(
                        stage_params[s], opt_states[s], stage_grads[s],
                        f, ok_s,
                    )
        return new_params, new_states, norm, guard_metrics, guard_state
