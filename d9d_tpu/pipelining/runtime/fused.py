"""Fused MPMD pipeline runtime: device-resident schedule programs.

The legacy ``PipelineScheduleExecutor`` interprets the validated global
linearization one tiny ``tracked_jit`` per action — O(microbatches ×
actions) host dispatches per step, the single-controller tax (39
dispatches a step against 1 at the tiny 1F1B config: ``pp_micro.*`` in
``BENCH_BASELINE.json``; no time measured on the chip, ROADMAP R-P). This module is
the compile-the-schedule answer (the MPMD pipeline-compilation lineage,
arxiv 2412.14374): a schedule compiler partitions the SAME linearization
into maximal *fusable runs* per rank, traces each run's actions —
compute, grad accumulation, loss-stat summation, the per-stage numerics
vectors, and every same-device send (lowered to an in-program value
rename) — into ONE ``tracked_jit`` program with full donation of the
run's dead activation/grad buffers, and the step loop shrinks to
dispatching a handful of fused programs plus the explicit cross-rank
boundary transfers.

Semantics contract: the run partitioner and the run tracer both consume
the same op descriptors, which are produced by a symbolic replay of the
legacy executor's action handlers (`executor.py`) — every stage function
is invoked through the identical raw ``_*_impl`` with arguments wired
through the identical residual-policy key dataflow, and gradient
accumulation folds in the identical microbatch order. Per-microbatch
results are therefore bit-identical to the legacy action loop
(``tests/pipelining/test_fused_parity.py`` pins this on CPU), which
stays available behind ``runtime="legacy"`` for one release as the
parity oracle. One documented exception: ``cache_acts`` weight grads —
the W slot's replayed VJP jaxpr lands in the same XLA program as its I
slot, and XLA's CSE/fusion of the shared subgraph can reassociate the
long f32 dW reductions (~1e-4 relative worst-case on a real model;
bit-exact on graphs XLA compiles identically in both contexts). Same
math, different float association — grad-exactness vs the sequential
baseline still holds at tolerance for every policy.

Partitioning rule (the wavefront): actions append to their rank's open
run until some action *reads a value produced by another rank's still-
open run* — that producer run is closed (sealed into the dispatch
sequence) first, so every cross-run edge points backward in dispatch
order and the sequence is trivially executable. Cross-rank boundary
transfers (``put_compat`` onto the consumer's submesh — distinct device
sets cannot share one SPMD program) are standalone entries in the same
sequence and close their producer the same way; transfers whose
destination stage declares no sharding (single-device tests, same-
footprint virtual stages) are inlined into the producing program as a
rename instead. At the tiny 1F1B config (one rank, two virtual stages)
the entire step fuses into a single program.

Telemetry: each fused program is tracked as ``pp_fused/r{R}/run{K}``
(compile spans, recompile guard, HBM inventory, d9d-audit capture —
every fused program carries a committed collective-census + donation
contract in ``AUDIT_BASELINE.json``), stage compute keeps its
``pp_s{S}/*`` named scopes inside the trace for device-side
attribution, and the step records ``pp/step`` plus the
``pp/fused_dispatches`` / ``pp/fused_transfers`` / ``pp/fused_programs``
gauges (docs/design/observability.md).

Numerics fold (PR 14 contract): when built with ``numerics=True`` the
per-stage ``pp_numerics/s{S}`` stats vector is computed INSIDE the
owning rank's final fused program, gated by a traced cadence flag
(``lax.cond``) — off-cadence steps run the identical program with the
stats branch producing NaNs, so the cadence adds zero dispatches and
zero recompiles.
"""

import contextlib
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp

from d9d_tpu.core import compat
from d9d_tpu.core.tracing import annotate
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
from d9d_tpu.pipelining.runtime.executor import PipelineExecutionResult
from d9d_tpu.pipelining.runtime.stage import PipelineStageRuntime
from d9d_tpu.pipelining.runtime.transfer import put_compat
from d9d_tpu.telemetry import get_telemetry, tracked_jit
from d9d_tpu.telemetry import numerics as numerics_mod

__all__ = ["FusedPipelineExecutor"]

# value keys in the dataflow environment (tuples; first element is the
# kind tag). "ext" producers are staged by the host at step start /
# first use; every other key is produced by a run or a transfer.
#   ("carry", mb)      first-stage input carry            ext
#   ("kw", s, mb)      stage kwargs on the stage submesh  ext
#   ("state", mb)      last-stage task state              ext
#   ("nu", s)          second-moment tree for numerics    ext (no donate)
#   ("flag", s)        traced cadence flag                ext (no donate)
#   ("in", s, mb)      carry staged by a Send             run/transfer
#   ("fo", s, mb)      forward output awaiting send       run
#   ("cot", s, mb)     cotangent wrt stage s output       run/transfer
#   ("gin", s, mb)     dI awaiting a BackwardSend         run
#   ("g", s, v)        grad accumulator, version v >= 1   run
#   ("aux", i)         (loss, weight, metrics) triple     run
#   ("saved", s, mb)   cache_acts residual payload        run
#   ("out", mb)        eval per-microbatch output         run
#   ("loss",)/("wsum",)/("met",)  summed loss statistics  run
#   ("num", s)         per-stage numerics vector          run

# donation is restricted to executor-owned intermediates (activations,
# cotangents, grad accumulators, cached residuals, loss auxes): ext keys
# may alias caller-owned arrays (microbatch trees, second moments), and
# donating those would invalidate the caller's buffers mid-step
_DONATABLE_KINDS = ("in", "fo", "cot", "gin", "g", "saved", "aux")

# relative compute weight per op kind, used to apportion a fused run's
# measured wall across its stages on timeline-cadence steps (and carried
# per-op in the RunManifest so offline consumers can do the same with the
# run's XLA cost_analysis FLOPs as the absolute anchor). bwd_full ≈ one
# forward + one backward in a single VJP (the last stage additionally
# folds its fwd_loss in under train); dI/dW splits are each ≈ one unit;
# renames, aux summation and the numerics cond are ~free.
_OP_WEIGHTS = {
    "fwd": 1.0,
    "fwd_loss": 1.0,
    "fwd_out": 1.0,
    "bwd_full": 2.0,
    "bwd_dI": 1.0,
    "bwd_dW": 1.0,
    "bwd_dI_acts": 1.0,
    "bwd_dW_acts": 1.0,
    "send": 0.0,
    "sum_aux": 0.0,
    "numerics": 0.0,
}


@dataclasses.dataclass
class _Op:
    """One legacy-handler-equivalent device action: the unit both the
    partitioner (reads/writes) and the run tracer (meta roles) consume."""

    kind: str
    stage: int
    mb: int
    reads: tuple
    writes: tuple
    meta: dict


class _Run:
    """One fusable run: a maximal contiguous slice of a rank's actions,
    compiled into a single tracked_jit program."""

    __slots__ = (
        "rank", "index", "ops", "param_stages", "input_keys",
        "output_keys", "donate_keys", "drop_after", "fn", "label",
        "stage_shares", "_writes", "_reads",
    )

    def __init__(self, rank: int, index: int):
        self.rank = rank
        self.index = index
        self.ops: list[_Op] = []
        self.param_stages: list[int] = []
        self.input_keys: list[tuple] = []
        self.output_keys: list[tuple] = []
        self.donate_keys: set[tuple] = set()
        self.drop_after: list[tuple] = []
        self.fn = None
        self.label = f"pp.run.r{rank}.{index}"
        self.stage_shares: dict[int, float] = {}
        self._writes: set[tuple] = set()
        self._reads: set[tuple] = set()


class _Transfer:
    """One explicit cross-rank boundary transfer (``put_compat`` onto
    the destination stage's sharding) in the dispatch sequence."""

    __slots__ = ("src", "dst", "dst_stage", "drop_after", "label")

    def __init__(self, src: tuple, dst: tuple, dst_stage: int):
        self.src = src
        self.dst = dst
        self.dst_stage = dst_stage
        self.drop_after: list[tuple] = []
        self.label = f"pp.xfer.s{dst_stage}.mb{src[2]}"


_EXT = "ext"


class FusedPipelineExecutor:
    """Drop-in replacement for ``PipelineScheduleExecutor``: same
    constructor surface plus ``numerics``, same result type, a few fused
    program dispatches per step instead of one per action.

    ``numerics=True`` appends the per-stage ``pp_numerics/s{S}`` stats
    assembly to each owning rank's last run under a traced cadence flag;
    ``step`` then requires ``numerics_moments`` (per-stage second-moment
    trees, ``telemetry/numerics.find_second_moments``) every call and
    returns the stats vectors in ``result.numerics`` (NaN-filled off
    cadence — the flag only flips a ``lax.cond`` branch).
    """

    def __init__(
        self,
        *,
        stages: dict[int, PipelineStageRuntime],
        program: PipelineProgram,
        stage_owner: dict[int, int],
        num_microbatches: int,
        train: bool = True,
        numerics: bool = False,
    ):
        self.stages = stages
        self.num_stages = len(stages)
        self.num_microbatches = num_microbatches
        self.stage_owner = stage_owner
        self.train = train
        self.numerics = numerics and train
        sim = validate_program(
            program,
            num_stages=self.num_stages,
            num_microbatches=num_microbatches,
            stage_owner=stage_owner,
            train=train,
        )
        self.order: tuple[tuple[int, Action], ...] = sim.order
        self._last = self.stages[self.num_stages - 1]
        self._rank_mesh = {
            stage_owner[s]: rt.mesh for s, rt in sorted(stages.items())
        }
        self._grad_final: dict[int, tuple] = {}
        self._aux_keys: list[tuple] = []
        self._ext_consumed: set[tuple] = set()
        entries = self._build_entries()
        self._seq = self._partition(entries)
        self._runs = [e for e in self._seq if isinstance(e, _Run)]
        for run in self._runs:
            self._build_run(run)
        # ext keys staged lazily right before their first consumer
        self._stage_before = self._ext_staging_plan()
        self.num_fused_programs = len(self._runs)
        self.num_transfers = len(self._seq) - len(self._runs)
        self.last_dispatches = 0
        self._tele = get_telemetry()

    # ------------------------------------------------------------------
    # op generation: symbolic replay of the legacy handlers

    def _flat_plan(self) -> list[Action]:
        flat: list[Action] = []

        def add(action: Action) -> None:
            if isinstance(action, Compose):
                for member in action.actions:
                    add(member)
            elif not isinstance(action, (ForwardRecv, BackwardRecv)):
                flat.append(action)

        for _rank, action in self.order:
            add(action)
        return flat

    def _build_entries(self) -> list:
        """The dispatch-ordered entry list: ("op", rank, _Op) device
        actions and ("xfer", src, dst, dst_stage) boundary transfers,
        mirroring ``PipelineScheduleExecutor``'s handlers key for key."""
        entries: list = []
        owner = self.stage_owner
        last_s = self.num_stages - 1
        in_key: dict[tuple[int, int], tuple] = {}
        sent_in: set[tuple] = set()  # ("in", s, mb) written by a Send
        grads_ver: dict[int, int] = {}
        weight_done: set[tuple[int, int]] = set()

        def op(kind, s, mb, reads, writes, **meta):
            reads = tuple(k for k in reads if k is not None)
            writes = tuple(k for k in writes if k is not None)
            entries.append(
                ("op", owner[s] if s >= 0 else owner[last_s],
                 _Op(kind, s, mb, reads, writes, meta))
            )

        def next_aux(s, mb) -> tuple:
            k = ("aux", len(self._aux_keys))
            self._aux_keys.append(k)
            return k

        def bump_grads(s) -> tuple[tuple | None, tuple]:
            v = grads_ver.get(s, 0) + 1
            grads_ver[s] = v
            acc = ("g", s, v - 1) if v > 1 else None
            gout = ("g", s, v)
            self._grad_final[s] = gout
            return acc, gout

        def route(s, mb) -> tuple | None:
            # _route_input_grad: local edge stores the cot directly,
            # cross-rank edges park it for the BackwardSend
            if s == 0:
                return None
            if owner[s - 1] == owner[s]:
                return ("cot", s - 1, mb)
            return ("gin", s, mb)

        def send(s_from, s_to, src, dst):
            if self.stages[s_to].carry_sharding is None:
                # no transfer target: the legacy put is the identity —
                # lower it into the producing program as a rename
                op("send", s_from, src[2], (src,), (dst,), src=src, dst=dst)
            else:
                entries.append(("xfer", src, dst, s_to))

        for action in self._flat_plan():
            s, mb = action.stage, action.microbatch
            stage = self.stages[s]
            is_last = stage.info.is_last

            if isinstance(action, ForwardCompute):
                if s == 0:
                    ik = ("carry", mb)
                elif ("in", s, mb) in sent_in:
                    ik = ("in", s, mb)
                else:
                    ik = ("fo", s - 1, mb)  # same-rank edge: direct pull
                in_key[(s, mb)] = ik
                kw = ("kw", s, mb)
                if is_last:
                    if not self.train:
                        if stage.has_output_fn:
                            op("fwd_out", s, mb,
                               (ik, kw, ("state", mb)), (("out", mb),),
                               carry=ik, kw=kw, state=("state", mb),
                               out=("out", mb))
                        else:
                            aux = next_aux(s, mb)
                            op("fwd_loss", s, mb,
                               (ik, kw, ("state", mb)),
                               (aux, ("out", mb)),
                               carry=ik, kw=kw, state=("state", mb),
                               aux=aux, out=("out", mb))
                    # train: forward folds into the backward
                else:
                    op("fwd", s, mb, (ik, kw), (("fo", s, mb),),
                       carry=ik, kw=kw, out=("fo", s, mb))

            elif isinstance(action, ForwardSend):
                sent_in.add(("in", s + 1, mb))
                send(s, s + 1, ("fo", s, mb), ("in", s + 1, mb))

            elif isinstance(action, BackwardSend):
                send(s, s - 1, ("gin", s, mb), ("cot", s - 1, mb))

            elif isinstance(action, BackwardFull) or (
                isinstance(action, BackwardInput)
                and stage.residual_policy == "cache_full"
            ):
                ik = in_key.pop((s, mb))
                cot = None if is_last else ("cot", s, mb)
                state = ("state", mb) if is_last else None
                aux = next_aux(s, mb) if is_last else None
                acc, gout = bump_grads(s)
                rt = route(s, mb)
                op("bwd_full", s, mb,
                   (ik, ("kw", s, mb), cot, state, acc),
                   (gout, rt, aux),
                   carry=ik, kw=("kw", s, mb), cot=cot, state=state,
                   acc=acc, gout=gout, route=rt, aux=aux)
                if isinstance(action, BackwardInput):
                    weight_done.add((s, mb))

            elif isinstance(action, BackwardInput):
                if stage.residual_policy == "cache_acts":
                    ik = in_key.pop((s, mb))
                    cot = None if is_last else ("cot", s, mb)
                    state = ("state", mb) if is_last else None
                    aux = next_aux(s, mb) if is_last else None
                    rt = route(s, mb)
                    op("bwd_dI_acts", s, mb,
                       (ik, ("kw", s, mb), cot, state),
                       (("saved", s, mb), rt, aux),
                       carry=ik, kw=("kw", s, mb), cot=cot, state=state,
                       saved=("saved", s, mb), route=rt, aux=aux)
                else:  # remat: inputs/cot stay live for the W slot
                    ik = in_key[(s, mb)]
                    cot = None if is_last else ("cot", s, mb)
                    state = ("state", mb) if is_last else None
                    aux = next_aux(s, mb) if is_last else None
                    rt = route(s, mb)
                    op("bwd_dI", s, mb,
                       (ik, ("kw", s, mb), cot, state),
                       (rt, aux),
                       carry=ik, kw=("kw", s, mb), cot=cot, state=state,
                       route=rt, aux=aux)

            elif isinstance(action, BackwardWeight):
                if stage.residual_policy == "cache_acts":
                    acc, gout = bump_grads(s)
                    op("bwd_dW_acts", s, mb,
                       (("saved", s, mb), acc), (gout,),
                       saved=("saved", s, mb), acc=acc, gout=gout)
                elif (s, mb) in weight_done:
                    weight_done.discard((s, mb))  # cache_full: no-op slot
                else:  # remat
                    ik = in_key.pop((s, mb))
                    cot = None if is_last else ("cot", s, mb)
                    state = ("state", mb) if is_last else None
                    acc, gout = bump_grads(s)
                    op("bwd_dW", s, mb,
                       (ik, ("kw", s, mb), cot, state, acc), (gout,),
                       carry=ik, kw=("kw", s, mb), cot=cot, state=state,
                       acc=acc, gout=gout)
            else:  # pragma: no cover
                raise TypeError(f"unknown action {action!r}")

        # numerics fold BEFORE the aux sum: each stats op appends to its
        # rank's still-open run (zero extra dispatches); the aux sum then
        # seals every remaining run
        if self.numerics:
            for s in sorted(self._grad_final):
                op("numerics", s, -1,
                   (self._grad_final[s], ("nu", s), ("flag", s)),
                   (("num", s),),
                   g=self._grad_final[s], nu=("nu", s),
                   flag=("flag", s), num=("num", s))
        if self._aux_keys:
            op("sum_aux", last_s, -1, tuple(self._aux_keys),
               (("loss",), ("wsum",), ("met",)),
               aux_keys=tuple(self._aux_keys))
        return entries

    # ------------------------------------------------------------------
    # wavefront partitioner

    def _result_keys(self) -> set[tuple]:
        keys: set[tuple] = set()
        if self.train:
            keys.update(self._grad_final.values())
            if self.numerics:
                keys.update(("num", s) for s in self._grad_final)
        else:
            keys.update(("out", mb) for mb in range(self.num_microbatches))
        if self._aux_keys:
            keys.update((("loss",), ("wsum",), ("met",)))
        return keys

    def _partition(self, entries: list) -> list:
        open_runs: dict[int, _Run] = {}
        producer: dict[tuple, Any] = {}
        consumers: dict[tuple, list] = {}
        seq: list = []
        counters: dict[int, int] = {}

        def close(rank: int) -> None:
            seq.append(open_runs.pop(rank))

        def consume(entity, key) -> None:
            p = producer.get(key, _EXT)
            if isinstance(p, _Run) and open_runs.get(p.rank) is p and (
                p is not entity
            ):
                close(p.rank)
            if p is _EXT:
                self._ext_consumed.add(key)
            consumers.setdefault(key, []).append(entity)

        for entry in entries:
            if entry[0] == "xfer":
                _, src, dst, dst_stage = entry
                t = _Transfer(src, dst, dst_stage)
                consume(t, src)
                seq.append(t)
                producer[dst] = t
                continue
            _, rank, op = entry
            run = open_runs.get(rank)
            if run is None:
                idx = counters.get(rank, 0)
                counters[rank] = idx + 1
                run = open_runs[rank] = _Run(rank, idx)
            for k in op.reads:
                if k in run._writes:
                    continue  # intra-run edge
                if k not in run._reads:
                    consume(run, k)
                    run._reads.add(k)
                    run.input_keys.append(k)
            for k in op.writes:
                producer[k] = run
                run._writes.add(k)
            if op.kind != "send" and op.kind != "sum_aux":
                if op.stage not in run.param_stages:
                    run.param_stages.append(op.stage)
            run.ops.append(op)
        for rank in sorted(open_runs):
            close(rank)

        # liveness: outputs = values consumed later or returned to the
        # caller; donation = last-consumer, non-result, non-pinned inputs
        results = self._result_keys()
        last_use = {k: lst[-1] for k, lst in consumers.items()}
        for ent in seq:
            if isinstance(ent, _Run):
                ent.param_stages.sort()
                ent.output_keys = [
                    k for op in ent.ops for k in op.writes
                    if consumers.get(k) or k in results
                ]
                ent.donate_keys = {
                    k for k in ent.input_keys
                    if last_use.get(k) is ent
                    and k not in results
                    and k[0] in _DONATABLE_KINDS
                }
                ent.drop_after = [
                    k for k in ent.input_keys
                    if last_use.get(k) is ent and k not in results
                ]
            else:
                ent.drop_after = (
                    [ent.src]
                    if last_use.get(ent.src) is ent
                    and ent.src not in results
                    else []
                )
        return seq

    def _ext_staging_plan(self) -> list[list[tuple]]:
        """Per dispatch-sequence position: the ext kwargs keys to stage
        right before that entity runs (first-use staging; carries/states
        go up front like the legacy executor)."""
        staged: set[tuple] = set()
        plan: list[list[tuple]] = []
        for ent in self._seq:
            keys = ent.input_keys if isinstance(ent, _Run) else [ent.src]
            need = [
                k for k in keys
                if k[0] == "kw" and k in self._ext_consumed
                and k not in staged
            ]
            staged.update(need)
            plan.append(need)
        return plan

    # ------------------------------------------------------------------
    # run tracing: the same op descriptors, interpreted symbolically

    def _build_run(self, run: _Run) -> None:
        stage_ids = tuple(run.param_stages)
        input_keys = tuple(run.input_keys)
        output_keys = tuple(run.output_keys)
        ops = tuple(run.ops)
        n_sp = len(stage_ids)

        def fn(*args):
            stage_args = dict(zip(stage_ids, args[:n_sp]))
            env = dict(zip(input_keys, args[n_sp:]))
            for op in ops:
                self._trace_op(op, stage_args, env)
            return tuple(env[k] for k in output_keys)

        donate = tuple(
            n_sp + i
            for i, k in enumerate(input_keys)
            if k in run.donate_keys
        )
        run.fn = tracked_jit(
            fn,
            name=f"pp_fused/r{run.rank}/run{run.index}",
            donate_argnums=donate,
        )
        # RunManifest: the run's ordered op descriptors, persisted on the
        # program's ExecutableRecord (and therefore the `executable` JSONL
        # sidecar + introspect inventory) at first compile. Offline
        # consumers re-derive the same per-stage apportionment from the
        # per-op `weight` column, anchored to the record's absolute
        # cost_analysis FLOPs.
        run.fn.manifest = {
            "rank": run.rank,
            "index": run.index,
            "ops": [
                {
                    "kind": op.kind,
                    "stage": op.stage,
                    "mb": op.mb,
                    "weight": _OP_WEIGHTS.get(op.kind, 1.0),
                    "reads": [list(k) for k in op.reads],
                    "writes": [list(k) for k in op.writes],
                }
                for op in run.ops
            ],
        }
        # per-stage wall shares for the timeline cadence: kind-weighted
        # op counts, normalized within the run (uniform over the run's
        # param stages when every op is weightless)
        weights: dict[int, float] = {}
        for op in run.ops:
            w = _OP_WEIGHTS.get(op.kind, 1.0)
            if w > 0.0 and op.stage >= 0:
                weights[op.stage] = weights.get(op.stage, 0.0) + w
        total_w = sum(weights.values())
        if total_w > 0.0:
            run.stage_shares = {s: w / total_w for s, w in weights.items()}
        elif run.param_stages:
            u = 1.0 / len(run.param_stages)
            run.stage_shares = {s: u for s in run.param_stages}

    def _trace_op(self, op: _Op, params: dict, env: dict) -> None:
        m = op.meta
        s = op.stage
        kind = op.kind
        if kind == "send":
            env[m["dst"]] = env[m["src"]]
            return
        if kind == "sum_aux":
            self._trace_sum_aux(m["aux_keys"], env)
            return
        if kind == "numerics":
            self._trace_numerics(s, m, params, env)
            return
        stage = self.stages[s]
        cot = env[m["cot"]] if m.get("cot") else None
        state = env[m["state"]] if m.get("state") else None
        if kind == "fwd":
            with jax.named_scope(f"pp_s{s}/fwd"):
                env[m["out"]] = stage._fwd_impl(
                    params[s], env[m["carry"]], env[m["kw"]]
                )
        elif kind == "fwd_loss":
            with jax.named_scope(f"pp_s{s}/fwd_loss"):
                aux = stage._fwd_loss_impl(
                    params[s], env[m["carry"]], env[m["kw"]], state
                )
            env[m["aux"]] = aux
            env[m["out"]] = aux
        elif kind == "fwd_out":
            with jax.named_scope(f"pp_s{s}/fwd_out"):
                env[m["out"]] = stage._fwd_out_impl(
                    params[s], env[m["carry"]], env[m["kw"]], state
                )
        elif kind == "bwd_full":
            with jax.named_scope(f"pp_s{s}/bwd"):
                gp, gc, aux = stage._bwd_full_impl(
                    params[s], env[m["carry"]], env[m["kw"]], cot, state
                )
            if m["aux"]:
                env[m["aux"]] = aux
            self._trace_acc(op, gp, params, env)
            if m["route"]:
                env[m["route"]] = gc
        elif kind == "bwd_dI":
            with jax.named_scope(f"pp_s{s}/bwd_dI"):
                gc, aux = stage._bwd_input_impl(
                    params[s], env[m["carry"]], env[m["kw"]], cot, state
                )
            if m["aux"]:
                env[m["aux"]] = aux
            if m["route"]:
                env[m["route"]] = gc
        elif kind == "bwd_dW":
            with jax.named_scope(f"pp_s{s}/bwd_dW"):
                gp = stage._bwd_weight_impl(
                    params[s], env[m["carry"]], env[m["kw"]], cot, state
                )
            self._trace_acc(op, gp, params, env)
        elif kind == "bwd_dI_acts":
            with jax.named_scope(f"pp_s{s}/bwd_dI_acts"):
                gc, aux, saved = stage._bwd_input_acts_impl(
                    params[s], env[m["carry"]], env[m["kw"]], cot, state
                )
            env[m["saved"]] = saved
            if m["aux"]:
                env[m["aux"]] = aux
            if m["route"]:
                env[m["route"]] = gc
        elif kind == "bwd_dW_acts":
            with jax.named_scope(f"pp_s{s}/bwd_dW_acts"):
                gp = stage._bwd_weight_acts_impl(params[s], env[m["saved"]])
            self._trace_acc(op, gp, params, env)
        else:  # pragma: no cover
            raise TypeError(f"unknown op kind {kind!r}")

    def _trace_acc(self, op: _Op, gp, params: dict, env: dict) -> None:
        """First microbatch adopts (cast) the grads, later ones fold in —
        the exact ``cast_grads``/``accumulate`` order of the legacy
        ``_add_grads``, traced inline."""
        s = op.stage
        m = op.meta
        stage = self.stages[s]
        if m["acc"] is None:
            if stage.grad_dtype is None:
                env[m["gout"]] = gp
            else:
                with jax.named_scope(f"pp_s{s}/cast_grads"):
                    env[m["gout"]] = jax.tree.map(
                        lambda x: x.astype(stage.grad_dtype), gp
                    )
        else:
            with jax.named_scope(f"pp_s{s}/grad_acc"):
                env[m["gout"]] = jax.tree.map(
                    lambda x, y: x + y.astype(x.dtype), env[m["acc"]], gp
                )

    def _trace_sum_aux(self, aux_keys: tuple, env: dict) -> None:
        auxes = [env[k] for k in aux_keys]
        with jax.named_scope("pp/loss_sum"):
            structures = {jax.tree.structure(a) for a in auxes}
            if len(structures) == 1:
                acc = auxes[0]
                for aux in auxes[1:]:
                    acc = jax.tree.map(lambda x, y: x + y, acc, aux)
                loss_sum, weight_sum, metrics_sum = acc
                metrics_sum = dict(metrics_sum)
            else:
                # key-union fallback, mirroring the legacy host merge
                loss_sum = weight_sum = None
                metrics_sum = {}
                for loss, weight, metrics in auxes:
                    loss_sum = loss if loss_sum is None else loss_sum + loss
                    weight_sum = (
                        weight if weight_sum is None else weight_sum + weight
                    )
                    for k, v in metrics.items():
                        metrics_sum[k] = (
                            v if k not in metrics_sum else metrics_sum[k] + v
                        )
        env[("loss",)] = loss_sum
        env[("wsum",)] = weight_sum
        env[("met",)] = metrics_sum

    def _trace_numerics(self, s: int, m: dict, params: dict, env: dict):
        g, nu, flag = env[m["g"]], env[m["nu"]], env[m["flag"]]
        p = params[s]

        def stats(g, nu, p):
            return numerics_mod.stacked_param_rows(
                g, params=None, new_params=p, nu=nu
            ).reshape(-1)

        shape = jax.eval_shape(stats, g, nu, p)
        with jax.named_scope(f"pp_numerics/s{s}/stats"):
            env[m["num"]] = jax.lax.cond(
                flag,
                lambda: stats(g, nu, p),
                lambda: jnp.full(shape.shape, jnp.nan, shape.dtype),
            )

    # ------------------------------------------------------------------
    # step loop: a handful of fused dispatches + boundary transfers

    def _mesh_scope(self, rank: int):
        mesh = self._rank_mesh.get(rank)
        return (
            compat.set_mesh(mesh)
            if mesh is not None
            else contextlib.nullcontext()
        )

    def _stage_ext(self, tree: PyTree, sharding) -> PyTree:
        # ext trees are never donated, so the legacy staging semantics
        # (identity when no sharding is declared) carry over unchanged
        return put_compat(tree, sharding)

    def _emit_timeline(self, run_walls, total: float, tele) -> None:
        """Timeline-cadence attribution: apportion each fused run's
        blocked wall across its stages by the run's kind-weighted op
        shares, then emit the legacy interpreter's exact per-stage gauge
        and counter set (executor.py's host-attributed block) plus the
        ``pp/bubble_frac`` rollup and per-run ``pp/run/r{R}/k{K}/wall_s``.
        Boundary transfers are not timed — their wall reads as bubble on
        every stage, matching the MPMD convention that comm off the
        critical path is idle time."""
        busy = [0.0] * self.num_stages
        for ent, wall in run_walls:
            for s, frac in ent.stage_shares.items():
                busy[s] += wall * frac
            tele.gauge(
                f"pp/run/r{ent.rank}/k{ent.index}/wall_s"
            ).set(wall)
        fracs = []
        for s in range(self.num_stages):
            bubble = max(total - busy[s], 0.0)
            frac = bubble / total if total > 0 else 0.0
            tele.gauge(f"pp/s{s}/busy_s").set(busy[s])
            tele.gauge(f"pp/s{s}/bubble_s").set(bubble)
            tele.gauge(f"pp/s{s}/bubble_frac").set(frac)
            tele.counter(f"pp/s{s}/busy_total_s").add(busy[s])
            tele.counter(f"pp/s{s}/bubble_total_s").add(bubble)
            fracs.append(frac)
        if fracs:
            tele.gauge("pp/bubble_frac").set(sum(fracs) / len(fracs))

    def step(
        self,
        microbatches: list[PyTree],
        *,
        numerics_on: bool = False,
        numerics_moments: dict[int, PyTree] | None = None,
        timeline: bool = False,
    ) -> PipelineExecutionResult:
        if len(microbatches) != self.num_microbatches:
            raise ValueError(
                f"program compiled for {self.num_microbatches} "
                f"microbatches, got {len(microbatches)}"
            )
        if self.numerics and numerics_moments is None:
            raise ValueError(
                "executor built with numerics=True: step() needs "
                "numerics_moments every call (the traced flag only "
                "flips the cond branch; the program signature is fixed)"
            )
        first = self.stages[0]
        last = self._last
        t_step0 = time.perf_counter()
        env: dict[tuple, Any] = {}
        kwargs_h: list[PyTree] = []
        with annotate("pp.stage_inputs"):
            for mb, micro in enumerate(microbatches):
                carry, kw, state = first.task.split_microbatch(micro)
                kwargs_h.append(kw)
                if ("carry", mb) in self._ext_consumed:
                    env[("carry", mb)] = self._stage_ext(
                        carry, first.carry_sharding
                    )
                if ("state", mb) in self._ext_consumed:
                    env[("state", mb)] = self._stage_ext(
                        state, last.state_sharding
                    )
            if self.numerics:
                flag = bool(numerics_on)
                for s in self._grad_final:
                    rt = self.stages[s]
                    env[("nu", s)] = numerics_moments.get(s)
                    flag_sharding = None
                    if rt.mesh is not None:
                        flag_sharding = jax.sharding.NamedSharding(
                            rt.mesh, jax.sharding.PartitionSpec()
                        )
                    env[("flag", s)] = self._stage_ext(
                        jnp.asarray(flag), flag_sharding
                    )

        dispatches = 0
        run_walls: list[tuple[_Run, float]] = []
        for pos, ent in enumerate(self._seq):
            for k in self._stage_before[pos]:
                env[k] = self._stage_ext(
                    kwargs_h[k[2]], self.stages[k[1]].kwargs_sharding
                )
            if isinstance(ent, _Run):
                args = [self.stages[s].params for s in ent.param_stages]
                args += [env[k] for k in ent.input_keys]
                # timeline cadence: serialize the dispatch loop (block per
                # run) so each run's wall is attributable. Off-cadence the
                # only delta is this false host branch — zero added
                # dispatches, transfers, or readbacks (bench-gate pinned).
                t_run = time.perf_counter() if timeline else 0.0
                with annotate(ent.label), self._mesh_scope(ent.rank):
                    outs = ent.fn(*args)
                if timeline:
                    # the timeline plane's one deliberate sync: only on
                    # pp_timeline_every_steps cadence steps (timeline=False
                    # skips it), where serializing the loop IS the measurement
                    # d9d-lint: disable=D9D003 — cadence-only attribution sync
                    jax.block_until_ready(outs)
                    run_walls.append((ent, time.perf_counter() - t_run))
                dispatches += 1
                for k, v in zip(ent.output_keys, outs):
                    env[k] = v
            else:
                with annotate(ent.label):
                    env[ent.dst] = put_compat(
                        env[ent.src],
                        self.stages[ent.dst_stage].carry_sharding,
                    )
            for k in ent.drop_after:
                env.pop(k, None)
        self.last_dispatches = dispatches

        numerics_out = None
        if self.numerics:
            numerics_out = {
                s: env[("num", s)] for s in sorted(self._grad_final)
            }
        total = time.perf_counter() - t_step0
        tele = self._tele
        tele.registry.record_span(
            "pp/step", t_step0, total,
            meta={
                "stages": self.num_stages, "train": self.train,
                "fused": True,
            },
        )
        tele.gauge("pp/fused_dispatches").set(dispatches)
        tele.gauge("pp/fused_transfers").set(self.num_transfers)
        tele.gauge("pp/fused_programs").set(self.num_fused_programs)
        if timeline:
            self._emit_timeline(run_walls, total, tele)

        return PipelineExecutionResult(
            grads=(
                {s: env[k] for s, k in sorted(self._grad_final.items())}
                if self.train
                else None
            ),
            loss_sum=env.get(("loss",)),
            weight_sum=env.get(("wsum",)),
            metrics=dict(env.get(("met",), {})),
            outputs=(
                [env.get(("out", mb)) for mb in range(self.num_microbatches)]
                if not self.train
                else None
            ),
            numerics=numerics_out,
        )
