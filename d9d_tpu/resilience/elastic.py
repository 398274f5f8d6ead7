"""Elastic topology: cross-mesh restore, live weight publish, fleet
shrink/grow (docs/design/elasticity.md).

Production means the chip count changes under you: a job trains on N
chips and resumes on M after a preemption, a serving fleet loses a
replica mid-drain and must not lose its requests, and freshly trained
weights must reach live batcher replicas without a restart. PR 5 made
the failure *exits* safe; this module is the recovery half (ROADMAP
item 4):

- **Topology-independent restore.** Checkpoints record the saving mesh
  (manifest v2 ``mesh`` block — :func:`job_mesh_spec`); restore
  compares it against the live job's mesh (:func:`tree_mesh_summary` /
  :func:`topology_mismatch`) and reshard-on-loads across the mismatch.
  The memory-bounded leg (PAPERS.md, arxiv 2112.01075's bounded
  collective redistribution, in its load-time form):
  :func:`bounded_restore_shardings` stages oversized leaves sharded
  flat across the new mesh's devices, and :func:`redistribute_tree`
  moves them to their final placement in chunks — never gathering more
  than ``hbm_budget_bytes`` of any array at once. The chunked path is
  SINGLE-CONTROLLER: its per-chunk host round-trip would touch
  non-addressable shards on a multi-process mesh, so under
  ``jax.process_count() > 1`` it degrades to direct placement —
  orbax's tensorstore reads stay shard-local and per-rank there (the
  arxiv 2412.14374 per-rank constraint), just not budget-capped for a
  huge replicated leaf.
- **Live train→serve weight publish.** :class:`WeightPublisher`
  snapshots trainer params at a step boundary and installs them into
  attached ``ContinuousBatcher`` replicas; each batcher swaps at its
  next chunk boundary (``install_weights``) with generation-stamped
  versioning — already-dispatched chunks complete on the weights they
  were dispatched with, and ``defer_to_idle`` holds the swap until
  in-flight *requests* finish. The batcher's jitted executables take
  params as a traced argument with an unchanged ``tracked_jit``
  fingerprint, so a publish causes zero steady-state recompiles
  (gated by ``tools/bench_compare.py``).
- **Preemption-driven shrink/grow.** :class:`ServingFleet` routes
  requests across N batcher replicas under the PR 5 backpressure
  contract (``QueueFullError`` cascades replica → fleet). ``shrink``
  — wired to PR 5's preemption signal via :meth:`bind_preemption` —
  drains the dying replica: queued requests migrate into survivors,
  running rows finish inside the grace window. If the replica dies
  mid-drain (``chaos.kill_replica_mid_drain``), its unfinished
  requests are resubmitted to survivors as *continuation prompts*
  (original prompt + tokens already emitted), which the serving loop's
  teacher-forced prompt consumption replays bit-identically to an
  uninterrupted decode under greedy sampling. A PAGED replica
  (``page_size`` set — docs/design/generation.md) needs nothing extra:
  a continuation is an ordinary fresh submit on the survivor, so it
  allocates pages like any request and may even prefix-hit the
  original prompt's cached pages there; the dead replica's pool dies
  with its device state. ``grow`` cold-starts a replacement replica
  from the latest published weights.

Import note: like :mod:`~d9d_tpu.resilience.chaos`, anything that
touches the loop/serve surface is imported lazily — the module itself
only needs jax + telemetry.
"""

import dataclasses
import logging
import math
import time
import weakref
from collections import deque
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from d9d_tpu.core.tree_sharding import normalize_params
from d9d_tpu.core.types import PyTree
from d9d_tpu.telemetry import get_telemetry

logger = logging.getLogger("d9d_tpu.resilience")

__all__ = [
    "ServingFleet",
    "WeightPublisher",
    "bounded_restore_shardings",
    "job_mesh_spec",
    "redistribute_tree",
    "topology_mismatch",
    "tree_mesh_summary",
]

# staging axis name for the bounded restore path; underscore-prefixed so
# it can never collide with the framework's mesh axis vocabulary
_STAGING_AXIS = "_elastic"


# ---------------------------------------------------------------------------
# mesh specs: what a checkpoint records about the topology that wrote it


def _leaf_nbytes(leaf: Any) -> int:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return math.prod(shape) * jnp.dtype(dtype).itemsize


def _committed_mesh(tree: PyTree) -> Mesh | None:
    """The mesh of the first NamedSharding-placed leaf, or None."""
    for leaf in jax.tree.leaves(tree):
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding):
            return sh.mesh
    return None


def tree_mesh_summary(tree: PyTree) -> dict[str, Any] | None:
    """``{"device_count", "axes"}`` of the mesh placing ``tree``'s leaves
    (read off the first NamedSharding), or None for an unplaced tree."""
    mesh = _committed_mesh(tree)
    if mesh is None:
        return None
    return {
        "device_count": int(mesh.devices.size),
        "axes": {str(k): int(v) for k, v in mesh.shape.items()},
    }


def leaf_sharding_specs(tree: PyTree) -> dict[str, str | None]:
    """Per-leaf PartitionSpec strings keyed by tree path — the manifest's
    record of how the save was laid out (diagnostic; restore placement is
    driven by the live target, never by these)."""
    out: dict[str, str | None] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        sh = getattr(leaf, "sharding", None)
        key = jax.tree_util.keystr(path)
        out[key] = str(sh.spec) if isinstance(sh, NamedSharding) else None
    return out


def job_mesh_spec(
    *,
    ctx=None,
    mesh: Mesh | None = None,
    zero_sharding: bool = False,
    arrays: PyTree | None = None,
) -> dict[str, Any]:
    """The saving-topology block a checkpoint records (manifest v2
    ``mesh``): MeshParameters axis sizes (incl. ``dp_r``), device count,
    the ``zero_sharding`` setting, and per-leaf sharding specs.

    ``ctx`` is a :class:`~d9d_tpu.core.mesh.MeshContext`; a bare ``mesh``
    also works (axis sizes read off ``mesh.shape``).
    """
    spec: dict[str, Any] = {"zero_sharding": bool(zero_sharding)}
    if ctx is not None:
        spec["mesh_parameters"] = ctx.params.as_dict()
        mesh = ctx.mesh
    if mesh is not None:
        spec["device_count"] = int(mesh.devices.size)
        spec["axes"] = {str(k): int(v) for k, v in mesh.shape.items()}
    if arrays is not None:
        spec["leaf_shardings"] = leaf_sharding_specs(arrays)
    return spec


def topology_mismatch(
    saved: dict[str, Any] | None, target: dict[str, Any] | None
) -> bool:
    """Did the checkpoint's saving mesh differ from the restore target's?

    Conservative: unknown on either side (pre-v2 manifest, unplaced
    target tree) reads as "no mismatch" — the plain restore path is
    always correct, the elastic path is an optimization + telemetry.
    """
    if not saved or not target:
        return False
    if "device_count" in saved and (
        int(saved["device_count"]) != int(target["device_count"])
    ):
        return True
    if saved.get("axes") and dict(saved["axes"]) != dict(target["axes"]):
        return True
    return False


# ---------------------------------------------------------------------------
# memory-bounded redistribution (chunked gather → re-place)


def _shard_slice_shape(
    idx: tuple[slice, ...], shape: tuple[int, ...]
) -> tuple[int, ...]:
    out = []
    for sl, dim in zip(idx, shape):
        start, stop, step = sl.indices(dim)
        out.append(max(0, (stop - start + step - 1) // step))
    return tuple(out)


def _zeros_on(shape, dtype, sharding) -> jax.Array:
    """An all-zeros array materialized shard-by-shard on ``sharding`` —
    never a full host or single-device copy."""
    return jax.make_array_from_callback(
        shape,
        sharding,
        lambda idx: np.zeros(_shard_slice_shape(idx, shape), dtype),
    )


def _chunked_place(
    leaf: jax.Array, target: NamedSharding, budget: int
) -> tuple[jax.Array, int]:
    """Move ``leaf`` onto ``target`` without ever gathering more than
    ``budget`` bytes of it at once: slice dim-0 chunks off the source,
    round-trip each through the host, and write it into a
    target-sharded accumulator via a donated dynamic_update_slice.
    Peak transient footprint per device: the target shard (required)
    plus one replicated ≤ budget chunk. Returns (placed, n_chunks).

    An eager op refuses an operand whose devices are not the ambient
    mesh's, and on a resume under another layout the source lives on
    one device set, the target on another and the ambient mesh is the
    caller's: each side runs under its own devices' mesh."""
    src = leaf.sharding
    if isinstance(src, NamedSharding):
        src_mesh = src.mesh
    else:
        devs = sorted(leaf.devices(), key=lambda d: d.id)
        src_mesh = Mesh(np.array(devs), ("src",))
    rows = leaf.shape[0]
    row_bytes = max(1, _leaf_nbytes(leaf) // max(rows, 1))
    chunk_rows = max(1, int(budget // row_bytes))
    repl = NamedSharding(target.mesh, P())
    out = _zeros_on(leaf.shape, leaf.dtype, target)

    def write(buf, chunk, start):
        zeros = (jnp.int32(0),) * (buf.ndim - 1)
        return lax.dynamic_update_slice(buf, chunk, (start,) + zeros)

    write_j = jax.jit(write, donate_argnums=0, out_shardings=target)
    n = 0
    for a in range(0, rows, chunk_rows):
        b = min(rows, a + chunk_rows)
        with jax.set_mesh(src_mesh):
            host_chunk = np.asarray(leaf[a:b])  # gather: ≤ budget bytes
        dev_chunk = jax.device_put(host_chunk, repl)
        with jax.set_mesh(target.mesh):
            out = write_j(out, dev_chunk, jnp.int32(a))
        n += 1
    return out, n


def redistribute_tree(
    tree: PyTree,
    target_shardings: PyTree,
    *,
    hbm_budget_bytes: int | None = None,
    telemetry=None,
) -> PyTree:
    """Re-place ``tree``'s leaves onto ``target_shardings`` (None leaves
    pass through untouched), moving any leaf larger than
    ``hbm_budget_bytes`` through the chunked gather→re-place path so no
    more than the budget of it is ever materialized outside its source
    and destination shards. Bumps ``resilience/reshard_chunks`` and
    returns the re-placed tree; with no budget this degrades to plain
    ``device_put`` per leaf (still one transfer, just unbounded)."""
    tele = telemetry if telemetry is not None else get_telemetry()
    moved = 0
    chunks = 0

    def place(sh, leaf):
        nonlocal moved, chunks
        if sh is None or not isinstance(leaf, jax.Array):
            return leaf
        cur = getattr(leaf, "sharding", None)
        try:
            if cur is not None and cur.is_equivalent_to(sh, leaf.ndim):
                return leaf
        except Exception:  # noqa: BLE001 — exotic sharding: fall through
            pass
        nbytes = _leaf_nbytes(leaf)
        moved += nbytes
        if (
            hbm_budget_bytes is None
            or nbytes <= hbm_budget_bytes
            or leaf.ndim == 0
            or leaf.shape[0] < 2
            or not isinstance(sh, NamedSharding)
            # chunking round-trips through THIS host: on a multi-process
            # mesh the slice would span non-addressable shards — degrade
            # to direct placement (shard-local, just not budget-capped)
            or jax.process_count() > 1
        ):
            chunks += 1
            return jax.device_put(leaf, sh)
        placed, n = _chunked_place(leaf, sh, hbm_budget_bytes)
        chunks += n
        return placed

    out = jax.tree.map(
        place, target_shardings, tree, is_leaf=lambda x: x is None
    )
    if chunks:
        tele.counter("resilience/reshard_chunks").add(chunks)
        tele.counter("resilience/reshard_bytes_total").add(moved)
    return out


def bounded_restore_shardings(
    target_tree: PyTree, *, hbm_budget_bytes: int | None
) -> PyTree:
    """Staging shardings for a cross-topology restore under an HBM
    budget: a tree of NamedShardings (or None = restore directly).

    A leaf stages when restoring it straight into its final placement
    would materialize more than the budget *per device* (a big
    replicated leaf) and dim 0 divides over the new mesh's device
    count: orbax then reads it 1/ndev-sharded (shard-local byte
    ranges), and :func:`redistribute_tree` re-places it chunked.
    Leaves whose final shard already fits the budget restore directly —
    tensorstore reads are shard-local and thus already bounded.
    """
    none_tree = jax.tree.map(lambda _: None, target_tree)
    if hbm_budget_bytes is None:
        return none_tree
    if jax.process_count() > 1:
        # the chunked re-place behind this staging is single-controller
        # (see redistribute_tree); multi-process restores go direct
        logger.warning(
            "elastic restore: HBM-budgeted staging is single-process "
            "only; restoring directly on %d processes",
            jax.process_count(),
        )
        return none_tree
    mesh = _committed_mesh(target_tree)
    if mesh is None or mesh.devices.size <= 1:
        return none_tree
    devs = mesh.devices.reshape(-1)
    flat = Mesh(devs, (_STAGING_AXIS,))
    staged = NamedSharding(flat, P(_STAGING_AXIS))

    def plan(leaf):
        sh = getattr(leaf, "sharding", None)
        shape = getattr(leaf, "shape", None)
        if not isinstance(sh, NamedSharding) or not shape or len(shape) == 0:
            return None
        nbytes = _leaf_nbytes(leaf)
        if nbytes <= hbm_budget_bytes:
            return None
        try:
            per_dev = (
                math.prod(sh.shard_shape(tuple(shape)))
                * jnp.dtype(leaf.dtype).itemsize
            )
        except Exception:  # noqa: BLE001 — odd sharding: assume worst
            per_dev = nbytes
        if per_dev <= hbm_budget_bytes:
            return None
        if shape[0] % devs.size != 0:
            # can't stage evenly over the devices: restore direct — the
            # budget is best-effort per-leaf, so say which leaf escaped
            logger.warning(
                "elastic restore: leaf of shape %s (%d bytes) exceeds "
                "the %d-byte HBM budget but dim 0 does not divide over "
                "%d devices; restoring unbounded",
                tuple(shape), nbytes, hbm_budget_bytes, devs.size,
            )
            return None
        return staged

    return jax.tree.map(plan, target_tree)


# ---------------------------------------------------------------------------
# live train→serve weight publish


@dataclasses.dataclass
class _CanaryPublish:
    """One in-flight canary generation: the candidate tree, its version
    stamp, and the single replica it was installed on (weakref — a dead
    canary replica must not be pinned by the pending decision)."""

    params: PyTree
    version: int
    target: weakref.ref
    unix_time: float


class WeightPublisher:
    """Fan a trainer's step-boundary param snapshot out to live serving
    replicas, generation-stamped.

    ``publish(params)`` normalizes placement, bumps the generation, and
    stages the tree into every attached batcher via
    ``ContinuousBatcher.install_weights`` — each swaps at its own next
    chunk boundary (no restart, no steady-state recompile; see
    serve.py). The publisher retains the newest fleet-wide published
    tree so a grown replica (:meth:`ServingFleet.grow`) can cold-start
    from it.

    Canaried publish (docs/design/elasticity.md "SLO autopilot"):
    :meth:`publish_canary` installs a candidate generation on exactly
    ONE replica and leaves :attr:`latest_params` (and every other
    replica) on the retained prior tree — grows and restarts during the
    canary stay on known-good weights. :meth:`promote_canary` fans the
    candidate out fleet-wide under the same generation stamp;
    :meth:`rollback_canary` re-installs the retained prior tree on the
    canary replica under a fresh stamp (a rollback is itself an
    auditable generation — two trees never share a stamp). The
    ``FleetAutopilot`` drives the promote/rollback decision from the
    canary replica's per-replica SLO deltas; a plain :meth:`publish`
    while a canary is pending supersedes (clears) it.

    Batchers are held by weakref: a retired replica must not be pinned
    (with its device cache) by the publish fan-out list.
    """

    def __init__(self, *, telemetry=None):
        self._targets: list[weakref.ref] = []
        self._tele = telemetry if telemetry is not None else get_telemetry()
        self.version = 0
        # version stamp of latest_params — diverges from ``version``
        # while a canary is pending (the canary takes a stamp without
        # becoming the fleet-wide tree until promoted)
        self.latest_version = 0
        self.latest_params: PyTree | None = None
        self.canary: _CanaryPublish | None = None

    def attach(self, batcher) -> None:
        self._targets.append(weakref.ref(batcher))

    def _live_targets(self) -> list[weakref.ref]:
        live = [ref for ref in self._targets if ref() is not None]
        self._targets = live
        return live

    def publish(self, params: PyTree, *, defer_to_idle: bool = False) -> int:
        """Install ``params`` into every live attached batcher; returns
        the new generation number. ``defer_to_idle`` asks each batcher
        to hold the swap until its in-flight requests finish. A pending
        canary is superseded: the fleet converges on THIS generation
        and the autopilot abandons the stale decision."""
        # the latent-placement class of the PR 5 resume bug: params out
        # of a restored checkpoint (or a fresh ``jit(init)``) can carry
        # uncommitted scalars whose placement conflicts with a batcher's
        # mesh-placed cache at the first post-publish dispatch;
        # ``install_weights`` re-running it is a pure traversal
        params = normalize_params(params)
        self.version += 1
        self.latest_version = self.version
        self.latest_params = params
        self.canary = None
        fanned = 0
        for ref in self._live_targets():
            b = ref()
            if b is None:  # died between the liveness scan and here
                continue
            b.install_weights(
                params, version=self.version, defer_to_idle=defer_to_idle
            )
            fanned += 1
        if fanned:
            self._tele.counter("serve/weight_publish_fanout").add(fanned)
        return self.version

    def publish_from(self, trainer, **kwargs) -> int:
        """Snapshot ``trainer.merged_params()`` (PEFT adapters folded,
        PP stages merged) and publish it. Call between trainer steps —
        the step boundary is what makes the snapshot consistent."""
        return self.publish(trainer.merged_params(), **kwargs)

    # -- canaried publish (decision loop: resilience/autopilot.py) -----

    def publish_canary(self, params: PyTree, *, batcher=None) -> int:
        """Install a candidate generation on ONE replica (``batcher``,
        or the first live attached one) and record it as the pending
        canary; returns its generation stamp. ``latest_params`` stays
        on the prior retained tree until :meth:`promote_canary` — the
        rollback target is therefore always at hand, and a concurrent
        ``grow()`` cold-starts on known-good weights.

        One canary at a time: a second ``publish_canary`` while one is
        pending raises — silently replacing it would strand the first
        canary replica on abandoned candidate weights with nothing left
        to roll it back. Resolve the pending one first
        (promote/rollback, or a fleet-wide :meth:`publish`, which
        supersedes by converging every replica on the new tree)."""
        if self.canary is not None:
            raise RuntimeError(
                f"a canary (generation {self.canary.version}) is already "
                "pending; promote/rollback it (or publish fleet-wide) "
                "before staging another"
            )
        if self.latest_params is None:
            # nothing retained = nothing to roll back to: a "canary"
            # with no known-good prior tree is just a publish that
            # cannot be undone — make the caller publish one first
            raise RuntimeError(
                "publish_canary needs a prior fleet-wide publish: the "
                "retained tree is the rollback target"
            )
        params = normalize_params(params)
        if batcher is None:
            live = self._live_targets()
            if not live:
                raise RuntimeError(
                    "publish_canary needs at least one live attached "
                    "batcher (attach one, or pass batcher=)"
                )
            batcher = live[0]()
        self.version += 1
        batcher.install_weights(params, version=self.version)
        self.canary = _CanaryPublish(
            params=params, version=self.version,
            target=weakref.ref(batcher), unix_time=time.time(),
        )
        self._tele.counter("serve/weight_canary").add(1)
        return self.version

    def promote_canary(self) -> int:
        """Fan the pending canary generation out to every OTHER live
        replica (the canary replica already runs it, same stamp) and
        make it the retained fleet-wide tree; returns its version."""
        c = self.canary
        if c is None:
            raise RuntimeError("no canary publish is pending")
        self.canary = None
        self.latest_params = c.params
        self.latest_version = c.version
        canary_b = c.target()
        fanned = 0
        for ref in self._live_targets():
            b = ref()
            if b is None or b is canary_b:
                continue
            b.install_weights(c.params, version=c.version)
            fanned += 1
        if fanned:
            self._tele.counter("serve/weight_publish_fanout").add(fanned)
        return c.version

    def rollback_canary(self) -> int:
        """Re-install the retained prior tree on the canary replica
        under a FRESH generation stamp (the audit trail must show the
        rollback as its own generation, never reuse the bad stamp);
        returns that stamp. A dead canary replica (killed mid-canary)
        just clears the pending state — its device tree died with it."""
        c = self.canary
        if c is None:
            raise RuntimeError("no canary publish is pending")
        self.canary = None
        b = c.target()
        if b is None or self.latest_params is None:
            return self.version
        self.version += 1
        b.install_weights(self.latest_params, version=self.version)
        return self.version


# ---------------------------------------------------------------------------
# serving fleet: preemption-driven shrink/grow


@dataclasses.dataclass
class _FleetRequest:
    prompt: list[int]
    max_new_tokens: int
    # ABSOLUTE perf_counter deadline, fixed at fleet submit time: a
    # migration resubmits with the REMAINING budget, so shrink/kill
    # recovery can never extend a request's lifetime past its contract
    deadline_t: float | None
    replica: int | None = None
    local_rid: int | None = None
    # tokens already emitted on replicas that died before finishing this
    # request; resubmission feeds prompt + prefix as a continuation
    prefix: list[int] = dataclasses.field(default_factory=list)
    migrations: int = 0
    # fleet-stable trace id (docs/design/observability.md): minted once
    # at the fleet front door and re-submitted verbatim across every
    # migration and kill-recovery continuation, so the request is ONE
    # continuous track however many replicas it crosses
    trace_id: str | None = None
    # admission tier (higher = more important): what the autopilot's
    # burn-driven shedding orders on — see ServingFleet.shed_queued
    priority: int = 0
    # disaggregated-serving stage: "direct" (unified fleet — the whole
    # request runs where it lands), "prefill" (awaiting its prefill leg
    # on a prefill-role replica: budget clamped to the first token),
    # "decode" (post-handoff or post-fallback: the continuation runs
    # out the remaining budget on a decode-capable replica)
    stage: str = "direct"


def _allocator(batcher):
    """A paged replica's host page allocator (``loop/kv_paging.py``):
    the fleet's prefix directory and capacity ranking read it. None for
    an unpaged replica."""
    return batcher._cache_mgr.allocator


class ServingFleet:
    """Route requests over N ``ContinuousBatcher`` replicas; shrink on
    preemption, grow from published weights.

    Admission rides the PR 5 backpressure contract: :meth:`submit`
    tries live replicas least-loaded-first and lets each replica's
    bounded queue reject (``QueueFullError``); when every replica
    rejects, the fleet re-raises — overload stays an explicit,
    retryable signal end to end. Internal *migrations* (shrink/kill
    recovery) are never dropped on backpressure: they wait in a
    fleet-level overflow queue and re-place at each step boundary.

    Deterministic chaos hooks (``resilience/chaos.py``): the
    ``shrink_at_step`` / ``kill_replica_mid_drain`` injectors arm
    ``_chaos_shrink`` / ``_chaos_kill``, consumed at exact step-round /
    drain-chunk indices.
    """

    def __init__(self, *, publisher: WeightPublisher | None = None,
                 telemetry=None, metrics_port: int | None = None):
        self._replicas: dict[int, Any] = {}
        self._live: set[int] = set()
        self._next_idx = 0
        self._reqs: dict[int, _FleetRequest] = {}
        self._by_replica: dict[tuple[int, int], int] = {}
        self._next_frid = 0
        self._overflow: deque[int] = deque()
        self._publisher = publisher
        self._tele = telemetry if telemetry is not None else get_telemetry()
        self._preemption: tuple[Any, int] | None = None
        self._chaos_shrink: tuple[int, int] | None = None
        self._chaos_kill: tuple[int, int] | None = None
        # disaggregated-serving chaos arms (resilience/chaos.py):
        # kill_prefill_mid_handoff arms the replica idx to die with
        # exported-but-unimported pages in flight; corrupt_handoff_payload
        # arms a byte flip on the next shipment (the checksum must catch)
        self._chaos_kill_handoff: int | None = None
        self._chaos_corrupt_handoff: bool = False
        self._rounds = 0
        # replica roles (docs/design/elasticity.md "Disaggregated
        # serving"): "prefill" replicas take new requests' first-token
        # leg, "decode" replicas run continuations; "unified" (default)
        # does both — an all-unified fleet behaves exactly as before
        self._roles: dict[int, str] = {}
        # fleet-wide prefix directory: content-chain block key → live
        # replica idx whose allocator holds it READY. Rebuilt each
        # scheduling round from the live replicas (a dead owner drops
        # out on the next sync; a stale entry is harmless — export
        # returns None and the request falls back to local prefill),
        # cleared fleet-wide whenever the publisher's generation moves
        self._prefix_dir: dict[bytes, int] = {}
        self._dir_seen_version: int | None = None
        # bound by FleetAutopilot.attach (resilience/autopilot.py):
        # polled once per scheduling round, BEFORE any chunk dispatches
        # — the control loop acts only at this boundary cadence
        self._autopilot = None
        # fleet-level rollup gauges (the per-replica gauges are
        # namespaced serve/r{i}/* — last-write-wins gauges cannot share
        # a name across replicas, so the fleet computes explicit sums);
        # weakref'd so the hub never pins a discarded fleet + replicas
        fleet_ref = weakref.ref(self)
        self._gauge_fns = {
            "serve/fleet_queue_depth":
                lambda: f._queue_depth() if (f := fleet_ref()) is not None
                else float("nan"),
            "serve/fleet_tokens_per_s":
                lambda: f._fleet_rate() if (f := fleet_ref()) is not None
                else float("nan"),
            # paged-KV rollups (docs/design/generation.md): fleet-wide
            # page-pool headroom; NaN while no live replica is paged
            "serve/fleet_kv_pages_free":
                lambda: f._kv_pages("pages_free")
                if (f := fleet_ref()) is not None else float("nan"),
            "serve/fleet_kv_pages_in_use":
                lambda: f._kv_pages("pages_in_use")
                if (f := fleet_ref()) is not None else float("nan"),
            # fleet prefix directory size (disaggregated serving)
            "serve/fleet_prefix_entries":
                lambda: float(len(f._prefix_dir))
                if (f := fleet_ref()) is not None else float("nan"),
        }
        for name, fn in self._gauge_fns.items():
            self._tele.gauge_fn(name, fn)
        # opt-in fleet metrics endpoint (telemetry/export.py): /metrics
        # aggregates every replica's namespaced instruments + the fleet
        # rollups from the shared registry; /healthz reports per-replica
        # status; /readyz = at least one live replica past its first
        # readback. close() shuts it down.
        self.metrics_server = None
        if metrics_port is not None:
            from d9d_tpu.telemetry import MetricsServer

            self.metrics_server = MetricsServer(
                self._tele,
                port=metrics_port,
                readiness=lambda: (
                    (f.ready, {"live_replicas": list(f.live_replicas)})
                    if (f := fleet_ref()) is not None else (False, {})
                ),
                health=lambda: (
                    f.replica_health() if (f := fleet_ref()) is not None
                    else {"gone": True}
                ),
            ).start()
        self.retired: set[int] = set()  # drained cleanly
        self.dead: set[int] = set()     # killed mid-drain
        # fleet-level retirement without completion (mirrors the PR 5
        # batcher surface): frid → reason, partial output kept
        self.failed: dict[int, str] = {}
        # finished requests retire out of _reqs into a bounded-FIFO
        # output snapshot: a long-lived fleet must not grow host memory
        # with total requests served, and finished() must not depend on
        # the replicas' own bounded done-FIFO staying warm (the same
        # retention invariant ContinuousBatcher._retire protects)
        self._finished_outputs: dict[int, list[int]] = {}
        self._finished_fifo: deque[int] = deque()

    # -- monitoring plane ----------------------------------------------

    def _queue_depth(self) -> float:
        """Waiting requests across the fleet: every live replica's
        admission queue plus the fleet-level overflow queue."""
        depth = len(self._overflow)
        for i in self._live:
            depth += len(self._replicas[i]._queue)
        return float(depth)

    def _fleet_rate(self) -> float:
        return float(sum(
            self._replicas[i].live_rate() for i in self._live
        ))

    def _kv_pages(self, attr: str) -> float:
        """Sum a paged-KV pool counter over live PAGED replicas (a
        mixed or unpaged fleet reports NaN rather than a misleading 0
        — absence of paging is not an empty pool)."""
        total, any_paged = 0.0, False
        for i in self._live:
            kv = _allocator(self._replicas[i])
            if kv is not None:
                any_paged = True
                total += float(getattr(kv, attr))
        return total if any_paged else float("nan")

    @property
    def ready(self) -> bool:
        """At least one live replica past its first readback — the
        fleet /readyz contract (a cold fleet mid-compile is not ready,
        a fleet that lost one replica but still serves is)."""
        return any(
            getattr(self._replicas[i], "ready", False) for i in self._live
        )

    def replica_health(self) -> dict[str, Any]:
        """Per-replica status block for the fleet /healthz endpoint —
        with an autopilot bound, its control-loop state (burning
        policies, pending canary, last decision) rides along so one
        scrape explains both what the fleet looks like and what the
        controller is about to do about it."""
        replicas = {}
        for idx, b in self._replicas.items():
            replicas[str(idx)] = {
                "live": idx in self._live,
                "retired": idx in self.retired,
                "dead": idx in self.dead,
                "ready": bool(getattr(b, "ready", False)),
                "active": int(b.active),
                "role": self._role(idx),
            }
        roles: dict[str, int] = {}
        for i in self._live:
            roles[self._role(i)] = roles.get(self._role(i), 0) + 1
        out = {
            "replicas": replicas,
            "overflow": len(self._overflow),
            "ready": self.ready,
            # live-replica count per fleet role: the disaggregated
            # provisioning view (what the role-aware autopilot scales)
            "roles": roles,
        }
        if self._autopilot is not None:
            out["autopilot"] = self._autopilot.status()
        return out

    def close(self) -> None:
        """Release the fleet's host-side attachments (metrics endpoint,
        the fleet rollup gauges, every replica's)."""
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None
        for name, fn in self._gauge_fns.items():
            # fn-guarded: a newer fleet's registration under the same
            # name must survive this (older) fleet's close
            self._tele.registry.unregister_gauge_fn(name, fn)
        for b in self._replicas.values():
            close = getattr(b, "close", None)
            if close is not None:
                close()

    def _trace(self, trace_id: str | None, event: str, **meta) -> None:
        """Fleet-side request_trace event (migrations, continuations —
        milestones no single replica can see)."""
        if trace_id is None:
            return
        rec: dict[str, Any] = {
            "trace_id": trace_id, "event": event, "t": time.perf_counter(),
        }
        if meta:
            rec["meta"] = meta
        self._tele.record_request_trace(rec)

    # -- replica lifecycle ---------------------------------------------

    _ROLES = ("prefill", "decode", "unified")

    def add_replica(self, batcher, *, role: str = "unified") -> int:
        """Register a replica under a fleet role. ``prefill`` replicas
        take new requests' first-token leg and hand off via KV page
        shipment; ``decode`` replicas run the continuations; ``unified``
        (the default) does both — a fleet of unified replicas behaves
        exactly as before this distinction existed."""
        if role not in self._ROLES:
            raise ValueError(
                f"role must be one of {self._ROLES}, got {role!r}"
            )
        idx = self._next_idx
        self._next_idx += 1
        self._replicas[idx] = batcher
        self._roles[idx] = role
        self._live.add(idx)
        # replica conflation fix (docs/design/observability.md): each
        # replica's serve instruments get a fleet-assigned namespace
        # (serve/r{i}/...) unless the embedder labeled it already
        if (
            getattr(batcher, "replica_label", None) is None
            and hasattr(batcher, "set_replica_label")
        ):
            batcher.set_replica_label(f"r{idx}")
        if self._publisher is not None:
            self._publisher.attach(batcher)
            if self._publisher.latest_params is not None:
                # latest_version, not version: while a canary is pending
                # the version counter belongs to the canary generation —
                # a replica added mid-canary runs the RETAINED tree and
                # must carry that tree's stamp
                batcher.install_weights(
                    self._publisher.latest_params,
                    version=self._publisher.latest_version,
                )
        self._tele.gauge("serve/fleet_replicas").set(len(self._live))
        return idx

    def grow(
        self, make_batcher: Callable[[PyTree], Any], *,
        role: str = "unified",
    ) -> int:
        """Cold-start a replacement replica from the latest *published*
        weights — the recovery half of a preemption shrink. The factory
        receives the published param tree and returns a batcher;
        ``role`` assigns the new replica's fleet pool (the role-aware
        autopilot grows prefill and decode pools independently)."""
        if self._publisher is None or self._publisher.latest_params is None:
            raise RuntimeError(
                "grow() cold-starts replicas from the latest published "
                "weights; attach a WeightPublisher and publish first"
            )
        idx = self.add_replica(
            make_batcher(self._publisher.latest_params), role=role
        )
        self._tele.counter("serve/fleet_grows").add(1)
        return idx

    def bind_preemption(self, guard, replica_idx: int) -> None:
        """Wire PR 5's preemption signal as the shrink trigger: once
        ``guard.triggered`` (SIGTERM landed), the next :meth:`step`
        drains ``replica_idx`` into the survivors."""
        self._preemption = (guard, int(replica_idx))

    # -- admission ------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        *,
        max_new_tokens: int,
        deadline_s: Optional[float] = None,
        priority: int = 0,
    ) -> int:
        """Queue a request on the least-loaded live replica; returns the
        fleet-level request id. Raises ``QueueFullError`` when every
        live replica's bounded queue rejects (fleet-level backpressure:
        shed or retry, exactly like the single-replica contract).

        ``priority`` tiers admission for the autopilot's burn-driven
        shedding (higher = protected longer; admission order itself
        stays FIFO — see ``ContinuousBatcher.submit``).

        The fleet front door mints the request's trace id here; every
        placement (including migrations and kill-recovery continuations)
        re-submits with the same id, so the request's schema-v3
        ``request_trace`` stream is one continuous track."""
        from d9d_tpu.loop.serve import QueueFullError, mint_trace_id

        frid = self._next_frid
        self._next_frid += 1
        # with any live prefill-role replica the request runs its
        # first-token leg there and hands off (docs/design/elasticity.md
        # "Disaggregated serving"); an all-unified/decode fleet serves
        # it in one place, exactly as before roles existed
        disagg = any(self._role(i) == "prefill" for i in self._live)
        req = _FleetRequest(
            [int(x) for x in prompt], int(max_new_tokens),
            time.perf_counter() + deadline_s
            if deadline_s is not None else None,
            trace_id=mint_trace_id(),
            priority=int(priority),
            stage="prefill" if disagg else "direct",
        )
        self._reqs[frid] = req
        # front-door placements consult the fleet prefix directory, so
        # refresh it HERE, not just at step boundaries — a shared prompt
        # submitted right after its twin finished must still ship pages
        # instead of recomputing ("once per fleet", not "once per round")
        self._sync_prefix_dir()
        try:
            placed = self._try_place(frid)
        except BaseException:
            # a replica-side validation error (bad budget, prompt over
            # decode_max_length, ...) must not leave a ghost request
            # that can never finish and wedges every later drain()
            del self._reqs[frid]
            raise
        if not placed:
            del self._reqs[frid]
            # the fleet owns the terminal rejection event: individual
            # replica rejections during placement are not terminal (a
            # survivor may still accept), this is
            self._trace(req.trace_id, "rejected",
                        live_replicas=len(self._live))
            raise QueueFullError(
                f"all {len(self._live)} live replicas rejected the "
                "request (bounded queues full); retry after drain"
            )
        return frid

    def _role(self, i: int) -> str:
        return self._roles.get(i, "unified")

    def _capacity_short(self, i: int, total_tokens: int) -> bool:
        """Would replica ``i``'s page pool head-of-line-block a request
        of this token footprint even after the next deferred flush?
        Contiguous replicas are never short (admission is slot-bounded
        there); prefix hits and LRU eviction could only help, so this
        is a conservative RANKING signal, not an admission gate."""
        kv = _allocator(self._replicas[i])
        if kv is None:
            return False
        return kv.pages_needed(total_tokens) > kv.pages_free_after_flush()

    def _place_order(
        self, req: _FleetRequest, *, exclude: frozenset = frozenset()
    ) -> list[int]:
        """Placement candidates, best first: role pool (a prefill-stage
        request prefers prefill replicas, a continuation prefers
        decode, unified serves either; the off-role pools stay as
        fallbacks — availability beats role purity), then KV capacity
        (a paged replica whose pool cannot map the request ranks behind
        one with headroom instead of accepting a head-of-line wait),
        then least-loaded."""
        if req.stage == "prefill":
            pools = ("prefill", "unified", "decode")
            remaining = 1
        else:
            pools = ("decode", "unified", "prefill")
            remaining = max(req.max_new_tokens - len(req.prefix), 1)
        total = len(req.prompt) + len(req.prefix) + remaining - 1
        return sorted(
            (i for i in self._live if i not in exclude),
            key=lambda i: (
                pools.index(self._role(i)),
                self._capacity_short(i, total),
                self._replicas[i].active,
                i,
            ),
        )

    def _try_place(
        self, frid: int, *, exclude: frozenset = frozenset(),
        prefer: int | None = None,
    ) -> bool:
        from d9d_tpu.loop.serve import QueueFullError

        req = self._reqs[frid]
        remaining = req.max_new_tokens - len(req.prefix)
        if remaining <= 0:
            return True  # fully emitted before its last replica died
        if req.stage == "prefill":
            # the prefill leg fills the prompt's pages and emits the
            # FIRST token (TTFT happens here); the remaining budget
            # runs on the decode side after the handoff
            remaining = 1
        deadline_s = None
        if req.deadline_t is not None:
            # preserve the ABSOLUTE deadline across migrations: the
            # survivor gets only the time still left on the contract
            deadline_s = req.deadline_t - time.perf_counter()
            if deadline_s <= 0:
                self.failed[frid] = "deadline"
                self._tele.counter("serve/expired").add(1)
                self._trace(
                    req.trace_id, "expired", reason="deadline",
                    at="fleet_place", tokens=len(req.prefix),
                )
                req.replica = req.local_rid = None
                return True  # retired: partial prefix kept, like PR 5
        order = self._place_order(req, exclude=exclude)
        if prefer is not None and prefer in order:
            order.remove(prefer)
            order.insert(0, prefer)
        prompt = req.prompt + req.prefix
        shipped = False
        for i in order:
            if not shipped:
                # fleet prefix directory: before the first (best)
                # candidate prefills a prompt another replica already
                # holds, ship those pages over instead of recomputing —
                # a shared prompt prefills once per FLEET. One attempt
                # per placement; failures just mean a local prefill.
                shipped = True
                self._maybe_ship_prefix(prompt, i)
            try:
                rid = self._replicas[i].submit(
                    prompt,
                    max_new_tokens=remaining,
                    deadline_s=deadline_s,
                    trace_id=req.trace_id,
                    priority=req.priority,
                )
            except QueueFullError:
                continue
            req.replica, req.local_rid = i, rid
            self._by_replica[(i, rid)] = frid
            return True
        req.replica = req.local_rid = None
        return False

    def _maybe_ship_prefix(self, prompt: list[int], target: int) -> None:
        """Local prefix miss + fleet-directory hit: ship the cached
        pages from their live owner into ``target`` before the prompt
        admits there. Every failure (stale directory entry, dead or
        mid-chunk owner, version skew, checksum, pool pressure) counts
        a miss and degrades to a local prefill — never an error."""
        tb = self._replicas[target]
        kv = _allocator(tb)
        if kv is None or not kv.prefix_cache_enabled or not self._prefix_dir:
            return
        ps = kv.page_size
        cap = (len(prompt) - 1) // ps  # admission's max hit run
        if cap <= 0:
            return
        tokens = prompt[: cap * ps]
        if len(kv.export_prefix(tokens)) >= cap:
            return  # full local hit: nothing a shipment could add
        keys = kv._chain_keys(tokens, cap)
        owner = None
        for d in range(cap - 1, -1, -1):  # deepest cached block wins
            cand = self._prefix_dir.get(keys[d])
            if cand is not None and cand in self._live and cand != target:
                owner = cand
                break
        if owner is None:
            self._tele.counter("serve/fleet_prefix_misses").add(1)
            return
        ship = self._replicas[owner].export_kv_pages(tokens)
        if ship is not None and tb.import_kv_pages(ship):
            self._tele.counter("serve/fleet_prefix_hits").add(1)
        else:
            self._tele.counter("serve/fleet_prefix_misses").add(1)

    def shed_queued(self, n: int) -> list[int]:
        """Retire up to ``n`` QUEUED (never-admitted) fleet requests as
        explicit ``failed[frid] == "shed"`` — lowest priority first,
        longest remaining deadline first within a tier (a deadline-less
        request is infinitely patient: it sheds before anything with a
        contract), newest first as the final tiebreak. Running rows are
        never shed (their committed tokens are real work); shedding
        only empties queue positions, which is exactly what relieves a
        burning latency SLO and what frees bounded-queue capacity so
        high-priority traffic stops seeing ``QueueFullError`` at the
        front door. Returns the shed fleet request ids.

        This is the autopilot's actuator (burn-driven admission
        tiering, docs/design/elasticity.md "SLO autopilot"); callers
        may also invoke it directly as a manual load-shed."""
        if n <= 0:
            return []
        queued_rids = {
            (i, q.rid)
            for i in self._live
            for q in self._replicas[i]._queue
        }
        overflow = set(self._overflow)
        candidates = []
        for frid, req in self._reqs.items():
            if frid in self.failed:
                continue
            if frid in overflow:
                where = "overflow"
            elif (
                req.replica is not None
                and (req.replica, req.local_rid) in queued_rids
            ):
                where = "replica"
            else:
                continue  # running (or already finishing): never shed
            candidates.append((frid, req, where))
        candidates.sort(key=lambda item: (
            item[1].priority,
            -(item[1].deadline_t if item[1].deadline_t is not None
              else math.inf),
            -item[0],
        ))
        shed: list[int] = []
        for frid, req, where in candidates[:n]:
            if where == "overflow":
                self._overflow.remove(frid)
                self._tele.counter("serve/shed").add(1)
                self._trace(
                    req.trace_id, "failed", reason="shed",
                    at="fleet_overflow", priority=req.priority,
                )
            else:
                b = self._replicas[req.replica]
                if not b.cancel_queued(req.local_rid, "shed"):
                    continue  # admitted since the scan: let it run
                self._by_replica.pop((req.replica, req.local_rid), None)
            self.failed[frid] = "shed"
            shed.append(frid)
        return shed

    # -- progress -------------------------------------------------------

    # finished-request output snapshots retained for the host API
    _MAX_FINISHED = 50_000

    def finished(self, frid: int) -> bool:
        if frid in self._finished_outputs or frid in self.failed:
            return True
        req = self._reqs.get(frid)
        if req is None:
            if 0 <= frid < self._next_frid:
                return True  # retired beyond the retention horizon
            raise KeyError(f"unknown fleet request id {frid}")
        if req.replica is None:
            return len(req.prefix) >= req.max_new_tokens
        b = self._replicas[req.replica]
        if req.local_rid not in b.done:
            return False
        if req.stage == "prefill" and req.local_rid not in b.failed:
            # the prefill LEG is done but the request is not: the
            # handoff (step()._poll_handoffs) still owes the decode
            # placement — unless the first token already exhausted the
            # budget, or EOS landed on it
            emitted = len(req.prefix) + len(b.outputs.get(req.local_rid, []))
            if emitted >= req.max_new_tokens:
                return True
            eos = getattr(b, "_eos", None)
            out = b.outputs.get(req.local_rid, [])
            return bool(out) and eos is not None and out[-1] == eos
        return True

    def outputs(self, frid: int) -> list[int]:
        """Emitted tokens for a fleet request: dead-replica prefix plus
        whatever its current replica has harvested (a retired request
        returns its snapshot, within the bounded retention horizon —
        like the batcher's ``_MAX_FINISHED_STATS`` contract, read
        results within it; past it this raises with an explanation)."""
        if frid in self._finished_outputs:
            return list(self._finished_outputs[frid])
        req = self._reqs.get(frid)
        if req is None:
            if 0 <= frid < self._next_frid:
                raise KeyError(
                    f"fleet request {frid} finished and was evicted from "
                    f"the bounded retention horizon "
                    f"({self._MAX_FINISHED} snapshots)"
                )
            raise KeyError(f"unknown fleet request id {frid}")
        toks = list(req.prefix)
        if req.replica is not None:
            toks += list(
                self._replicas[req.replica].outputs.get(req.local_rid, [])
            )
        return toks[: req.max_new_tokens]

    def _retire_finished(self) -> None:
        """Snapshot finished requests' outputs and drop their live
        records (bounded FIFO) — called at the end of every drain so
        neither ``_reqs`` nor ``_by_replica`` grows with lifetime
        traffic, and a finished request's result stays readable even
        after its replica's own done-FIFO rotates."""
        for frid in [f for f in self._reqs if self.finished(f)]:
            self._finished_outputs[frid] = self.outputs(frid)
            req = self._reqs.pop(frid)
            if req.replica is not None:
                # surface replica-level retirements (deadline expiry on
                # the replica) at the fleet: "finished" must not make a
                # failed request read as a successful short completion
                reason = self._replicas[req.replica].failed.get(
                    req.local_rid
                )
                if reason is not None:
                    self.failed.setdefault(frid, reason)
                self._by_replica.pop((req.replica, req.local_rid), None)
            self._finished_fifo.append(frid)
        while len(self._finished_fifo) > self._MAX_FINISHED:
            old = self._finished_fifo.popleft()
            self._finished_outputs.pop(old, None)
            self.failed.pop(old, None)

    def step(self) -> None:
        """One scheduling round: poll the bound autopilot (its control
        actions happen HERE, at the clean boundary before any chunk
        dispatches — never on an evaluation thread), consume the
        preemption/chaos triggers, retry overflow placements, advance
        every live replica a chunk."""
        self._rounds += 1
        if self._autopilot is not None:
            self._autopilot.poll()
        if self._preemption is not None:
            guard, idx = self._preemption
            if guard.triggered and idx in self._live:
                self._preemption = None
                self._tele.counter("resilience/preempt_shrinks").add(1)
                self.shrink(idx)
        if (
            self._chaos_shrink is not None
            and self._rounds >= self._chaos_shrink[1]
            and self._chaos_shrink[0] in self._live
        ):
            idx = self._chaos_shrink[0]
            self._chaos_shrink = None
            self.shrink(idx)
        self._sync_prefix_dir()
        self._poll_handoffs()
        for frid in [self._overflow.popleft() for _ in range(len(self._overflow))]:
            if not self._try_place(frid):
                self._overflow.append(frid)
        for i in sorted(self._live):
            self._replicas[i].step_chunk()

    # -- disaggregated serving: prefix directory + handoff -------------

    def _sync_prefix_dir(self) -> None:
        """Rebuild the fleet prefix directory from the live paged
        replicas' READY entries (dead/retired owners drop out here).
        A weight publish moves the generation: the directory clears
        fleet-wide and repopulates NEXT round, once the replicas have
        applied the publish at their own boundaries — and the shipment
        weights-version pin keeps even the in-between window safe."""
        if self._publisher is not None:
            v = self._publisher.version
            if v != self._dir_seen_version:
                self._dir_seen_version = v
                if self._prefix_dir:
                    self._prefix_dir = {}
                    self._tele.counter(
                        "serve/fleet_prefix_invalidations"
                    ).add(1)
                return
        dir_: dict[bytes, int] = {}
        for i in sorted(self._live):
            kv = _allocator(self._replicas[i])
            if kv is None or not kv.prefix_cache_enabled:
                continue
            for key, e in kv._entries.items():
                if e.ready and key not in dir_:
                    dir_[key] = i
        self._prefix_dir = dir_

    def _poll_handoffs(self) -> None:
        """Advance prefill-stage requests whose first-token leg is done:
        harvest the leg's tokens into the continuation prefix, flip the
        stage to decode, and hand off (page shipment + placement). A
        leg that already exhausted its budget or hit EOS is complete —
        it retires through the normal finished() path untouched."""
        for frid, req in list(self._reqs.items()):
            if (
                req.stage != "prefill" or req.replica is None
                or frid in self.failed
            ):
                continue
            src = req.replica
            b = self._replicas[src]
            if req.local_rid not in b.done or req.local_rid in b.failed:
                continue
            out = list(b.outputs.get(req.local_rid, []))
            eos = getattr(b, "_eos", None)
            if len(req.prefix) + len(out) >= req.max_new_tokens or (
                out and eos is not None and out[-1] == eos
            ):
                continue  # complete at the prefill leg: nothing to hand off
            self._by_replica.pop((src, req.local_rid), None)
            req.prefix = req.prefix + out
            req.replica = req.local_rid = None
            req.stage = "decode"
            self._handoff(frid, req, src)

    def _handoff(self, frid: int, req: _FleetRequest, src: int) -> None:
        """One prefill→decode handoff: export the prompt's READY prefix
        pages from the prefill replica, import them into the chosen
        decode target, place the continuation there. The original trace
        id, absolute deadline, priority tier and weights-version pin
        all ride along. EVERY failure — dead source, dirty boundary,
        version skew, corrupt shipment, pool pressure — degrades to the
        placement below, which re-prefills from prompt + harvested
        tokens token-identically (the PR 8/10 kill-recovery contract):
        fallback, not failure, is the contract."""
        prompt = req.prompt + req.prefix
        order = self._place_order(req)
        targets = [i for i in order if i != src] or order
        target = targets[0] if targets else None
        ship = None
        src_b = self._replicas.get(src)
        if target is not None and src in self._live and src_b is not None:
            tkv = _allocator(self._replicas[target])
            if tkv is not None and _allocator(src_b) is not None:
                cap = (len(prompt) - 1) // tkv.page_size
                if cap > 0:
                    ship = src_b.export_kv_pages(
                        prompt[: cap * tkv.page_size]
                    )
        if self._chaos_kill_handoff == src:
            # chaos: the prefill replica dies with exported-but-
            # unimported pages in flight — the shipment is lost with
            # it; its other in-flight requests recover via continuation
            self._chaos_kill_handoff = None
            ship = None
            self._live.discard(src)
            self._tele.gauge("serve/fleet_replicas").set(len(self._live))
            self._recover_killed(src)
        if ship is not None and self._chaos_corrupt_handoff:
            # chaos: flip one payload byte — the per-page checksum must
            # catch it BEFORE the importer mutates anything
            self._chaos_corrupt_handoff = False
            name = sorted(ship.payload)[0]
            raw = ship.payload[name].copy()
            raw.view(np.uint8).flat[0] ^= 0xFF
            ship.payload[name] = raw
        imported = False
        if ship is not None and target is not None:
            imported = self._replicas[target].import_kv_pages(ship)
        if imported:
            self._tele.counter("serve/fleet_handoffs").add(1)
        else:
            self._tele.counter("serve/fleet_handoff_fallbacks").add(1)
        self._trace(
            req.trace_id, "handoff",
            from_replica=src, to_replica=target,
            pages=ship.n_pages if (ship is not None and imported) else 0,
            fallback=not imported, prefix_tokens=len(req.prefix),
        )
        if not self._try_place(frid, prefer=target):
            self._overflow.append(frid)

    def drain(self, max_rounds: int = 10_000) -> dict[int, list[int]]:
        """Run scheduling rounds until every live fleet request
        finishes; returns ``{fleet_rid: tokens}`` for them, then
        retires their records into the bounded snapshot store."""
        rounds = 0
        while not all(self.finished(frid) for frid in self._reqs):
            self.step()
            rounds += 1
            if rounds > max_rounds:
                raise RuntimeError("fleet drain exceeded max_rounds")
        out = {frid: self.outputs(frid) for frid in self._reqs}
        self._retire_finished()
        return out

    # -- shrink / recovery ---------------------------------------------

    def shrink(self, idx: int) -> None:
        """Retire replica ``idx``: stop routing to it, migrate its
        queued (never-admitted) requests into survivors under the
        backpressure contract, and drain its running rows to completion
        inside the preemption grace window. A replica that dies during
        this drain is recovered by :meth:`_recover_killed`."""
        b = self._replicas[idx]
        self._live.discard(idx)
        self._tele.counter("serve/fleet_shrinks").add(1)
        self._tele.gauge("serve/fleet_replicas").set(len(self._live))
        for rid, _prompt, _mnt, _dl in b.eject_queued():
            frid = self._by_replica.pop((idx, rid), None)
            if frid is None:
                # submitted directly to the batcher, not through the
                # fleet: it can't be migrated (the caller holds THIS
                # replica's rid), so retire it as an explicit failure
                # instead of silently destroying it
                b.fail_request(rid, "shrunk")
                continue
            # migrated: the receiving replica re-admits under a new
            # local rid; drop the dying replica's now-dead records
            b.outputs.pop(rid, None)
            b.request_stats.pop(rid, None)
            req = self._reqs[frid]
            req.replica = req.local_rid = None
            req.migrations += 1
            self._tele.counter("serve/fleet_migrated").add(1)
            self._trace(
                req.trace_id, "migrate", reason="shrink",
                from_replica=idx, migrations=req.migrations,
            )
            if not self._try_place(frid, exclude=frozenset({idx})):
                self._overflow.append(frid)
        chunks = 0
        while b._busy() or b._pending:
            if (
                self._chaos_kill is not None
                and self._chaos_kill[0] == idx
                and chunks >= self._chaos_kill[1]
            ):
                self._chaos_kill = None
                self._recover_killed(idx)
                return
            b.step_chunk()
            chunks += 1
            # the grace drain must not stall the rest of the fleet: the
            # survivors — now carrying the migrated queue — keep
            # dispatching while the dying replica finishes its rows
            # (their own deadlines are absolute; a synchronous-only
            # drain would expire them spuriously)
            for i in sorted(self._live):
                self._replicas[i].step_chunk()
        self.retired.add(idx)

    def _recover_killed(self, idx: int) -> None:
        """The dying replica is gone mid-drain: resubmit its unfinished
        requests to survivors as continuation prompts (original prompt +
        tokens already harvested), so completed work is kept and greedy
        decoding resumes token-identically."""
        b = self._replicas[idx]
        self.dead.add(idx)
        self._tele.counter("serve/fleet_replica_deaths").add(1)
        # the dead replica's prefix pages die with it: drop its directory
        # entries NOW so no waiter wedges on a dead owner — shipping falls
        # back to local prefill until the next directory rebuild
        self._prefix_dir = {
            k: i for k, i in self._prefix_dir.items() if i != idx
        }
        recovered = 0
        for frid, req in self._reqs.items():
            if req.replica != idx or req.local_rid in b.done:
                continue
            # the dead replica's mapping is gone with it — drop it so
            # the index doesn't accumulate stale (dead-replica, rid)
            # entries across migrations
            self._by_replica.pop((idx, req.local_rid), None)
            req.prefix = req.prefix + list(b.outputs.get(req.local_rid, []))
            req.replica = req.local_rid = None
            req.migrations += 1
            recovered += 1
            self._tele.counter("serve/fleet_migrated").add(1)
            # the continuation keeps the ORIGINAL trace id: the harvested
            # prefix + the survivor's teacher-forced replay stay one track
            self._trace(
                req.trace_id, "continuation", reason="replica_death",
                from_replica=idx, prefix_tokens=len(req.prefix),
                migrations=req.migrations,
            )
            if len(req.prefix) >= req.max_new_tokens:
                continue
            if not self._try_place(frid, exclude=frozenset({idx})):
                self._overflow.append(frid)
        # black-box dump at the moment of death (no-op unless a flight
        # recorder is configured on the hub): the last metric windows +
        # span tail are exactly the post-mortem a dead replica can no
        # longer answer for itself
        self._tele.dump_flight_record(
            "replica_death",
            extra={"replica": idx, "recovered_requests": recovered},
        )

    @property
    def live_replicas(self) -> tuple[int, ...]:
        return tuple(sorted(self._live))
