"""ZeRO-style optimizer-state sharding over the ``dp_r`` (dp_replicate)
mesh axis (PAPERS.md, arxiv 2004.13336 — ZeRO stage 1/2).

The data-parallel replicate axis keeps a full copy of the fp32 masters
and Adam moments on every chip, and the optimizer step streams all of it
through HBM (the fp32 master/optimizer stream plus the fp32 grad
accumulator). ZeRO has never run on the chip (ROADMAP R-L).
ZeRO's observation is that the *update* is elementwise, so each replica
only needs 1/N of the state:

- gradients are **reduce-scattered** into the local shard (the scan-carry
  grad accumulator is annotated with the sharded spec, so XLA turns the
  backward's dp_r all-reduce into a reduce-scatter and the fp32
  accumulator itself shrinks to 1/N per chip);
- the optimizer **update runs on 1/N** of the masters/moments (the
  moments live sharded in HBM between steps — the durable 1/N);
- the new parameters are **all-gathered** back to the replicated layout
  the forward pass needs.

Everything is expressed as ``with_sharding_constraint`` annotations
around the existing ``optimizer.update`` / ``apply_updates`` seam
(loop/train_step.py, pipelining/training.py) — XLA SPMD inserts the
reduce-scatter/all-gather pair and fuses it with the update, so the
update math is untouched and CPU-exactness-testable against the
replicated path (tests/parallel/test_zero.py).

Composition: the transform *extends* each leaf's existing sharding (the
plan's fsdp/ep axes stay), adding ``dp_r`` to the largest still-divisible
dim. Leaves with no eligible dim (scalars, the StochasticAdamW RNG key,
odd shapes) stay as they are — the transform degrades per-leaf, never
per-tree. With ``dp_replicate == 1`` every constraint is an identity, so
the wrapped path is bit-identical to the unwrapped one by construction.

Checkpoint interplay: sharded state keeps its **global** shapes — only
the placement changes — so orbax saves/restores round-trip unchanged,
and restoring a sharded save onto a replicated mesh layout (or vice
versa) is just a resharding device_put on load (gather-on-load), driven
by the live state the trainer passes as the restore target
(tests/loop/test_zero_checkpoint.py). The same contract carries across
*chip counts*: the trainer builds these tables from the live state on
whatever mesh it initialized with, so an N-chip ``dp_replicate`` save
restores onto M chips as the M-chip 1/M layout with no table
translation — the elastic-restore path (docs/design/elasticity.md,
tests/resilience/test_elastic_restore.py) only adds mismatch detection
and HBM-bounded staging on top.
"""

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from d9d_tpu.core.mesh import AXIS_DP_REPLICATE
from d9d_tpu.core.types import PyTree

__all__ = [
    "ZeroSharding",
    "ZeroShardedOptimizer",
    "build_zero_sharding",
    "constrain_tree",
    "place_tree",
]


def _axis_names(entry) -> tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, tuple):
        return tuple(entry)
    return (entry,)


def _extend_spec(
    spec: P, shape: tuple[int, ...], mesh: Mesh, axis: str
) -> P | None:
    """Insert ``axis`` into ``spec`` on the best eligible dim of ``shape``.

    Eligible: the dim's per-shard size (after any axes already in its
    entry) divides evenly by the new axis size and the entry doesn't
    already name ``axis``. Among eligible dims the one with the largest
    per-shard size wins (maximum bytes moved off-replica). Returns None
    when no dim is eligible (or ``axis`` already shards the leaf) — the
    caller leaves such leaves untouched.
    """
    n = mesh.shape[axis]
    if n <= 1:
        return None
    entries = list(spec) + [None] * (len(shape) - len(spec))
    best: tuple[int, int] | None = None  # (per_shard_size, dim)
    for d, size in enumerate(shape):
        names = _axis_names(entries[d])
        if axis in names:
            return None
        factor = math.prod(mesh.shape[a] for a in names) if names else 1
        if size % (factor * n) != 0:
            continue
        per = size // factor
        if best is None or per > best[0]:
            best = (per, d)
    if best is None:
        return None
    d = best[1]
    names = _axis_names(entries[d])
    entries[d] = names + (axis,) if names else axis
    return P(*entries)


def _spec_of(leaf: jax.Array, mesh: Mesh, candidates: list[P]) -> P | None:
    """Recover the PartitionSpec of ``leaf``'s current placement.

    jit outputs on this rig carry GSPMD shardings (no spec attribute), so
    non-Named shardings are matched by *equivalence* against the small
    candidate set a job actually uses: replicated plus the distinct specs
    of the parameter tree. Unmatched placements return None and the leaf
    is left alone — never guess a spec and silently reshard.
    """
    sh = leaf.sharding
    if isinstance(sh, NamedSharding):
        return sh.spec
    for spec in candidates:
        try:
            if sh.is_equivalent_to(NamedSharding(mesh, spec), leaf.ndim):
                return spec
        except Exception:  # noqa: BLE001 — exotic sharding: skip the leaf
            return None
    return None


def _shardable(leaf: Any, axis_size: int) -> bool:
    """Only float leaves big enough to split carry optimizer state worth
    sharding; integer riders (step counters, the StochasticAdamW RNG
    key) stay replicated so their semantics can't be touched."""
    return (
        isinstance(leaf, jax.Array)
        and jnp.issubdtype(leaf.dtype, jnp.floating)
        and leaf.ndim >= 1
        and leaf.size >= axis_size
    )


@dataclasses.dataclass(frozen=True)
class ZeroSharding:
    """The computed sharding tables for one (params, opt_state) pair.

    ``grad_shardings``/``state_shardings`` leaves are ``NamedSharding``s
    where the leaf participates in the 1/N split and ``None`` where it is
    left untouched; ``param_shardings`` is the gather-back target (the
    parameters' original placement).
    """

    axis: str
    axis_size: int
    mesh: Mesh
    param_shardings: PyTree
    grad_shardings: PyTree
    state_shardings: PyTree
    # per-microbatch gradients are pinned to this (the parameters' own,
    # axis-replicated layout) BEFORE being accumulated into the sharded
    # carry: the backward pass then partitions exactly as the unsharded
    # baseline — XLA's bidirectional sharding propagation would otherwise
    # re-partition the backward matmuls off the carry constraint and
    # perturb gradient values at the ulp level. The accumulate is then a
    # shard-local elementwise add (carry[i] += g[i]), so accumulated
    # grads, moments and parameters stay BITWISE identical to the
    # replicated path; only the grad-norm scalar (reduced shard-wise +
    # psum instead of whole-array) can differ in summation order.
    grad_pin_shardings: PyTree = None

    @property
    def active(self) -> bool:
        return self.axis_size > 1


def build_zero_sharding(
    *,
    params: PyTree,
    opt_state: PyTree,
    mesh: Mesh,
    axis: str = AXIS_DP_REPLICATE,
) -> ZeroSharding:
    """Compute the ZeRO sharding tables from live (concrete) trees.

    Must run on the *initialized* state — shardings are read off the
    arrays themselves, so the plan's fsdp/ep placement composes without
    re-deriving it here.
    """
    if axis not in mesh.shape:
        raise ValueError(
            f"zero sharding axis {axis!r} not in mesh axes "
            f"{tuple(mesh.shape)}"
        )
    n = mesh.shape[axis]

    param_sh = jax.tree.map(
        lambda p: p.sharding if isinstance(p, jax.Array) else None, params
    )
    candidates: list[P] = [P()]
    for sh in jax.tree.leaves(param_sh):
        if isinstance(sh, NamedSharding) and sh.spec not in candidates:
            candidates.append(sh.spec)

    def extend(leaf):
        if not _shardable(leaf, n):
            return None
        spec = _spec_of(leaf, mesh, candidates)
        if spec is None:
            return None
        new_spec = _extend_spec(spec, leaf.shape, mesh, axis)
        if new_spec is None:
            return None
        return NamedSharding(mesh, new_spec)

    grad_sh = jax.tree.map(extend, params)
    # pin targets: only leaves that actually reshard need the baseline
    # anchor (see the field comment); leave the rest unconstrained
    grad_pin = jax.tree.map(
        lambda g_sh, p_sh: p_sh if g_sh is not None else None,
        grad_sh,
        param_sh,
        is_leaf=_none_leaf,
    )
    return ZeroSharding(
        axis=axis,
        axis_size=n,
        mesh=mesh,
        param_shardings=param_sh,
        grad_shardings=grad_sh,
        state_shardings=jax.tree.map(extend, opt_state),
        grad_pin_shardings=grad_pin,
    )


def _none_leaf(x: Any) -> bool:
    # sharding tables carry None where a leaf opted out; None is normally
    # an EMPTY pytree, so the table must lead the map with None-as-leaf
    # for the structures to stay zippable
    return x is None


def constrain_tree(tree: PyTree, shardings: PyTree) -> PyTree:
    """``with_sharding_constraint`` each leaf whose sharding entry is not
    None (trace-time annotation; XLA inserts the collectives)."""
    return jax.tree.map(
        lambda s, x: x if s is None else lax.with_sharding_constraint(x, s),
        shardings,
        tree,
        is_leaf=_none_leaf,
    )


def place_tree(tree: PyTree, shardings: PyTree) -> PyTree:
    """Eagerly reshard ``tree`` onto ``shardings`` (None = leave leaf).

    Used once at init (and after a gather-on-load restore) to move the
    live optimizer state onto its 1/N layout.
    """
    return jax.tree.map(
        lambda s, x: x if s is None else jax.device_put(x, s),
        shardings,
        tree,
        is_leaf=_none_leaf,
    )


def tree_bytes_per_device(tree: PyTree) -> int:
    """Per-chip bytes of a (possibly sharded) pytree — the
    ``opt/state_bytes_per_chip`` gauge and bench column. Host leaves
    count their full size (they are replicated by definition)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        if isinstance(leaf, jax.Array):
            try:
                shape = leaf.sharding.shard_shape(leaf.shape)
            except Exception:  # noqa: BLE001 — unplaced/abstract: full size
                shape = leaf.shape
        total += math.prod(shape) * jnp.dtype(dtype).itemsize
    return total


class ZeroShardedOptimizer:
    """Wrap any engine-compatible optimizer with the ZeRO collectives.

    ``update`` constrains the incoming grads to the 1/N layout
    (reduce-scatter — a no-op slice when the accumulator upstream already
    carries the sharded spec), runs the inner update (which XLA then
    partitions to the shard), and pins the new state back onto the 1/N
    layout; ``apply_updates`` constrains the written parameters back to
    their original (replicated-over-``axis``) placement — the all-gather.

    The wrapper preserves the OptimizerOwnsApply capabilities of the
    inner optimizer (``accepts_fp32_grads`` passthrough; StochasticAdamW
    keeps owning its stochastic-rounding write).
    """

    def __init__(self, inner, zero: ZeroSharding):
        self.inner = inner
        self.zero = zero

    @property
    def accepts_fp32_grads(self) -> bool:
        return getattr(self.inner, "accepts_fp32_grads", False)

    def init(self, params: PyTree):
        # plain inner init: the sharded placement is applied eagerly by
        # the caller via place_tree (build_zero_sharding needs the
        # concrete state first, so init-time constraint would be circular)
        return self.inner.init(params)

    def update(
        self, grads: PyTree, state: PyTree, params: PyTree
    ) -> tuple[PyTree, PyTree]:
        grads = constrain_tree(grads, self.zero.grad_shardings)
        updates, new_state = self.inner.update(grads, state, params)
        new_state = constrain_tree(new_state, self.zero.state_shardings)
        return updates, new_state

    def apply_updates(self, params: PyTree, updates: PyTree) -> PyTree:
        apply = getattr(self.inner, "apply_updates", optax.apply_updates)
        new_params = apply(params, updates)
        return constrain_tree(new_params, self.zero.param_shardings)
