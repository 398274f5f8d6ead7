"""Declarative split/merge of arbitrary pytrees into N shards.

Used for microbatch splitting and pipeline transient state. Parity with the
reference's pytree sharding spec system (d9d/core/sharding/spec.py:6-25,
shard.py:99, unshard.py:60, auto_spec.py:26,49), re-typed for JAX arrays.

A *spec tree* mirrors the data tree's structure (or is a single spec applied
to every array leaf). Each leaf spec is either ``SpecShard(dim)`` — split
that leaf along ``dim`` into N equal chunks — or ``SpecReplicate()`` — every
shard sees the same leaf.

The spec tree's structure drives flattening: wherever the spec has a leaf,
the corresponding data subtree is treated as one shardable unit. This lets a
``SpecShard(0)`` apply to a plain python list (e.g. a list of strings in a
batch), which is sliced as a sequence — matching the reference's list-leaf
handling (auto_spec.py / unshard.py list paths).
"""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from d9d_tpu.core.types import PyTree


@dataclasses.dataclass(frozen=True)
class SpecShard:
    dim: int = 0


@dataclasses.dataclass(frozen=True)
class SpecReplicate:
    pass


ShardingSpec = SpecShard | SpecReplicate


def _is_spec(x: Any) -> bool:
    return isinstance(x, (SpecShard, SpecReplicate))


def _broadcast_spec(tree: PyTree, spec: PyTree) -> PyTree:
    """If ``spec`` is a single leaf spec, broadcast it over ``tree``'s leaves."""
    if _is_spec(spec):
        return jax.tree.map(lambda _: spec, tree)
    return spec


def _shardable_on(leaf: Any, dim: int) -> bool:
    ndim = getattr(leaf, "ndim", None)
    if ndim is None or ndim == 0:
        return False
    return (-ndim <= dim < ndim) if dim < 0 else dim < ndim


def shard_spec_on_dim(tree: PyTree, dim: int = 0) -> PyTree:
    """Auto-spec: shard array leaves on ``dim``; python lists become sequence
    leaves sharded on dim 0; everything else replicates.

    Parity: reference ``shard_spec_on_dim`` (core/sharding/auto_spec.py:26).
    """

    def leaf_spec(leaf: Any) -> ShardingSpec:
        if isinstance(leaf, list):
            return SpecShard(0)
        if _shardable_on(leaf, dim):
            return SpecShard(dim)
        return SpecReplicate()

    return jax.tree.map(leaf_spec, tree, is_leaf=lambda x: isinstance(x, list))


def _split_leaf(leaf: Any, s: SpecShard, num_shards: int) -> list[Any]:
    if isinstance(leaf, (list, tuple)):
        if s.dim != 0:
            raise ValueError(f"sequence leaves can only shard on dim 0, got {s.dim}")
        if len(leaf) % num_shards != 0:
            raise ValueError(
                f"cannot shard sequence of length {len(leaf)} into {num_shards} chunks"
            )
        step = len(leaf) // num_shards
        return [leaf[i * step : (i + 1) * step] for i in range(num_shards)]
    if not _shardable_on(leaf, s.dim):
        raise ValueError(f"cannot shard leaf {type(leaf).__name__} on dim {s.dim}")
    if leaf.shape[s.dim] % num_shards != 0:
        raise ValueError(
            f"cannot shard leaf of shape {leaf.shape} on dim {s.dim} "
            f"into {num_shards} equal chunks"
        )
    if isinstance(leaf, jax.Array):
        return list(jnp.split(leaf, num_shards, axis=s.dim))
    return list(np.split(np.asarray(leaf), num_shards, axis=s.dim))


def _merge_leaf(parts: list[Any], s: SpecShard) -> Any:
    first = parts[0]
    if isinstance(first, list):
        return [item for part in parts for item in part]
    if isinstance(first, tuple):
        return tuple(item for part in parts for item in part)
    if isinstance(first, jax.Array):
        return jnp.concatenate(parts, axis=s.dim)
    return np.concatenate([np.asarray(p) for p in parts], axis=s.dim)


def shard_tree(tree: PyTree, spec: PyTree, num_shards: int) -> list[PyTree]:
    """Split ``tree`` into ``num_shards`` trees according to ``spec``.

    Parity: reference ``shard_tree`` (core/sharding/shard.py:99).
    """
    spec = _broadcast_spec(tree, spec)
    spec_leaves, spec_treedef = jax.tree.flatten(spec, is_leaf=_is_spec)
    data_units = spec_treedef.flatten_up_to(tree)

    shards_per_unit: list[list[Any]] = []
    for unit, s in zip(data_units, spec_leaves):
        if isinstance(s, SpecReplicate):
            shards_per_unit.append([unit] * num_shards)
        elif isinstance(s, SpecShard):
            shards_per_unit.append(_split_leaf(unit, s, num_shards))
        else:
            raise TypeError(f"unknown sharding spec leaf: {s!r}")

    return [
        jax.tree.unflatten(spec_treedef, [per[i] for per in shards_per_unit])
        for i in range(num_shards)
    ]


def unshard_tree(shards: list[PyTree], spec: PyTree) -> PyTree:
    """Merge shards back into one tree (inverse of :func:`shard_tree`).

    Parity: reference ``unshard_tree`` (core/sharding/unshard.py:60).
    Sharded leaves are concatenated along their dim (numpy leaves stay
    numpy); replicated leaves take the first shard's value.
    """
    if not shards:
        raise ValueError("need at least one shard")
    spec = _broadcast_spec(shards[0], spec)
    spec_leaves, spec_treedef = jax.tree.flatten(spec, is_leaf=_is_spec)
    all_units = [spec_treedef.flatten_up_to(s) for s in shards]

    merged: list[Any] = []
    for i, s in enumerate(spec_leaves):
        if isinstance(s, SpecReplicate):
            merged.append(all_units[0][i])
        elif isinstance(s, SpecShard):
            merged.append(_merge_leaf([units[i] for units in all_units], s))
        else:
            raise TypeError(f"unknown sharding spec leaf: {s!r}")
    return jax.tree.unflatten(spec_treedef, merged)


def replicate_uncommitted(tree: PyTree, mesh) -> PyTree:
    """Pin every *uncommitted* (single-default-device) array leaf to a
    mesh-replicated NamedSharding; committed/sharded leaves pass through.

    A ``jax.jit`` output that no input sharding constrains (e.g. a fresh
    optimizer step counter) comes back uncommitted on the default
    device. The live step tolerates that — jit relocates uncommitted
    operands freely — but the placement round-trips through a checkpoint
    as a *committed* single-device array, which then conflicts with the
    mesh-placed parameters at the first post-restore step. Normalizing
    at init keeps the job state's placement stable across
    save/restore (docs/design/resilience.md, checkpoint fallback).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())

    def fix(x):
        if isinstance(x, jax.Array) and not x.committed:
            return jax.device_put(x, replicated)
        return x

    return jax.tree.map(fix, tree)


def normalize_params(params: PyTree) -> PyTree:
    """Pin uncommitted leaves of a handed-over param tree to the
    mesh-replicated placement of its committed leaves
    (:func:`replicate_uncommitted`); identity for trees with no
    committed NamedSharding to normalize against. What the serving loop
    does to every tree it is given, at construction and at a publish."""
    from jax.sharding import NamedSharding

    for leaf in jax.tree.leaves(params):
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding):
            return replicate_uncommitted(params, sh.mesh)
    return params
