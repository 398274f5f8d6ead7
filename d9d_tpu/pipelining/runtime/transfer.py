"""Cross-stage-mesh transfers that work on every runtime.

Pipeline stages live on different submeshes, and the executor/optimizer
move activations, grad-norm scalars, and clip factors between them with
``jax.device_put``. A single process (one host, however many chips) may
copy between different device sets directly. Multi-controller jax may not:
jax 0.9.0's ``device_put`` still raises "For a cross-host reshard in
multi-controller JAX, input and target sharding should have the same set
of devices" for it (``jax/_src/dispatch.py``; re-tested in PR 21 with two
CPU processes, the runtime ``tests/core/test_multiprocess_e2e.py`` drives),
with only experimental support in the TFRT TPU runtime. So the multi-process
runtimes are the ones that still take ``put_compat``'s fallback, which
reassembles from addressable shards:
for every destination device this process owns, the matching global slice
must already live on a source device this process owns, which holds for
replicated values (every process has a local copy) and for pipeline
layouts that keep stage boundaries process-local (interleave processes
across the non-pp axes). Single-device copies are always legal and stay
async — no host round-trip.
"""

import jax

from d9d_tpu.core.types import PyTree

__all__ = ["put_compat"]


def _tuple_index(idx) -> tuple:
    return tuple(
        (s.start, s.stop, s.step) if isinstance(s, slice) else s for s in idx
    )


def _shardwise_put(x: jax.Array, sharding) -> jax.Array:
    if not hasattr(x, "addressable_shards"):
        return jax.device_put(x, sharding)
    by_index = {}
    for s in x.addressable_shards:
        by_index.setdefault(_tuple_index(s.index), s.data)
    idx_map = sharding.devices_indices_map(x.shape)
    pieces = []
    for dev in sharding.addressable_devices:
        key = _tuple_index(idx_map[dev])
        if key not in by_index:
            raise ValueError(
                "pipeline stage transfer needs a slice this process does "
                "not own; lay pp stages out so every process holds the "
                "same global slices on both sides of a stage boundary "
                "(interleave processes across the non-pp axes), or use a "
                "runtime with cross-host device transfers"
            )
        pieces.append(jax.device_put(by_index[key], dev))
    return jax.make_array_from_single_device_arrays(x.shape, sharding, pieces)


# Whether this runtime accepts a direct device_put between different
# device sets (single process: yes; multi-controller: no). Classified once:
# when the first cross-set payload put raises ValueError, a tiny dedicated
# probe REPLICATING the failure mode (an array on a source device moved
# onto the destination sharding's device set) decides whether that was a
# capability limit (→ shard-wise fallback forever) or a real error in the
# payload itself (→ re-raised, never masked).
_cross_set_direct: bool | None = None


def _probe_cross_set(src_device, dst_sharding) -> bool:
    """Can this runtime device_put onto a different-device-set sharding?"""
    import numpy as np

    from jax.sharding import NamedSharding, PartitionSpec

    probe = jax.device_put(np.zeros((1,), np.float32), src_device)
    replicated = NamedSharding(dst_sharding.mesh, PartitionSpec())
    try:
        jax.block_until_ready(jax.device_put(probe, replicated))
    except ValueError:
        return False
    return True


def put_compat(tree: PyTree, sharding) -> PyTree:
    """``jax.device_put`` onto ``sharding``, with the shard-wise fallback
    for runtimes that reject different-device-set copies. Same-set puts
    and host->device stages always take the direct path, so unrelated
    device_put failures surface unmasked there."""
    global _cross_set_direct
    if sharding is None:
        return tree

    dst_set = getattr(sharding, "device_set", None)

    def one(x):
        global _cross_set_direct
        src = getattr(x, "sharding", None)
        cross = (
            src is not None
            and dst_set is not None
            and getattr(src, "device_set", dst_set) != dst_set
        )
        if not cross or _cross_set_direct is True:
            return jax.device_put(x, sharding)
        if _cross_set_direct is False:
            return _shardwise_put(x, sharding)
        try:
            out = jax.device_put(x, sharding)
        except ValueError:
            if _probe_cross_set(
                next(iter(src.addressable_devices)), sharding
            ):
                # runtime CAN do cross-set puts — the payload itself is
                # broken; don't let the fallback mask its error
                _cross_set_direct = True
                raise
            _cross_set_direct = False
            return _shardwise_put(x, sharding)
        _cross_set_direct = True
        return out

    return jax.tree.map(one, tree)
