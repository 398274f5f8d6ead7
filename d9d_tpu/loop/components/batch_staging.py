"""Host batch → device staging, shared by Trainer and Inference.

Reshapes the prepared batch to [num_microbatches, microbatch, ...] and
places it with dp sharding on the batch dim; on context-parallel meshes
the sequence dim additionally shards over cp_s — but only for leaves whose
dim 2 both equals the configured sequence length AND divides evenly by the
cp size (a [B, T+1] raw-ids leaf or ragged feature leaf falls back to
batch-only sharding rather than failing device_put).
"""

import warnings
from collections.abc import Callable

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from d9d_tpu.core.mesh import MeshContext
from d9d_tpu.core.tracing import annotate
from d9d_tpu.core.types import PyTree


def split_microbatches(
    prepared: PyTree, *, num_microbatches: int, microbatch_size: int
) -> list[PyTree]:
    """Host-side: cut a prepared global batch into a microbatch list (the
    pipeline executor places each carry/kwargs/state on its stage's
    submesh, so no device_put happens here)."""
    n, m = num_microbatches, microbatch_size

    def cut(x):
        x = np.asarray(x)
        if x.shape[0] != n * m:
            raise ValueError(
                f"batch leading dim {x.shape[0]} != global batch {n * m}"
            )
        return x.reshape(n, m, *x.shape[1:])

    stacked = jax.tree.map(cut, prepared)
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(n)]


def make_batch_stager(
    ctx: MeshContext,
    *,
    num_microbatches: int,
    microbatch_size: int,
    seq_len: int,
) -> Callable[[PyTree], PyTree]:
    seq_sharding = NamedSharding(
        ctx.mesh, P(None, ctx.batch_axes, ctx.sequence_axes)
    )
    flat_sharding = NamedSharding(ctx.mesh, P(None, ctx.batch_axes))
    cp_size = ctx.axis_size(*ctx.sequence_axes)
    if cp_size > 1 and seq_len % cp_size != 0:
        # an off-by-one here used to silently un-shard every sequence leaf,
        # changing memory/perf without failing
        raise ValueError(
            f"seq_len {seq_len} not divisible by the context-parallel axis "
            f"size {cp_size}; no leaf could ever be sequence-sharded"
        )
    warned_shapes: set[tuple[int, ...]] = set()

    def stage(batch: PyTree) -> PyTree:
        def reshape(x):
            x = np.asarray(x)
            if x.shape[0] != num_microbatches * microbatch_size:
                raise ValueError(
                    f"batch leading dim {x.shape[0]} != global batch "
                    f"{num_microbatches * microbatch_size}"
                )
            return x.reshape(
                num_microbatches, microbatch_size, *x.shape[1:]
            )

        def pick(x):
            if x.ndim >= 3 and x.shape[2] == seq_len:
                return seq_sharding
            if cp_size > 1 and x.ndim >= 3 and x.shape[2] != seq_len:
                if x.shape not in warned_shapes:
                    warned_shapes.add(x.shape)
                    warnings.warn(
                        f"batch leaf with shape {x.shape} has a dim-2 of "
                        f"{x.shape[2]} != seq_len {seq_len}; it will be "
                        "batch-sharded only and bypass context-parallel "
                        "sequence sharding",
                        stacklevel=2,
                    )
            return flat_sharding

        with annotate("loop.batch_staging"):
            batch_r = jax.tree.map(reshape, batch)
            return jax.device_put(batch_r, jax.tree.map(pick, batch_r))

    return stage
