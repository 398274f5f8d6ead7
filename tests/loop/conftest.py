"""Loop-suite fixtures: re-export the paged toy serving factory (the
pageable deterministic model lives with the chaos fixtures; the KV
handoff shipment tests here exercise the same batcher surface), and what
the Trainer tests of this directory and of ``tests/models/`` each
restated: a causal LM's provider, seeded token batches, and a pipeline
engine's stages overwritten with a flat run's parameters."""

import jax
import jax.numpy as jnp
import numpy as np

from d9d_tpu.loop import DatasetProvider, ModelProvider
from d9d_tpu.parallel import replicate_plan
from tests.resilience.conftest import paged_toy_factory  # noqa: F401


class LMProvider(ModelProvider):
    """A causal LM: ``build(stage)`` makes a stage's module, ``plan(ctx)``
    its sharding plan; ``init`` is shown token inputs of zeros."""

    def __init__(self, build, plan=replicate_plan):
        self.build, self.plan = build, plan

    def build_module(self, stage):
        return self.build(stage)

    def build_plan(self, ctx):
        return self.plan(ctx)

    def sample_inputs(self, batch_size, seq_len):
        z = jnp.zeros((batch_size, seq_len), jnp.int32)
        return (z, z, z)


class SeededBatches(DatasetProvider):
    """``input_ids`` of ``shape`` drawn from ``RandomState(seed)``: a new
    batch a step, ``steps`` of them (``None``: without end), or with
    ``fresh=False`` the first one again every step, so that a loss must
    fall."""

    def __init__(self, shape, vocab, seed=0, *, fresh=True, steps=None):
        self.shape, self.vocab, self.seed = shape, vocab, seed
        self.fresh, self.steps = fresh, steps

    def build(self):
        rng = np.random.RandomState(self.seed)
        batch, step = None, 0
        while self.steps is None or step < self.steps:
            if batch is None or self.fresh:
                batch = {"input_ids": rng.randint(
                    0, self.vocab, size=self.shape)}
            yield batch
            step += 1


def sync_stage_params(engine, full_params):
    """Overwrite every stage's params with the same-path leaves of a full
    model tree (host numpy), then re-init optimizer state to match."""

    def pull(path, leaf):
        src = full_params
        for k in path:
            src = src[k.key]
        return jax.device_put(np.asarray(src), leaf.sharding)

    for rt in engine.stages.values():
        rt.params = jax.tree_util.tree_map_with_path(pull, rt.params)
    engine.opt_states = engine.optimizer.init(
        {s: rt.params for s, rt in engine.stages.items()}
    )
