"""Live train→serve weight publish (docs/design/elasticity.md):
``install_weights`` swaps a published param tree into a running
``ContinuousBatcher`` at a chunk boundary — post-publish requests are
token-identical to a fresh batcher built with the new weights, the swap
causes ZERO steady-state recompiles (params are a traced argument with
an unchanged signature), and generation-stamped versioning records
which weights produced each request's tail."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.chaos

from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.resilience.elastic import WeightPublisher
from d9d_tpu.telemetry import introspect

VOCAB = 32


class ShiftDecodeLM(nn.Module):
    """Param-dependent deterministic decode model: next token =
    ``(tok + round(shift)) % vocab`` where ``shift`` is a trainable
    scalar — publishing a tree with a different shift visibly (and
    exactly predictably) changes every subsequent emission. Carries a
    real decode cache (``cache_index`` + a written memory leaf) so the
    serving loop's cache machinery runs for real."""

    vocab: int = VOCAB
    decode_max_length: int = 64

    @nn.compact
    def __call__(self, tokens, positions, labels=None, mask=None):
        b = tokens.shape[0]
        shift = self.param("shift", lambda _rng: jnp.float32(1.0))
        idx = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        mem = self.variable(
            "cache", "mem",
            lambda: jnp.zeros((b, self.decode_max_length), jnp.int32),
        )
        i = jnp.broadcast_to(idx.value, (b,))
        mem.value = mem.value.at[
            jnp.arange(b), jnp.clip(i, 0, self.decode_max_length - 1)
        ].set(tokens[:, 0])
        idx.value = idx.value + 1
        step = jnp.round(shift).astype(jnp.int32)
        return jax.nn.one_hot((tokens + step) % self.vocab, self.vocab) * 20.0

    def logits(self, tokens, positions, mask=None):
        return self(tokens, positions)


def _params(shift: float):
    return {"shift": jnp.float32(shift)}


def _expected(prompt, n, shift):
    toks = []
    last = prompt[-1]
    for _ in range(n):
        last = (last + shift) % VOCAB
        toks.append(last)
    return toks


def _batcher(params, **kwargs):
    kwargs.setdefault("batch_size", 2)
    kwargs.setdefault("chunk_size", 4)
    return ContinuousBatcher(ShiftDecodeLM(), params, **kwargs)


def test_post_publish_requests_token_identical_to_fresh_batcher():
    b = _batcher(_params(1.0))
    r1 = b.submit([3, 4], max_new_tokens=5)
    b.drain()
    assert b.outputs[r1] == _expected([3, 4], 5, 1)

    version = b.install_weights(_params(2.0))
    r2 = b.submit([3, 4], max_new_tokens=5)
    b.drain()
    # token-identical to a cold batcher built with the published tree
    fresh = _batcher(_params(2.0))
    rf = fresh.submit([3, 4], max_new_tokens=5)
    fresh.drain()
    assert b.outputs[r2] == fresh.outputs[rf] == _expected([3, 4], 5, 2)
    assert b.weights_version == version == 1
    assert b.request_stats[r2].weights_version == 1
    assert b.request_stats[r1].weights_version == 0


def test_publish_applies_at_chunk_boundary_not_mid_chunk():
    """Install mid-request: tokens already harvested (old chunks) keep
    the old step; emissions from chunks dispatched after the boundary
    switch to the new step — exactly the chunk-boundary contract."""
    b = _batcher(_params(1.0), chunk_size=4)
    rid = b.submit([5], max_new_tokens=8)
    first = b.step_chunk()  # one K=4 chunk, all on the old weights
    assert first[rid] == _expected([5], 4, 1)
    b.install_weights(_params(2.0))
    b.drain()
    tail = b.outputs[rid][4:]
    # the tail continues from the last OLD-weights token with step 2
    assert tail == _expected([b.outputs[rid][3]], 4, 2)
    assert b.request_stats[rid].weights_version == 1


def test_defer_to_idle_finishes_inflight_on_old_weights():
    b = _batcher(_params(1.0), chunk_size=2)
    rid = b.submit([7], max_new_tokens=6)
    b.step_chunk()  # request now mid-flight
    b.install_weights(_params(2.0), defer_to_idle=True)
    b.drain()
    # the in-flight request finished wholly on the old generation
    assert b.outputs[rid] == _expected([7], 6, 1)
    assert b.request_stats[rid].weights_version == 0
    # the deferred swap lands before the next request's first chunk
    r2 = b.submit([7], max_new_tokens=4)
    b.drain()
    assert b.outputs[r2] == _expected([7], 4, 2)
    assert b.request_stats[r2].weights_version == 1


def test_publish_causes_zero_steady_state_recompiles():
    b = _batcher(_params(1.0))
    b.submit([2], max_new_tokens=10)
    b.drain()  # warm: both fused variants compiled
    mark = len(introspect.inventory())
    b.install_weights(_params(3.0))
    r = b.submit([2], max_new_tokens=10)
    b.drain()
    assert b.outputs[r] == _expected([2], 10, 3)
    new_records = introspect.inventory()[mark:]
    assert not new_records, [r.name for r in new_records]


def _step_until_idle(b):
    while b.active:
        b.step()


def test_single_token_steps_publish_too():
    """``step()``, the single-token surface: the swap lands at the next
    step's boundary."""
    b = _batcher(_params(1.0), chunk_size=1)
    r1 = b.submit([4], max_new_tokens=3)
    _step_until_idle(b)
    b.install_weights(_params(2.0))
    r2 = b.submit([4], max_new_tokens=3)
    _step_until_idle(b)
    assert b.outputs[r1] == _expected([4], 3, 1)
    assert b.outputs[r2] == _expected([4], 3, 2)
    assert b.request_stats[r2].weights_version == 1


def test_publisher_fans_out_and_records_telemetry():
    from d9d_tpu.telemetry import Telemetry

    tele = Telemetry()
    b1 = _batcher(_params(1.0), telemetry=tele)
    b2 = _batcher(_params(1.0), telemetry=tele)
    pub = WeightPublisher(telemetry=tele)
    pub.attach(b1)
    pub.attach(b2)
    version = pub.publish(_params(2.0))
    assert version == 1
    assert pub.latest_params is not None
    for b in (b1, b2):
        r = b.submit([6], max_new_tokens=4)
        b.drain()
        assert b.outputs[r] == _expected([6], 4, 2)
    # one applied install per batcher, with a publish-latency sample
    assert tele.counter("serve/weight_publish").value == 2
    assert tele.histogram("serve/weight_publish_s").count == 2
    assert tele.counter("serve/weight_publish_fanout").value == 2


def test_publisher_weakrefs_do_not_pin_batchers():
    pub = WeightPublisher()
    b = _batcher(_params(1.0))
    pub.attach(b)
    del b
    import gc

    gc.collect()
    # publishing into a dead target is a no-op, not an error
    assert pub.publish(_params(2.0)) == 1
    assert pub._targets == []


def test_publish_from_trainer_snapshot():
    """publish_from snapshots merged_params() — the step-boundary
    train→serve handoff surface."""

    class FakeTrainer:
        def merged_params(self):
            return _params(5.0)

    pub = WeightPublisher()
    b = _batcher(_params(1.0))
    pub.attach(b)
    pub.publish_from(FakeTrainer())
    r = b.submit([1], max_new_tokens=3)
    b.drain()
    assert b.outputs[r] == _expected([1], 3, 5)


def test_install_normalizes_uncommitted_leaves():
    """The satellite fix: a published tree whose committed leaves name a
    mesh gets its uncommitted scalar riders replicated onto it (the PR 5
    latent-placement class) before the first dispatch."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("x",))
    committed = jax.device_put(
        jnp.zeros((4,), jnp.float32), NamedSharding(mesh, P())
    )
    uncommitted = jnp.float32(2.0)
    assert not uncommitted.committed
    tree = {"shift": uncommitted, "anchor": committed}
    b = ContinuousBatcher(
        ShiftDecodeLM(), tree, batch_size=2, chunk_size=2
    )
    assert b._params["shift"].committed
    installed = b.install_weights({"shift": jnp.float32(3.0),
                                   "anchor": committed})
    assert installed == 1
    assert b._pending_weights[0]["shift"].committed
