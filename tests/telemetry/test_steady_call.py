"""``TrackedJit`` in the steady state (telemetry/introspect.py): a call
whose arguments have the signature of the call before is recognised by
the C++ dispatch of that signature's executable, with no Python walk
over the argument leaves; any other signature is still told apart
exactly, compiled once and counted on its own record."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.telemetry import recompile_guard, tracked_jit
from d9d_tpu.telemetry import introspect
from tests.telemetry.test_introspect import _fresh_hub  # noqa: F401 (autouse)


@pytest.fixture
def walks(monkeypatch):
    """Counts of the per-leaf signature work and of whole walks."""
    counts = {"leaves": 0, "walks": 0}
    leaf_sig = introspect._leaf_sig
    key_of = introspect.TrackedJit._signature_key

    def counted_leaf(x):
        counts["leaves"] += 1
        return leaf_sig(x)

    def counted_walk(self, args, kwargs):
        counts["walks"] += 1
        return key_of(self, args, kwargs)

    monkeypatch.setattr(introspect, "_leaf_sig", counted_leaf)
    monkeypatch.setattr(introspect.TrackedJit, "_signature_key", counted_walk)
    return counts


def a_cache(n=40):
    return {f"layer{i}": {"k": jnp.zeros((4, 8), jnp.bfloat16),
                          "v": jnp.zeros((4, 8), jnp.bfloat16)}
            for i in range(n)}


def bump(cache, x):
    return jax.tree.map(lambda c: c + x, cache)


@pytest.mark.parametrize("donate", [False, True], ids=["kept", "donated"])
def test_a_steady_call_walks_no_leaf(walks, donate):
    kw = {"donate_argnums": 0} if donate else {}
    f = tracked_jit(bump, name="unit/steady_walk", **kw)
    cache, one = a_cache(), jnp.ones((), jnp.bfloat16)
    cache = f(cache, one)  # compiles: one walk over 81 leaves
    assert walks == {"leaves": 81, "walks": 1}
    cache = f(cache, one)  # outputs in place of inputs: C++ asks once more
    warm = dict(walks)
    assert warm["walks"] <= 2
    for _ in range(10):
        cache = f(cache, one)
    assert walks == warm  # ten calls, not one leaf looked at from Python
    assert f.last_call.arg_leaves == 81
    np.testing.assert_allclose(
        np.asarray(cache["layer3"]["k"], np.float32), 12.0)
    (rec,) = introspect.inventory()
    assert rec.calls == 12 and rec.arg_leaves == 81
    assert rec.key_s >= 0 and rec.enqueue_s > 0


def test_a_walk_the_dispatch_asks_for_is_the_keys_not_the_enqueues(
        monkeypatch):
    """The second call's arguments are the first call's outputs: C++ has
    not seen them and asks Python, inside the steady call. Those seconds
    are reported as ``key_s``."""
    import time

    slow = 0.02
    key_of = introspect.TrackedJit._signature_key

    def slow_walk(self, args, kwargs):
        time.sleep(slow)
        return key_of(self, args, kwargs)

    monkeypatch.setattr(introspect.TrackedJit, "_signature_key", slow_walk)
    f = tracked_jit(bump, name="unit/asked", donate_argnums=0)
    cache, one = a_cache(4), jnp.ones((), jnp.bfloat16)
    cache = f(cache, one)
    assert f.last_call.key_s >= slow  # the compile's own walk
    t0 = time.perf_counter()
    cache = f(cache, one)
    wall = time.perf_counter() - t0
    asked = f.last_call
    assert asked.key_s >= slow and asked.enqueue_s > 0
    assert asked.key_s + asked.enqueue_s <= wall
    cache = f(cache, one)  # now C++ knows them: no walk, nothing carried
    assert f.last_call.key_s < slow and f._walked_s == 0.0


def test_a_python_scalar_for_an_array_is_still_another_signature(walks):
    """jax.jit retraces for a weak-typed scalar in an array's place (the
    result's type may differ); so does the wrapper, armed or not."""
    f = tracked_jit(lambda x, s: x * s, name="unit/weak")
    plain = jax.jit(lambda x, s: x * s)
    x = jnp.ones((4,), jnp.bfloat16)
    strong = jnp.full((), 2.0, jnp.float32)
    for _ in range(3):
        assert f(x, strong).dtype == plain(x, strong).dtype == jnp.float32
    assert len(introspect.inventory()) == 1
    # same shape and dtype to the AOT executable, another trace to jit
    assert f(x, 2.0).dtype == plain(x, 2.0).dtype == jnp.bfloat16
    assert [r.calls for r in introspect.inventory()] == [3, 1]
    for value in (3.0, 4.5):  # and the scalars share theirs, by type
        assert f(x, value).dtype == jnp.bfloat16
    assert [r.calls for r in introspect.inventory()] == [3, 3]


def test_alternating_signatures_compile_once_each_and_walk_once_a_call(walks):
    f = tracked_jit(lambda x: x + 1, name="unit/alternating")
    a, b = jnp.ones((2,)), jnp.ones((3,))
    for _ in range(4):
        np.testing.assert_allclose(np.asarray(f(a)), 2.0)
        np.testing.assert_allclose(np.asarray(f(b)), 2.0)
    assert sorted(r.calls for r in introspect.inventory()) == [4, 4]
    # a signature that is not the last call's costs the walk it always
    # cost, and no second one
    assert walks["walks"] <= 8


def test_a_new_shape_after_the_steady_state_recompiles_and_warns(
        _fresh_hub, caplog):
    guard = recompile_guard()
    guard.configure(warmup_steps=1)
    f = tracked_jit(lambda x: (x * 2).sum(), name="unit/steady_then_new")
    for _ in range(3):
        f(jnp.ones((4, 4)))
    guard.note_step(1)
    with caplog.at_level(logging.WARNING, "d9d_tpu.telemetry.introspect"):
        f(jnp.ones((5, 4)))
    snap = _fresh_hub.registry.snapshot()
    assert snap["counters"]["compile/recompile"] == 1
    assert snap["counters"]["compile/count"] == 2
    assert [r for r in caplog.records if "steady-state recompile" in r.message]
    f(jnp.ones((4, 4)))  # and back: the first executable, no third compile
    assert _fresh_hub.registry.snapshot()["counters"]["compile/count"] == 2


def test_keyword_arguments_and_placement_are_part_of_the_signature(walks):
    f = tracked_jit(lambda x, *, scale: x * scale, name="unit/kw")
    x = jnp.ones((4,))
    for _ in range(3):
        np.testing.assert_allclose(np.asarray(f(x, scale=jnp.float32(3))), 3.0)
    steady = dict(walks)
    np.testing.assert_allclose(np.asarray(f(x, scale=jnp.float32(5))), 5.0)
    assert walks == steady
    devices = jax.devices()
    if len(devices) > 1:
        moved = jax.device_put(x, devices[1])
        out = f(moved, scale=jax.device_put(jnp.float32(3), devices[1]))
        assert out.devices() == {devices[1]}
        assert len(introspect.inventory()) == 2


def test_a_jax_without_the_pieces_walks_every_call_as_before(
        walks, monkeypatch):
    from jax._src.lib import xla_client

    pjit = xla_client._xla.pjit

    def gone(name, *a, **k):
        if name == "unit/older_jax":  # the wrapper's own; jax makes others
            raise TypeError("pjit() takes other arguments in this jaxlib")
        return pjit(name, *a, **k)

    monkeypatch.setattr(xla_client._xla, "pjit", gone)
    f = tracked_jit(lambda x: x + 1, name="unit/older_jax")
    for _ in range(4):
        np.testing.assert_allclose(np.asarray(f(jnp.ones((2,)))), 2.0)
    assert walks["walks"] == 4 and f._steady is None
    (rec,) = introspect.inventory()
    assert rec.calls == 4
