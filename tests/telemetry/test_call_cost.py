"""``TrackedJit`` accounts for itself (telemetry/introspect.py): each
call's own cost on the host (the signature walk and lookup, the enqueue,
the argument leaves) is the caller's to read, and the running totals sit
beside ``ExecutableRecord.calls``. Host clock only: no sync, no
readback, no span of the wrapper's own."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.telemetry import tracked_jit
from d9d_tpu.telemetry import introspect
from tests.telemetry.test_introspect import _fresh_hub  # noqa: F401 (autouse)


def a_tree(n: int = 7):
    """``n`` array leaves in a dict beside a tuple of two: n + 2."""
    return (
        {f"w{i}": jnp.full((4,), float(i), jnp.bfloat16) for i in range(n)},
        (jnp.ones((2, 2)), jnp.zeros((3,), jnp.int32)),
    )


def summed(tree, scale):
    return sum(x.astype(jnp.float32).sum() for x in jax.tree.leaves(tree)) \
        * scale


@pytest.mark.parametrize("calls", [1, 2, 5])
def test_a_call_says_what_it_cost_and_the_record_adds_up(calls):
    f = tracked_jit(summed, name="unit/cost")
    assert f.last_call is None  # nothing called yet
    tree = a_tree()
    seen = []
    for _ in range(calls):
        t0 = time.perf_counter()
        out = f(tree, 2.0)
        wall = time.perf_counter() - t0
        cost = f.last_call
        # 7 + 2 array leaves and the weak-typed scalar, exactly
        assert cost.arg_leaves == 10
        assert cost.key_s >= 0.0 and cost.enqueue_s > 0.0
        # two pieces of the call: never more than the whole (a compile's
        # seconds, in the first call, are in neither)
        assert cost.key_s + cost.enqueue_s <= wall
        seen.append(cost)
    assert float(out) == pytest.approx(2.0 * (4 * sum(range(7)) + 4))
    (rec,) = introspect.inventory()
    assert rec.calls == calls and rec.arg_leaves == 10
    assert rec.key_s == pytest.approx(sum(c.key_s for c in seen))
    assert rec.enqueue_s == pytest.approx(sum(c.enqueue_s for c in seen))


def test_the_compile_is_in_neither_piece(monkeypatch):
    """The first call lowers and compiles; its ``compile/*`` span has
    those seconds, the call's own cost has none of them."""
    slow = 0.05
    compile_ = introspect.TrackedJit._compile

    def slow_compile(self, key, args, kwargs):
        time.sleep(slow)
        return compile_(self, key, args, kwargs)

    monkeypatch.setattr(introspect.TrackedJit, "_compile", slow_compile)
    f = tracked_jit(summed, name="unit/first")
    t0 = time.perf_counter()
    f(a_tree(), 1.0)
    wall = time.perf_counter() - t0
    first = f.last_call
    assert wall > slow
    assert first.key_s + first.enqueue_s <= wall - slow
    (rec,) = introspect.inventory()
    assert rec.key_s == first.key_s and rec.enqueue_s == first.enqueue_s


def test_each_signature_keeps_its_own_totals():
    f = tracked_jit(lambda x: x + 1, name="unit/two")
    f(jnp.ones((2,)))
    f(jnp.ones((2,)))
    f(jnp.ones((3,)))  # a second signature: a second record
    by_calls = sorted(introspect.inventory(), key=lambda r: r.calls)
    assert [r.calls for r in by_calls] == [1, 2]
    assert all(r.arg_leaves == 1 and r.enqueue_s > 0 for r in by_calls)


def test_the_wrapper_emits_no_span_per_call(_fresh_hub):
    f = tracked_jit(lambda x: x * 2, name="unit/quiet")
    for _ in range(3):
        f(jnp.ones((2,)))
    names = [
        s.name for s in _fresh_hub.registry.spans
        if not s.name.startswith("host/")
    ]
    assert names == ["compile/unit/quiet"]  # the caller owns the call's span


def test_a_degraded_site_still_reports_its_calls(monkeypatch):
    f = tracked_jit(lambda x: x + 1, name="unit/degraded")

    def boom(*a, **k):
        raise RuntimeError("AOT unavailable")

    monkeypatch.setattr(f._jit, "lower", boom, raising=False)
    if f._jit.lower is not boom:  # a jit object that refuses attributes
        pytest.skip("cannot stub lower() on this jax")
    out = f(jnp.ones((2,)))
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert f.last_call.arg_leaves == 0 and f.last_call.key_s == 0.0
    assert f.last_call.enqueue_s > 0.0


def test_donation_passes_through_and_is_counted_once():
    f = tracked_jit(
        lambda cache, x: jax.tree.map(lambda c: c + x, cache),
        name="unit/donated", donate_argnums=0,
    )
    cache = {f"k{i}": jnp.zeros((8,), jnp.bfloat16) for i in range(5)}
    for _ in range(4):
        cache = f(cache, jnp.ones((), jnp.bfloat16))
    assert f.last_call.arg_leaves == 6
    np.testing.assert_allclose(np.asarray(cache["k0"], np.float32), 4.0)
    (rec,) = introspect.inventory()
    assert rec.calls == 4
