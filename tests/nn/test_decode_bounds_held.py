"""``decode_flags.caller_holds_bounds`` (PR 37): a caller that enforces
the decode contracts on the host traces no ``checkify.debug_check``, so
its compiled program carries no unordered effect and keeps jax's C++
dispatch; every other caller keeps the checks (tests/nn/
test_decode_contracts.py shows them firing under ``checkify``)."""

import jax
import jax.numpy as jnp
import pytest

from d9d_tpu.nn import decode_flags
from tests.nn.test_decode_contracts import gqa_setup  # noqa: F401 (fixture)


def a_step(blk, variables, x, cos, sin, held):
    def fn(cache, x):
        if held:
            with decode_flags.caller_holds_bounds():
                return blk.apply({"params": variables["params"],
                                  "cache": cache}, x, cos, sin,
                                 mutable=["cache"])
        return blk.apply({"params": variables["params"], "cache": cache},
                         x, cos, sin, mutable=["cache"])

    return jax.jit(fn)


@pytest.mark.parametrize("held", [False, True], ids=["checked", "held"])
def test_the_check_and_its_effect_are_traced_unless_the_caller_holds_them(
        gqa_setup, held):
    blk, x4, cos, sin, variables = gqa_setup
    step = a_step(blk, variables, x4, cos, sin, held)
    traced = step.trace(variables["cache"], x4)
    assert bool(traced.jaxpr.effects) is (not held)
    compiled = traced.lower().compile()
    out, _ = compiled(variables["cache"], x4)
    # the program that carries the effect gets no C++ call from jax
    assert hasattr(compiled._call, "_cache_miss") is held
    assert jnp.isfinite(out).all()


def test_both_programs_compute_the_same(gqa_setup):
    blk, x4, cos, sin, variables = gqa_setup
    outs = [
        a_step(blk, variables, x4, cos, sin, held)(variables["cache"], x4)[0]
        for held in (False, True)
    ]
    assert jnp.array_equal(*outs)


def test_the_flag_ends_with_its_block():
    assert not decode_flags.bounds_held_by_caller()
    with decode_flags.caller_holds_bounds():
        assert decode_flags.bounds_held_by_caller()
    assert not decode_flags.bounds_held_by_caller()
