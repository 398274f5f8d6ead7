"""Decode cache contract assertions (ADVICE r4).

Two contracts are traced and therefore unverifiable by shape alone:
the multi-token prefill fast path requires an EMPTY cache (start == 0),
and the cache must never overflow (``dynamic_update_slice`` clamps past
capacity and attention silently degrades). ``_decode_contract_checks``
expresses both as ``checkify.debug_check`` — a no-op in plain jit, a
loud error when the caller functionalizes with ``checkify.checkify``.
These tests prove the violations ARE caught that way, and that the
valid flow stays silent.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import checkify

from d9d_tpu.nn.attention import GroupedQueryAttention
from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu.ops.rope import compute_rope_frequencies, make_rope_cos_sin


def _rope(b, t, d, start=0):
    inv, scale = compute_rope_frequencies(d, 10000.0)
    pos = jnp.broadcast_to(jnp.arange(start, start + t), (b, t))
    return make_rope_cos_sin(pos, inv, scale)


@pytest.fixture(scope="module")
def gqa_setup():
    blk = GroupedQueryAttention(
        hidden_size=32,
        num_heads=4,
        num_kv_heads=2,
        head_dim=8,
        sdpa=eager_sdpa,
        dtype=jnp.float32,
        decode_max_length=8,
    )
    b = 1
    x4 = jax.random.normal(jax.random.PRNGKey(0), (b, 4, 32))
    cos, sin = _rope(b, 4, 8)
    variables = jax.jit(blk.init)(jax.random.PRNGKey(1), x4, cos, sin)
    # init ran a forward, so its cache is warm — tests start from zeros
    fresh = jax.tree.map(jnp.zeros_like, variables["cache"])
    return blk, x4, cos, sin, {"params": variables["params"],
                               "cache": fresh}


def _checked_apply(blk, params, cache, x, cos, sin):
    def fn(x):
        out, state = blk.apply(
            {"params": params, "cache": cache}, x, cos, sin,
            mutable=["cache"],
        )
        return out, state

    err, (out, state) = checkify.checkify(
        jax.jit(fn), errors=checkify.user_checks
    )(x)
    return err, out, state


def test_valid_prefill_then_steps_pass_checks(gqa_setup):
    blk, x4, cos, sin, variables = gqa_setup
    params = variables["params"]
    err, _, state = _checked_apply(
        blk, params, variables["cache"], x4, cos, sin
    )
    err.throw()  # no error on an empty-cache prefill
    c1, s1 = _rope(1, 1, 8, start=4)
    err, _, _ = _checked_apply(
        blk, params, state["cache"], x4[:, :1], c1, s1
    )
    err.throw()  # single-token step within capacity: silent


def test_prefill_on_warm_cache_fails_loudly(gqa_setup):
    blk, x4, cos, sin, variables = gqa_setup
    params = variables["params"]
    _, _, state = _checked_apply(
        blk, params, variables["cache"], x4, cos, sin
    )
    err, _, _ = _checked_apply(
        blk, params, state["cache"], x4, cos, sin
    )
    with pytest.raises(checkify.JaxRuntimeError, match="empty cache"):
        err.throw()


def test_cache_overflow_fails_loudly(gqa_setup):
    blk, x4, cos, sin, variables = gqa_setup
    params = variables["params"]
    cache = variables["cache"]
    state = {"cache": cache}
    # capacity 8: two 4-token prefills fill it; the second call violates
    # the prefill contract too, so drive with single-token steps instead
    _, _, state = _checked_apply(blk, params, cache, x4, cos, sin)
    for i in range(4, 8):
        c1, s1 = _rope(1, 1, 8, start=i)
        err, _, state = _checked_apply(
            blk, params, state["cache"], x4[:, :1], c1, s1
        )
        err.throw()
    c1, s1 = _rope(1, 1, 8, start=8)
    err, _, _ = _checked_apply(
        blk, params, state["cache"], x4[:, :1], c1, s1
    )
    with pytest.raises(checkify.JaxRuntimeError, match="overflow"):
        err.throw()
