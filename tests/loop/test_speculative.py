"""Speculative decoding (loop/speculative.py): greedy acceptance makes
the output BIT-IDENTICAL to target-only greedy generate() — with a
perfect draft (draft == target, everything accepted), a disagreeing
draft (rejections exercise the per-row index-rewind path), and eos
freezing. GDN hybrids are rejected by contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.e2e  # whole-model decode loops (slow tier)

from d9d_tpu.loop.generate import generate
from d9d_tpu.loop.speculative import speculative_generate
from d9d_tpu.models.qwen3 import (
    Qwen3DenseCausalLM,
    Qwen3DenseConfig,
    Qwen3MoeCausalLM,
    Qwen3MoeConfig,
)
from d9d_tpu.ops.attention.eager import eager_sdpa

VOCAB = 64


def _dense(layers=2, seed=0, dml=40):
    cfg = Qwen3DenseConfig(
        vocab_ranges=(("default", VOCAB),),
        hidden_size=32,
        num_layers=layers,
        num_heads=4,
        num_kv_heads=2,
        head_dim=8,
        intermediate_size=64,
        remat=False,
    )
    model = Qwen3DenseCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
        decode_max_length=dml,
    )
    b, t = 2, 8
    z = jnp.zeros((b, t), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    params = jax.jit(model.clone(decode_max_length=0).init)(
        jax.random.PRNGKey(seed), z, pos, z
    )["params"]
    return model, params


def _prompt(b, p, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, VOCAB, (b, p)), jnp.int32
    )


@pytest.mark.slow  # ~8s/param compile-bound on the 2-core rig
@pytest.mark.parametrize("k", [1, 3, 5])
def test_perfect_draft_matches_generate(k):
    """draft == target: every proposal accepted, output still exact."""
    model, params = _dense()
    prompt = _prompt(2, 5)
    n = 10
    want = np.asarray(generate(model, params, prompt, max_new_tokens=n))
    got = np.asarray(speculative_generate(
        model, params, model, params, prompt,
        max_new_tokens=n, speculate_k=k,
    ))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize(
    "k",
    # both params are ~8s compile-bound on the 2-core rig; K=2 stays in
    # tier-1 as the rejection/rewind parity pin, K=4 rides the slow tier
    [2, pytest.param(4, marks=pytest.mark.slow)],
)
def test_disagreeing_draft_matches_generate(k):
    """A differently-initialized draft disagrees often — rejections and
    per-row rewinds must preserve exact target-greedy output."""
    model, params = _dense(seed=0)
    draft, draft_params = _dense(seed=7)
    prompt = _prompt(3, 4, seed=1)[:2]
    n = 9
    want = np.asarray(generate(model, params, prompt, max_new_tokens=n))
    got = np.asarray(speculative_generate(
        model, params, draft, draft_params, prompt,
        max_new_tokens=n, speculate_k=k,
    ))
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow  # ~9s compile-bound on the 2-core rig
def test_eos_freezes_rows():
    model, params = _dense(seed=0)
    draft, draft_params = _dense(seed=7)
    prompt = _prompt(2, 4, seed=2)
    n = 10
    want = np.asarray(generate(model, params, prompt, max_new_tokens=n))
    eos = int(want[0, 3])  # force a mid-sequence eos for row 0
    want_eos = np.asarray(generate(
        model, params, prompt, max_new_tokens=n, eos_id=eos
    ))
    got = np.asarray(speculative_generate(
        model, params, draft, draft_params, prompt,
        max_new_tokens=n, speculate_k=3, eos_id=eos,
    ))
    np.testing.assert_array_equal(got, want_eos)


def test_gdn_hybrid_rejected_by_contract():
    cfg = Qwen3MoeConfig(
        vocab_ranges=(("default", VOCAB),),
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=8,
        moe_intermediate_size=32,
        num_experts=4,
        num_experts_per_tok=2,
        remat=False,
        linear_attention_layers=(0,),
    )
    model = Qwen3MoeCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
        decode_max_length=24,
    )
    b, t = 1, 4
    z = jnp.zeros((b, t), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    params = jax.jit(model.clone(decode_max_length=0).init)(
        jax.random.PRNGKey(0), z, pos, z
    )["params"]
    with pytest.raises(NotImplementedError, match="recurrent state"):
        speculative_generate(
            model, params, model, params, _prompt(1, 3),
            max_new_tokens=4, speculate_k=2,
        )


def test_capacity_validation():
    model, params = _dense(dml=10)
    with pytest.raises(ValueError, match="speculative slots"):
        speculative_generate(
            model, params, model, params, _prompt(1, 4),
            max_new_tokens=4, speculate_k=4,
        )


def test_max_new_tokens_validation():
    """ADVICE r5 #2: max_new_tokens=0 must raise a clear ValueError up
    front (matching ContinuousBatcher.submit), not an IndexError from
    the output-buffer write."""
    model, params = _dense()
    with pytest.raises(ValueError, match="max_new_tokens must be >= 1"):
        speculative_generate(
            model, params, model, params, _prompt(1, 3),
            max_new_tokens=0, speculate_k=2,
        )
