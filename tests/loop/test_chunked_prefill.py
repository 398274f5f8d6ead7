"""Chunked prefill (generate(prefill_chunk_size=...)): streaming a long
prompt through the decode cache in bounded pieces must reproduce the
unchunked generation EXACTLY — first chunk on the empty-cache fast path,
continuation chunks through the slot-cache path
(d9d_tpu.nn.decode_flags.continuation_chunk), across dense GQA
(+window), MLA, the GDN hybrid, ragged left-padded batches, and both
decode-attention backends."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.e2e  # whole-model generation loops (slow tier)

from d9d_tpu.loop.generate import generate
from d9d_tpu.models.qwen3 import (
    Qwen3DenseCausalLM,
    Qwen3DenseConfig,
    Qwen3MoeCausalLM,
    Qwen3MoeConfig,
)
from d9d_tpu.ops.attention.eager import eager_sdpa

VOCAB = 64


def _dense(decode_max_length=0, window=None):
    cfg = Qwen3DenseConfig(
        vocab_ranges=(("default", VOCAB),),
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=8,
        intermediate_size=64,
        remat=False,
        window_size=window,
    )
    return Qwen3DenseCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
        decode_max_length=decode_max_length,
    )


def _init_params(model):
    b, t = 2, 8
    z = jnp.zeros((b, t), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    full = model.clone(decode_max_length=0)
    return jax.jit(full.init)(jax.random.PRNGKey(0), z, pos, z)["params"]


def _prompt(b, p, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, VOCAB, (b, p)), jnp.int32
    )


@pytest.mark.parametrize("chunk", [1, 3, 4, 7, 16])
@pytest.mark.slow  # >10s compile-bound on the 2-core rig; e2e tier covers it
def test_dense_chunked_matches_unchunked(chunk):
    dec = _dense(decode_max_length=24)
    params = _init_params(dec)
    prompt = _prompt(2, 7)
    want = np.asarray(generate(dec, params, prompt, max_new_tokens=8))
    got = np.asarray(generate(
        dec, params, prompt, max_new_tokens=8, prefill_chunk_size=chunk
    ))
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow  # >10s compile-bound on the 2-core rig; e2e tier covers it
def test_windowed_chunked_matches_unchunked():
    """Sliding window crossing chunk boundaries: the slot path must
    apply the window by global position, not within-chunk position."""
    dec = _dense(decode_max_length=24, window=3)
    params = _init_params(dec)
    prompt = _prompt(2, 9, seed=1)
    want = np.asarray(generate(dec, params, prompt, max_new_tokens=6))
    got = np.asarray(generate(
        dec, params, prompt, max_new_tokens=6, prefill_chunk_size=2
    ))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", ["eager", "pallas"])
@pytest.mark.slow  # >10s compile-bound on the 2-core rig; e2e tier covers it
def test_ragged_chunked_matches_unchunked(backend, monkeypatch):
    """Left-padded ragged rows: pad slots stay masked across chunks —
    including through the flash-decode kernel's kv_valid path with
    multi-token continuation rows (the TPU serving configuration)."""
    dec = _dense(decode_max_length=24)
    params = _init_params(dec)
    prompt = _prompt(3, 8, seed=2)
    lengths = jnp.asarray([8, 5, 2], jnp.int32)
    want = np.asarray(generate(
        dec, params, prompt, max_new_tokens=6, prompt_lengths=lengths
    ))
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", backend)
    got = np.asarray(generate(
        dec, params, prompt, max_new_tokens=6, prompt_lengths=lengths,
        prefill_chunk_size=3,
    ))
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow  # >10s compile-bound on the 2-core rig; e2e tier covers it
def test_pallas_decode_backend_chunked(monkeypatch):
    """Continuation chunks through the flash-decode kernel (env-forced,
    interpret mode on CPU) must match the eager routing."""
    dec = _dense(decode_max_length=24)
    params = _init_params(dec)
    prompt = _prompt(2, 7, seed=3)
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "eager")
    want = np.asarray(generate(
        dec, params, prompt, max_new_tokens=6, prefill_chunk_size=3
    ))
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "pallas")
    got = np.asarray(generate(
        dec, params, prompt, max_new_tokens=6, prefill_chunk_size=3
    ))
    np.testing.assert_array_equal(got, want)


def _hybrid_moe(decode_max_length=0, mla=False):
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
        linear_attention_layers=(0,),  # GDN on layer 0, attention on 1
    )
    return Qwen3MoeCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
        decode_max_length=decode_max_length,
    )


@pytest.mark.slow  # >10s compile-bound on the 2-core rig; e2e tier covers it
def test_hybrid_gdn_chunked_matches_unchunked():
    """GDN layers thread recurrent state + conv tail across chunks."""
    dec = _hybrid_moe(decode_max_length=24)
    b, t = 2, 8
    z = jnp.zeros((b, t), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    full = dec.clone(decode_max_length=0)
    params = jax.jit(full.init)(jax.random.PRNGKey(0), z, pos, z)["params"]
    prompt = _prompt(2, 7, seed=4)
    want = np.asarray(generate(dec, params, prompt, max_new_tokens=6))
    got = np.asarray(generate(
        dec, params, prompt, max_new_tokens=6, prefill_chunk_size=2
    ))
    np.testing.assert_array_equal(got, want)


def test_mla_chunked_matches_unchunked():
    from d9d_tpu.nn.attention import MultiHeadLatentAttention
    from d9d_tpu.nn.decode_flags import continuation_chunk
    from d9d_tpu.ops.rope import (
        compute_rope_frequencies,
        make_rope_cos_sin,
    )

    b, p = 2, 9
    inv, sc = compute_rope_frequencies(8, 10000.0)

    def rope(start, t):
        pos = jnp.broadcast_to(jnp.arange(start, start + t), (b, t))
        return make_rope_cos_sin(pos, inv, sc)

    full = MultiHeadLatentAttention(
        hidden_size=32, num_heads=4, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, kv_lora_rank=16,
        sdpa=eager_sdpa, dtype=jnp.float32,
    )
    dec = full.clone(decode_max_length=16)
    x = jax.random.normal(jax.random.PRNGKey(7), (b, p, 32))
    cos, sin = rope(0, p)
    variables = jax.jit(full.init)(jax.random.PRNGKey(1), x, cos, sin)
    params = variables["params"]
    want = full.apply({"params": params}, x, cos, sin)

    cache = jax.tree.map(
        jnp.zeros_like,
        jax.jit(dec.init)(
            jax.random.PRNGKey(1), x[:, :1], cos[:, :1], sin[:, :1])["cache"],
    )
    outs = []
    chunk = 3
    for i, lo in enumerate(range(0, p, chunk)):
        hi = min(lo + chunk, p)
        c, s = rope(lo, hi - lo)
        ctx = continuation_chunk() if i else contextlib.nullcontext()
        with ctx:
            o, st = dec.apply(
                {"params": params, "cache": cache},
                x[:, lo:hi], c, s, mutable=["cache"],
            )
        cache = st["cache"]
        outs.append(o)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5
    )


def test_first_chunk_contract_still_enforced():
    """Without the continuation flag, a multi-token call on a warm cache
    must still fail loudly under checkify (the fast path is invalid)."""
    from jax.experimental import checkify

    dec = _dense(decode_max_length=24)
    params = _init_params(dec)
    b, t = 2, 4
    ids = jnp.ones((b, t), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def two_prefills(ids):
        _, st = dec.apply(
            {"params": params}, ids, pos,
            method=dec.logits, mutable=["cache"],
        )
        out, _ = dec.apply(
            {"params": params, "cache": st["cache"]}, ids, pos,
            method=dec.logits, mutable=["cache"],
        )
        return out

    err, _ = checkify.checkify(
        jax.jit(two_prefills), errors=checkify.user_checks
    )(ids)
    with pytest.raises(checkify.JaxRuntimeError, match="empty cache"):
        err.throw()
