"""KV-cache generation (loop/generate.py): greedy decode must reproduce
the full-forward argmax sequence token for token, and the cache path must
match full-forward logits exactly (teacher forcing)."""
import numpy as np
import pytest

from d9d_tpu.core.compat import HAS_MODERN_JAX

# the SPMD/multiprocess e2e tier needs the modern jax runtime
# (core/compat.py emulates only ambient-mesh bookkeeping)
requires_modern_jax = pytest.mark.skipif(
    not HAS_MODERN_JAX, reason="needs the modern-jax SPMD runtime"
)

# slow tier (r5 quick-tier trim): whole-model prefill+decode parity loops
# dominate the quick tier (~5 min on a 1-CPU box); the quick decode
# signal lives in tests/nn/test_decode_contracts.py and
# tests/ops/test_decode_attention.py
pytestmark = pytest.mark.e2e

import functools

import jax
import jax.numpy as jnp

from d9d_tpu.loop.generate import generate
from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
from d9d_tpu.ops.attention.eager import eager_sdpa

VOCAB = 64


def _cfg():
    return Qwen3DenseConfig(
        vocab_ranges=(("default", VOCAB),),
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=8,
        intermediate_size=64,
        remat=False,
    )


def _models(decode_max_length):
    cfg = _cfg()
    full = Qwen3DenseCausalLM(config=cfg, sdpa=eager_sdpa, dtype=jnp.float32)
    dec = Qwen3DenseCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
        decode_max_length=decode_max_length,
    )
    b, t = 2, 8
    z = jnp.zeros((b, t), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    params = jax.jit(full.init)(jax.random.PRNGKey(0), z, pos, z)["params"]
    return full, dec, params


def _full_logits(full, params, ids):
    b, t = ids.shape
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    return jax.jit(lambda p, ids: full.apply(
        {"params": p}, ids, pos, method=full.logits))(params, ids)


class TestDecodeParity:
    def test_prefill_plus_steps_match_full_forward(self):
        """Feed a fixed sequence through the cache path (prefill + 1-token
        steps) and compare every step's logits against the full forward."""
        full, dec, params = _models(decode_max_length=16)
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, VOCAB, (2, 12)), jnp.int32)
        want = _full_logits(full, params, ids)  # [B, 12, V]

        p = 8
        pos = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (2, p))
        # prefill and the single-token step: one compiled program each
        decode = jax.jit(functools.partial(
            dec.apply, method=dec.logits, mutable=["cache"]))
        got, state = decode({"params": params}, ids[:, :p], pos)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want[:, :p]), rtol=2e-5, atol=2e-5
        )
        cache = state["cache"]
        for i in range(p, 12):
            step_pos = jnp.full((2, 1), i, jnp.int32)
            logits_i, state = decode(
                {"params": params, "cache": cache},
                ids[:, i : i + 1], step_pos,
            )
            cache = state["cache"]
            np.testing.assert_allclose(
                np.asarray(logits_i[:, 0]), np.asarray(want[:, i]),
                rtol=2e-5, atol=2e-5,
            )

    @pytest.mark.slow  # >20s compile-bound on the 2-core rig; e2e tier covers it
    def test_greedy_generate_matches_full_forward_argmax(self):
        full, dec, params = _models(decode_max_length=16)
        rng = np.random.default_rng(1)
        prompt = jnp.asarray(rng.integers(0, VOCAB, (2, 6)), jnp.int32)
        out = generate(dec, params, prompt, max_new_tokens=8)
        assert out.shape == (2, 8)

        # oracle: grow the sequence with full forwards + argmax
        seq = prompt
        want = []
        for _ in range(8):
            logits = _full_logits(full, params, seq)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            want.append(nxt)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(
            np.asarray(out), np.stack([np.asarray(w) for w in want], axis=1)
        )

    def test_sampled_generate_reproducible_and_in_range(self):
        _, dec, params = _models(decode_max_length=16)
        prompt = jnp.ones((2, 4), jnp.int32)
        a = generate(dec, params, prompt, max_new_tokens=6,
                     temperature=0.8, rng=jax.random.PRNGKey(7))
        b = generate(dec, params, prompt, max_new_tokens=6,
                     temperature=0.8, rng=jax.random.PRNGKey(7))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert (np.asarray(a) >= 0).all() and (np.asarray(a) < VOCAB).all()

    def test_eos_freezes_finished_rows(self):
        _, dec, params = _models(decode_max_length=32)
        prompt = jnp.ones((2, 4), jnp.int32)
        greedy = generate(dec, params, prompt, max_new_tokens=12)
        eos = int(np.asarray(greedy)[0, 3])  # force an early stop for row 0
        out = np.asarray(
            generate(dec, params, prompt, max_new_tokens=12, eos_id=eos)
        )
        hit = np.argmax(out[0] == eos)
        assert (out[0, hit:] == eos).all()

    @pytest.mark.slow  # >20s compile-bound on the 2-core rig; e2e tier covers it
    def test_hybrid_gdn_decode_matches_full_forward(self):
        """The hybrid family decodes through GDN recurrent state + conv
        tail + KV caches on the attention layers; teacher-forced step
        logits must match the full forward."""
        from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig

        cfg = Qwen3MoeConfig.hybrid_tiny(VOCAB)
        full = Qwen3MoeCausalLM(
            config=cfg, sdpa=eager_sdpa, dtype=jnp.float32
        )
        dec = Qwen3MoeCausalLM(
            config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
            decode_max_length=16,
        )
        b, t = 2, 8
        z = jnp.zeros((b, t), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        params = jax.jit(full.init)(jax.random.PRNGKey(2), z, pos, z)["params"]

        rng = np.random.default_rng(3)
        ids = jnp.asarray(rng.integers(0, VOCAB, (b, 12)), jnp.int32)
        fp = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32), (b, 12))
        want = full.apply({"params": params}, ids, fp, method=full.logits)

        p = 8
        ppos = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (b, p))
        got, state = dec.apply(
            {"params": params}, ids[:, :p], ppos,
            method=dec.logits, mutable=["cache"],
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want[:, :p]), rtol=5e-5, atol=5e-5
        )
        cache = state["cache"]
        for i in range(p, 12):
            logits_i, state = dec.apply(
                {"params": params, "cache": cache},
                ids[:, i : i + 1], jnp.full((b, 1), i, jnp.int32),
                method=dec.logits, mutable=["cache"],
            )
            cache = state["cache"]
            np.testing.assert_allclose(
                np.asarray(logits_i[:, 0]), np.asarray(want[:, i]),
                rtol=5e-5, atol=5e-5,
            )

    @pytest.mark.slow  # >20s compile-bound on the 2-core rig; e2e tier covers it
    def test_hybrid_generate_greedy(self):
        from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig

        cfg = Qwen3MoeConfig.hybrid_tiny(VOCAB)
        full = Qwen3MoeCausalLM(
            config=cfg, sdpa=eager_sdpa, dtype=jnp.float32
        )
        dec = Qwen3MoeCausalLM(
            config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
            decode_max_length=16,
        )
        b, t = 2, 8
        z = jnp.zeros((b, t), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        params = jax.jit(full.init)(jax.random.PRNGKey(4), z, pos, z)["params"]
        prompt = jnp.ones((2, 5), jnp.int32)
        out = generate(dec, params, prompt, max_new_tokens=6)
        assert out.shape == (2, 6)
        # oracle: grow with full forwards
        seq = prompt
        for j in range(6):
            fp = jnp.broadcast_to(
                jnp.arange(seq.shape[1], dtype=jnp.int32), (2, seq.shape[1])
            )
            logits = full.apply(
                {"params": params}, seq, fp, method=full.logits
            )
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            assert (np.asarray(out[:, j]) == np.asarray(nxt)).all(), j
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)

    @pytest.mark.slow  # >10s compile-bound on the 2-core rig; e2e tier covers it
    def test_ragged_prompts_match_per_row_unpadded(self):
        """Left-padded batch + prompt_lengths must generate exactly what
        each row generates alone, unpadded (rope positions and the
        key-validity mask make pads invisible)."""
        full, dec, params = _models(decode_max_length=20)
        rng = np.random.default_rng(5)
        rows = [
            jnp.asarray(rng.integers(0, VOCAB, (1, 4)), jnp.int32),
            jnp.asarray(rng.integers(0, VOCAB, (1, 7)), jnp.int32),
        ]
        want = [
            np.asarray(generate(dec, params, r, max_new_tokens=6))
            for r in rows
        ]

        p = 7
        padded = jnp.concatenate(
            [
                jnp.pad(rows[0], ((0, 0), (p - 4, 0))),
                rows[1],
            ],
            axis=0,
        )
        got = np.asarray(
            generate(
                dec, params, padded, max_new_tokens=6,
                prompt_lengths=jnp.asarray([4, 7], jnp.int32),
            )
        )
        np.testing.assert_array_equal(got[0], want[0][0])
        np.testing.assert_array_equal(got[1], want[1][0])

    @pytest.mark.slow  # ~9s compile-bound on the 2-core rig; e2e tier covers it
    def test_ragged_prompts_flash_prefill_backend(self):
        """The ragged contract through the Pallas flash backend (what the
        prefill fast path runs on TPU; interpret mode here): segment ids
        must make left pads invisible exactly like the eager mask."""
        from d9d_tpu.ops.attention.pallas_flash import make_pallas_flash_sdpa

        cfg = _cfg()
        flash = make_pallas_flash_sdpa()  # interpret auto-on off-TPU
        dec = Qwen3DenseCausalLM(
            config=cfg, sdpa=flash, dtype=jnp.float32,
            decode_max_length=20,
        )
        b, t = 2, 8
        z = jnp.zeros((b, t), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        params = jax.jit(dec.init)(jax.random.PRNGKey(9), z, pos, z)["params"]

        rng = np.random.default_rng(10)
        short = jnp.asarray(rng.integers(0, VOCAB, (1, 4)), jnp.int32)
        long = jnp.asarray(rng.integers(0, VOCAB, (1, 7)), jnp.int32)
        want_short = np.asarray(generate(dec, params, short, max_new_tokens=5))
        want_long = np.asarray(generate(dec, params, long, max_new_tokens=5))
        padded = jnp.concatenate(
            [jnp.pad(short, ((0, 0), (3, 0))), long], axis=0
        )
        got = np.asarray(
            generate(
                dec, params, padded, max_new_tokens=5,
                prompt_lengths=jnp.asarray([4, 7], jnp.int32),
            )
        )
        np.testing.assert_array_equal(got[0], want_short[0])
        np.testing.assert_array_equal(got[1], want_long[0])

    @pytest.mark.slow  # >20s compile-bound on the 2-core rig; e2e tier covers it
    def test_ragged_prompts_hybrid(self):
        """Same ragged contract through the GDN hybrid (padding_mask
        threads to the linear-attention layers)."""
        from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig

        cfg = Qwen3MoeConfig.hybrid_tiny(VOCAB)
        dec = Qwen3MoeCausalLM(
            config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
            decode_max_length=20,
        )
        b, t = 2, 8
        z = jnp.zeros((b, t), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        params = jax.jit(dec.init)(jax.random.PRNGKey(6), z, pos, z)["params"]

        rng = np.random.default_rng(8)
        short = jnp.asarray(rng.integers(0, VOCAB, (1, 3)), jnp.int32)
        long = jnp.asarray(rng.integers(0, VOCAB, (1, 6)), jnp.int32)
        want_short = np.asarray(
            generate(dec, params, short, max_new_tokens=5)
        )
        want_long = np.asarray(generate(dec, params, long, max_new_tokens=5))
        padded = jnp.concatenate(
            [jnp.pad(short, ((0, 0), (3, 0))), long], axis=0
        )
        got = np.asarray(
            generate(
                dec, params, padded, max_new_tokens=5,
                prompt_lengths=jnp.asarray([3, 6], jnp.int32),
            )
        )
        np.testing.assert_array_equal(got[0], want_short[0])
        np.testing.assert_array_equal(got[1], want_long[0])

    @pytest.mark.slow  # >20s compile-bound on the 2-core rig; e2e tier covers it
    def test_top_p_sampling(self):
        _, dec, params = _models(decode_max_length=16)
        prompt = jnp.ones((2, 4), jnp.int32)
        a = generate(dec, params, prompt, max_new_tokens=6,
                     temperature=0.8, top_p=0.9,
                     rng=jax.random.PRNGKey(11))
        b = generate(dec, params, prompt, max_new_tokens=6,
                     temperature=0.8, top_p=0.9,
                     rng=jax.random.PRNGKey(11))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # top_p -> 0 collapses to greedy (only the argmax survives)
        tiny_p = generate(dec, params, prompt, max_new_tokens=6,
                          temperature=0.8, top_p=1e-6,
                          rng=jax.random.PRNGKey(12))
        greedy = generate(dec, params, prompt, max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(tiny_p), np.asarray(greedy))
        # filters without a temperature are a silent no-op -> rejected
        with pytest.raises(ValueError, match="have no effect"):
            generate(dec, params, prompt, max_new_tokens=2, top_p=0.9)
        with pytest.raises(ValueError, match="have no effect"):
            generate(dec, params, prompt, max_new_tokens=2, top_k=5)

    def test_top_k_sampling(self):
        _, dec, params = _models(decode_max_length=16)
        prompt = jnp.ones((2, 4), jnp.int32)
        # top_k=1 collapses to greedy regardless of temperature
        k1 = generate(dec, params, prompt, max_new_tokens=6,
                      temperature=1.2, top_k=1, rng=jax.random.PRNGKey(5))
        greedy = generate(dec, params, prompt, max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(k1), np.asarray(greedy))
        # reproducible under a fixed key
        a = generate(dec, params, prompt, max_new_tokens=6,
                     temperature=0.8, top_k=8, rng=jax.random.PRNGKey(6))
        b = generate(dec, params, prompt, max_new_tokens=6,
                     temperature=0.8, top_k=8, rng=jax.random.PRNGKey(6))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @requires_modern_jax
    def test_generate_with_sharded_params(self, devices):
        """Generation under a mesh: FSDP-sharded params + jitted decode
        must reproduce the single-device greedy sequence (the multi-chip
        inference story: same program, sharded weights)."""
        import flax.linen as nn

        from d9d_tpu.core import MeshParameters
        from d9d_tpu.loop import init_sharded_params
        from d9d_tpu.parallel import fsdp_plan

        # build() installs the mesh ambiently ("most recently built wins");
        # do it FIRST so every array in this test is created under it — a
        # prior test's leaked mesh (e.g. the MLA ring tests' 4-device one)
        # must not own the reference arrays
        ctx = MeshParameters(dp_shard=8).build()
        full, dec, params = _models(decode_max_length=16)
        prompt = jnp.asarray([[3, 1, 4, 1], [5, 9, 2, 6]], jnp.int32)
        want = np.asarray(generate(dec, params, prompt, max_new_tokens=8))
        z = jnp.zeros((2, 8), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
        sharded, _ = init_sharded_params(
            dec, (z, pos, z), jax.random.PRNGKey(0), ctx, fsdp_plan(ctx)
        )
        # replace values with the reference params (full.init leaves are
        # still boxed LogicallyPartitioned — unbox before mapping),
        # resharded onto the plan's placements, to compare decode exactly
        sharded = jax.tree.map(
            lambda ref, tgt: jax.device_put(ref, tgt.sharding),
            nn.unbox(params), sharded["params"],
        )
        got = np.asarray(generate(dec, sharded, prompt, max_new_tokens=8))
        np.testing.assert_array_equal(got, want)

    def test_llama_family_generates(self):
        from d9d_tpu.models.llama import LlamaCausalLM, llama3_tiny

        cfg = llama3_tiny(VOCAB)
        dec = LlamaCausalLM(
            config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
            decode_max_length=16,
        )
        b, t = 2, 8
        z = jnp.zeros((b, t), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        full = LlamaCausalLM(config=cfg, sdpa=eager_sdpa, dtype=jnp.float32)
        params = jax.jit(full.init)(jax.random.PRNGKey(0), z, pos, z)["params"]
        prompt = jnp.ones((2, 4), jnp.int32)
        out = generate(dec, params, prompt, max_new_tokens=8)
        assert out.shape == (2, 8)
