"""MLA + GatedDeltaNet block tests: shapes, causality, grads, variants."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d9d_tpu.core.compat import HAS_MODERN_JAX

# the SPMD/multiprocess e2e tier needs the modern jax runtime
# (core/compat.py emulates only ambient-mesh bookkeeping)
requires_modern_jax = pytest.mark.skipif(
    not HAS_MODERN_JAX, reason="needs the modern-jax SPMD runtime"
)
pytestmark = pytest.mark.e2e  # slow tier: heavy kernel/e2e parity


from d9d_tpu.nn.attention import MultiHeadLatentAttention
from d9d_tpu.nn.linear_attention import DecayGateKind, GatedDeltaNet
from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu.ops.rope import compute_rope_frequencies, make_rope_cos_sin


def _rope(b, t, d_rope):
    inv, scale = compute_rope_frequencies(d_rope, 10000.0)
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    return make_rope_cos_sin(pos, inv, scale)


class TestMLA:
    def _block(self, q_lora=None):
        return MultiHeadLatentAttention(
            hidden_size=64,
            num_heads=4,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=12,
            kv_lora_rank=32,
            q_lora_rank=q_lora,
            sdpa=eager_sdpa,
            dtype=jnp.float32,
        )

    @pytest.mark.parametrize("q_lora", [None, 24])
    def test_shapes_and_grads(self, q_lora):
        blk = self._block(q_lora)
        b, t = 2, 10
        x = jax.random.normal(jax.random.PRNGKey(0), (b, t, 64))
        cos, sin = _rope(b, t, 8)
        params = jax.jit(blk.init)(jax.random.PRNGKey(1), x, cos, sin)
        out = jax.jit(blk.apply)(params, x, cos, sin)
        assert out.shape == (b, t, 64)
        if q_lora is not None:
            assert "down_proj" in params["params"]["q_proj"]

        g = jax.jit(jax.grad(
            lambda p: jnp.sum(blk.apply(p, x, cos, sin) ** 2)))(params)
        assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))

    def test_causality(self):
        blk = self._block()
        b, t = 1, 8
        x = jax.random.normal(jax.random.PRNGKey(0), (b, t, 64))
        cos, sin = _rope(b, t, 8)
        params = jax.jit(blk.init)(jax.random.PRNGKey(1), x, cos, sin)
        apply = jax.jit(blk.apply)
        out1 = apply(params, x, cos, sin)
        x2 = x.at[:, -1].set(99.0)  # perturb the future
        out2 = apply(params, x2, cos, sin)
        np.testing.assert_allclose(
            np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), atol=1e-5
        )

    @pytest.mark.parametrize("absorbed", [True, False])
    def test_latent_cache_decode_matches_full_forward(self, absorbed):
        """MLA decode caches (latent, rotated rope key) per token; prefill
        + teacher-forced single-token steps must reproduce the full
        forward at every position — in BOTH the absorbed (rank-space)
        form and the decompressed oracle (``decode_absorbed=False``,
        which re-expands every cache slot through kv_up per step)."""
        b, t, p = 2, 12, 8
        full = self._block()
        dec = MultiHeadLatentAttention(
            hidden_size=64,
            num_heads=4,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=12,
            kv_lora_rank=32,
            sdpa=eager_sdpa,
            dtype=jnp.float32,
            decode_max_length=16,
            decode_absorbed=absorbed,
        )
        x = jax.random.normal(jax.random.PRNGKey(3), (b, t, 64))
        cos, sin = _rope(b, t, 8)
        params = jax.jit(full.init)(jax.random.PRNGKey(1), x, cos, sin)
        want = jax.jit(full.apply)(params, x, cos, sin)

        # prefill and the single-token step: one compiled program each
        decode = jax.jit(functools.partial(dec.apply, mutable=["cache"]))
        got_pre, state = decode(params, x[:, :p], cos[:, :p], sin[:, :p])
        np.testing.assert_allclose(
            np.asarray(got_pre), np.asarray(want[:, :p]),
            rtol=2e-5, atol=2e-5,
        )
        cache = state["cache"]
        for i in range(p, t):
            got_i, state = decode(
                {**params, "cache": cache},
                x[:, i : i + 1], cos[:, i : i + 1], sin[:, i : i + 1],
            )
            cache = state["cache"]
            np.testing.assert_allclose(
                np.asarray(got_i[:, 0]), np.asarray(want[:, i]),
                rtol=2e-5, atol=2e-5,
            )
        # the cache really is the compressed form: latent + rope key only
        slot_bytes = sum(
            np.prod(v.shape[2:])
            for k, v in cache.items()
            if k.startswith("cached")
        )
        assert slot_bytes == 32 + 8  # kv_lora_rank + d_rope per token


class TestGatedDeltaNet:
    def _block(self, gate=DecayGateKind.mamba, hqk=2, hv=4):
        return GatedDeltaNet(
            hidden_size=64,
            num_qk_heads=hqk,
            num_v_heads=hv,
            head_qk_dim=16,
            head_v_dim=8,
            conv_size=4,
            decay_gate=gate,
            chunk_size=8,
            dtype=jnp.float32,
        )

    @pytest.mark.parametrize("gate", [DecayGateKind.mamba, DecayGateKind.logsigmoid])
    @pytest.mark.parametrize("hqk,hv", [(2, 4), (4, 4)])
    def test_shapes_and_grads(self, gate, hqk, hv):
        blk = self._block(gate, hqk, hv)
        b, t = 2, 24
        x = jax.random.normal(jax.random.PRNGKey(0), (b, t, 64))
        params = jax.jit(blk.init)(jax.random.PRNGKey(1), x)
        out = jax.jit(blk.apply)(params, x)
        assert out.shape == (b, t, 64)
        g = jax.jit(jax.grad(lambda p: jnp.sum(blk.apply(p, x) ** 2)))(params)
        assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))

    def test_causality(self):
        blk = self._block()
        b, t = 1, 16
        x = jax.random.normal(jax.random.PRNGKey(0), (b, t, 64))
        params = jax.jit(blk.init)(jax.random.PRNGKey(1), x)
        apply = jax.jit(blk.apply)
        out1 = apply(params, x)
        x2 = x.at[:, -1].set(7.0)
        out2 = apply(params, x2)
        np.testing.assert_allclose(
            np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), atol=1e-5
        )

    def test_mask_zeroes_padding_influence(self):
        blk = self._block()
        b, t = 1, 12
        x = jax.random.normal(jax.random.PRNGKey(0), (b, t, 64))
        params = jax.jit(blk.init)(jax.random.PRNGKey(1), x)
        mask = jnp.ones((b, t)).at[:, 6:].set(0.0)
        apply = jax.jit(blk.apply)
        out_masked = apply(params, x, mask)
        x_zeroed = x * mask[..., None]
        out_zeroed = apply(params, x_zeroed, mask)
        np.testing.assert_allclose(
            np.asarray(out_masked[:, :6]), np.asarray(out_zeroed[:, :6]), atol=1e-5
        )

    def test_dt_bias_init_is_inverse_softplus(self):
        blk = self._block()
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 64))
        import flax.linen as nn

        params = nn.unbox(jax.jit(blk.init)(jax.random.PRNGKey(1), x))
        dt_bias = params["params"]["decay_gate"]["dt_bias"]
        dt = np.asarray(jax.nn.softplus(dt_bias))
        assert (dt >= 1e-4 - 1e-9).all() and (dt <= 0.2).all()


@requires_modern_jax
def test_mla_with_ring_attention_matches_eager(devices):
    """MLA composes with context-parallel ring attention (long-context
    path for the latent-attention family): same outputs and grads as the
    eager backend on the gathered sequence."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from d9d_tpu.core import MeshParameters
    from d9d_tpu.ops.attention.ring import make_ring_sdpa

    ctx = MeshParameters(cp_shard=4).build(devices[:4])
    ring = make_ring_sdpa(
        ctx.mesh, seq_axis="cp_s", batch_axes=(), head_axes=()
    )

    def block(sdpa):
        return MultiHeadLatentAttention(
            hidden_size=64,
            num_heads=4,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=12,
            kv_lora_rank=32,
            sdpa=sdpa,
            dtype=jnp.float32,
        )

    b, t = 2, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (b, t, 64))
    cos, sin = _rope(b, t, 8)
    params = jax.jit(block(eager_sdpa).init)(jax.random.PRNGKey(1), x, cos, sin)

    def loss_eager(p, x):
        return jnp.sum(jnp.sin(block(eager_sdpa).apply(p, x, cos, sin)))

    x_sharded = jax.device_put(
        x, NamedSharding(ctx.mesh, P(None, "cp_s", None))
    )

    def loss_ring(p, x):
        return jnp.sum(jnp.sin(block(ring).apply(p, x, cos, sin)))

    l_e, g_e = jax.jit(jax.value_and_grad(loss_eager))(params, x)
    l_r, g_r = jax.jit(jax.value_and_grad(loss_ring))(params, x_sharded)
    np.testing.assert_allclose(float(l_r), float(l_e), rtol=1e-4, atol=1e-4)
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-5
        ),
        g_r,
        g_e,
    )
