"""fused_qkv parity: one-matmul q/k/v must be numerically identical to
three Dense projections with the SAME parameter pytree (r4 dense-MFU
lever; checkpoints/plans see no difference)."""

import pytest

# slow tier (r5 quick-tier trim): whole-model double-build parity
pytestmark = pytest.mark.e2e

import jax
import jax.numpy as jnp
import numpy as np

from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
from d9d_tpu.ops.attention.eager import eager_sdpa


def _cfg(fused):
    return Qwen3DenseConfig(
        vocab_ranges=(("default", 64),), hidden_size=32, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, intermediate_size=64,
        remat=False, fused_qkv=fused,
    )


@pytest.mark.slow  # >10s compile-bound on the 2-core rig; e2e tier covers it
def test_fused_qkv_matches_unfused_params_and_outputs():
    from d9d_tpu.core import MeshParameters

    # a previous test may leave a tp>1 ambient mesh (MeshParameters.build
    # sets it globally), which the fused path rightfully rejects — pin the
    # single-device mesh this test is about
    MeshParameters().build(jax.devices()[:1])
    b, t = 2, 16
    tokens = jnp.zeros((b, t), jnp.int32).at[:, 5:].set(3)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    labels = jnp.ones((b, t), jnp.int32)

    m_ref = Qwen3DenseCausalLM(config=_cfg(False), sdpa=eager_sdpa,
                               dtype=jnp.float32)
    m_fused = Qwen3DenseCausalLM(config=_cfg(True), sdpa=eager_sdpa,
                                 dtype=jnp.float32)
    p_ref = jax.jit(m_ref.init)(jax.random.PRNGKey(0), tokens, pos, labels)
    p_fused = jax.jit(m_fused.init)(jax.random.PRNGKey(0), tokens, pos, labels)

    # identical parameter pytree: same paths, shapes, and init values
    ref_leaves = jax.tree_util.tree_leaves_with_path(p_ref)
    fused_leaves = jax.tree_util.tree_leaves_with_path(p_fused)
    assert [k for k, _ in ref_leaves] == [k for k, _ in fused_leaves]
    for (k, a), (_, b_) in zip(ref_leaves, fused_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_), err_msg=str(k))

    def loss(m, p):
        out = m.apply(p, tokens, pos, labels)
        leaf = jax.tree.leaves(out)[0]
        return jnp.sum(leaf.astype(jnp.float32))

    l_ref, g_ref = jax.value_and_grad(lambda p: loss(m_ref, p))(p_ref)
    l_fused, g_fused = jax.value_and_grad(lambda p: loss(m_fused, p))(p_ref)
    np.testing.assert_allclose(np.asarray(l_fused), np.asarray(l_ref),
                               rtol=1e-6, atol=1e-6)
    for a, b_ in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_fused)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-6)


def test_fused_qkv_rejects_tp_mesh():
    import jax
    import pytest

    from d9d_tpu.core import MeshParameters

    ctx = MeshParameters(tp=2).build(jax.devices()[:2])
    del ctx  # MeshParameters.build sets the ambient mesh
    b, t = 1, 8
    tokens = jnp.zeros((b, t), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    labels = jnp.zeros((b, t), jnp.int32)
    m = Qwen3DenseCausalLM(config=_cfg(True), sdpa=eager_sdpa,
                           dtype=jnp.float32)
    with pytest.raises(ValueError, match="fused_qkv"):
        jax.jit(m.init)(jax.random.PRNGKey(0), tokens, pos, labels)


@pytest.fixture
def no_ambient_mesh():
    """The tests above leave their mesh ambient, and under a mesh of
    several devices "auto" keeps the token-chunk loop."""
    import numpy as np

    with jax.set_mesh(jax.sharding.Mesh(np.empty((), dtype=object), ())):
        yield


def test_cce_auto_respects_vocab_budget(no_ambient_mesh):
    """auto must keep looping when n*V exceeds the swept slab even at
    small n (large-vocab models never materialize [N, V]): every slab is
    a block of the vocabulary inside the budget, the tokens whole."""
    from unittest import mock

    import d9d_tpu.ops.linear_ce as lce

    h = jnp.ones((1024, 8), jnp.float32)
    w = jnp.ones((131072, 8), jnp.float32)  # n*V = 2^27 >> swept budget
    labels = jnp.zeros((1024,), jnp.int32)
    with mock.patch.object(
        lce, "_block_stats", wraps=lce._block_stats
    ) as spy, mock.patch.object(lce, "_chunk_loss") as chunk:
        lce.linear_cross_entropy(h, w, labels)
    # block loop: _block_stats is called via the scan's body trace, with
    # all 1024 tokens against a [65536, 8] block — never the whole table
    assert spy.called and not chunk.called
    for call in spy.call_args_list:
        assert call.args[0].shape[0] == 1024
        assert call.args[2].shape[0] * 1024 <= lce._AUTO_SINGLE_CHUNK_MAX_LOGITS
