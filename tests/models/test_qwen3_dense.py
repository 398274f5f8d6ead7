import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytestmark = pytest.mark.e2e  # slow tier: heavy kernel/e2e parity


from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu.pipelining import PipelineStageInfo
from tests.models import tiny


@pytest.fixture(scope="module")
def tiny_cfg():
    return Qwen3DenseConfig.tiny(vocab_size=128)


def make_model(cfg, stage=PipelineStageInfo(), dtype=jnp.float32):
    return Qwen3DenseCausalLM(
        config=cfg, sdpa=eager_sdpa, stage=stage, dtype=dtype, param_dtype=jnp.float32
    )


def test_forward_loss_shape(tiny_cfg):
    model = make_model(tiny_cfg)
    tokens = jnp.arange(24).reshape(2, 12) % 128
    positions = jnp.broadcast_to(jnp.arange(12), (2, 12))
    labels = jnp.roll(tokens, -1, axis=1)
    params = {"params": tiny.seeded_params(model)}
    loss = jax.jit(model.apply)(params, tokens, positions, labels)
    assert loss.shape == (2, 12)
    assert np.isfinite(np.asarray(loss)).all()


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_grads_flow(tiny_cfg):
    model = make_model(tiny_cfg)
    tokens = jnp.arange(16).reshape(2, 8) % 128
    positions = jnp.broadcast_to(jnp.arange(8), (2, 8))
    labels = jnp.roll(tokens, -1, axis=1)
    params = model.init(jax.random.PRNGKey(0), tokens, positions, labels)

    def loss_fn(p):
        return model.apply(p, tokens, positions, labels).mean()

    grads = jax.grad(loss_fn)(params)
    leaves = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in leaves)
    assert any(np.abs(np.asarray(g)).max() > 0 for g in leaves)


def test_pipeline_stage_split_matches_full(tiny_cfg):
    """Two chained stages with the full model's params must reproduce the
    single-stage forward exactly (global layer naming contract)."""
    full = make_model(tiny_cfg)
    tokens = jnp.arange(16).reshape(2, 8) % 128
    positions = jnp.broadcast_to(jnp.arange(8), (2, 8))
    labels = jnp.roll(tokens, -1, axis=1)
    params = {"params": tiny.seeded_params(full)}
    full_loss = jax.jit(full.apply)(params, tokens, positions, labels)

    s0 = make_model(tiny_cfg, PipelineStageInfo(0, 2))
    s1 = make_model(tiny_cfg, PipelineStageInfo(1, 2))
    p = params["params"]
    p0 = {"params": {"model": {
        "embed_tokens": p["model"]["embed_tokens"],
        "layers_0": p["model"]["layers_0"],
    }}}
    p1 = {"params": {
        "model": {"layers_1": p["model"]["layers_1"], "norm": p["model"]["norm"]},
        "lm_head": p["lm_head"],
    }}
    h = jax.jit(s0.apply)(p0, tokens, positions)
    assert h.shape == (2, 8, tiny_cfg.hidden_size)
    loss = jax.jit(s1.apply)(p1, h, positions, labels)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(full_loss), rtol=1e-5)


def test_hf_parity(tiny_cfg):
    """Numerical parity vs transformers Qwen3ForCausalLM with copied weights.

    Mirrors the reference's block-level HF parity tests
    (test/d9d_test/modules/block/attention/grouped_query/test_hf_qwen3.py).
    """
    torch = pytest.importorskip("torch")
    from transformers import Qwen3Config, Qwen3ForCausalLM

    cfg = tiny_cfg
    hf_cfg = Qwen3Config(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta,
        tie_word_embeddings=False,
        attention_bias=False,
    )
    torch.manual_seed(0)
    hf = Qwen3ForCausalLM(hf_cfg).eval()

    def t2j(t):
        return jnp.asarray(t.detach().numpy())

    hfm = hf.model
    layers = {}
    for i, hl in enumerate(hfm.layers):
        layers[f"layers_{i}"] = {
            "input_layernorm": {"weight": t2j(hl.input_layernorm.weight)},
            "post_attention_layernorm": {
                "weight": t2j(hl.post_attention_layernorm.weight)
            },
            "self_attn": {
                "q_proj": {"kernel": t2j(hl.self_attn.q_proj.weight).T},
                "k_proj": {"kernel": t2j(hl.self_attn.k_proj.weight).T},
                "v_proj": {"kernel": t2j(hl.self_attn.v_proj.weight).T},
                "o_proj": {"kernel": t2j(hl.self_attn.o_proj.weight).T},
                "q_norm": {"weight": t2j(hl.self_attn.q_norm.weight)},
                "k_norm": {"weight": t2j(hl.self_attn.k_norm.weight)},
            },
            "mlp": {
                "gate_proj": {"kernel": t2j(hl.mlp.gate_proj.weight).T},
                "up_proj": {"kernel": t2j(hl.mlp.up_proj.weight).T},
                "down_proj": {"kernel": t2j(hl.mlp.down_proj.weight).T},
            },
        }
    params = {"params": {
        "model": {
            "embed_tokens": {"embedding_default": t2j(hfm.embed_tokens.weight)},
            "norm": {"weight": t2j(hfm.norm.weight)},
            **layers,
        },
        "lm_head": {"head_default": t2j(hf.lm_head.weight)},
    }}

    model = make_model(cfg)
    tokens_np = np.arange(20).reshape(2, 10) % cfg.vocab_size
    positions = jnp.broadcast_to(jnp.arange(10), (2, 10))
    ours = jax.jit(lambda p, t: model.apply(
        p, t, positions, method=model.logits
    ))(params, jnp.asarray(tokens_np))
    with torch.no_grad():
        theirs = hf(torch.tensor(tokens_np)).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_remat_policy_variants_match(devices):
    """remat off / full / dots_no_batch compute identical losses."""
    import dataclasses

    from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
    from d9d_tpu.ops.attention.eager import eager_sdpa

    base = dataclasses.replace(Qwen3DenseConfig.tiny(), remat=False)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (2, 16)), jnp.int32
    )
    positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))

    def loss_and_grad(cfg):
        model = Qwen3DenseCausalLM(config=cfg, sdpa=eager_sdpa, dtype=jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), tokens, positions, tokens)
        params = {"params": variables["params"]}
        return jax.value_and_grad(
            lambda p: model.apply(p, tokens, positions, tokens).mean()
        )(params)

    l0, g0 = loss_and_grad(base)
    for policy in ("full", "dots_no_batch"):
        cfg = dataclasses.replace(base, remat=True, remat_policy=policy)
        l, g = loss_and_grad(cfg)
        np.testing.assert_allclose(float(l), float(l0), rtol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
            ),
            g,
            g0,
        )
