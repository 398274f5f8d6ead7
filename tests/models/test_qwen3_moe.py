"""Qwen3-MoE model tests: forward shapes, EP==local parity on the mesh,
HF parity (reference strategy: moe block + model HF tests, SURVEY §4.2-4.3)."""

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
# slow tier: heavy kernel/e2e parity
pytestmark = [pytest.mark.e2e, requires_modern_jax]


from d9d_tpu.core import MeshParameters
from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig
from d9d_tpu.nn.moe import MoELayer
from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu.ops.attention.pallas_flash import make_pallas_flash_sdpa
from tests.jaxpr_tools import count, equations
from tests.models import tiny

B, T = 4, 16


@pytest.fixture(scope="module")
def ctx():
    return MeshParameters(dp_shard=4, tp=2, ep_shard=8).build(jax.devices())


def _model(ep_axes=None):
    return Qwen3MoeCausalLM(
        config=Qwen3MoeConfig.tiny(ep_axes=ep_axes),
        sdpa=eager_sdpa,
        dtype=jnp.float32,
    )


def _inputs(vocab=256):
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, vocab, (B, T)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    return tokens, positions


def _variables(model):
    """Seeded weights of ``model`` as ``apply`` takes them."""
    return {"params": tiny.seeded_params(model)}


@pytest.fixture(scope="module")
def local_path():
    """The tiny model without ``ep_axes`` on ``_inputs()``: its weights,
    loss and gradient from one jitted program, on the host so that every
    mesh of this module can read them; the three EP comparisons' oracle."""
    tokens, positions = _inputs()
    local = _model()

    def loss(p):
        out = local.apply(p, tokens, positions, tokens)
        return out.sum(), out

    (_, loss_local), g_local = jax.jit(
        jax.value_and_grad(loss, has_aux=True))(_variables(local))
    return jax.tree.map(np.asarray, (_variables(local), loss_local, g_local))


def test_forward_loss_shape(ctx, local_path):
    _, loss, _ = local_path
    assert loss.shape == (B, T)
    assert np.isfinite(np.asarray(loss)).all()


def test_ep_matches_local(ctx, local_path):
    tokens, positions = _inputs()
    params, loss_local, g_local = local_path

    ep = _model(ep_axes=ctx.ep_shard_axes)
    loss_ep = jax.jit(ep.apply)(params, tokens, positions, tokens)
    np.testing.assert_allclose(
        np.asarray(loss_ep), np.asarray(loss_local), rtol=2e-4, atol=2e-5
    )

    g_ep = jax.jit(
        jax.grad(lambda p: ep.apply(p, tokens, positions, tokens).sum())
    )(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-4
        ),
        g_local,
        g_ep,
    )


def test_mlp_only_layers_are_dense(ctx):
    cfg = Qwen3MoeConfig(
        vocab_ranges=(("default", 64),),
        hidden_size=32,
        num_layers=2,
        num_heads=2,
        num_kv_heads=1,
        head_dim=16,
        moe_intermediate_size=32,
        num_experts=4,
        num_experts_per_tok=2,
        intermediate_size=48,
        mlp_only_layers=(0,),
        remat=False,
    )
    model = Qwen3MoeCausalLM(config=cfg, sdpa=eager_sdpa, dtype=jnp.float32)
    tokens, positions = _inputs(vocab=64)
    variables = jax.eval_shape(  # the tree's names: nothing compiles
        lambda: model.init(jax.random.PRNGKey(0), tokens, positions, tokens))
    layers = variables["params"]["model"]
    assert "gate_proj" in layers["layers_0"]["mlp"]  # dense SwiGLU
    assert "router" in layers["layers_1"]["mlp"]  # MoE


def test_moe_layer_tokens_per_expert_stats(ctx):
    layer = MoELayer(
        hidden_dim=16,
        intermediate_dim_grouped=32,
        num_grouped_experts=8,
        top_k=2,
        dtype=jnp.float32,
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    variables = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
    _, stats = jax.jit(lambda p: layer.apply(
        {"params": p}, x, mutable=["moe_stats"]
    ))(variables["params"])
    tpe = stats["moe_stats"]["tokens_per_expert"]
    tpe = tpe[0] if isinstance(tpe, tuple) else tpe
    assert int(np.asarray(tpe).sum()) == 2 * 8 * 2


def test_local_path_has_no_buffer_choice(ctx):
    """Without ``ep_axes`` the layer is the local permute path and nothing
    of the EP path's run-time buffer choice is in its program: no
    conditional and no custom VJP of the exchange, forward or backward,
    and the only statistic sown is ``tokens_per_expert``."""
    layer = MoELayer(
        hidden_dim=16, intermediate_dim_grouped=32, num_grouped_experts=8,
        top_k=2, dtype=jnp.float32,
    )
    # 256 rows: a call of 128 or fewer takes the all-expert products
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 16))
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]

    def loss(p, x):
        out, stats = layer.apply({"params": p}, x, mutable=["moe_stats"])
        return (out ** 2).sum(), stats

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, has_aux=True))(params, x)
    eqns = list(equations(jaxpr.jaxpr))
    names = {eqn.primitive.name for eqn in eqns}
    assert "ragged_dot_general" in names  # the scan reaches the experts
    assert not names & {"cond", "custom_vjp_call_jaxpr"}
    # the only custom VJPs are the row movements' given transposes, each
    # calling its mirror in the backward (ops/moe.py), never the exchange's
    assert {
        eqn.params["bwd"].__name__ for eqn in eqns
        if eqn.primitive.name == "custom_vjp_call"
    } <= {"_permute_rows_bwd", "_spread_to_pairs_bwd", "_combine_pairs_bwd"}
    assert not names & {"all_gather", "ragged_all_to_all", "shard_map"}
    _, stats = jax.eval_shape(loss, params, x)
    assert set(stats["moe_stats"]) == {"tokens_per_expert"}


@pytest.mark.parametrize("token_layout", [False, True], ids=["legacy", "layout"])
@pytest.mark.parametrize("routing", ["seeded", "one_shard"])
def test_ep_layer_sows_buffer_use(ctx, token_layout, routing):
    """The EP flows report the receive buffer taken, the rows needed and a
    fallback beside ``tokens_per_expert``; with every token forced onto
    one shard's experts the last rung runs and the layer still equals the
    local path."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from d9d_tpu.ops.ep_dispatch import ep_buffer_ladder

    kw = dict(
        hidden_dim=16, intermediate_dim_grouped=32, num_grouped_experts=16,
        top_k=2, router_enable_expert_bias=True, dtype=jnp.float32,
    )
    local = MoELayer(**kw)
    ep = MoELayer(
        ep_axes=ctx.ep_shard_axes,
        token_axes=(ctx.batch_axes, ctx.sequence_axes) if token_layout else None,
        **kw,
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16))
    params = jax.jit(local.init)(jax.random.PRNGKey(0), x)["params"]
    if routing == "one_shard":
        # the selection bias sends every token to experts 0 and 1
        bias = jnp.zeros((16,)).at[:2].set(10.0)
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: bias if "e_score_correction_bias" in str(path)
            else leaf, params,
        )
    if token_layout:
        x = jax.device_put(
            x, NamedSharding(ctx.mesh, P(ctx.batch_axes, ctx.sequence_axes))
        )
    want = jax.jit(local.apply)({"params": params}, x)
    got, stats = jax.jit(
        lambda p, x: ep.apply({"params": p}, x, mutable=["moe_stats"])
    )(params, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
    )

    stats = {k: float(np.asarray(v).sum()) if k != "tokens_per_expert"
             else np.asarray(v) for k, v in stats["moe_stats"].items()}
    world = 8
    rows = 8 * 16 * 2  # the one EP group's assignment rows
    ladder = ep_buffer_ladder(rows // world, world)
    needed = stats["tokens_per_expert"].reshape(world, -1).sum(axis=1).max()
    assert stats["ep_dispatches"] == 1.0
    assert stats["ep_rows_needed"] == needed
    assert stats["ep_buffer_rows"] == min(r for r in ladder if r >= needed)
    assert stats["ep_fallbacks"] == float(stats["ep_buffer_rows"] == ladder[-1])
    if routing == "one_shard":
        assert needed == rows and stats["ep_fallbacks"] == 1.0


@pytest.mark.parametrize(
    "mesh_kw",
    [
        {"dp_shard": 4, "tp": 2, "ep_shard": 8},
        # cp in the token axes AND the ep suffix: t@cp_s flatten path
        {"dp_shard": 2, "cp_shard": 2, "tp": 2, "ep_shard": 4},
    ],
    ids=["dp_tp", "dp_cp_tp"],
)
def test_ep_token_layout_matches_local(mesh_kw, local_path):
    """The token-layout EP flow (shard_map riding the residual
    [B@dp, T@cp, D] sharding, non-token ep axes subdividing ownership)
    computes the same loss/grads as the local path."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    ctx = MeshParameters(**mesh_kw).build(jax.devices())
    tokens, positions = _inputs()
    params, loss_local, g_local = local_path

    import dataclasses

    # thread the residual layout (batch over dp; t over cp_s when present)
    cfg = dataclasses.replace(
        Qwen3MoeConfig.tiny(ep_axes=ctx.ep_shard_axes),
        moe_token_axes=(ctx.batch_axes, ctx.sequence_axes),
    )
    ep = Qwen3MoeCausalLM(config=cfg, sdpa=eager_sdpa, dtype=jnp.float32)
    sharded_tokens = jax.device_put(
        tokens, NamedSharding(ctx.mesh, P(ctx.batch_axes, ctx.sequence_axes))
    )
    loss_ep = jax.jit(ep.apply)(params, sharded_tokens, positions, tokens)
    np.testing.assert_allclose(
        np.asarray(loss_ep), np.asarray(loss_local), rtol=2e-4, atol=2e-5
    )

    g_ep = jax.jit(
        jax.grad(lambda p: ep.apply(p, sharded_tokens, positions, tokens).sum())
    )(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-4
        ),
        g_local,
        g_ep,
    )


class TestHybridLinearAttention:
    """Hybrid GDN:attention stacks (beyond-reference; BASELINE config 5)."""

    def test_param_structure_and_forward(self, ctx):
        model = Qwen3MoeCausalLM(
            config=Qwen3MoeConfig.hybrid_tiny(), sdpa=eager_sdpa,
            dtype=jnp.float32,
        )
        tokens, positions = _inputs()
        params = _variables(model)
        layers = params["params"]["model"]
        for i in (0, 1, 2):
            assert "linear_attn" in layers[f"layers_{i}"], i
            assert "self_attn" not in layers[f"layers_{i}"], i
        assert "self_attn" in layers["layers_3"]
        loss = jax.jit(model.apply)(params, tokens, positions, tokens)
        assert loss.shape == (B, T)
        assert np.isfinite(np.asarray(loss)).all()

    def test_hybrid_trains(self, ctx):
        """Loss decreases on a memorizable batch through GDN + MoE layers."""
        import optax

        model = Qwen3MoeCausalLM(
            config=Qwen3MoeConfig.hybrid_tiny(), sdpa=eager_sdpa,
            dtype=jnp.float32,
        )
        tokens, positions = _inputs()
        params = _variables(model)
        opt = optax.adam(3e-3)
        state = opt.init(params)

        @jax.jit
        def step(p, s):
            l, g = jax.value_and_grad(
                lambda p: model.apply(p, tokens, positions, tokens).mean()
            )(p)
            u, s = opt.update(g, s, p)
            return optax.apply_updates(p, u), s, l

        losses = []
        for _ in range(20):
            params, state, l = step(params, state)
            losses.append(float(l))
        assert losses[-1] < losses[0] * 0.7, losses


def test_hybrid_padding_mask_blocks_contamination(ctx):
    """Padded positions must not leak into later tokens through the GDN
    conv/recurrent state (HF apply_mask_to_padding_states semantics)."""
    model = Qwen3MoeCausalLM(
        config=Qwen3MoeConfig.hybrid_tiny(), sdpa=eager_sdpa,
        dtype=jnp.float32,
    )
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 256, (1, 12)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32), (1, 12))
    params = _variables(model)

    # garbage in the first 4 (padded) positions must not change outputs at
    # the real positions when both masks exclude them: padding_mask zeroes
    # GDN inputs, the sdpa mask blocks attention to the padded keys (the
    # same split HF makes — attention_mask drives both)
    pad_mask = jnp.asarray([[0, 0, 0, 0] + [1] * 8], jnp.int32)
    attn_mask = pad_mask[:, None, None, :].astype(bool)
    corrupted = tokens.at[:, :4].set(7)
    logits = jax.jit(lambda t, padding_mask: model.apply(
        params, t, positions, method=model.logits,
        mask=attn_mask, padding_mask=padding_mask,
    ))
    out_a = logits(tokens, pad_mask)
    out_b = logits(corrupted, pad_mask)
    np.testing.assert_allclose(
        np.asarray(out_a[:, 4:]), np.asarray(out_b[:, 4:]), atol=1e-5
    )
    # sdpa mask alone is NOT enough — without padding_mask the pad tokens
    # still flow through the GDN conv/recurrence (the bug being pinned)
    out_c = logits(corrupted, None)
    assert not np.allclose(np.asarray(out_a[:, 4:]), np.asarray(out_c[:, 4:]),
                           atol=1e-5)


class TestRematPolicies:
    """All remat policies must produce identical gradients — they differ
    only in what gets recomputed vs saved (models/qwen3/dense.py
    _remat_policy; "save_expensive" keeps named grouped-dot outputs) — and
    every one keeps the flash call's output and log-sum-exp: through the
    Pallas backend the gradient's jaxpr holds three kernel calls a layer
    (forward, dq, dk/dv), never a second forward."""

    def test_grad_parity_across_policies(self):
        toks = jnp.ones((2, 16), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
        grads = {}
        for policy in ("full", "dots_no_batch", "save_expensive"):
            cfg = Qwen3MoeConfig(
                vocab_ranges=(("default", 64),), hidden_size=32,
                num_layers=2, num_heads=2, num_kv_heads=1, head_dim=16,
                moe_intermediate_size=32, num_experts=4,
                num_experts_per_tok=2, remat=True, remat_policy=policy,
            )
            m = Qwen3MoeCausalLM(
                config=cfg,
                sdpa=make_pallas_flash_sdpa(block_q=16, block_kv=16),
                dtype=jnp.float32,
            )
            variables = jax.jit(m.init)(jax.random.PRNGKey(0), toks, pos, toks)
            params = variables["params"]
            rest = {k: v for k, v in variables.items() if k != "params"}

            def loss(p):
                out = m.apply(
                    {"params": p, **rest}, toks, pos, toks,
                    mutable=["moe_stats", "moe_buffers"],
                )[0]
                return sum(
                    jnp.sum(leaf.astype(jnp.float32))
                    for leaf in jax.tree.leaves(out)
                )

            traced = jax.jit(jax.grad(loss)).trace(params)
            assert count(
                traced.jaxpr.jaxpr,
                lambda eqn: eqn.primitive.name == "pallas_call") == 3 * 2, policy
            grads[policy] = traced.lower().compile()(params)

        ref = jax.tree.leaves(grads["full"])
        for policy in ("dots_no_batch", "save_expensive"):
            for a, b in zip(ref, jax.tree.leaves(grads[policy])):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
