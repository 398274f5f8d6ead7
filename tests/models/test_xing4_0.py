"""Xing4.0-29B-A4B (``xing4_0``) on the DeepSeek backbone: the DeepSeek-V3
block on a four-stream residual path mixed by Sinkhorn iterations, one
trained multi-token-prediction module, and a chip's share of an
expert-parallel job (a held range of the routed experts). The program
against the plain reference (``benchmarks/references/xing4_0.py``): logits,
the module's logits, the whole loss and its gradients, at the tiny preset
on the CPU rig with seeded weights; the serving path at tiny size; what
``hc_mult`` 1 without a range or a module leaves as it was."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import build, correct
from benchmarks.references import xing4_0 as reference
from d9d_tpu.core import MeshParameters
from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.models.deepseek import (
    DeepseekCausalLM,
    deepseek_v2_tiny,
    xing4_0_29b_a4b,
    xing4_0_29b_a4b_share8,
    xing4_0_tiny,
)
from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu import parallel
from d9d_tpu.parallel.plan import logical_to_mesh_sharding
from d9d_tpu.pipelining import PipelineStageInfo
from tests.models.tiny import F32_REL_RMS, VOCAB, count
from tests.models.tiny import ids as _ids
from tests.models import tiny

CFG = xing4_0_tiny(VOCAB)
HF = build.hf_view(CFG)


def _model(cfg=CFG, dtype=jnp.float32, dml=0, **extra):
    return DeepseekCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=dtype, param_dtype=dtype,
        decode_max_length=dml, **extra,
    )


def _bias_every_router(params, rng):
    blocks = [params["model"]["layers_1"]]
    if "mtp" in params:
        blocks.append(params["mtp"]["block"])
    for block in blocks:
        router = block["mlp"]["router"]
        router["e_score_correction_bias"] = jnp.asarray(
            rng.uniform(-0.3, 0.3, router["gate"]["kernel"].shape[1]),
            jnp.float32,
        )


def _params(cfg=CFG, dtype=jnp.float32, seed=0):
    """Seeded weights with a non-zero selection bias in every router."""
    return tiny.seeded_params(_model(cfg, dtype), seed, _bias_every_router)


@pytest.fixture(scope="module")
def trained():
    """The (2, 17) sample through the Trainer's task and through the
    reference, logits, loss, metrics and gradients: one compiled program
    each, read by the three tests below."""
    model, params = _model(), _params()
    sample = np.asarray(_ids((2, 17)))
    return (tiny.loss_and_grads(model, params, sample),
            tiny.reference_loss_and_grads(reference, params, HF, sample))


def test_presets_hold_the_published_sizes():
    full = xing4_0_29b_a4b()
    assert (full.num_layers, full.hidden_size, full.num_heads) == (40, 3584, 32)
    assert full.mla.q_lora_rank == 768 and full.mla.kv_lora_rank == 512
    assert (full.mla.qk_nope_head_dim, full.mla.qk_rope_head_dim,
            full.mla.v_head_dim) == (128, 64, 128)
    assert (full.num_experts, full.num_routed_experts) == (64, 64)
    assert (full.num_experts_per_tok, full.moe_intermediate_size) == (4, 1024)
    assert full.mlp_only_layers == (0, 1) and full.intermediate_size == 9216
    assert full.vocab_size == 131_072 and not full.tie_word_embeddings
    assert (full.hc_mult, full.hc_sinkhorn_iters, full.hc_eps,
            full.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert full.num_mtp_modules == 1 and full.rope_scaling.factor == 64.0
    # the tiny twin keeps every mechanism on, at the published constants
    assert (CFG.hc_mult, CFG.hc_sinkhorn_iters, CFG.hc_eps,
            CFG.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert CFG.num_mtp_modules == 1 and CFG.num_experts < CFG.num_routed_experts
    # ISSUE 35's arithmetic, from abstract shapes at the published widths
    share = xing4_0_29b_a4b_share8()
    assert (share.num_experts, share.first_held_expert, share.vocab_size,
            share.num_layers, share.mlp_only_layers) == (8, 0, 16_384, 5, (0,))
    z = jnp.zeros((1, 8), jnp.int32)
    model = _model(share, jnp.bfloat16)
    shapes = nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), z, z, z)["params"]))
    layers = shapes["model"]
    assert round(count(layers["layers_1"]["self_attn"]) / 1e6, 2) == 28.41
    assert round(count(layers["layers_1"]["attn_mhc"]) / 1e6, 3) == 0.344
    assert round(count(layers["layers_0"]["mlp"]) / 1e6, 1) == 99.1
    assert round(count(layers["layers_1"]) / 1e6, 1) == 128.4
    assert round(count(shapes["mtp"]) / 1e6, 1) == 154.1
    head = count(shapes["lm_head"]) + count(layers["embed_tokens"])
    assert round(head / 1e6, 1) == 117.4
    assert round(count(shapes) / 1e6, 1) == 913.5


def test_training_mode_matches_the_reference(trained):
    """Main-head logits and the whole loss (next token + 0.3 x the
    module's), through the Trainer's task as the benchmark compares them."""
    system, want = trained
    checks = correct.compare_training(system, want)
    assert checks["logits_rel_rms"] <= F32_REL_RMS, checks
    assert checks["loss_gap"] <= 1e-5, checks


def test_the_modules_logits_match_the_reference():
    model, params = _model(), _params()
    tokens = _ids((2, 16))
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    got, main = jax.jit(lambda p: tuple(
        model.apply({"params": p}, tokens, pos, method=method)
        for method in ("mtp_logits", "logits")))(params)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: reference.mtp_logits(p, HF, tokens))(params)
    assert want.shape == (2, 15, VOCAB)
    # the program's last position was given the first token: of no use
    assert correct.rel_rms(got[:, :-1], want) <= F32_REL_RMS
    # and the module is not the main head at another name
    assert correct.rel_rms(main[:, :-1], want) > 0.1


def test_the_loss_sees_the_module_and_its_terms_reach_the_metrics(trained):
    model, params = _model(), _params()
    system, want = trained
    host, total, mb = system["metrics"], system["loss"], system["mb"]
    assert host["loss/next_token"] + 0.3 * host["loss/mtp"] == pytest.approx(
        total, rel=1e-6)
    # near ln(64) each at seeded init; the module's 15 of 16 positions
    assert 3.5 < host["loss/next_token"] < 5.5
    assert 3.0 < host["loss/mtp"] < 5.5
    # two expert blocks (the stack's and the module's) of 32 tokens
    assert host["moe/rows_routed"] == 2 * 32 * CFG.num_experts_per_tok
    assert 0 < host["moe/rows_held"] < host["moe/rows_routed"]
    with jax.default_matmul_precision("highest"):
        without = dict(params)
        without.pop("mtp")
        plain = float(jax.jit(lambda p: reference.loss(
            p, HF, mb["tokens"], mb["labels"]))(without))
    whole = want["loss"]
    assert plain == pytest.approx(host["loss/next_token"], abs=1e-5)
    assert whole == pytest.approx(total, abs=1e-5)
    # ignored labels are masked in both terms (the same compiled program)
    masked = tiny.loss_and_grads(
        model, params, np.asarray(_ids((2, 17))),
        labels=np.where(np.arange(16) % 3 == 0, -100, mb["labels"]))
    assert masked["weight"] == 2 * 10 and np.isfinite(masked["loss"])


def test_gradients_match_the_reference(trained):
    """Of ``loss_sum / weight`` through the task, against the gradient of
    the reference's whole loss."""
    got, want = trained[0]["grads"], trained[1]["grads"]
    scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(want))
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        name = jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            flat_got[path], w, rtol=2e-4, atol=2e-6 * scale, err_msg=name)
    # every new mechanism's parameters take a gradient ...
    live = {
        "merge": got["mtp"]["merge"]["kernel"],
        "phi_pre": got["model"]["layers_1"]["mlp_mhc"]["phi_pre"],
        "phi_res": got["model"]["layers_1"]["attn_mhc"]["phi_res"],
        "a_post": got["mtp"]["block"]["mlp_mhc"]["a_post"],
        "experts": got["model"]["layers_1"]["mlp"]["grouped_experts"][
            "gate_proj"],
    }
    for name, g in live.items():
        assert float(jnp.abs(g).max()) > 1e-6 * scale, name
    # ... and the selection bias none
    bias = got["model"]["layers_1"]["mlp"]["router"]["e_score_correction_bias"]
    assert not np.asarray(bias).any()


@pytest.mark.parametrize("first", [0, 4, 12])
def test_each_share_matches_the_reference_given_the_same_share(first):
    """The reference is told the held range (``first_held_expert``) and
    leaves out what the absent experts would add, as the program does."""
    cfg = dataclasses.replace(CFG, first_held_expert=first)
    # the range is no part of what ``init`` draws: one tree for the three
    model, params = _model(cfg), _params()
    hf = dict(build.hf_view(cfg), first_held_expert=first)
    sample = np.asarray(_ids((1, 13), seed=first))
    system = tiny.loss_and_grads(model, params, sample, grads=False)
    checks = correct.compare_training(
        system,
        tiny.reference_loss_and_grads(
            reference, params, hf, sample, grads=False),
    )
    assert checks["logits_rel_rms"] <= F32_REL_RMS, checks
    assert checks["loss_gap"] <= 1e-5, checks
    if first:
        wrong = tiny.reference_loss_and_grads(
            reference, params, HF, sample, grads=False)
        assert correct.rel_rms(system["logits"], wrong["logits"]) > 1e-3


def test_the_shares_of_the_model_add_up_to_the_uncut_reference():
    """One expert layer's output over all four shares, the shared expert
    counted once, against the reference holding all 16 experts."""
    from d9d_tpu.nn.moe import MoELayer

    whole = dataclasses.replace(CFG, num_experts=16, num_routed_experts=0)
    params = _params(whole)["model"]["layers_1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, CFG.hidden_size))
    hf = build.hf_view(whole)
    with jax.default_matmul_precision("highest"):
        want = reference.sparse_block(x, params, hf)
        shared = reference.swiglu(x, params["shared_expert_module"]["expert"])

    def share(first):
        layer = MoELayer(
            hidden_dim=CFG.hidden_size,
            intermediate_dim_grouped=CFG.moe_intermediate_size,
            num_grouped_experts=CFG.num_experts, top_k=CFG.num_experts_per_tok,
            router_enable_expert_bias=True, router_score_function="sigmoid",
            routed_scaling=CFG.routed_scaling_factor,
            num_routed_experts=16, first_held_expert=first,
            dtype=jnp.float32, param_dtype=jnp.float32,
        )
        cut = {
            "router": params["router"],
            "grouped_experts": {
                k: v[first:first + CFG.num_experts]
                for k, v in params["grouped_experts"].items()
            },
        }
        return layer.apply({"params": cut}, x)

    # one program: un-jitted, every share's ``lax.switch`` is a compile
    routed = jax.jit(lambda: sum(
        share(first) for first in range(0, 16, CFG.num_experts)))()
    np.testing.assert_allclose(routed + shared, want, rtol=1e-4, atol=1e-6)


def test_prefill_then_cached_decode_matches_the_full_forward():
    model, params = _model(dml=16), _params()
    ids = np.asarray(_ids((1, 16), seed=2))
    got = correct.cached_logits(model, params, ids, 5)
    want = correct.reference_logits(reference, {"params": params}, HF, ids)[0]
    assert correct.rel_rms(got, want) <= F32_REL_RMS


def test_generate_and_the_batcher_serve_the_model():
    """The n-stream path holds no state: ``generate`` equals the greedy
    continuation of the full forward, and ``ContinuousBatcher`` serves the
    same streams; the module's parameters ride along unused."""
    model, params = _model(dml=32), _params()
    prompts = [np.asarray(_ids((n,), seed=n)).tolist() for n in (3, 6, 4)]
    n_new, width = 5, 16
    plain = _model()
    pos = jnp.arange(width, dtype=jnp.int32)[None]
    want = tiny.greedy_oracle(
        lambda p, t: plain.apply({"params": p}, t, pos, method="logits"),
        params, prompts, n_new, width)
    got = correct.generate_streams(
        model, params, prompts, n_new, max(len(p) for p in prompts)).tolist()
    assert got == want
    batcher = ContinuousBatcher(model, params, batch_size=2, page_size=8)
    rids = [batcher.submit(p, max_new_tokens=n_new) for p in prompts]
    outputs = batcher.drain()
    assert [outputs[r] for r in rids] == want
    batcher.close()


def test_one_stream_no_range_and_no_module_is_the_model_it_was():
    """``hc_mult`` 1, every expert held, no module: today's parameter tree
    and today's program, whatever the other new fields say."""
    old = deepseek_v2_tiny(VOCAB)
    same = dataclasses.replace(
        old, hc_mult=1, num_routed_experts=old.num_experts,
        num_mtp_modules=0, hc_sinkhorn_iters=7, mtp_loss_weight=0.9,
    )
    trees = [
        jax.tree.map(np.asarray, tiny.seeded_params(_model(cfg)))
        for cfg in (old, same)
    ]
    assert jax.tree.structure(trees[0]) == jax.tree.structure(trees[1])
    assert set(trees[0]) == {"model", "lm_head"}
    assert set(trees[0]["model"]["layers_1"]) == {
        "input_layernorm", "self_attn", "post_attention_layernorm", "mlp"}
    jax.tree.map(np.testing.assert_array_equal, *trees)
    tokens = _ids((2, 8))
    programs = [
        str(jax.make_jaxpr(lambda p, cfg=cfg: _model(cfg).apply(
            {"params": p}, tokens, tokens, tokens))(trees[0]))
        for cfg in (old, same)
    ]
    assert programs[0] == programs[1] and "sinkhorn" not in programs[0]


@pytest.mark.parametrize("stage,fails", [
    (PipelineStageInfo(0, 1), False),
    (PipelineStageInfo(0, 2), True),
    (PipelineStageInfo(1, 2), True),
], ids=["one_stage", "first_of_two", "last_of_two"])
def test_the_module_needs_embedding_and_head_on_one_stage(stage, fails):
    z = jnp.zeros((1, 8), jnp.int32)
    model = _model(stage=stage)
    carry = z if stage.is_first else jnp.zeros(
        (1, 8, CFG.hc_mult, CFG.hidden_size))

    def init():  # shapes are enough: nothing compiles
        return jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), carry, z, z))["params"]

    if fails:
        with pytest.raises(ValueError, match="one pipeline stage"):
            init()
    else:
        assert "mtp" in init()


def test_between_pipeline_stages_the_carry_is_the_stream():
    """Two stages of the stack without the module: the first hands on
    ``[B, T, n, C]`` and the last reads it out, equal to one stage."""
    cfg = dataclasses.replace(CFG, num_mtp_modules=0)
    params = _params(cfg)
    tokens = _ids((2, 8))
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
    whole = jax.jit(_model(cfg).apply)({"params": params}, tokens, pos, tokens)

    def stage_params(keep):
        model = {k: v for k, v in params["model"].items() if k in keep}
        out = {"model": model}
        if "norm" in keep:
            out["lm_head"] = params["lm_head"]
        return out

    first = _model(cfg, stage=PipelineStageInfo(0, 2))
    last = _model(cfg, stage=PipelineStageInfo(1, 2))
    carry = jax.jit(first.apply)(
        {"params": stage_params({"embed_tokens", "layers_0"})}, tokens, pos)
    assert carry.shape == (2, 8, cfg.hc_mult, cfg.hidden_size)
    loss = jax.jit(last.apply)(
        {"params": stage_params({"layers_1", "norm"})}, carry, pos, tokens)
    np.testing.assert_allclose(loss, whole, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("plan", ["replicate", "fsdp", "fsdp_ep"])
def test_every_new_parameter_gets_a_spec_and_the_stream_is_pinned(plan):
    ctx = MeshParameters(dp_shard=2).build(jax.devices()[:2])
    model = _model(act_sharding=ctx.batch_sharding())
    z = jnp.zeros((2, 8), jnp.int32)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), z, z, z))
    spec = nn.get_partition_spec(abstract)["params"]
    rules = getattr(parallel, plan + "_plan")(ctx).rules
    shardings = logical_to_mesh_sharding(spec, ctx.mesh, rules)
    shapes = nn.unbox(abstract)["params"]
    assert jax.tree.structure(shardings) == jax.tree.structure(shapes)
    sharded = plan != "replicate"
    for path, sharding in jax.tree_util.tree_leaves_with_path(shardings):
        name = jax.tree_util.keystr(path)
        if "phi_" in name or "merge" in name:
            assert any(a is not None for a in sharding.spec) == sharded, name
        if "['a_" in name or "['b_" in name or "norm']" in name:
            assert all(a is None for a in sharding.spec), name
    # a step under the plan: the stream's batch and sequence axes are
    # pinned as the plain stream's are, its stream axis is left whole
    params = jax.tree.map(
        lambda a, s: jax.device_put(jnp.zeros(a.shape, a.dtype), s),
        shapes, shardings)
    tokens = jax.device_put(_ids((2, 8)), ctx.batch_sharding())
    out = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, t, t, mutable=["moe_stats"])[0])(params, tokens)
    assert out.shape == (2, 8) and np.isfinite(np.asarray(out)).all()


def test_steps_through_trainer_under_remat_log_both_terms():
    from d9d_tpu.telemetry import get_telemetry

    trainer = tiny.trainer(
        lambda stage: DeepseekCausalLM(
            config=dataclasses.replace(CFG, remat=True), sdpa=eager_sdpa,
            stage=stage, dtype=jnp.float32),
        total_steps=3, one_batch=False,
    )
    before = jax.tree.map(np.asarray, nn.unbox(trainer.params)["params"])
    history = trainer.train()
    after = jax.tree.map(np.asarray, nn.unbox(trainer.params)["params"])
    assert len(history) == 3
    for row in history:
        assert row["loss"] == pytest.approx(
            row["loss/next_token"] + 0.3 * row["loss/mtp"], rel=1e-5)
        assert row["moe/rows_routed"] == 2 * 64 * CFG.num_experts_per_tok
    for path in (("mtp", "merge", "kernel"),
                 ("model", "layers_1", "attn_mhc", "phi_res"),
                 ("mtp", "block", "mlp_mhc", "b_post")):
        a, b = before, after
        for key in path:
            a, b = a[key], b[key]
        assert not np.array_equal(a, b), path
    # the routing counts ride the fetched steps' spans
    steps = [s for s in get_telemetry().registry.spans
             if s.name == "train/step" and s.meta]
    assert steps and steps[-1].meta["moe/rows_routed"] == 256.0
    assert 0 < steps[-1].meta["moe/rows_held"] < 256.0
    trainer.close()
