"""GLM-4.7-Flash (``glm4_moe_lite``) on the DeepSeek backbone: MLA with q
compression and d_qk == d_v, a dense first layer, the sigmoid ``noaux_tc``
router with a non-zero selection bias. The program against the plain
reference (``benchmarks/references/glm4_moe_lite.py``) in training mode
and through the caches, at the tiny preset on the CPU rig with seeded
weights, and one gradient step through ``Trainer``."""

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import build, correct
from benchmarks.references import glm4_moe_lite as reference
from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.models.deepseek import (
    DeepseekCausalLM,
    glm4_moe_lite_tiny,
    glm_4_7_flash,
)
from d9d_tpu.nn.attention import MultiHeadLatentAttention
from d9d_tpu.ops.attention.eager import eager_sdpa
from tests.models import tiny
from tests.models.tiny import F32_REL_RMS, VOCAB, count
from tests.models.tiny import ids as _ids

CFG = glm4_moe_lite_tiny(VOCAB)
HF = build.hf_view(CFG)

# bf16 weights and activations against the float32 reference reading the
# same bf16 weights: every activation is rounded to 8 bits of mantissa
# (relative step 2^-8 = 0.0039) a few times a layer; two layers at these
# widths give 0.0067 on the CPU. 0.015 is the benchmark's own bound
# (benchmarks/harness/correct.py LOGITS_REL_RMS_TOL): arithmetic one step
# coarser (fp8, relative step 2^-4) reads sixteen times the bf16 figure.
BF16_REL_RMS = correct.LOGITS_REL_RMS_TOL
LOSS_TOL = {jnp.float32: 1e-5, jnp.bfloat16: correct.LOSS_TOL}


def _model(dtype=jnp.float32, dml=0):
    return DeepseekCausalLM(
        config=CFG, sdpa=eager_sdpa, dtype=dtype, param_dtype=dtype,
        decode_max_length=dml,
    )


def _bias_the_router(params, rng):
    router = params["model"]["layers_1"]["mlp"]["router"]
    router["e_score_correction_bias"] = jnp.asarray(
        rng.uniform(-0.3, 0.3, CFG.num_experts), jnp.float32)


def _params(dtype=jnp.float32, seed=0):
    """Seeded weights with a NON-ZERO selection bias: zero at init as in
    the published code, so every comparison below sets one."""
    return tiny.seeded_params(_model(dtype), seed, _bias_the_router)


def test_presets_hold_the_published_sizes():
    full = glm_4_7_flash()
    assert (full.num_layers, full.hidden_size, full.num_heads) == (47, 2048, 20)
    assert full.mla.q_lora_rank == 768 and full.mla.kv_lora_rank == 512
    assert full.mla.qk_nope_head_dim + full.mla.qk_rope_head_dim == 256
    assert full.mla.v_head_dim == 256 and full.mlp_only_layers == (0,)
    assert full.router_score_function == "sigmoid" and full.router_expert_bias
    # the tiny twin keeps every mechanism on
    assert CFG.mla.q_lora_rank and CFG.router_score_function == "sigmoid"
    assert CFG.mla.v_head_dim == (
        CFG.mla.qk_nope_head_dim + CFG.mla.qk_rope_head_dim
    )
    # ISSUE 27's arithmetic, from abstract shapes at the published widths
    z = jnp.zeros((1, 8), jnp.int32)
    two = DeepseekCausalLM(
        config=dataclasses.replace(full, num_layers=2),
        sdpa=eager_sdpa, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    shapes = nn.unbox(jax.eval_shape(
        lambda: two.init(jax.random.PRNGKey(0), z, z, z)["params"]
    ))

    assert round(count(shapes["model"]["layers_0"]) / 1e6) == 85
    assert round(count(shapes["model"]["layers_1"]) / 1e6) == 635
    head = count(shapes["lm_head"]) + count(shapes["model"]["embed_tokens"])
    assert round(head / 1e6) == 634


@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, F32_REL_RMS), (jnp.bfloat16, BF16_REL_RMS),
], ids=["float32", "bfloat16"])
def test_training_mode_matches_the_reference(dtype, tol):
    model, params = _model(dtype), _params(dtype)
    sample = np.asarray(_ids((2, 17)))
    system = correct.training_system(model, {"params": params}, sample)
    want = correct.training_reference(
        reference, {"params": params}, HF, sample
    )
    checks = correct.compare_training(system, want)
    assert checks["logits_rel_rms"] <= tol, checks
    assert checks["loss_gap"] <= LOSS_TOL[dtype], checks


def test_the_comparisons_depend_on_the_bias():
    """The reference with the bias removed gives other logits: some token
    is routed by it, so the comparisons here prove the program reads it."""
    params = _params()
    tokens = _ids((2, 16))
    logits = jax.jit(lambda p: reference.logits(p, HF, tokens))
    with_bias = logits(params)
    unbiased = jax.tree.map(lambda a: a, params)
    unbiased["model"]["layers_1"]["mlp"]["router"][
        "e_score_correction_bias"] = jnp.zeros((CFG.num_experts,))
    without = logits(unbiased)
    assert correct.rel_rms(without, with_bias) > 1e-3


@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, F32_REL_RMS), (jnp.bfloat16, BF16_REL_RMS),
], ids=["float32", "bfloat16"])
def test_prefill_then_absorbed_decode_matches_the_full_forward(dtype, tol):
    """One prefill of 5 tokens, then 11 single-token steps through the
    latent cache in the absorbed form, against the reference's full
    forward over all 16 positions (the benchmark's serving comparison)."""
    model, params = _model(dtype, dml=16), _params(dtype)
    ids = np.asarray(_ids((1, 16), seed=2))
    got = correct.cached_logits(model, params, ids, 5)
    want = correct.reference_logits(reference, {"params": params}, HF, ids)[0]
    assert correct.rel_rms(got, want) <= tol


def test_absorbed_decode_matches_the_decompressed_oracle():
    """MLA at this family's geometry (q compression, d_qk == d_v): every
    single-token step in rank space equals the step that decompresses the
    whole cache (``decode_absorbed=False``)."""
    mla = CFG.mla
    kwargs = dict(
        hidden_size=CFG.hidden_size, num_heads=CFG.num_heads,
        qk_nope_head_dim=mla.qk_nope_head_dim,
        qk_rope_head_dim=mla.qk_rope_head_dim, v_head_dim=mla.v_head_dim,
        kv_lora_rank=mla.kv_lora_rank, q_lora_rank=mla.q_lora_rank,
        sdpa=eager_sdpa, norm_eps=CFG.norm_eps, decode_max_length=12,
        dtype=jnp.float32,
    )
    b, t = 2, 10
    x = jax.random.normal(jax.random.PRNGKey(3), (b, t, CFG.hidden_size))
    angles = jnp.arange(t)[:, None] * (
        1.0 / 1e6 ** (jnp.arange(0, mla.qk_rope_head_dim, 2)
                      / mla.qk_rope_head_dim)
    )
    cos = jnp.broadcast_to(jnp.cos(angles), (b, t, angles.shape[-1]))
    sin = jnp.broadcast_to(jnp.sin(angles), (b, t, angles.shape[-1]))
    outs = {}
    for absorbed in (True, False):
        module = MultiHeadLatentAttention(decode_absorbed=absorbed, **kwargs)
        variables = jax.jit(module.init)(
            jax.random.PRNGKey(4), x[:, :1], cos[:, :1], sin[:, :1]
        )
        step = jax.jit(functools.partial(module.apply, mutable=["cache"]))
        cache, steps = variables["cache"], []
        for i in range(t):
            out, state = step(
                {"params": variables["params"], "cache": cache},
                x[:, i:i + 1], cos[:, i:i + 1], sin[:, i:i + 1],
            )
            cache = state["cache"]
            steps.append(out)
        outs[absorbed] = jnp.concatenate(steps, axis=1)
    np.testing.assert_allclose(outs[True], outs[False], rtol=2e-5, atol=2e-6)


def test_served_streams_paged_and_contiguous_equal_generate():
    """``ContinuousBatcher`` over the latent page pool and over contiguous
    per-row caches serves what ``generate`` does, token for token, and
    counts the positions it attended."""
    model, params = _model(dml=32), _params()
    prompts = [np.asarray(_ids((n,), seed=n)).tolist() for n in (3, 6, 4)]
    n_new = 9
    want = correct.generate_streams(
        model, params, prompts, n_new, max(len(p) for p in prompts)).tolist()
    for page_size in (None, 8):
        batcher = ContinuousBatcher(
            model, params, batch_size=2, page_size=page_size
        )
        rids = [batcher.submit(p, max_new_tokens=n_new) for p in prompts]
        outputs = batcher.drain()
        assert [outputs[r] for r in rids] == want, page_size
        # a request of p prompt and o output tokens takes p + o - 1 steps
        # and its step i attends i + 1 positions
        steps = [len(p) + n_new - 1 for p in prompts]
        stats = batcher.stats
        assert stats.slot_steps_busy == sum(steps)
        assert stats.positions_attended == sum(
            s * (s + 1) // 2 for s in steps
        )
        if page_size:
            # ceil((p + o - 1) / 8) pages a request, two requests at once
            assert 0 < stats.pool_pages_peak <= 4
            assert stats.pool_pages_total >= stats.pool_pages_peak
        else:
            assert stats.pool_pages_peak == stats.pool_pages_total == 0
        batcher.close()


def test_a_gradient_step_through_trainer_leaves_the_bias_alone():
    trainer = tiny.trainer(
        lambda stage: DeepseekCausalLM(
            config=CFG, sdpa=eager_sdpa, stage=stage, dtype=jnp.float32),
        total_steps=2, one_batch=False, weight_decay=0.1,
    )
    def router(p):
        return nn.unbox(p)["params"]["model"]["layers_1"]["mlp"]["router"]

    before = jax.tree.map(np.asarray, router(trainer.params))
    history = trainer.train()
    after = jax.tree.map(np.asarray, router(trainer.params))
    assert all(np.isfinite(row["loss"]) for row in history)
    # the gate learns; the selection bias is the balancing controller's,
    # not the optimizer's: zero gradient, zero at init, still zero
    assert not np.array_equal(before["gate"]["kernel"], after["gate"]["kernel"])
    assert not after["e_score_correction_bias"].any()


def test_the_bias_has_no_gradient():
    model, params = _model(), _params()
    tokens = _ids((2, 16))
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))

    def loss(p):
        return model.apply(
            {"params": p}, tokens, pos, tokens, mutable=["moe_stats"]
        )[0].mean()

    grads = jax.jit(jax.grad(loss))(params)
    router = grads["model"]["layers_1"]["mlp"]["router"]
    assert not np.asarray(router["e_score_correction_bias"]).any()
    assert np.asarray(router["gate"]["kernel"]).any()
