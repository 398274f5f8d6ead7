"""AI21-Jamba2-3B's family on the shared backbone: Mamba-1 mixers beside
multi-query attention without rotation, a dense SwiGLU in every layer, a
tied table. The program against the plain reference
(``benchmarks/references/jamba.py``) on seeded weights at a small size
with the real pattern (a period of 14 with attention at 7), in training
mode, through the caches, and through the loss the Trainer trains with;
what the comparison catches (a state carried in bf16); and gradient steps
through ``Trainer``."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict, unflatten_dict

from benchmarks.harness import build, correct
from benchmarks.references import jamba as reference
from d9d_tpu.models.jamba import JambaCausalLM, jamba2_3b, jamba_tiny
from d9d_tpu.ops.attention.eager import eager_sdpa
from tests.models import tiny
from tests.models.tiny import VOCAB, count
from tests.models.tiny import ids as _ids

# one whole period and one layer more: Mamba x 7, attention, Mamba x 7
CFG = jamba_tiny(VOCAB, num_layers=15, attn_layer_period=14,
                 attn_layer_offset=7)
# the bf16 program on this rig: one layer of each kind (see BF16_REL_RMS)
PAIR = jamba_tiny(VOCAB)
# the view the benchmark hands the reference at the tiny size: it
# carries none of the family's keys, the reference reads the tree
HF = build.hf_view(CFG)

# Float32 program against the float32 reference: the same sums in another
# order (a chunked associative scan against a sequential one); the CPU
# gives 1e-6. The one family not held to tiny.F32_REL_RMS (1e-5).
F32_REL_RMS = 1e-4
# The benchmark's own bound. bf16 weights against the float32 reference
# reading the same weights. XLA's CPU backend rounds a bf16 program after
# every element-wise op where the TPU keeps a fusion's intermediates in
# float32: this rig reads 0.011 at two layers, 0.03 at these 15 and 0.05
# at 28, where the chip reads 0.006 to 0.008 at all 28 at the published
# widths (my chip runs, PR 32; PERF.md section 6). So the bf16 program is
# held to the bound here at one layer of each kind, and at the model's
# depth by the cell on the chip.
BF16_REL_RMS = correct.LOGITS_REL_RMS_TOL
BF16 = jnp.bfloat16


def _model(cfg=CFG, dtype=jnp.float32, dml=0, param_dtype=None):
    return JambaCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=dtype,
        param_dtype=param_dtype or dtype, decode_max_length=dml,
    )


def _params(dtype=jnp.float32, seed=0, cfg=CFG):
    return tiny.seeded_params(_model(cfg, param_dtype=dtype), seed)


def test_presets_hold_the_published_sizes():
    full = jamba2_3b()
    assert (full.num_layers, full.hidden_size) == (28, 2560)
    assert full.vocab_size == 65_536
    assert (full.num_heads, full.num_kv_heads, full.head_dim) == (20, 1, 128)
    assert set(range(28)) - set(full.mamba_layers) == {7, 21}
    assert full.mlp_only_layers == tuple(range(28))
    assert full.intermediate_size == 8192 and full.num_experts == 1
    assert (full.mamba_d_state, full.mamba_d_conv, full.mamba_expand,
            full.mamba_dt_rank) == (16, 4, 2, 160)
    assert full.rope_fraction == 0.0 and not full.qk_norm
    assert full.tie_word_embeddings and full.embedding_init_std == 0.02
    assert set(range(15)) - set(CFG.mamba_layers) == {7}
    # ISSUE 32's arithmetic, from abstract shapes at the published widths
    z = jnp.zeros((1, 8), jnp.int32)
    whole = JambaCausalLM(config=full, sdpa=eager_sdpa, dtype=BF16,
                          param_dtype=BF16)
    shapes = nn.unbox(jax.eval_shape(
        lambda: whole.init(jax.random.PRNGKey(0), z, z, z)["params"]
    ))

    layers = shapes["model"]
    assert round(count(layers["layers_0"]["mamba"]) / 1e5) == 412
    assert round(count(layers["layers_0"]["mlp"]) / 1e5) == 629
    assert round(count(layers["layers_0"]) / 1e6) == 104
    assert round(count(layers["layers_7"]) / 1e5) == 767
    assert count(layers["embed_tokens"]) == 65_536 * 2560
    assert "lm_head" not in shapes  # the head reads the table
    assert round(count(shapes) / 1e7) == 303


def test_the_tied_table_is_drawn_at_the_familys_range():
    table = _params()["model"]["embed_tokens"]["embedding_default"]
    assert 0.017 < float(jnp.std(table)) < 0.023


@pytest.mark.parametrize("cfg,dtype,tol", [
    (CFG, jnp.float32, F32_REL_RMS), (PAIR, BF16, BF16_REL_RMS),
], ids=["float32", "bf16"])
def test_training_mode_matches_the_reference(cfg, dtype, tol):
    params = _params(dtype, cfg=cfg)
    sample = np.asarray(_ids((2, 25)))
    system = correct.training_system(
        _model(cfg, dtype), {"params": params}, sample
    )
    want = correct.training_reference(
        reference, {"params": params}, build.hf_view(cfg), sample
    )
    checks = correct.compare_training(system, want)
    assert checks["logits_rel_rms"] <= tol, checks
    assert checks["loss_gap"] <= correct.LOSS_TOL, checks


@pytest.mark.parametrize("cfg,dtype,tol", [
    (CFG, jnp.float32, F32_REL_RMS), (PAIR, BF16, BF16_REL_RMS),
], ids=["float32", "bf16"])
def test_prefill_then_cached_decode_matches_the_full_forward(cfg, dtype, tol):
    """One prefill of 6 tokens, then 18 single-token steps through the
    state, the conv tails and the attention layer's cache, against the
    reference's full forward (the benchmark's serving comparison)."""
    params = _params(dtype, cfg=cfg)
    ids = np.asarray(_ids((1, 24), seed=2))
    got = correct.cached_logits(_model(cfg, dtype, dml=24), params, ids, 6)
    want = correct.reference_logits(
        reference, {"params": params}, build.hf_view(cfg), ids
    )[0]
    assert correct.rel_rms(got, want) <= tol


def test_a_state_space_stack_adds_its_residual_stream_in_float32():
    """Mamba's ``residual_in_fp32``: in a bf16 program every layer hands
    on a float32 stream (the blocks' operands and outputs stay bf16), and
    the head is fed the model's own type. On the chip at the published
    sizes this is 0.0024 to 0.0034 from the reference where a bf16 stream
    reads 0.0059 to 0.0082 (my chip runs, PR 32)."""
    model, params = _model(PAIR, BF16), _params(BF16, cfg=PAIR)
    ids = _ids((1, 6))
    pos = jnp.arange(6, dtype=jnp.int32)[None]
    hidden, state = jax.jit(lambda p: model.apply(
        {"params": p}, ids, pos, capture_intermediates=True
    ))(params)
    seen = state["intermediates"]["model"]
    for layer in ("layers_0", "layers_1"):
        assert seen[layer]["__call__"][0].dtype == jnp.float32
    assert seen["layers_0"]["mamba"]["__call__"][0].dtype == BF16
    assert seen["layers_1"]["mlp"]["__call__"][0].dtype == BF16
    assert hidden.dtype == BF16


def _decode_with_state_in(model, params, ids, leaf="ssm_state"):
    """Logits of every position, one token a step through the cache, with
    the recurrent state (the cache leaves named ``leaf``) kept in float32
    and with it rounded to bf16 after every step: one compiled program,
    which of the two is an argument."""

    def carried(cache, rounded):
        flat = flatten_dict(cache)
        return unflatten_dict({
            p: jnp.where(rounded, v.astype(BF16).astype(v.dtype), v)
            if p[-1] == leaf else v
            for p, v in flat.items()
        })

    @jax.jit
    def run(params, ids, rounded):
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
        first, state = model.apply(
            {"params": params}, ids[:, :1], pos[None, :1], method="logits",
            mutable=["cache"],
        )

        def step(cache, xs):
            tok, p = xs
            out, new = model.apply(
                {"params": params, "cache": cache}, tok[None, None],
                p[None, None], method="logits", mutable=["cache"],
            )
            return carried(new["cache"], rounded), out[0, 0]

        _, rest = jax.lax.scan(
            step, carried(state["cache"], rounded), (ids[0, 1:], pos[1:])
        )
        return jnp.concatenate([first[0], rest], axis=0)

    return tuple(
        np.asarray(run(params, jnp.asarray(ids), rounded), np.float32)
        for rounded in (False, True))


def test_a_state_carried_in_bf16_fails_the_comparison():
    """The tolerance has to catch the cheaper arithmetic: at the model's
    own depth (28 layers, attention at 7 and 21), bf16 weights and
    everything else float32, a state rounded to bf16 a step loses what
    its slow channels remember and reads 0.020 over 600 steps, while the
    float32 state reads under 0.001."""
    deep = jamba_tiny(VOCAB, 28, 14, 7)
    params = _params(BF16, cfg=deep)
    ids = np.asarray(_ids((1, 600), seed=3))
    model = _model(deep, jnp.float32, dml=600, param_dtype=BF16)
    want = correct.reference_logits(
        reference, {"params": params}, build.hf_view(deep), ids
    )[0]
    kept, rounded = _decode_with_state_in(model, params, ids)
    assert correct.rel_rms(kept, want) <= BF16_REL_RMS / 5
    assert correct.rel_rms(rounded, want) > BF16_REL_RMS


def test_the_fused_loss_and_its_gradients_are_the_references():
    """The path the Trainer trains with: the fused cross-entropy on the
    tied table, and ``jax.grad`` of it against ``jax.grad`` of the
    reference's loss, leaf by leaf (the table's gradient has both its
    uses in it)."""
    model, params = _model(), _params()
    tokens, labels = _ids((2, 20), seed=4), _ids((2, 20), seed=5)
    pos = jnp.broadcast_to(jnp.arange(20, dtype=jnp.int32), (2, 20))

    def loss(p):
        return model.apply({"params": p}, tokens, pos, labels).mean()

    def want_loss(p):
        return reference.loss(p, HF, tokens, labels)

    got, grads = jax.jit(jax.value_and_grad(loss))(params)
    want, want_grads = jax.jit(jax.value_and_grad(want_loss))(params)
    assert abs(float(got) - float(want)) <= 1e-5
    got_flat, want_flat = flatten_dict(grads), flatten_dict(want_grads)
    assert got_flat.keys() == want_flat.keys()
    for path, g in got_flat.items():
        assert correct.rel_rms(g, want_flat[path]) <= 1e-3, path
    table = got_flat[("model", "embed_tokens", "embedding_default")]
    assert np.asarray(table).any()


def test_the_reference_reads_the_layer_kinds_from_the_tree():
    params = _params()
    assert reference.layer_kinds(params["model"], 15) == (
        ["mamba"] * 7 + ["attention"] + ["mamba"] * 7
    )
    # with the family's keys it holds the tree to them
    keys = {
        "attn_layer_period": 14, "attn_layer_offset": 7,
        "tie_word_embeddings": True, "num_experts": 1,
        "mamba_proj_bias": False, "mamba_conv_bias": True,
        "mamba_expand": 2, "mamba_d_state": 16, "mamba_dt_rank": 4,
        "mamba_d_conv": 4, "intermediate_size": 128,
    }
    reference.check_sizes(params, {**HF, **keys})
    with pytest.raises(AssertionError):
        reference.check_sizes(params, {**HF, **keys, "attn_layer_offset": 6})
    with pytest.raises(AssertionError):
        reference.check_sizes(params, {**HF, **keys, "mamba_d_state": 8})


def test_tied_embeddings_need_one_stage():
    from d9d_tpu.pipelining import PipelineStageInfo

    z = jnp.zeros((1, 4), jnp.int32)
    first = JambaCausalLM(
        config=CFG, sdpa=eager_sdpa,
        stage=PipelineStageInfo(stage_index=0, num_stages=2),
    )
    with pytest.raises(ValueError, match="one pipeline stage"):
        first.init(jax.random.PRNGKey(0), z, z)


def test_gradient_steps_through_trainer_lower_the_loss():
    trainer = tiny.trainer(
        lambda stage: JambaCausalLM(
            config=jamba_tiny(VOCAB), sdpa=eager_sdpa, stage=stage,
            dtype=jnp.float32),
        total_steps=4, one_batch=True,
    )
    table = lambda p: np.asarray(  # noqa: E731
        nn.unbox(p)["params"]["model"]["embed_tokens"]["embedding_default"]
    )
    before = table(trainer.params)
    history = trainer.train()
    losses = [row["loss"] for row in history]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert "lm_head" not in nn.unbox(trainer.params)["params"]
    assert not np.array_equal(before, table(trainer.params))
