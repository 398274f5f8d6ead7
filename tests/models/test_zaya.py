"""ZAYA1's family on the shared decoder: compressed convolutional
attention (two causal convolutions over the joined query and key latents,
a q-k mean, half the value heads a token late, a key temperature, three
per-row tails beside the paged pool) under a top-1 MLP router that
carries state from layer to layer and may skip, with learned scales on
both residual additions. The program against the plain reference
(``benchmarks/references/zaya.py``) at the tiny preset on the CPU rig
with seeded weights whose learned vectors stand 0.3 from their ones and
zeros: logits, loss and gradients, prefill then cached decode,
``generate`` and the paged ``ContinuousBatcher`` (rows admitted over rows
that have served); what the comparison catches (each reading changed in
the reference alone); the presets' counts."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import build, correct
from benchmarks.references import zaya as reference
from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.models.qwen3.moe import CcaParameters
from d9d_tpu.models.zaya import (
    ZayaBackbone,
    ZayaCausalLM,
    zaya1_8b,
    zaya1_8b_decode,
    zaya_tiny,
)
from d9d_tpu.nn.decode_flags import recurrent_leaves
from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu.pipelining import PipelineStageInfo
from tests.models import tiny
from tests.models.tiny import F32_REL_RMS, VOCAB, count
from tests.models.tiny import ids as _ids

# every learned vector (the eight residual ones a layer, gamma, theta,
# the router's biases) 0.3 from its published one or zero; the taps and
# the convolutions' biases are drawn at random as they are
CFG = zaya_tiny(VOCAB, init_jitter=0.3)
# what the benchmark hands the reference at the tiny size: none of the
# family's keys, so the reference reads the tree
HF = build.hf_view(CFG)
BF16 = jnp.bfloat16
PAGE = 4


def _model(cfg=CFG, dtype=jnp.float32, dml=0, **extra):
    return ZayaCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=dtype, param_dtype=dtype,
        decode_max_length=dml, **extra,
    )


def _off_its_seed(params, rng):
    """The selection bias off its zeros, so that a forgotten one shows."""
    for layer in params["model"].values():
        if "mlp" in layer:
            bias = layer["mlp"]["router"]["e_score_correction_bias"]
            layer["mlp"]["router"]["e_score_correction_bias"] = jnp.asarray(
                rng.uniform(-0.1, 0.1, bias.shape), bias.dtype)


def _params():
    return tiny.seeded_params(_model(), 0, _off_its_seed)


SAMPLE = np.asarray(_ids((2, 25)))


def _rel(got, want) -> float:
    """Relative RMS distance of two trees of gradients, over all leaves."""
    pairs = list(zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    error = sum(float(jnp.sum(jnp.square(a - b))) for a, b in pairs)
    return (error / sum(float(jnp.sum(jnp.square(b))) for _, b in pairs)) ** 0.5


@pytest.fixture(scope="module")
def sampled():
    """The (2, 25) sample through the Trainer's task and through the
    reference: logits, loss and gradients, one compiled program each."""
    model, params = _model(), _params()
    return (
        tiny.loss_and_grads(model, params, SAMPLE),
        tiny.reference_loss_and_grads(reference, params, HF, SAMPLE),
    )


def test_presets_hold_the_published_sizes():
    full = zaya1_8b()
    assert (full.num_layers, full.hidden_size) == (40, 2048)
    assert set(full.layer_kinds) == {"cca"} and len(full.layer_kinds) == 40
    assert (full.num_heads, full.num_kv_heads, full.head_dim) == (8, 2, 128)
    assert full.cca == CcaParameters(
        time0=2, time1=2, conv1_grouped=True, qk_mean=True, value_shift=True,
        key_temperature=True)
    assert full.rope_fraction == 0.5 and full.rope_theta == 5e6
    assert (full.num_experts, full.num_routed_experts, full.first_held_expert,
            full.num_experts_per_tok, full.moe_intermediate_size) == (
        16, 17, 0, 1, 2048)
    assert full.router_skip and full.router_carry and full.residual_scaling
    assert full.router_hidden_size == 256 and full.router_expert_bias
    assert full.router_score_function == "softmax" and not full.norm_topk_prob
    assert full.shared_expert is None and not full.qk_norm
    assert full.vocab_size == 262_272 and full.tie_word_embeddings
    assert full.norm_eps == 1e-5 and full.init_jitter == 0.0
    assert full.float32_stream
    # ISSUE 56's hand count, from abstract shapes: the benchmark's cut
    # (the first 12 layers, every width, every expert, the whole table)
    # and, from its layers, the whole 40
    cut = zaya1_8b_decode()
    assert (cut.num_layers, cut.num_experts, cut.vocab_size) == (
        12, 16, 262_272)
    assert cut.init_jitter == 0.02 and cut.cca == full.cca
    z = jnp.zeros((1, 1), jnp.int32)
    served = nn.unbox(jax.eval_shape(
        lambda: _model(cut, BF16, dml=1152).init(
            jax.random.PRNGKey(0), z, z, z)))
    params = served["params"]
    layer = params["model"]["layers_1"]
    assert abs(count(layer) / 207.6e6 - 1) < 0.005
    assert count(layer["mlp"]["grouped_experts"]) == 16 * 3 * 2048 * 2048
    # W_Q and W_O 2,097,152 each, W_K 524,288, W_V1 + W_V2 524,288, the
    # depthwise taps and bias 3,840, the grouped taps and bias 328,960,
    # theta 2: ISSUE 56's 5.57 M
    assert count(layer["self_attn"]) == 5_575_682
    assert round(count(layer["mlp"]["router"]) / 1e6, 2) == 0.66
    # the first layer's router is handed no state and has no gamma
    first = params["model"]["layers_0"]
    assert "carry_scale" not in first["mlp"]["router"]
    assert count(layer) - count(first) == 256
    assert count(params["model"]["embed_tokens"]) == 262_272 * 2048
    assert "lm_head" not in params
    assert round(count(params) / 1e9, 3) == 3.028
    assert 8.3e9 <= count(params) + 28 * count(layer) <= 8.9e9
    # a caller's tails a layer: c[t-1] and c1[t-1] of 1,280, a late value
    per_row = recurrent_leaves(served["cache"])
    assert sorted({p[-1] for p in per_row}) == [
        "conv1_tail", "conv_tail", "value_tail"]
    tails = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                for v in per_row.values())
    assert tails == 12 * (1280 + 1280 + 128) * 2
    assert 256 * tails == 16_515_072  # the cell's serve.recurrent_state_gb


def test_training_mode_matches_the_reference(sampled):
    """Logits and the loss through the Trainer's task, and the gradient
    of that loss against the reference's own. Float32 against float32,
    the same sums in another order: ``tiny.F32_REL_RMS`` (1e-5; the CPU
    reads 2e-7), the gradients 1e-3 (2e-7)."""
    system, want = sampled
    checks = correct.compare_training(system, want)
    assert checks["logits_rel_rms"] <= F32_REL_RMS, checks
    assert checks["loss_gap"] <= 1e-5, checks
    assert _rel(system["grads"], want["grads"]) <= 1e-3
    # every learned vector is exercised: none has a zero gradient
    layer = system["grads"]["model"]["layers_1"]
    for leaf in (
        *layer["attn_residual"].values(), *layer["mlp_residual"].values(),
        layer["self_attn"]["key_temperature"],
        layer["self_attn"]["conv1_bias"],
        layer["mlp"]["router"]["fc1"]["bias"],
    ):
        assert float(jnp.abs(leaf).max()) > 0
    # the weight is the chosen score itself, so the router learns through
    # it, and through the carry the router before it does
    assert float(jnp.abs(
        layer["mlp"]["router"]["carry_scale"]).max()) > 0


@pytest.fixture(scope="module")
def reread():
    """The sample's logits under the reference with one reading changed
    at a time: one compiled program for all six."""
    run = jax.jit(lambda p, t: [
        reference.logits(p, HF, t, {reading: False})
        for reading in reference.READINGS])
    with jax.default_matmul_precision("highest"):
        others = run(_params(), jnp.asarray(SAMPLE[:, :-1]))
    return dict(zip(reference.READINGS, map(np.asarray, others)))


# what the benchmark's bound (0.015) tells and what only this float32
# test does, at the tiny preset (my CPU runs, PR 56)
TOLD_BY_THE_CELL = ("conv1_grouped", "qk_mean", "value_shift", "temperature")
TOLD_HERE_ALONE = ("depth_carry", "skip")


@pytest.mark.parametrize("reading", reference.READINGS)
def test_the_comparison_catches_each_reading(sampled, reread, reading):
    """Each reading, changed in the reference alone (the second
    convolution depthwise, no q-k mean, no value shift, no temperature,
    no depth carry, the skip's rows sent through expert 0), moves the
    logits: the attention's four beyond the benchmark's bound (0.03 to
    0.22 where the bound is 0.015), so a program that read one otherwise
    would fail the cell. The router's two do not reach it: the carry
    moves a near-uniform 5-way choice for few tokens (0.004), and a
    skipped token gets an expert's output at a weight of a fifth in a
    stream the attention branch dominates (7e-5, 3 rows of 288 skip
    here). The cell's bound does not tell those (PERF.md section 7);
    this float32 test does, by a factor of 7 and more."""
    gap = correct.rel_rms(sampled[0]["logits"], reread[reading])
    if reading in TOLD_BY_THE_CELL:
        assert gap > correct.LOGITS_REL_RMS_TOL, (reading, gap)
    else:
        assert reading in TOLD_HERE_ALONE
        assert 5 * F32_REL_RMS < gap < correct.LOGITS_REL_RMS_TOL, (
            reading, gap)


def test_the_file_keys_are_asserted_against_the_tree():
    params = _params()
    keys = {
        "cca_time0": 2, "cca_time1": 2, "router_hidden_size": 8,
        "tie_word_embeddings": True, "attention_bias": False,
        "hidden_act": "silu", "sliding_window": None,
        "layer_types": ["hybrid"] * 40, "num_experts": 4,
    }
    reference.check_sizes(params, {**HF, **keys})
    for key, wrong in (
        ("cca_time1", 3), ("router_hidden_size", 16), ("num_experts", 5),
        ("tie_word_embeddings", False), ("num_key_value_heads", 4),
    ):
        with pytest.raises(AssertionError):
            reference.check_sizes(params, {**HF, **keys, key: wrong})


def test_prefill_then_cached_decode_matches_the_full_forward():
    """One prefill of 6 tokens (both convolutions over a sequence), then
    18 single-token steps through the three tails and the key/value
    cache, against the reference's full forward: the benchmark's serving
    comparison, and ``generate``'s two phases."""
    model, params = _model(dml=24), _params()
    ids = np.asarray(_ids((1, 24), seed=2))
    got = correct.cached_logits(model, params, ids, 6)
    want = correct.reference_logits(reference, {"params": params}, HF, ids)[0]
    assert correct.rel_rms(got, want) <= F32_REL_RMS


@pytest.fixture(scope="module")
def served():
    """One paged batcher over four requests on two slots, so that rows
    are admitted over rows that have served: shared by the tests below."""
    model, params = _model(dml=32), _params()
    prompts = [np.asarray(_ids((n,), seed=n)).tolist() for n in (3, 7, 4, 5)]
    batcher = ContinuousBatcher(
        model, params, batch_size=2, page_size=PAGE, chunk_size=4)
    rids = [batcher.submit(p, max_new_tokens=12) for p in prompts]
    outputs = batcher.drain()
    yield model, params, prompts, batcher, [outputs[r] for r in rids]
    batcher.close()


def test_generate_and_the_batcher_serve_the_model(served):
    """``generate`` (a left-padded prefill: the pads leave ``c[-1]``,
    ``c1[-1]`` and the late value zero) equals the greedy continuation of
    the reference's full forward, argmax of its logits a position, and
    the paged batcher (a token a step through the tails and the page
    pool) serves the same streams, with ``loop/serve.py``'s admission and
    ``nn/decode_flags.py`` as they were."""
    model, params, prompts, _, streams = served
    n_new = 12
    got = correct.generate_streams(
        model, params, prompts, n_new, max(len(p) for p in prompts)).tolist()
    assert got[:2] == tiny.greedy_oracle(
        lambda p, t: reference.logits(p, HF, t), params, prompts[:2], n_new,
        32)
    assert streams == got


def test_a_recycled_slot_serves_as_a_fresh_one(served):
    """The third and fourth requests were admitted into slots the first
    two had just left: their streams are ``generate``'s on a fresh cache
    (above), so the three tails were cleared (they are not zero once a
    row has served), the state is counted, only the key/value caches are
    paged, and the skip's count rides the fused chunk's one readback."""
    _, _, prompts, batcher, _ = served
    per_row = recurrent_leaves(batcher._cache)
    slots, layers = 2, 4
    assert sorted(p[-1] for p in per_row) == sorted(
        ["conv_tail", "conv1_tail", "value_tail"] * layers)
    assert all(float(jnp.abs(v).max()) > 0 for v in per_row.values())
    stats = batcher.stats
    # 4 + 2 heads of 16 twice, a late value head of 16, float32 here
    assert stats.recurrent_state_bytes == layers * slots * (96 + 96 + 16) * 4
    assert stats.rows_reset == len(prompts)
    # four expert layers x top-1 x 2 rows a step, idle rows included; all
    # 4 experts held, so what is not held was skipped
    assert stats.moe_rows_routed == layers * slots * stats.device_steps
    assert stats.moe_rows_skipped > 0
    assert stats.moe_rows_held == stats.moe_rows_routed - stats.moe_rows_skipped
    assert stats.readbacks == stats.chunks
    assert batcher._cache_mgr.allocator.prefix_cache_enabled is False


def test_a_carrying_router_refuses_pipeline_stages():
    """The router's state goes from layer to layer beside the stream, and
    a stage hands on the stream alone."""
    z = jnp.zeros((1, 4), jnp.int32)
    staged = ZayaBackbone(
        config=CFG, sdpa=eager_sdpa,
        stage=PipelineStageInfo(stage_index=0, num_stages=2))
    with pytest.raises(NotImplementedError, match="router_carry"):
        jax.eval_shape(lambda: staged.init(jax.random.PRNGKey(0), z, z))
