"""Solar-Open2-250B's family on the shared decoder: Kimi delta attention
mixers beside gated grouped-query attention without rotation, sparse
experts under a sigmoid ``noaux_tc`` router and a shared expert in every
layer, a chip's share of the routed experts. The program against the
plain reference (``benchmarks/references/solar_open2.py``) at the tiny
preset on the CPU rig with seeded weights: logits and the loss, prefill
then cached decode, ``generate`` and the paged ``ContinuousBatcher``
(recurrent leaves and a held range in one fused chunk, rows admitted over
rows that have served); what the comparison catches (each of the
mechanism's readings changed in the reference alone, a state carried in
bf16); the eight shares against the uncut layer; the presets' counts."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import build, correct
from benchmarks.references import qwen3_moe as plain
from benchmarks.references import solar_open2 as reference
from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.models.qwen3.moe import KdaParameters
from d9d_tpu.models.solar import (
    GQA_LAYERS,
    SolarCausalLM,
    solar_open2_250b,
    solar_open2_250b_share8,
    solar_tiny,
)
from d9d_tpu.nn.decode_flags import recurrent_leaves
from d9d_tpu.ops.attention.eager import eager_sdpa
from tests.models import tiny
from tests.models.test_jamba import _decode_with_state_in
from tests.models.tiny import F32_REL_RMS, VOCAB, count
from tests.models.tiny import ids as _ids

CFG = solar_tiny(VOCAB)
# what the benchmark hands the reference at the tiny size: none of the
# family's keys, so the reference reads the tree and takes the published
# switches (beta to 2, the GQA gate on, routed scaling 1)
HF = build.hf_view(CFG)
BF16 = jnp.bfloat16
PAGE = 4


def _model(cfg=CFG, dtype=jnp.float32, dml=0, param_dtype=None):
    return SolarCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=dtype,
        param_dtype=param_dtype or dtype, decode_max_length=dml,
    )


def _off_their_seeds(params, rng):
    """The selection bias, the KDA norms' weights and the output gates'
    biases off their initial zeros and ones, so that a forgotten one
    shows."""
    for layer in params["model"].values():
        if "mlp" in layer:
            bias = layer["mlp"]["router"]["e_score_correction_bias"]
            layer["mlp"]["router"]["e_score_correction_bias"] = jnp.asarray(
                rng.uniform(-0.2, 0.2, bias.shape), bias.dtype)
        if "kda" in layer:
            m = layer["kda"]
            m["o_norm"]["weight"] = jnp.asarray(
                rng.uniform(0.5, 1.5, m["o_norm"]["weight"].shape),
                m["o_norm"]["weight"].dtype)
            m["g_b_proj"]["bias"] = jnp.asarray(
                rng.normal(size=m["g_b_proj"]["bias"].shape),
                m["g_b_proj"]["bias"].dtype)


def _params(cfg=CFG, dtype=jnp.float32, seed=0):
    return tiny.seeded_params(
        _model(cfg, param_dtype=dtype), seed, _off_their_seeds)


SAMPLE = np.asarray(_ids((2, 25)))


@pytest.fixture(scope="module")
def sampled():
    """The (2, 25) sample through the Trainer's task and through the
    reference: logits and loss, one compiled program each."""
    model, params = _model(), _params()
    return (
        tiny.loss_and_grads(model, params, SAMPLE, grads=False),
        tiny.reference_loss_and_grads(
            reference, params, HF, SAMPLE, grads=False),
    )


def test_presets_hold_the_published_sizes():
    full = solar_open2_250b()
    assert (full.num_layers, full.hidden_size) == (48, 4096)
    assert [i for i, k in enumerate(full.layer_kinds) if k == "attention"] == (
        list(GQA_LAYERS))
    assert set(full.layer_kinds) == {"kda", "attention"}
    assert (full.num_heads, full.num_kv_heads, full.head_dim) == (64, 8, 128)
    assert full.rope_fraction == 0.0 and not full.qk_norm
    assert full.use_output_gate and not full.output_gate_per_head
    assert full.kda == KdaParameters(
        num_heads=64, head_dim=128, conv_size=4, gate_rank=128,
        allow_neg_eigval=True, chunk_size=64)
    assert (full.num_experts, full.num_routed_experts,
            full.num_experts_per_tok, full.moe_intermediate_size) == (
        320, 320, 8, 1280)
    assert full.router_score_function == "sigmoid" and full.router_expert_bias
    assert full.router_n_group == 1 and full.routed_scaling_factor == 1.0
    assert full.shared_expert.intermediate_size == 1280
    assert not full.shared_expert.enable_gate and not full.mlp_only_layers
    assert full.vocab_size == 196_608 and not full.tie_word_embeddings
    assert full.float32_stream and full.norm_eps == 1e-5
    # the tiny twin keeps every mechanism on: one period, a share, the bias
    assert CFG.layer_kinds == ("attention", "kda", "kda", "kda")
    assert CFG.num_experts < CFG.num_routed_experts and CFG.shared_expert
    assert CFG.kda.allow_neg_eigval and CFG.use_output_gate
    # the whole model by abstract shapes: the row's 250B-A15B
    z = jnp.zeros((1, 8), jnp.int32)
    whole = jax.eval_shape(
        lambda: _model(full, BF16).init(jax.random.PRNGKey(0), z, z, z)["params"])
    assert abs(count(whole) / 250.3e9 - 1) < 0.005
    # ISSUE 51's arithmetic for the share, from abstract shapes
    share = solar_open2_250b_share8()
    assert (share.num_experts, share.first_held_expert, share.vocab_size,
            share.num_layers) == (40, 0, 24_576, 4)
    assert share.layer_kinds == ("attention", "kda", "kda", "kda")
    shapes = nn.unbox(jax.eval_shape(
        lambda: _model(share, BF16).init(
            jax.random.PRNGKey(0), z, z, z)["params"]))
    layers = shapes["model"]
    assert count(layers["layers_1"]["kda"]) == 137_740_480
    assert count(layers["layers_0"]["self_attn"]) == 109_051_904
    experts = count(layers["layers_0"]["mlp"]["grouped_experts"])
    assert round(experts / 1e6, 1) == 629.1
    assert round((count(layers["layers_1"]) - experts) / 1e6, 2) == 154.79
    assert round((count(layers["layers_0"]) - experts) / 1e6, 2) == 126.10
    tables = count(layers["embed_tokens"]) + count(shapes["lm_head"])
    assert round(tables / 1e6, 1) == 201.3
    assert round(count(shapes) / 1e9, 3) == 3.308
    # a caller's state: 3 mixers x (64 x 128 x 128 float32 + 3 x 24,576 bf16)
    cache = jax.eval_shape(
        lambda: _model(share, BF16, dml=64).init(
            jax.random.PRNGKey(0), z[:, :1], z[:, :1], z[:, :1])["cache"])
    per_row = recurrent_leaves(cache)
    assert sorted(p[-1] for p in per_row) == (
        ["conv_tail"] * 3 + ["delta_state"] * 3)
    state = sum(
        int(np.prod(v.shape)) * v.dtype.itemsize for v in per_row.values())
    assert state == 3 * (4_194_304 + 147_456)
    assert 256 * state == 3_334_471_680  # the cell's serve.recurrent_state_gb


def test_training_mode_matches_the_reference(sampled):
    """Logits (the chunked form over 25 positions: two sub-blocks) and the
    loss through the Trainer's task. Float32 against float32, the same
    sums in another order: ``tiny.F32_REL_RMS`` (1e-5; the CPU reads
    1e-7)."""
    system, want = sampled
    checks = correct.compare_training(system, want)
    assert checks["logits_rel_rms"] <= F32_REL_RMS, checks
    assert checks["loss_gap"] <= 1e-5, checks


def _decay_a_head(x, p, heads):
    """The decay one number a head (its mean over the channels): what a
    Gated DeltaNet head has."""
    g = _DECAY(x, p, heads)
    return jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)


def _silu_after_the_norm(o, x, p, eps):
    """Gated DeltaNet's output gate: a SiLU gate times the norm."""
    b, t, heads, d = o.shape
    normed = plain.rms_norm(o, p["o_norm"]["weight"], eps)
    return normed.reshape(b, t, heads * d) * jax.nn.silu(
        reference.gate_pair(x, p, "g"))


def _softmax_scores(x, router):
    scores = jax.nn.softmax(
        x @ router["gate"]["kernel"].astype(jnp.float32), axis=-1)
    return scores, scores + router["e_score_correction_bias"]


_DECAY = reference.decay
READINGS = {
    "beta_without_its_factor_2": ({"kda_allow_neg_eigval": False}, None),
    "the_gqa_gate_off": ({"use_gqa_gate": False}, None),
    "the_decay_a_head_not_a_channel": ({}, ("decay", _decay_a_head)),
    "a_silu_gate_after_the_norm": ({}, ("output_gate", _silu_after_the_norm)),
    "softmax_scores": ({}, ("routing_scores", _softmax_scores)),
}


@pytest.mark.parametrize("reading", READINGS)
def test_the_comparison_catches_each_reading(sampled, monkeypatch, reading):
    """Each of the mechanism's readings, changed in the reference alone
    (a published switch through its key, an assumed one through the one
    function of the reference that states it), moves the logits beyond the
    benchmark's bound (0.22 to 0.80 where the bound is 0.015): a program
    that read it otherwise would fail the cell. The score function alone
    does not reach the bound. A full-rank gate pair cannot share the tree
    and is left out."""
    keys, swapped = READINGS[reading]
    if swapped:
        monkeypatch.setattr(reference, *swapped)
    other = jax.jit(
        lambda p, t: reference.logits(p, {**HF, **keys}, t)
    )(_params(), jnp.asarray(SAMPLE[:, :-1]))
    gap = correct.rel_rms(sampled[0]["logits"], np.asarray(other))
    if reading == "softmax_scores":
        # under ``norm_topk_prob`` the two score functions choose alike
        # but for the bias and weigh the chosen nearly alike at seeded
        # logits: 0.008 here, 0.002 with all 16 experts held (my CPU runs,
        # PR 51). The cell's bound does not tell them apart (PERF.md
        # section 7); this float32 test does, by a factor of 800
        assert 100 * F32_REL_RMS < gap < correct.LOGITS_REL_RMS_TOL, gap
        return
    assert gap > correct.LOGITS_REL_RMS_TOL, (reading, gap)


def test_the_file_keys_are_asserted_against_the_tree():
    params = _params()
    assert reference.layer_kinds(params["model"], 4) == [
        "attention", "kda", "kda", "kda"]
    keys = {
        "gqa_layers": list(GQA_LAYERS), "use_rope": False,
        "tie_word_embeddings": False, "kda_use_full_proj": False,
        "first_k_dense_replace": 0, "n_shared_experts": 1,
        "linear_attn_config": {
            "short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
            "num_kv_heads": None},
        "n_routed_experts": 4,
        "share": {"published": {"n_routed_experts": 16}},
    }
    reference.check_sizes(params, {**HF, **keys})
    for key, wrong in (
        ("gqa_layers", [1, 5]), ("kda_use_full_proj", True),
        ("linear_attn_config", dict(keys["linear_attn_config"], num_heads=8)),
        ("linear_attn_config", dict(
            keys["linear_attn_config"], short_conv_kernel_size=2)),
        ("share", {"published": {"n_routed_experts": 8}}),
    ):
        with pytest.raises(AssertionError):
            reference.check_sizes(params, {**HF, **keys, key: wrong})


def test_prefill_then_cached_decode_matches_the_full_forward():
    """One prefill of 6 tokens (the chunked form), then 18 single-token
    steps through the state, the conv tails and the attention layer's
    cache, against the reference's full forward (the benchmark's serving
    comparison)."""
    model, params = _model(dml=24), _params()
    ids = np.asarray(_ids((1, 24), seed=2))
    got = correct.cached_logits(model, params, ids, 6)
    want = correct.reference_logits(reference, {"params": params}, HF, ids)[0]
    assert correct.rel_rms(got, want) <= F32_REL_RMS


def test_a_state_carried_in_bf16_fails_the_comparison():
    """The cheaper arithmetic has to show: bf16 weights and everything
    else float32, the float32 state through 200 steps of the cache stays
    within this file's float32 bound (7e-7 here against 1e-5) and a state
    rounded to bf16 a step reads 0.0046 (0.0049 over 400 steps), 460
    times the bound. It does NOT reach the benchmark's bf16 bound
    (0.015): a third of it, where Granite's Mamba-2 state read 0.0005 to
    0.003 (PR 48); a write of strength up to 2 feeds a rounded read-out
    back into the state, and the l2-normalised keys keep it from growing.
    On the chip the cell's bound does not tell a bf16 state from a
    float32 one (PERF.md section 7); this test does."""
    params = _params(dtype=BF16)
    ids = np.asarray(_ids((1, 200), seed=3))
    model = _model(CFG, jnp.float32, dml=200, param_dtype=BF16)
    want = correct.reference_logits(reference, {"params": params}, HF, ids)[0]
    kept, rounded = _decode_with_state_in(
        model, params, ids, leaf="delta_state")
    assert correct.rel_rms(kept, want) <= F32_REL_RMS
    assert 100 * F32_REL_RMS < correct.rel_rms(rounded, want)
    assert correct.rel_rms(rounded, want) < correct.LOGITS_REL_RMS_TOL


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
    """``generate`` (a prefill through the chunked form, left-padded)
    equals the greedy continuation of the reference's full forward, and
    the paged batcher (a token a step through ``kda_step``, a recycled
    slot's state zeroed on admission) serves the same streams, with
    ``loop/serve.py`` and ``nn/decode_flags.py`` as they were."""
    model, params, prompts, _, streams = served
    n_new = 12
    got = correct.generate_streams(
        model, params, prompts, n_new, max(len(p) for p in prompts)).tolist()
    assert got[:2] == tiny.greedy_oracle(
        lambda p, t: reference.logits(p, HF, t), params, prompts[:2], n_new,
        32)
    assert streams == got


def test_recurrent_leaves_and_a_held_range_share_one_chunk(served):
    """The fused chunk's one readback brings the tokens and the held
    range's counts; the per-row state (a matrix a head, four dimensions)
    is counted and zeroed on admission; only the GQA layer is paged."""
    _, _, prompts, batcher, _ = served
    per_row = recurrent_leaves(batcher._cache)
    slots, mixers = 2, 3
    assert sorted(p[-1] for p in per_row) == (
        ["conv_tail"] * mixers + ["delta_state"] * mixers)
    state = next(v for p, v in per_row.items() if p[-1] == "delta_state")
    assert state.shape == (slots, 4, 16, 16) and state.dtype == jnp.float32
    stats = batcher.stats
    assert stats.recurrent_state_bytes == mixers * slots * (
        4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert stats.rows_reset == len(prompts)
    # four expert layers x 4 experts a token x 2 rows a step, idle rows
    # included; 4 of 16 held
    assert stats.moe_rows_routed == 4 * 4 * slots * stats.device_steps
    assert 0 < stats.moe_rows_held < stats.moe_rows_routed
    assert stats.readbacks == stats.chunks
    assert batcher._cache_mgr.allocator.prefix_cache_enabled is False


def test_the_eight_shares_add_up_to_the_uncut_reference():
    """One expert layer's output over all eight shares of two experts,
    the shared expert (which every chip computes alike) counted once,
    against the reference holding all 16."""
    from d9d_tpu.nn.moe import MoELayer

    whole = solar_tiny(VOCAB, num_experts=16)
    params = _params(whole)["model"]["layers_1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 12, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = reference.sparse_block(x, params, build.hf_view(whole))
        shared = reference.shared_expert(x, params)
    held = 2

    def share(first):
        layer = MoELayer(
            hidden_dim=CFG.hidden_size,
            intermediate_dim_grouped=CFG.moe_intermediate_size,
            num_grouped_experts=held, top_k=CFG.num_experts_per_tok,
            router_enable_expert_bias=True, router_score_function="sigmoid",
            shared_expert=CFG.shared_expert, num_routed_experts=16,
            first_held_expert=first, dtype=jnp.float32,
            param_dtype=jnp.float32,
        )
        cut = dict(params, grouped_experts={
            k: v[first:first + held]
            for k, v in params["grouped_experts"].items()})
        return layer.apply({"params": cut}, x)

    # one program: un-jitted, every share's ``lax.switch`` is a compile
    shares = jax.jit(lambda: [share(first) for first in range(0, 16, held)])()
    assert len(shares) == 8
    # every share holds the shared expert's output: seven of eight taken
    # off, so the float32 noise is that of eight sums of order 1 (2e-6 on
    # one element of 768 here), not of the difference
    np.testing.assert_allclose(
        sum(shares) - 7 * shared, want, rtol=1e-4, atol=1e-5)
    # and the reference, told a share, leaves out what the others add
    cut = dict(params, grouped_experts={
        k: v[6:8] for k, v in params["grouped_experts"].items()})
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            reference.sparse_block(x, cut, dict(HF, first_held_expert=6)),
            shares[3], rtol=1e-4, atol=1e-6)
