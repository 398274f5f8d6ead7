"""MiMo-V2-Flash (``mimo_v2_flash``) on the shared decoder: window and full
attention layers in one stack, each kind with its own key/value heads,
rotary base and cache, value heads narrower than the keys', a chip's
share of the routed experts. The program against the plain reference
(``benchmarks/references/mimo_v2_flash.py``) at the tiny preset on the CPU
rig with seeded weights: logits and the loss the Trainer trains with,
gradients through the flash kernel, prefill then cached decode,
``generate`` and ``ContinuousBatcher`` past the window and past a ring of
pages; the sixteen shares against the uncut layer; what the per-layer
pattern leaves of the presets that predate it."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

from benchmarks.harness import build, correct
from benchmarks.references import mimo_v2_flash as reference
from d9d_tpu.loop.generate import generate
from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.models.deepseek import deepseek_v2_tiny
from d9d_tpu.models.jamba import jamba_tiny
from d9d_tpu.models.mimo import (
    TINY_WINDOW,
    MimoCausalLM,
    mimo_v2_flash,
    mimo_v2_flash_share16,
    mimo_v2_flash_tiny,
)
from d9d_tpu.models.qwen3.moe import AttentionKind, Qwen3MoeConfig
from d9d_tpu.nn.decode_flags import recurrent_leaves, window_leaves
from d9d_tpu.ops.attention.pallas_decode import window_pages
from d9d_tpu.ops.attention.eager import eager_sdpa
from tests.models import tiny
from tests.models.tiny import F32_REL_RMS, VOCAB, count
from tests.models.tiny import ids as _ids

CFG = mimo_v2_flash_tiny(VOCAB)
# what the benchmark hands the reference at the tiny size: none of the
# family's keys, so the reference reads the tree and its tiny constants
HF = build.hf_view(CFG)
PAGE = 4
RING = window_pages(TINY_WINDOW, PAGE) * PAGE  # 12 positions a row


def _model(cfg=CFG, dtype=jnp.float32, dml=0, sdpa=eager_sdpa):
    return MimoCausalLM(
        config=cfg, sdpa=sdpa, dtype=dtype, param_dtype=dtype,
        decode_max_length=dml,
    )


def _bias_and_sinks(params, rng):
    layers = sum(name.startswith("layers_") for name in params["model"])
    for i in range(layers):
        layer = params["model"][f"layers_{i}"]
        if "router" in layer["mlp"]:
            router = layer["mlp"]["router"]
            router["e_score_correction_bias"] = jnp.asarray(
                rng.uniform(-0.3, 0.3, router["gate"]["kernel"].shape[1]),
                jnp.float32,
            )
        if "sinks" in layer["self_attn"]:
            layer["self_attn"]["sinks"] = jnp.asarray(
                rng.uniform(-1.0, 1.0, layer["self_attn"]["sinks"].shape),
                jnp.float32,
            )


def _params(cfg=CFG, seed=0):
    """Seeded weights with a selection bias in every router and a sink
    logit on every window layer's heads that are not zero."""
    return tiny.seeded_params(_model(cfg), seed, _bias_and_sinks)


@pytest.fixture(scope="module")
def params():
    return _params()


def test_presets_hold_the_published_sizes():
    full = mimo_v2_flash()
    assert (full.num_layers, full.hidden_size, full.num_heads) == (48, 4096, 64)
    assert (full.head_dim, full.v_head_dim, full.num_kv_heads) == (192, 128, 4)
    assert int(full.head_dim * full.rope_fraction) == 64
    assert (full.rope_theta, full.attention_value_scale) == (5_000_000.0, 0.707)
    window = full.attention_kind("window")
    # what the kinds state, and the plain fields where they state nothing
    # (both kinds: 64 query heads, 0.334 of a head rotated, no scaling law)
    shared = dict(num_heads=64, rope_fraction=0.334,
                  rope_scaling=full.rope_scaling)
    assert window == AttentionKind(8, 10_000.0, 128, True, **shared)
    assert full.attention_kind("attention") == AttentionKind(
        4, 5_000_000.0, None, False, **shared)
    assert full.layer_kinds.count("attention") == 9
    assert [i for i, k in enumerate(full.layer_kinds) if k == "attention"] \
        == [0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert (full.num_experts, full.num_routed_experts,
            full.num_experts_per_tok, full.moe_intermediate_size) \
        == (256, 256, 8, 2048)
    assert full.mlp_only_layers == (0,) and full.intermediate_size == 16_384
    assert full.shared_expert is None and full.routed_scaling_factor == 1.0
    assert full.router_score_function == "sigmoid" and full.router_expert_bias
    assert not full.qk_norm and full.norm_eps == 1e-5
    assert full.vocab_size == 152_576 and not full.tie_word_embeddings
    # the tiny twin: every mechanism on, the family's constants as published
    assert CFG.layer_kinds == ("attention", "window", "window", "attention")
    assert (CFG.attention_value_scale, CFG.rope_fraction, CFG.norm_eps) \
        == (0.707, 0.334, 1e-5)
    assert CFG.attention_kind("window").rope_theta == 10_000.0
    assert CFG.attention_kind("window").window_size == TINY_WINDOW \
        == reference.TINY_WINDOW
    assert CFG.num_routed_experts == 16 * CFG.num_experts
    # ISSUE 41's arithmetic, from abstract shapes at the published widths
    share = mimo_v2_flash_share16()
    assert (share.num_experts, share.first_held_expert, share.vocab_size,
            share.num_layers) == (16, 0, 19_072, 7)
    assert share.layer_kinds == (
        "attention", "window", "window", "window", "window", "attention",
        "window")
    z = jnp.zeros((1, 8), jnp.int32)
    model = _model(share, jnp.bfloat16)
    shapes = nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), z, z, z)["params"]))
    layers = shapes["model"]
    assert round(count(layers["layers_0"]["self_attn"]) / 1e6, 1) == 89.1
    assert round(count(layers["layers_1"]["self_attn"]) / 1e6, 1) == 94.4
    assert round(count(layers["layers_0"]["mlp"]) / 1e6, 1) == 201.3
    experts = layers["layers_1"]["mlp"]["grouped_experts"]
    assert round(count(experts) / 16 / 1e6, 2) == 25.17
    assert round(count(layers["layers_1"]["mlp"]["router"]) / 1e6, 2) == 1.05
    head = count(shapes["lm_head"]) + count(layers["embed_tokens"])
    assert round(head / 1e6, 1) == 156.2
    assert round(2 * count(shapes) / 1e9, 2) == 6.86  # GB of bf16 weights


def test_training_mode_matches_the_reference_and_the_loss(params):
    model = _model()
    sample = np.asarray(_ids((2, 21)))  # contexts past the window of 6
    checks = correct.compare_training(
        correct.training_system(model, {"params": params}, sample),
        correct.training_reference(reference, {"params": params}, HF, sample),
    )
    assert checks["logits_rel_rms"] <= F32_REL_RMS, checks
    assert checks["loss_gap"] <= 1e-5, checks


@pytest.mark.parametrize("wrong", [
    {"sliding_window": TINY_WINDOW + 1}, {"attention_value_scale": 1.0},
    {"swa_rope_theta": 5_000_000}, {"partial_rotary_factor": 0.5},
])
def test_the_comparison_catches_each_family_constant(params, wrong):
    """A window one position wider, an unscaled value, the full kind's
    base on a window layer, twelve rotated numbers for eight: each reads
    far outside what float32 rounding leaves."""
    sample = np.asarray(_ids((1, 17), seed=3))
    got = tiny.loss_and_grads(_model(), params, sample, grads=False)
    off = tiny.reference_loss_and_grads(
        reference, params, {**HF, **wrong}, sample, grads=False)
    assert correct.rel_rms(got["logits"], off["logits"]) > 1e-3


def test_the_file_keys_are_asserted_against_the_tree(params):
    """At the real size the reference holds the tree to every key of the
    file; a tree of another pattern is refused."""
    import json

    from benchmarks.harness import manifest

    body = json.loads((
        manifest.BENCH_DIR / "configs" / "mimo-v2-flash-share16-decode.json"
    ).read_text())
    z = jnp.zeros((1, 8), jnp.int32)
    model = _model(mimo_v2_flash_share16(), jnp.bfloat16)
    shapes = nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), z, z, z)["params"]))
    reference.check_sizes(shapes, body)
    swapped = dict(body, hybrid_layer_pattern=[1, 0] + [1] * 46)
    with pytest.raises(AssertionError):
        reference.check_sizes(shapes, swapped)


def test_gradients_through_the_flash_kernel_match_the_eager_backend(params):
    """Forward and backward through the Pallas flash kernel (interpret
    mode here) with a window, sinks and value heads padded to the keys'
    width, against the eager backend: loss and every gradient."""
    from d9d_tpu.ops.attention.pallas_flash import make_pallas_flash_sdpa

    # one layer of each kind is what the kernel sees of the model
    cfg = dataclasses.replace(
        CFG, num_layers=2, layer_kinds=("attention", "window"))
    params = {
        **params, "model": {
            k: v for k, v in params["model"].items()
            if k not in ("layers_2", "layers_3")
        },
    }
    sample = _ids((2, 17), seed=4)
    tokens, labels = sample[:, :-1], sample[:, 1:]
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), tokens.shape)

    def loss(p, sdpa):
        return _model(cfg, sdpa=sdpa).apply(
            {"params": p}, tokens, pos, labels).mean()

    flash = make_pallas_flash_sdpa(block_q=8, block_kv=8)
    run = jax.jit(jax.value_and_grad(loss), static_argnums=1)
    want, want_g = run(params, eager_sdpa)
    got, got_g = run(params, flash)
    assert abs(float(got) - float(want)) <= 1e-5
    for path, g in flatten_dict(got_g).items():
        w = flatten_dict(want_g)[path]
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5, err_msg=str(path))
    sinks = got_g["model"]["layers_1"]["self_attn"]["sinks"]
    assert float(jnp.abs(sinks).max()) > 0


def test_prefill_then_cached_decode_matches_the_full_forward(params):
    model = _model(dml=32)
    ids = np.asarray(_ids((1, 24), seed=2))
    got = correct.cached_logits(model, params, ids, 5)
    want = correct.reference_logits(reference, {"params": params}, HF, ids)[0]
    assert correct.rel_rms(got, want) <= F32_REL_RMS


@pytest.fixture(scope="module")
def served(params):
    """One batcher, requests whose contexts (to 27) pass the window (6)
    and the ring (12): shared by the tests below."""
    model = _model(dml=32)
    prompts = [np.asarray(_ids((n,), seed=n)).tolist() for n in (3, 7, 4, 5)]
    batcher = ContinuousBatcher(
        model, params, batch_size=2, page_size=PAGE, chunk_size=4
    )
    rids = [batcher.submit(p, max_new_tokens=20) for p in prompts]
    outputs = batcher.drain()
    yield model, prompts, batcher, [outputs[r] for r in rids]
    batcher.close()


def test_generate_and_the_batcher_serve_past_the_window_and_the_ring(
        params, served):
    """``generate`` (a whole context a layer) equals the greedy
    continuation of the reference's full forward, and the paged batcher
    (a ring of 12 positions a window layer a row) serves the same
    streams."""
    model, prompts, _, streams = served
    n_new = 20
    assert max(len(p) for p in prompts) + n_new > RING > TINY_WINDOW
    got = correct.generate_streams(
        model, params, prompts, n_new, max(len(p) for p in prompts)
    ).tolist()
    assert [got[0]] == tiny.greedy_oracle(
        lambda p, t: reference.logits(p, HF, t), params, prompts[:1], n_new,
        32)
    assert streams == got


def test_the_batcher_holds_two_kinds_of_cache_and_counts_both(served):
    _, prompts, batcher, streams = served
    flat = flatten_dict(batcher._cache)
    rings = window_leaves(batcher._cache)
    assert len(rings) == 4 and not recurrent_leaves(batcher._cache)
    slots, (hkv_w, d, dv) = 2, (4, CFG.head_dim, CFG.v_head_dim)
    for path, leaf in rings.items():
        width = d if path[-1] == "ring_key" else dv
        assert leaf.shape == (slots * RING // PAGE, hkv_w, PAGE, width), path
        # a ring has no table of its own and no sibling pool
        assert path[:-1] + ("page_table",) not in flat
    # the full layers keep their pools and the allocator's table
    pools = [p for p in flat if p[-1] == "cached_key"]
    assert len(pools) == 2
    assert all(flat[p].shape[1:] == (2, PAGE, d) for p in pools)
    stats = batcher.stats
    assert stats.window_cache_bytes == 2 * slots * RING * hkv_w * (d + dv) * 4
    assert stats.recurrent_state_bytes == 0
    # two window layers, each the context or the window, the smaller
    busy = stats.slot_steps_busy
    assert stats.window_positions_attended <= 2 * TINY_WINDOW * busy
    assert stats.window_positions_attended > 2 * (TINY_WINDOW - 1) * busy * 0.7
    assert stats.positions_attended > stats.window_positions_attended // 2
    # the held range's own counts came out with the tokens: 3 expert
    # layers x 4 experts a token x 2 rows a step, idle rows included
    assert stats.moe_rows_routed == 3 * 4 * slots * stats.device_steps
    assert 0 < stats.moe_rows_held < stats.moe_rows_routed / 4
    assert stats.readbacks == stats.chunks
    assert all(len(s) == 20 for s in streams)


def test_a_model_with_window_layers_serves_without_the_prefix_cache(
        params, served):
    model, _, batcher, _ = served
    assert batcher._cache_mgr.allocator.prefix_cache_enabled is False
    with pytest.raises(ValueError, match="ring"):
        ContinuousBatcher(
            model, params, batch_size=2, page_size=PAGE, prefix_cache=True)
    with pytest.raises(ValueError, match="kv_quant"):
        ContinuousBatcher(
            model, params, batch_size=2, page_size=PAGE, kv_quant="int8")
    # unpaged, a window layer keeps a context like any other
    plain = ContinuousBatcher(model, params, batch_size=2)
    assert not window_leaves(plain._cache)
    assert plain.stats.window_cache_bytes == 0
    plain.close()


def test_the_paged_kernel_reads_a_ring_as_the_eager_path_does(
        params, served, monkeypatch):
    """The Pallas decode kernels (interpret mode here): the paged one on
    the rings and the pools, value heads of their own width, the window's
    pages from the floor's page on."""
    model, prompts, _, streams = served
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "pallas")
    batcher = ContinuousBatcher(
        model, params, batch_size=2, page_size=PAGE, chunk_size=4
    )
    rids = [batcher.submit(p, max_new_tokens=20) for p in prompts[:2]]
    outputs = batcher.drain()
    assert [outputs[r] for r in rids] == streams[:2]
    batcher.close()


def test_keys_wider_than_a_lane_tile_are_cached_in_whole_tiles():
    """At the published head widths (192 / 128) a cached key row is 256
    numbers, in the contiguous cache, the pools and the rings alike, and
    the padded path attends as the unpadded prefill does."""
    cfg = dataclasses.replace(
        CFG, head_dim=192, v_head_dim=128, num_heads=2, num_kv_heads=1,
        attention_kinds=(("window", AttentionKind(2, 10_000.0, 6, True)),),
        num_layers=2, layer_kinds=("attention", "window"),
    )
    model, params = _model(cfg, dml=32), _params(cfg)
    ids = np.asarray(_ids((1, 20), seed=6))
    got = correct.cached_logits(model, params, ids, 5)
    want = correct.reference_logits(
        reference, {"params": params}, build.hf_view(cfg), ids)[0]
    assert correct.rel_rms(got, want) <= F32_REL_RMS
    batcher = ContinuousBatcher(model, params, batch_size=2, page_size=PAGE)
    shapes = {
        p[-1]: leaf.shape[-1] for p, leaf in flatten_dict(batcher._cache).items()
    }
    assert shapes["cached_key"] == shapes["ring_key"] == 256
    assert shapes["cached_value"] == shapes["ring_value"] == 128
    prompt = ids[0, :5].tolist()
    rid = batcher.submit(prompt, max_new_tokens=15)
    served = batcher.drain()[rid]
    assert served == np.asarray(generate(
        model, params, jnp.asarray([prompt], jnp.int32), max_new_tokens=15
    ))[0].tolist()
    batcher.close()


def test_the_sixteen_shares_add_up_to_the_uncut_reference():
    """One expert layer's routed output over all sixteen shares of four
    experts against the reference holding all 64 (the guide's section 4:
    no shared expert here, so nothing is counted once)."""
    from d9d_tpu.nn.moe import MoELayer

    whole = dataclasses.replace(CFG, num_experts=64, num_routed_experts=0)
    params = _params(whole)["model"]["layers_1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 12, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = reference.sparse_block(x, params, build.hf_view(whole))

    def share(first):
        layer = MoELayer(
            hidden_dim=CFG.hidden_size,
            intermediate_dim_grouped=CFG.moe_intermediate_size,
            num_grouped_experts=CFG.num_experts, top_k=CFG.num_experts_per_tok,
            router_enable_expert_bias=True, router_score_function="sigmoid",
            num_routed_experts=64, first_held_expert=first,
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
    shares = jax.jit(lambda: [
        share(first) for first in range(0, 64, CFG.num_experts)])()
    assert len(shares) == 16
    np.testing.assert_allclose(sum(shares), want, rtol=1e-4, atol=1e-6)
    # and the reference, told a share, leaves out what the others add
    hf = dict(HF, first_held_expert=8)
    cut = {
        "router": params["router"],
        "grouped_experts": {
            k: v[8:12] for k, v in params["grouped_experts"].items()},
    }
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            reference.sparse_block(x, cut, hf), shares[2], rtol=1e-4,
            atol=1e-6)


def _tree(cfg: Qwen3MoeConfig):
    z = jnp.zeros((1, 8), jnp.int32)
    model = MimoCausalLM(config=cfg, sdpa=eager_sdpa, dtype=jnp.float32)
    return jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), z, z, z)["params"])


@pytest.mark.parametrize("old,kinds,cleared", [
    (Qwen3MoeConfig.tiny(VOCAB), ("attention",) * 2, {}),
    (Qwen3MoeConfig.hybrid_tiny(VOCAB), ("gdn",) * 3 + ("attention",),
     {"linear_attention_layers": ()}),
    (jamba_tiny(VOCAB), ("mamba", "attention"), {"mamba_layers": ()}),
    (deepseek_v2_tiny(VOCAB), ("mla",) * 2, {}),
], ids=["qwen3", "gdn-hybrid", "jamba", "deepseek"])
def test_the_older_fields_read_as_instances_of_the_pattern(old, kinds, cleared):
    """``mamba_layers``, ``linear_attention_layers`` and ``mla`` give the
    pattern ``layer_kind`` reads, and a config that states the same
    pattern outright builds the same parameter tree."""
    assert tuple(old.layer_kind(i) for i in range(old.num_layers)) == kinds
    stated = dataclasses.replace(old, layer_kinds=kinds, **cleared)
    assert jax.tree.structure(_tree(stated)) == jax.tree.structure(_tree(old))
    assert jax.tree.leaves(_tree(stated)) == jax.tree.leaves(_tree(old))
    # every kind's rotary base is the plain field's
    assert {old.attention_kind(k).rope_theta for k in kinds} == {old.rope_theta}


def test_a_pattern_is_held_to_the_kinds_that_exist():
    with pytest.raises(ValueError, match="names no kind"):
        dataclasses.replace(CFG, layer_kinds=("attention", "swa") * 2)
    with pytest.raises(ValueError, match="layer_kinds for"):
        dataclasses.replace(CFG, layer_kinds=("attention",))
    with pytest.raises(ValueError, match="redefine"):
        dataclasses.replace(
            CFG, attention_kinds=(("mla", AttentionKind()),))


def test_gradient_steps_through_trainer_lower_the_loss():
    trainer = tiny.trainer(
        lambda stage: MimoCausalLM(
            config=CFG, sdpa=eager_sdpa, stage=stage, dtype=jnp.float32),
        total_steps=4, one_batch=True,
    )
    history = trainer.train()
    losses = [row["loss"] for row in history]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    # the held range counts its rows on the training path as before
    assert 0 < history[-1]["moe/rows_held"] < history[-1]["moe/rows_routed"]
