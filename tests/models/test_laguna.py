"""Laguna-XS.2 (``laguna``) on the shared decoder: window and full
attention layers whose query heads, rotation and window differ by kind,
a sigmoid gate a head, a chip's share of the routed experts beside a
shared one. The program against the plain reference
(``benchmarks/references/laguna.py``) at the tiny preset on the CPU rig
with seeded weights: logits, the loss and the gradient of every
parameter, through the eager backend and through the Pallas flash kernel
(interpret mode) on a sequence four tiny windows long; a planted fault
for each kind-specific piece; the eight shares against the uncut layer;
the paged batcher past the window and a ring of pages; what the flash
wrapper counts of its grid; the Trainer's FLOPs by a layer's kind."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

from benchmarks.harness import build, correct
from benchmarks.references import laguna as reference
from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.models.laguna import (
    FULL_ROPE_SCALING,
    TINY_WINDOW,
    LagunaCausalLM,
    laguna_tiny,
    laguna_xs2,
    laguna_xs2_share8,
)
from d9d_tpu.models.qwen3.moe import AttentionKind
from d9d_tpu.nn.decode_flags import window_leaves
from d9d_tpu.ops import RopeScalingNone
from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu.ops.attention.pallas_decode import window_pages
from d9d_tpu.ops.attention.pallas_flash import make_pallas_flash_sdpa
from tests.models import tiny
from tests.models.tiny import F32_REL_RMS, VOCAB, count
from tests.models.tiny import ids as _ids

CFG = laguna_tiny(VOCAB)
# what the benchmark hands the reference at the tiny size: none of the
# family's keys, so the reference reads the tree and its tiny constants
HF = build.hf_view(CFG)
SEQ = 4 * TINY_WINDOW
PAGE = 4
RING = window_pages(TINY_WINDOW, PAGE) * PAGE  # 20 positions a row


def _model(cfg=CFG, dml=0, sdpa=eager_sdpa):
    return LagunaCausalLM(
        config=cfg, sdpa=sdpa, dtype=jnp.float32, param_dtype=jnp.float32,
        decode_max_length=dml,
    )


def _params(cfg=CFG, seed=0):
    return tiny.seeded_params(_model(cfg), seed)


@pytest.fixture(scope="module")
def params():
    return _params()


def _window_kind(**changes) -> tuple:
    return (("window", dataclasses.replace(
        dict(CFG.attention_kinds)["window"], **changes)),)


def test_presets_hold_the_published_sizes():
    whole = laguna_xs2()
    assert (whole.num_layers, whole.hidden_size, whole.head_dim) \
        == (40, 2048, 128)
    assert whole.attention_kind("attention") == AttentionKind(
        num_kv_heads=8, rope_theta=500_000.0, window_size=None,
        use_sinks=False, num_heads=48, rope_fraction=0.5,
        rope_scaling=FULL_ROPE_SCALING,
    )
    assert whole.attention_kind("window") == AttentionKind(
        num_kv_heads=8, rope_theta=10_000.0, window_size=512,
        use_sinks=False, num_heads=64, rope_fraction=1.0,
        rope_scaling=RopeScalingNone(),
    )
    assert [i for i, k in enumerate(whole.layer_kinds) if k == "attention"] \
        == list(range(0, 40, 4))
    assert (whole.num_experts, whole.num_routed_experts,
            whole.num_experts_per_tok, whole.moe_intermediate_size) \
        == (256, 256, 8, 512)
    assert whole.mlp_only_layers == (0,) and whole.intermediate_size == 8192
    assert whole.shared_expert.intermediate_size == 512
    assert not whole.shared_expert.enable_gate
    assert whole.routed_scaling_factor == 2.5 and whole.norm_topk_prob
    assert whole.router_score_function == "softmax"
    assert whole.use_output_gate and whole.output_gate_per_head
    assert not whole.qk_norm and whole.norm_eps == 1e-6
    assert whole.vocab_size == 100_352 and not whole.tie_word_embeddings
    # the tiny twin: every mechanism on, the family's constants as published
    assert CFG.layer_kinds == ("attention", "window", "window", "attention")
    assert CFG.attention_kind("window").window_size == TINY_WINDOW \
        == reference.TINY_WINDOW
    assert (CFG.num_heads, CFG.attention_kind("window").num_heads) == (6, 8)
    assert CFG.num_routed_experts == 8 * CFG.num_experts
    assert CFG.rope_scaling == FULL_ROPE_SCALING
    # ISSUE 44's arithmetic, from abstract shapes at the published widths
    share = laguna_xs2_share8()
    assert (share.num_experts, share.first_held_expert, share.vocab_size,
            share.num_layers) == (32, 0, 12_544, 5)
    assert share.layer_kinds == (
        "attention", "window", "window", "window", "attention")
    z = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(lambda: LagunaCausalLM(
        config=share, sdpa=eager_sdpa, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
    ).init(jax.random.PRNGKey(0), z, z, z)["params"])
    layers = shapes["model"]
    assert count(layers["layers_0"]["self_attn"]) == 29_458_432
    assert count(layers["layers_1"]["self_attn"]) == 37_879_808
    mlp = layers["layers_1"]["mlp"]
    assert count(mlp["grouped_experts"]) == 32 * 3_145_728 == 100_663_296
    assert count(mlp["shared_expert_module"]) == 3_145_728
    assert count(mlp["router"]) == 524_288
    assert count(layers["layers_0"]["mlp"]) == 50_331_648
    assert count(shapes["lm_head"]) == count(layers["embed_tokens"]) \
        == 25_690_112
    assert [round(count(layers[f"layers_{i}"]) / 1e6, 1) for i in range(5)] \
        == [79.8, 142.2, 142.2, 142.2, 133.8]
    assert round(count(shapes) / 1e6, 1) == 691.6
    # the whole model, with one gate logit a head: the source's 33.4 B
    whole_shapes = jax.eval_shape(lambda: LagunaCausalLM(
        config=whole, sdpa=eager_sdpa, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
    ).init(jax.random.PRNGKey(0), z, z, z)["params"])
    assert round(count(whole_shapes) / 1e9, 2) == 33.44


def test_training_mode_matches_the_reference_and_the_loss(params):
    sample = np.asarray(_ids((2, SEQ + 1)))  # four windows deep
    checks = correct.compare_training(
        correct.training_system(_model(), {"params": params}, sample),
        correct.training_reference(reference, {"params": params}, HF, sample),
    )
    assert checks["logits_rel_rms"] <= F32_REL_RMS, checks
    assert checks["loss_gap"] <= 1e-5, checks


def _loss_and_gradients(cfg, params, sample, sdpa):
    tokens, labels = sample[:, :-1], sample[:, 1:]
    pos = jnp.broadcast_to(
        jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)

    def loss(p):
        return _model(cfg, sdpa=sdpa).apply(
            {"params": p}, tokens, pos, labels).mean()

    return jax.jit(jax.value_and_grad(loss))(params)


@pytest.fixture(scope="module")
def reference_gradients(params):
    sample = _ids((2, SEQ + 1), seed=4)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, HF, sample[:, :-1], sample[:, 1:])
    ))(params)
    return sample, float(want), flatten_dict(want_g)


@pytest.mark.parametrize("backend", ["eager", "pallas_flash"])
def test_every_gradient_matches_the_reference(
        params, reference_gradients, backend):
    """The loss and the gradient of every parameter against the plain
    reference's: the backward of the per-head gate, of the two rotations
    and of a windowed attention call, through the eager backend and
    through the Pallas flash kernels (interpret mode here; blocks of 16,
    so a window layer's grid skips blocks forward and backward)."""
    sample, want, want_g = reference_gradients
    sdpa = eager_sdpa if backend == "eager" else make_pallas_flash_sdpa(
        block_q=16, block_kv=16)
    got, got_g = _loss_and_gradients(CFG, params, sample, sdpa)
    assert abs(float(got) - want) <= 1e-5
    got_g = flatten_dict(got_g)
    assert set(got_g) == set(want_g)
    for path, w in want_g.items():
        np.testing.assert_allclose(
            got_g[path], w, rtol=2e-3, atol=2e-5, err_msg=str(path))
    for layer in range(CFG.num_layers):
        gate = got_g[("model", f"layers_{layer}", "self_attn", "gate_proj",
                      "kernel")]
        assert float(jnp.abs(gate).max()) > 0


@pytest.mark.parametrize("fault", [
    {"attention_kinds": _window_kind(window_size=None)},
    {"attention_kinds": _window_kind(
        rope_theta=CFG.rope_theta, rope_fraction=CFG.rope_fraction,
        rope_scaling=CFG.rope_scaling)},
    {"rope_theta": 10_000.0, "rope_fraction": 1.0,
     "rope_scaling": RopeScalingNone()},
    {"use_output_gate": False},
    {"routed_scaling_factor": 1.0},
], ids=["window-off", "full-rotation-on-both", "window-rotation-on-both",
        "gate-off", "routed-scale-off"])
def test_a_planted_fault_in_a_kind_specific_piece_fails(params, fault):
    """The program with one piece taken out, on the same weights against
    the unchanged reference: each reads far outside what float32 rounding
    leaves."""
    sample = np.asarray(_ids((1, SEQ + 1), seed=3))
    wrong = _model(dataclasses.replace(CFG, **fault))
    got = tiny.loss_and_grads(wrong, params, sample, grads=False)
    want = tiny.reference_loss_and_grads(
        reference, params, HF, sample, grads=False)
    assert correct.rel_rms(got["logits"], want["logits"]) > 1e-3


def test_a_gate_a_number_wide_is_another_tree_and_is_refused(params):
    """Qwen3-Next's gate, one logit a number, builds a ``gate_proj`` as
    wide as ``o_proj``'s input: the reference, which reads one logit a
    head, refuses such a tree."""
    wide = dataclasses.replace(CFG, output_gate_per_head=False)
    z = jnp.zeros((2, 8), jnp.int32)
    tree = nn.unbox(jax.eval_shape(  # shapes are enough: nothing compiles
        lambda: _model(wide).init(jax.random.PRNGKey(0), z, z, z)["params"]))
    attn = tree["model"]["layers_1"]["self_attn"]
    heads = CFG.attention_kind("window").num_heads
    assert attn["gate_proj"]["kernel"].shape == (
        CFG.hidden_size, heads * CFG.head_dim)
    assert params["model"]["layers_1"]["self_attn"]["gate_proj"][
        "kernel"].shape == (CFG.hidden_size, heads)
    with pytest.raises((TypeError, ValueError)):
        jax.eval_shape(lambda t: reference.logits(t, HF, _ids((1, 8))), tree)


def test_the_file_keys_are_asserted_against_the_tree():
    """At the real size the reference holds the tree to every key of the
    file; a tree of another pattern is refused."""
    import json

    from benchmarks.harness import manifest

    body = json.loads((
        manifest.BENCH_DIR / "configs" / "laguna-xs.2-share8.json"
    ).read_text())
    z = jnp.zeros((1, 8), jnp.int32)
    model = LagunaCausalLM(
        config=laguna_xs2_share8(), sdpa=eager_sdpa, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
    )
    shapes = nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), z, z, z)["params"]))
    reference.check_sizes(shapes, body)
    build.check_against_file(laguna_xs2_share8(), body)
    swapped = dict(
        body, layer_types=["sliding_attention"] + body["layer_types"][1:])
    with pytest.raises(AssertionError):
        reference.check_sizes(shapes, swapped)


def test_the_eight_shares_add_up_to_the_uncut_reference():
    """One expert layer over all eight shares of four experts, the
    shared expert (which every chip computes alike) counted once, against
    the reference holding all 32 (the guide's section 4)."""
    from d9d_tpu.nn.moe import MoELayer

    whole = dataclasses.replace(CFG, num_experts=32, num_routed_experts=0)
    params = _params(whole)["model"]["layers_1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 12, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = reference.feed_forward(x, params, build.hf_view(whole))
        shared = reference.shared_part(x, params)

    def share(first):
        layer = MoELayer(
            hidden_dim=CFG.hidden_size,
            intermediate_dim_grouped=CFG.moe_intermediate_size,
            num_grouped_experts=CFG.num_experts, top_k=CFG.num_experts_per_tok,
            shared_expert=CFG.shared_expert,
            routed_scaling=CFG.routed_scaling_factor,
            num_routed_experts=32, first_held_expert=first,
            dtype=jnp.float32, param_dtype=jnp.float32,
        )
        cut = {
            **params,
            "grouped_experts": {
                k: v[first:first + CFG.num_experts]
                for k, v in params["grouped_experts"].items()
            },
        }
        return layer.apply({"params": cut}, x)

    # one program: un-jitted, every share's ``lax.switch`` is a compile
    shares = jax.jit(lambda: [
        share(first) for first in range(0, 32, CFG.num_experts)])()
    assert len(shares) == 8
    np.testing.assert_allclose(
        sum(shares) - 7 * shared, want, rtol=1e-4, atol=1e-6)
    # and the reference, told a share, leaves out what the others add
    cut = {
        **params,
        "grouped_experts": {
            k: v[8:12] for k, v in params["grouped_experts"].items()},
    }
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            reference.feed_forward(x, cut, dict(HF, first_held_expert=8)),
            shares[2], rtol=1e-4, atol=1e-6)


def test_the_paged_batcher_decodes_as_the_reference_forward(params):
    """The tiny preset through ``ContinuousBatcher``: a ring of pages for
    the window kind, 8 against 6 query heads on one cache layout of 2
    key/value heads, contexts past the window (16) and the ring (20),
    equal to the greedy continuation of the reference's full forward."""
    n_new, width = 22, 32
    model = _model(dml=width)
    prompts = [np.asarray(_ids((n,), seed=n)).tolist() for n in (3, 7)]
    batcher = ContinuousBatcher(
        model, params, batch_size=2, page_size=PAGE, chunk_size=4
    )
    rids = [batcher.submit(p, max_new_tokens=n_new) for p in prompts]
    outputs = batcher.drain()
    rings = window_leaves(batcher._cache)
    batcher.close()
    assert len(rings) == 4  # key and value rings of two window layers
    for leaf in rings.values():
        assert leaf.shape == (2 * RING // PAGE, 2, PAGE, CFG.head_dim)
    assert len(prompts[1]) + n_new > RING > TINY_WINDOW

    assert [outputs[rid] for rid in rids] == tiny.greedy_oracle(
        lambda p, t: reference.logits(p, HF, t), params, prompts, n_new, width)


def test_the_flash_wrapper_counts_what_its_grid_visits_and_computes():
    """``grid_visits`` is ``_skip_block``'s rule on plain integers, and
    the wrapper leaves a call's counts in the telemetry registry by kind
    and pass where the call is traced."""
    from d9d_tpu.ops.attention import pallas_flash as pf
    from d9d_tpu.telemetry import get_telemetry

    def config(window, block_q=1024, block_kv=512, t=4096):
        return pf._FlashConfig(
            causal=True, scale=1.0, window=window, has_sinks=False,
            has_segments=False, block_q=block_q, block_kv=block_kv,
            seq_len=t, interpret=True,
        )

    # the cell's shapes at the configured blocks: a whole grid of 4 x 8
    # pairs with 20 under the diagonal; under a window of 512 the 11 in
    # reach inside bands of 4 x 3 (forward, dq) and 8 x 2 (dk/dv)
    assert pf.grid_visits(config(None), 4096, 4096) == (32, 20)
    assert pf.grid_visits(config(None), 4096, 4096, "dkv") == (32, 20)
    assert pf.grid_visits(config(512), 4096, 4096) == (12, 11)
    assert pf.grid_visits(config(512), 4096, 4096, "dkv") == (16, 11)
    assert pf.grid_visits(config(512), 4096, 4096, "fused") == (32, 11)
    # and at the blocks the wrapper takes from that window: 8 q blocks
    # reach 2 kv blocks each, the first its own alone
    bq, bkv = pf._window_blocks(512)
    assert (bq, bkv) == (512, 512)
    for kernel in ("fwd", "dkv"):
        assert pf.grid_visits(config(512, bq, bkv), 4096, 4096, kernel) \
            == (16, 15)
    for window in (None, 5, 16, 40):
        cfg = config(window, 16, 8, 64)
        computing = sum(
            not bool(pf._skip_block(cfg, iq, ik))
            for iq in range(4) for ik in range(8)
        )
        assert pf.grid_visits(cfg, 64, 64, "fused") == (32, computing)
        for kernel in ("fwd", "dkv"):
            visited, in_band = pf.grid_visits(cfg, 64, 64, kernel)
            assert in_band == computing
            assert visited == 32 if window is None else visited <= 32

    q = jnp.ones((2, 64, 4, 16), jnp.float32)
    kv = jnp.ones((2, 64, 2, 16), jnp.float32)
    sdpa = make_pallas_flash_sdpa(block_q=16, block_kv=16)
    jax.eval_shape(lambda: sdpa(q, kv, kv, window_size=TINY_WINDOW))
    jax.eval_shape(lambda: sdpa(q, kv, kv))
    gauges = get_telemetry().registry.gauges
    read = {
        name: gauges[f"flash/{name}"].value for name in (
            "window/fwd/blocks_visited", "window/fwd/blocks_computed",
            "window/bwd/blocks_visited", "window/bwd/blocks_computed",
            "full/fwd/blocks_visited", "full/fwd/blocks_computed",
        )
    }
    # 2 x 4 (batch, head) grids: whole ones of 4 x 4 pairs with no window;
    # a window of 16 reaches its own block and the one before, a band of
    # 4 x 2 in each of the three kernels, the first block's cut by
    # position 0 (the backward is dq and dk/dv)
    assert read == {
        "window/fwd/blocks_visited": 64, "window/fwd/blocks_computed": 56,
        "window/bwd/blocks_visited": 128, "window/bwd/blocks_computed": 112,
        "full/fwd/blocks_visited": 128, "full/fwd/blocks_computed": 80,
    }


def test_the_trainers_flops_read_a_layers_kind():
    """``telemetry/flops.py`` counts a layer's scores at its own kind's
    query heads and, under a window, at the keys a query sees; a stack of
    one kind reads what it read."""
    from d9d_tpu.models.qwen3.moe import Qwen3MoeConfig
    from d9d_tpu.telemetry.flops import keys_per_query, model_flops_per_token

    assert keys_per_query(4096) == 2048
    assert round(keys_per_query(4096, 512), 1) == 480.1
    assert keys_per_query(256, 512) == 128
    share = laguna_xs2_share8()
    scores = model_flops_per_token(0, seq_len=4096, config=share)
    full, window = 2 * 48 * 2048, 3 * 64 * keys_per_query(4096, 512)
    assert scores == pytest.approx(12 * 128 * (full + window))
    plain = Qwen3MoeConfig.qwen3_30b_a3b()
    assert model_flops_per_token(0, seq_len=4096, config=plain) \
        == 6.0 * 48 * 32 * 128 * 4096
