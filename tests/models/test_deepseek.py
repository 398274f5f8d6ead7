"""DeepSeek-V2 family (models/deepseek): MLA attention + shared-expert
MoE riding the Qwen3-MoE backbone — training forward/grads, decode
parity against the full forward, and the serving loops."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.e2e  # slow tier: whole-model loops

from d9d_tpu.loop.generate import generate
from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.loop.speculative import speculative_generate
from d9d_tpu.models.deepseek import DeepseekCausalLM, deepseek_v2_tiny
from d9d_tpu.ops.attention.eager import eager_sdpa
from tests.models import tiny
from tests.models.tiny import VOCAB


def _models(dml=0):
    cfg = deepseek_v2_tiny(VOCAB)
    model = DeepseekCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
        decode_max_length=dml,
    )
    full = model.clone(decode_max_length=0)
    return full, model, tiny.seeded_params(full)


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_forward_loss_and_grads():
    full, _, params = _models()
    b, t = 2, 8
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, VOCAB, (b, t)), jnp.int32
    )
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    loss = jax.jit(full.apply)({"params": params}, ids, pos, ids)
    assert np.isfinite(float(loss.sum()))
    # MLA params exist where GQA's would not
    layer = params["model"]["layers_1"]["self_attn"]
    assert "kv_down_proj" in layer and "kv_up_proj" in layer
    g = jax.jit(jax.grad(
        lambda xp: float_sum(full, xp, ids, pos)
    ))(params)
    assert all(
        np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g)
    )


def float_sum(model, params, ids, pos):
    return jnp.sum(
        model.apply({"params": params}, ids, pos, ids).astype(jnp.float32)
    )


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_greedy_generate_matches_full_forward_argmax():
    """Teacher-forced rollout through the FULL forward must equal the
    cached decode loop token for token (MLA latent-cache + absorbed
    decode correctness at the model level)."""
    full, dec, params = _models(dml=20)
    prompt = jnp.asarray(
        np.random.RandomState(1).randint(0, VOCAB, (1, 5)), jnp.int32
    )
    n, width = 6, 16
    got = np.asarray(generate(dec, params, prompt, max_new_tokens=n))[0]
    pos = jnp.arange(width, dtype=jnp.int32)[None]
    want, = tiny.greedy_oracle(
        lambda p, t: full.apply({"params": p}, t, pos, method=full.logits),
        params, [np.asarray(prompt)[0].tolist()], n, width)
    assert got.tolist() == want


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_serving_and_speculative():
    full, dec, params = _models(dml=24)
    prompts = [
        np.random.RandomState(s).randint(0, VOCAB, 3 + s % 3).tolist()
        for s in range(3)
    ]
    n = 5

    def oracle(p):
        out = generate(
            dec, params, jnp.asarray([p], jnp.int32), max_new_tokens=n
        )
        return np.asarray(out)[0].tolist()

    batcher = ContinuousBatcher(dec, params, batch_size=2)
    rids = [batcher.submit(p, max_new_tokens=n) for p in prompts]
    outputs = batcher.drain()
    for rid, p in zip(rids, prompts):
        assert outputs[rid] == oracle(p), rid

    # speculative with a perfect draft: MLA verify (decompressed
    # continuation chunks) + index rewind must stay exact
    prompt2 = jnp.asarray([prompts[0], prompts[0]], jnp.int32)
    want = np.asarray(generate(dec, params, prompt2, max_new_tokens=n))
    got = np.asarray(speculative_generate(
        dec, params, dec, params, prompt2,
        max_new_tokens=n, speculate_k=3,
    ))
    np.testing.assert_array_equal(got, want)


def test_first_layer_dense_rest_sparse():
    _, _, params = _models()
    l0 = params["model"]["layers_0"]["mlp"]
    l1 = params["model"]["layers_1"]["mlp"]
    assert "gate_proj" in l0  # dense SwiGLU (first_k_dense_replace)
    assert "router" in l1 and "shared_expert_module" in l1
    # ungated shared expert (DeepSeek style)
    assert "gate" not in l1["shared_expert_module"]
