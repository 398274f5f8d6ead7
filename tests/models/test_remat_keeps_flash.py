"""A rematerialised decoder layer keeps the flash call's output and
log-sum-exp (``models/qwen3/dense.py _remat_policy``, the names inside
``ops/attention/pallas_flash.py``'s forward rules): with the compiler
forbidden to merge the recomputed forward with the first
(``remat_prevent_cse``, as the Xing4.0 and Laguna presets set it) the
gradient of the loss holds three Pallas calls an attention layer (forward,
dq, dk/dv), where the parent commit traced four, and the loss and every
gradient are those of the same stack with no rematerialisation, float32
rounding apart. Tiny stacks on the Pallas backend in interpret mode:
grouped-query attention (Qwen3-MoE), MLA with value heads narrower than
the keys (Xing4.0: the padded path, an MTP block's layer beside the
stack's) and window beside full kinds (Laguna)."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

from d9d_tpu.models.deepseek import DeepseekCausalLM, xing4_0_tiny
from d9d_tpu.models.laguna import LagunaCausalLM, laguna_tiny
from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig
from d9d_tpu.ops.attention.pallas_flash import make_pallas_flash_sdpa
from tests.jaxpr_tools import count

VOCAB = 64
SEQ = 32
LAGUNA = laguna_tiny(VOCAB)
# name: (model class, config); two attention layers each, so that the
# three stacks compile in seconds on the CPU rig
STACKS = {
    "qwen3_moe": (Qwen3MoeCausalLM, Qwen3MoeConfig.tiny(VOCAB)),
    # the dense layer and the multi-token-prediction module's block, whose
    # layer is rematerialised too; 24-wide keys on 16-wide values
    # (two Sinkhorn rounds: the n-stream path is not what is held here)
    "xing_like_mla": (DeepseekCausalLM, dataclasses.replace(
        xing4_0_tiny(VOCAB), num_layers=1, hc_sinkhorn_iters=2)),
    # a full layer of 6 query heads and a window layer of 8
    "laguna_like_kinds": (LagunaCausalLM, dataclasses.replace(
        LAGUNA, num_layers=2, layer_kinds=LAGUNA.layer_kinds[:2])),
}
ATTENTION_LAYERS = 2


def pallas_call(eqn) -> bool:
    """A flash call: the n-stream path's own calls (``ops/mhc.py``, named
    ``mhc_*``) are counted in ``tests/nn/test_hyper_connections.py``."""
    return (eqn.primitive.name == "pallas_call"
            and not (eqn.params["name"] or "").startswith("mhc_"))


def kept_output(eqn) -> bool:
    return eqn.primitive.name == "name" and eqn.params["name"] == "sdpa_out"


@pytest.mark.parametrize("stack", list(STACKS))
def test_three_calls_a_layer_and_the_gradients_of_no_remat(stack):
    cls, cfg = STACKS[stack]
    assert cfg.num_layers + cfg.num_mtp_modules == ATTENTION_LAYERS
    sdpa = make_pallas_flash_sdpa(block_q=16, block_kv=16)
    sample = jnp.asarray(
        np.random.RandomState(1).randint(0, VOCAB, (1, SEQ + 1)), jnp.int32)
    tokens, labels = sample[:, :-1], sample[:, 1:]
    pos = jnp.arange(SEQ, dtype=jnp.int32)[None]

    def model(**remat):
        return cls(config=dataclasses.replace(cfg, **remat), sdpa=sdpa,
                   dtype=jnp.float32, param_dtype=jnp.float32)

    def traced(m, params):
        def loss(p):
            out = m.apply({"params": p}, tokens, pos, labels,
                          mutable=["moe_stats", "moe_buffers"])[0]
            return out.astype(jnp.float32).mean()

        return jax.jit(jax.value_and_grad(loss)).trace(params)

    plain = model(remat=False)
    # seeded numbers in the tree's shapes: no init program to compile
    shapes = nn.unbox(jax.eval_shape(
        lambda: plain.init(jax.random.PRNGKey(0), tokens, pos, labels)
    )["params"])
    draw = np.random.RandomState(0)
    params = jax.tree.map(
        lambda leaf: jnp.asarray(
            0.1 * draw.standard_normal(leaf.shape), leaf.dtype), shapes)
    kept = traced(model(remat=True, remat_prevent_cse=True), params)
    assert count(kept.jaxpr.jaxpr, pallas_call) == 3 * ATTENTION_LAYERS
    # one name a call, the kernel's own: a second on a copy of the result
    # (the attention modules carried one) keeps a second copy
    assert count(kept.jaxpr.jaxpr, kept_output) == ATTENTION_LAYERS
    got, got_g = kept.lower().compile()(params)
    want, want_g = traced(plain, params).lower().compile()(params)
    # float32 rounding apart: the CPU compiler fuses the two programs'
    # element-wise work differently (the kernels' bits are held equal in
    # tests/ops/test_pallas_flash.py)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    got_g, want_g = flatten_dict(got_g), flatten_dict(want_g)
    assert set(got_g) == set(want_g)
    for path, w in want_g.items():
        np.testing.assert_allclose(
            got_g[path], w, rtol=1e-5, atol=1e-7, err_msg=str(path))
