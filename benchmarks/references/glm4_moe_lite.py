"""Plain reference for the ``glm4_moe_lite`` family (zai-org/GLM-4.7-Flash).

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
no kernels, no cache, nothing imported from the program under test nor
from the DeepSeek-V2 reference (the norm, rotation, softmax and loss
helpers are the Qwen3 reference's): the layer equations are the
DeepSeek-V3 ones the model's published code uses, written down here
from them. Sizes come from the configuration file's Hugging Face keys
(``build.hf_view`` at the tiny size); the weights are the program's
parameter tree, read by its leaf names. The router's score function is the family's (sigmoid)
and is not a key.

Per layer: RMSNorm -> latent attention -> residual -> RMSNorm -> a dense
SwiGLU in the first ``first_k_dense_replace`` layers, else routed experts
plus the always-on shared expert -> residual.

Latent attention: queries through a rank-``q_lora_rank`` bottleneck
(down, RMSNorm, up), split a head into ``qk_nope_head_dim`` without
position and ``qk_rope_head_dim`` rotated; keys and values from a
rank-``kv_lora_rank`` latent (RMSNorm, then one up-projection a head to
``k_nope | v``) and one rotated key of ``qk_rope_head_dim`` shared by all
heads; softmax scale ``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``
(``rope_scaling`` is null: no YaRN temperature).

Router (``topk_method`` ``noaux_tc``): scores = sigmoid(x W) in float32;
the experts are chosen by scores + ``e_score_correction_bias``; their
weights are the unbiased scores, divided by their sum + 1e-20 when
``norm_topk_prob``, times ``routed_scaling_factor``.

Departures from the published code, none of which changes the
mathematics at seeded weights:

- rotary pairs are (i, i + d/2) on the 64 rope dimensions (the
  ``rotate_half`` layout). The DeepSeek-V3 code de-interleaves q_rope and
  k_rope (pairs (2i, 2i + 1)) before the same rotation, which is a fixed
  permutation of those projections' output columns (``assumed`` in the
  configuration file);
- ``n_group`` is 1 in this family, so the group mask is the identity and
  is not written; another value is refused;
- the experts are evaluated densely, one at a time over every token with
  a zero weight where the router did not choose it;
- the multi-token-prediction layer (``num_nextn_predict_layers``) is not
  built: it does not feed the next-token logits.
"""

import jax
import jax.numpy as jnp

from . import qwen3_moe as plain

F32 = jnp.float32


def swiglu(x, p):
    return plain.swiglu(
        x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
        p["down_proj"]["kernel"],
    )


def latent_attention(x, p, cfg, positions):
    b, t, _ = x.shape
    h = cfg["num_attention_heads"]
    d_nope, d_rope, d_v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                           cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv_freq = plain.rotary_inv_freq(d_rope, cfg["rope_theta"])

    q = p["q_proj"]
    q = plain.rms_norm(
        x @ q["down_proj"]["kernel"].astype(F32), q["norm"]["weight"], eps
    ) @ q["up_proj"]["kernel"].astype(F32)
    q = q.reshape(b, t, h, d_nope + d_rope)
    q = jnp.concatenate(
        [q[..., :d_nope], plain.rotate(q[..., d_nope:], positions, inv_freq)],
        axis=-1,
    )

    kv = x @ p["kv_down_proj"]["kernel"].astype(F32)
    latent = plain.rms_norm(kv[..., :rank], p["kv_down_norm"]["weight"], eps)
    k_rope = plain.rotate(kv[..., rank:][:, :, None, :], positions, inv_freq)
    up = (latent @ p["kv_up_proj"]["kernel"].astype(F32)).reshape(
        b, t, h, d_nope + d_v
    )
    k = jnp.concatenate(
        [up[..., :d_nope], jnp.broadcast_to(k_rope, (b, t, h, d_rope))],
        axis=-1,
    )
    out = plain.causal_attention(
        q, k, up[..., d_nope:], (d_nope + d_rope) ** -0.5
    ).reshape(b, t, h * d_v)
    return out @ p["o_proj"]["kernel"].astype(F32)


def routing_weights(x, router, cfg):
    """``x [N, D]`` -> ``[N, E]``: each token's weight on every expert,
    zero where the router did not choose it."""
    if cfg.get("n_group", 1) != 1:
        raise NotImplementedError("this family routes with n_group 1")
    scores = jax.nn.sigmoid(x @ router["gate"]["kernel"].astype(F32))
    choice = scores + router["e_score_correction_bias"].astype(F32)
    _, chosen = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(weights)


def sparse_block(x, p, cfg):
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    weights = routing_weights(flat, p["router"], cfg)
    experts = p["grouped_experts"]

    def one_expert(acc, e):
        out = plain.swiglu(
            flat, experts["gate_proj"][e], experts["up_proj"][e],
            experts["down_proj"][e],
        )
        return acc + out * weights[:, e][:, None], None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(flat), jnp.arange(weights.shape[-1])
    )
    out = routed.reshape(b, t, d)
    if cfg.get("n_shared_experts", 0):
        out = out + swiglu(x, p["shared_expert_module"]["expert"])
    return out


def hidden_states(params, cfg, tokens):
    model = params["model"]
    eps = cfg["rms_norm_eps"]
    x = model["embed_tokens"]["embedding_default"][tokens].astype(F32)
    positions = jnp.arange(tokens.shape[1])
    for layer in range(cfg["num_hidden_layers"]):
        p = model[f"layers_{layer}"]
        x = x + latent_attention(
            plain.rms_norm(x, p["input_layernorm"]["weight"], eps),
            p["self_attn"], cfg, positions,
        )
        h = plain.rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        if layer < cfg["first_k_dense_replace"]:
            x = x + swiglu(h, p["mlp"])
        else:
            x = x + sparse_block(h, p["mlp"], cfg)
    return plain.rms_norm(x, model["norm"]["weight"], eps)


def logits(params, cfg, tokens):
    return plain.logits(params, cfg, tokens, hidden_states)


def loss(params, cfg, tokens, labels):
    return plain.loss(params, cfg, tokens, labels, hidden_states)
