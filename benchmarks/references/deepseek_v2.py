"""Plain reference for the DeepSeek-V2 family (deepseek-ai/DeepSeek-V2-Lite).

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
no kernels, no cache, nothing imported from the program under test.
Sizes come from the configuration file's Hugging Face keys.

Per layer: RMSNorm -> multi-head latent attention -> residual -> RMSNorm
-> a dense SwiGLU in the first ``first_k_dense_replace`` layers, else
routed experts (softmax, top-k, weights not renormalised, times
``routed_scaling_factor``) plus the always-on shared experts -> residual.

Latent attention as published: queries are projected directly (no
``q_lora_rank`` in V2-Lite) and split into a 128-wide part without
position and a 64-wide rotary part; keys and values come from a rank-512
latent (RMSNorm, then one up-projection per head to ``k_nope | v``) and
one shared 64-wide rotary key. The softmax scale is ``(128+64)**-0.5``
times the square of YaRN's ``mscale`` (``0.1*mscale*ln(factor)+1``),
and the rotary frequencies are YaRN-blended.

Departures, none of which changes the mathematics: experts are evaluated
densely (see ``qwen3_moe``); rotary pairs are (i, i + d/2) where HF
stores the same weights interleaved and permutes them on load; the
shared experts are one SwiGLU of width ``n_shared_experts *
moe_intermediate_size``, which is how HF builds them too.
"""

import math

import jax.numpy as jnp

from . import qwen3_moe as plain

F32 = jnp.float32


def yarn_inv_freq(dim: int, theta: float, scaling: dict):
    """YaRN (arXiv:2309.00071): high-frequency pairs keep their
    frequency, low-frequency pairs are divided by ``factor``, with a
    linear ramp between the two correction dimensions."""
    base = plain.rotary_inv_freq(dim, theta)
    if not scaling:
        return base
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim // 2 - 1)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=F32) - low) / max(high - low, 1e-3), 0, 1
    )
    return base * (1 - ramp) + base / scaling["factor"] * ramp


def yarn_mscale(scaling: dict) -> float:
    if not scaling or scaling["factor"] <= 1:
        return 1.0
    return 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0


def latent_attention(x, p, cfg, positions):
    b, t, _ = x.shape
    h = cfg["num_attention_heads"]
    d_nope, d_rope, d_v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                           cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    inv_freq = yarn_inv_freq(d_rope, cfg["rope_theta"], cfg.get("rope_scaling"))
    scale = (d_nope + d_rope) ** -0.5 * yarn_mscale(cfg.get("rope_scaling")) ** 2

    q = (x @ p["q_proj"]["kernel"].astype(F32)).reshape(
        b, t, h, d_nope + d_rope
    )
    q = jnp.concatenate(
        [q[..., :d_nope],
         plain.rotate(q[..., d_nope:], positions, inv_freq)], axis=-1,
    )
    kv = x @ p["kv_down_proj"]["kernel"].astype(F32)
    latent = plain.rms_norm(
        kv[..., :rank], p["kv_down_norm"]["weight"], cfg["rms_norm_eps"]
    )
    k_rope = plain.rotate(kv[..., rank:][:, :, None, :], positions, inv_freq)
    up = (latent @ p["kv_up_proj"]["kernel"].astype(F32)).reshape(
        b, t, h, d_nope + d_v
    )
    k = jnp.concatenate(
        [up[..., :d_nope], jnp.broadcast_to(k_rope, (b, t, h, d_rope))],
        axis=-1,
    )
    out = plain.causal_attention(q, k, up[..., d_nope:], scale)
    return out.reshape(b, t, h * d_v) @ p["o_proj"]["kernel"].astype(F32)


def feed_forward(x, p, cfg, layer: int):
    if layer < cfg["first_k_dense_replace"]:
        return plain.swiglu(
            x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
            p["down_proj"]["kernel"],
        )
    b, t, d = x.shape
    routed = plain.sparse_experts(
        x.reshape(b * t, d), p["router"]["gate"]["kernel"],
        p["grouped_experts"], cfg["num_experts_per_tok"],
        cfg["norm_topk_prob"],
    ).reshape(b, t, d) * cfg["routed_scaling_factor"]
    shared = p["shared_expert_module"]["expert"]
    return routed + plain.swiglu(
        x, shared["gate_proj"]["kernel"], shared["up_proj"]["kernel"],
        shared["down_proj"]["kernel"],
    )


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
        x = x + feed_forward(
            plain.rms_norm(x, p["post_attention_layernorm"]["weight"], eps),
            p["mlp"], cfg, layer,
        )
    return plain.rms_norm(x, model["norm"]["weight"], eps)


def logits(params, cfg, tokens):
    return plain.logits(params, cfg, tokens, hidden_states)


def loss(params, cfg, tokens, labels):
    return plain.loss(params, cfg, tokens, labels, hidden_states)
