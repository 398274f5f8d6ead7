"""Plain reference for the Qwen3-MoE family (Qwen/Qwen3-30B-A3B).

The forward pass and the next-token loss as the published description
gives them, in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks, nothing imported from the program under test. Sizes
come from the configuration file's Hugging Face keys; the weights are
the program's own parameter tree (unboxed), read by its leaf names.

Per layer: RMSNorm -> grouped-query attention (per-head RMSNorm on q
and k, rotary embedding on the whole head, causal softmax) -> residual
-> RMSNorm -> sparse experts (softmax over all experts, top-k, weights
renormalised when ``norm_topk_prob``) -> residual.

Departures from the Hugging Face implementation, none of which changes
the mathematics: the experts are evaluated densely, one expert at a time
over every token with a zero weight where the router did not pick it
(so no sort, no gather); rotary pairs are (i, i + d/2), which is the
layout HF's ``rotate_half`` uses.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(F32)


def rotary_inv_freq(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))


def rotate(x, positions, inv_freq, scale: float = 1.0):
    """``x [B, T, H, D]`` rotated at ``positions [T]``; pairs (i, i+D/2)."""
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(angles) * scale)[None, :, None, :]
    sin = (jnp.sin(angles) * scale)[None, :, None, :]
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_attention(q, k, v, scale: float):
    """``q [B,T,H,Dq]``, ``k [B,T,H,Dq]``, ``v [B,T,H,Dv]`` -> ``[B,T,H,Dv]``."""
    t = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    keep = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(keep[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def swiglu(x, gate_w, up_w, down_w):
    g = x @ gate_w.astype(F32)
    u = x @ up_w.astype(F32)
    return (jax.nn.silu(g) * u) @ down_w.astype(F32)


def sparse_experts(x, router_w, experts, top_k: int, renormalise: bool):
    """``x [N, D]`` -> ``[N, D]``: softmax router, top-k, dense evaluation."""
    probs = jax.nn.softmax(x @ router_w.astype(F32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    if renormalise:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    n_experts = probs.shape[-1]
    weights = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], top_i
    ].set(top_p)  # [N, E], zero where not routed

    def one_expert(acc, e):
        out = swiglu(
            x, experts["gate_proj"][e], experts["up_proj"][e],
            experts["down_proj"][e],
        )
        return acc + out * weights[:, e][:, None], None

    acc, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x), jnp.arange(n_experts)
    )
    return acc


def gqa_attention(x, p, cfg, positions):
    b, t, _ = x.shape
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = (x @ p["q_proj"]["kernel"].astype(F32)).reshape(b, t, h, d)
    k = (x @ p["k_proj"]["kernel"].astype(F32)).reshape(b, t, hkv, d)
    v = (x @ p["v_proj"]["kernel"].astype(F32)).reshape(b, t, hkv, d)
    q = rms_norm(q, p["q_norm"]["weight"], eps)
    k = rms_norm(k, p["k_norm"]["weight"], eps)
    inv_freq = rotary_inv_freq(d, cfg["rope_theta"])
    q = rotate(q, positions, inv_freq)
    k = rotate(k, positions, inv_freq)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    out = causal_attention(q, k, v, d ** -0.5).reshape(b, t, h * d)
    return out @ p["o_proj"]["kernel"].astype(F32)


def moe_block(x, p, cfg):
    b, t, d = x.shape
    out = sparse_experts(
        x.reshape(b * t, d), p["router"]["gate"]["kernel"],
        p["grouped_experts"], cfg["num_experts_per_tok"],
        cfg["norm_topk_prob"],
    )
    return out.reshape(b, t, d)


def hidden_states(params, cfg, tokens):
    model = params["model"]
    eps = cfg["rms_norm_eps"]
    x = model["embed_tokens"]["embedding_default"][tokens].astype(F32)
    positions = jnp.arange(tokens.shape[1])
    for layer in range(cfg["num_hidden_layers"]):
        p = model[f"layers_{layer}"]
        x = x + gqa_attention(
            rms_norm(x, p["input_layernorm"]["weight"], eps),
            p["self_attn"], cfg, positions,
        )
        x = x + moe_block(
            rms_norm(x, p["post_attention_layernorm"]["weight"], eps),
            p["mlp"], cfg,
        )
    return rms_norm(x, model["norm"]["weight"], eps)


def logits(params, cfg, tokens, hidden_fn=hidden_states):
    """``tokens [B, T]`` int -> logits ``[B, T, V]`` float32."""
    with jax.default_matmul_precision("highest"):
        h = hidden_fn(params, cfg, tokens)
        return h @ params["lm_head"]["head_default"].astype(F32).T


def loss(params, cfg, tokens, labels, hidden_fn=hidden_states):
    """Mean next-token cross-entropy over ``labels [B, T]``."""
    lg = logits(params, cfg, tokens, hidden_fn)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)
