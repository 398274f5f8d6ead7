"""Plain reference for the ``xing4_0`` family (XingChen-AGI/Xing4.0-29B-A4B),
as one chip of an expert-parallel training job sees it.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
no kernels, no cache, nothing imported from the program under test (the
norm, rotation, softmax and feed-forward helpers are the Qwen3
reference's, the YaRN frequencies and temperature the DeepSeek-V2
reference's). Written from the papers, not from the program: the block
is DeepSeek-V3's (arXiv:2412.19437), the residual path is
manifold-constrained hyper-connections (arXiv:2512.24880, on
hyper-connections, arXiv:2409.19606), the extra module is DeepSeek-V3's
multi-token prediction (section 2.2). Sizes come from the configuration
file's Hugging Face keys (``build.hf_view`` at the tiny size); the
weights are the program's parameter tree, read by its leaf names.

**The residual path.** With ``n`` streams of width ``C``, the stream of
one token is ``x_l`` in ``R^{n x C}``. It starts as ``n`` copies of the
embedding. Around each sublayer ``F`` (attention; the dense or expert
feed-forward), with ``x' = vec(x_l) / rms(vec(x_l))`` over all ``n C``
numbers (no gain):

    H_pre  = sigmoid(a_pre (x' phi_pre) + b_pre)            [n]
    H_post = 2 sigmoid(a_post (x' phi_post) + b_post)       [n]
    H_res  = Sinkhorn(exp(clamp(a_res mat(x' phi_res) + b_res)))   [n, n]
    x_{l+1} = H_res x_l + H_post^T F(RMSNorm(H_pre x_l))

Sinkhorn is ``hc_sinkhorn_iters`` rounds of (every row divided by its
sum + ``hc_eps``, then every column by its sum + ``hc_eps``); the clamp
is ``[mhc_h_res_clamp_min, mhc_h_res_clamp_max]``; ``mat`` fills an ``n
x n`` matrix row by row. The stack's output is the sum over the streams,
then the final RMSNorm.

**Latent attention** with q compression and YaRN: as the GLM reference
has it, with the rotary frequencies YaRN-blended and the softmax scale
``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5`` times the square of
YaRN's ``0.1 mscale_all_dim ln(factor) + 1``.

**Router** (``noaux_tc``): scores = sigmoid(x W) in float32 over all
published experts; chosen by scores + ``e_score_correction_bias``;
weights the unbiased scores, divided by their sum + 1e-20 when
``norm_topk_prob``, times ``routed_scaling_factor``.

**The share.** The tree holds ``E`` of the router's ``R`` experts (both
read from its shapes), those from ``first_held_expert`` on (a key of the
file; 0 where absent), and a slice of the vocabulary (the table's own
rows). The router scores, chooses and renormalises over all ``R``; only
the held experts are evaluated, each densely over every token with a
zero weight where it was not chosen. What the absent experts would add
is left out: it is computed on the chips that hold them, and by neither
program nor reference. The shared expert is whole.

**Multi-token prediction** (present when the tree has an ``mtp``
sub-tree; depth 1). For ``i < T - 1``: ``h'_i = M [RMSNorm(h_i) ;
RMSNorm(Emb(t_{i+1}))]`` with ``h_i`` the main stack's output before
its final norm; one more block of the expert kind at positions ``0 ..
T - 2``, on ``n`` streams of its own that start as ``n`` copies of
``h'`` and are read out by the sum; its own final RMSNorm; the shared
head. Its loss is ``-(1 / T) sum_i log P_i[t_{i+2}]`` (the paper
divides the ``T - 1`` terms by ``T``), and the whole loss is the mean
next-token cross-entropy + ``mtp_loss_weight`` (0.3 where absent,
DeepSeek-V3's first phase) times it.

Assumptions, each also listed under ``assumed`` in the configuration
file: the stream starts as copies and is read out by a sum (the
hyper-connections paper); the wide RMSNorm has no gain; ``h_i`` is
taken before the final norm; the merge takes ``[h ; Emb]`` in the
paper's order (the published DeepSeek-V3 code concatenates ``[Emb ; h]``:
a fixed permutation of ``M``'s rows); the MTP block runs streams of its
own. Departures from the published code, none of which changes the
mathematics at seeded weights: rotary pairs are (i, i + d/2) (see the
GLM reference); ``n_group`` is 1; the experts are evaluated densely.
"""

import jax
import jax.numpy as jnp

from . import qwen3_moe as plain
from .deepseek_v2 import yarn_inv_freq, yarn_mscale

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
    scaling = cfg.get("rope_scaling")
    inv_freq = yarn_inv_freq(d_rope, cfg["rope_theta"], scaling)
    scale = (d_nope + d_rope) ** -0.5 * yarn_mscale(scaling) ** 2

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
    out = plain.causal_attention(q, k, up[..., d_nope:], scale)
    return out.reshape(b, t, h * d_v) @ p["o_proj"]["kernel"].astype(F32)


def routing_weights(x, router, cfg):
    """``x [N, D]`` -> ``[N, R]``: each token's weight on every published
    expert, zero where the router did not choose it."""
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
    """The held experts' part of the routed output, plus the shared
    expert."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    weights = routing_weights(flat, p["router"], cfg)  # [N, R]
    experts = p["grouped_experts"]
    held = experts["gate_proj"].shape[0]
    first = cfg.get("first_held_expert", 0)
    assert first + held <= weights.shape[-1]

    def one_expert(acc, e):
        out = plain.swiglu(
            flat, experts["gate_proj"][e], experts["up_proj"][e],
            experts["down_proj"][e],
        )
        return acc + out * weights[:, first + e][:, None], None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(flat), jnp.arange(held)
    )
    out = routed.reshape(b, t, d)
    if cfg.get("n_shared_experts", 0):
        out = out + swiglu(x, p["shared_expert_module"]["expert"])
    return out


def sinkhorn(matrix, rounds: int, eps: float):
    """``[..., n, n]`` positive -> rows, then columns, brought to sum 1."""
    for _ in range(rounds):
        matrix = matrix / (matrix.sum(axis=-1, keepdims=True) + eps)
        matrix = matrix / (matrix.sum(axis=-2, keepdims=True) + eps)
    return matrix


def mixing(x, p, cfg):
    """``x [B, T, n, C]`` -> ``H_pre [B, T, n]``, ``H_post [B, T, n]``,
    ``H_res [B, T, n, n]``."""
    b, t, n, c = x.shape
    flat = x.reshape(b, t, n * c)
    unit = flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
        + cfg["rms_norm_eps"]
    )
    pre = p["a_pre"] * (unit @ p["phi_pre"].astype(F32)) + p["b_pre"]
    post = p["a_post"] * (unit @ p["phi_post"].astype(F32)) + p["b_post"]
    res = p["a_res"] * (unit @ p["phi_res"].astype(F32)).reshape(
        b, t, n, n
    ) + p["b_res"]
    res = jnp.clip(
        res, cfg.get("mhc_h_res_clamp_min", -30),
        cfg.get("mhc_h_res_clamp_max", 30),
    )
    h_res = sinkhorn(
        jnp.exp(res), cfg.get("hc_sinkhorn_iters", 20),
        cfg.get("hc_eps", 1e-6),
    )
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), h_res


def around(x, p, cfg, sublayer):
    """One sublayer on the n-stream path: ``H_res x + H_post^T F(H_pre x)``."""
    h_pre, h_post, h_res = mixing(x, p, cfg)
    out = sublayer(jnp.einsum("btj,btjc->btc", h_pre, x))
    return (
        jnp.einsum("btij,btjc->btic", h_res, x)
        + h_post[..., None] * out[:, :, None, :]
    )


def block(x, p, cfg, positions, dense: bool):
    """One decoder block on the stream ``x [B, T, n, C]``."""
    eps = cfg["rms_norm_eps"]
    x = around(x, p["attn_mhc"], cfg, lambda u: latent_attention(
        plain.rms_norm(u, p["input_layernorm"]["weight"], eps),
        p["self_attn"], cfg, positions,
    ))

    def feed_forward(u):
        u = plain.rms_norm(u, p["post_attention_layernorm"]["weight"], eps)
        return swiglu(u, p["mlp"]) if dense else sparse_block(u, p["mlp"], cfg)

    return around(x, p["mlp_mhc"], cfg, feed_forward)


def streams_of(model: dict) -> int:
    return model["layers_0"]["attn_mhc"]["phi_pre"].shape[1]


def copies(x, n: int):
    return jnp.broadcast_to(x[:, :, None, :], (*x.shape[:2], n, x.shape[-1]))


def stack_output(params, cfg, tokens):
    """The main stack's output before its final norm, ``[B, T, C]``."""
    model = params["model"]
    x = model["embed_tokens"]["embedding_default"][tokens].astype(F32)
    x = copies(x, streams_of(model))
    positions = jnp.arange(tokens.shape[1])
    for layer in range(cfg["num_hidden_layers"]):
        x = block(
            x, model[f"layers_{layer}"], cfg, positions,
            dense=layer < cfg["first_k_dense_replace"],
        )
    return x.sum(axis=2)


def hidden_states(params, cfg, tokens):
    return plain.rms_norm(
        stack_output(params, cfg, tokens), params["model"]["norm"]["weight"],
        cfg["rms_norm_eps"],
    )


def mtp_hidden_states(params, cfg, tokens):
    """The module's normed output at positions ``0 .. T - 2``: position i
    merges ``h_i`` with the embedding of ``t_{i+1}``."""
    p, eps = params["mtp"], cfg["rms_norm_eps"]
    table = params["model"]["embed_tokens"]["embedding_default"]
    h = stack_output(params, cfg, tokens)[:, :-1]
    following = table[tokens[:, 1:]].astype(F32)
    merged = jnp.concatenate([
        plain.rms_norm(h, p["hnorm"]["weight"], eps),
        plain.rms_norm(following, p["enorm"]["weight"], eps),
    ], axis=-1) @ p["merge"]["kernel"].astype(F32)
    x = block(
        copies(merged, streams_of(params["model"])), p["block"], cfg,
        jnp.arange(tokens.shape[1] - 1), dense=False,
    )
    return plain.rms_norm(x.sum(axis=2), p["norm"]["weight"], eps)


def logits(params, cfg, tokens):
    return plain.logits(params, cfg, tokens, hidden_states)


def mtp_logits(params, cfg, tokens):
    """``[B, T - 1, V]``: position i predicts ``t_{i+2}``."""
    return plain.logits(params, cfg, tokens, mtp_hidden_states)


def picked_log_probs(lg, labels):
    logp = jax.nn.log_softmax(lg, axis=-1)
    return jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def loss(params, cfg, tokens, labels):
    """Mean next-token cross-entropy over ``labels [B, T]``, plus
    ``mtp_loss_weight`` times the module's loss on the token after."""
    total = -jnp.mean(picked_log_probs(logits(params, cfg, tokens), labels))
    if "mtp" in params:
        after = picked_log_probs(mtp_logits(params, cfg, tokens), labels[:, 1:])
        mtp = -jnp.sum(after) / labels.size
        total = total + cfg.get("mtp_loss_weight", 0.3) * mtp
    return total
