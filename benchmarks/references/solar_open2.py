"""Plain reference for the ``solar_open2`` family (upstage/Solar-Open2-250B),
as one chip of an expert-parallel deployment sees it.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
the delta-rule recurrence one token at a time in a ``lax.scan``: no
kernels, no cache, no chunks, nothing imported from the program under
test (the norm, attention and feed-forward helpers are the Qwen3
reference's). Written from the catalog row's ``config.json`` keys and
from the layer equations of Kimi delta attention (Kimi Linear, arXiv
2510.26692; fla-core's ``KimiDeltaAttention``); the weights are the
program's parameter tree, read by its leaf names.

Model: pre-norm blocks, RMSNorm at ``rms_norm_eps``, no bias but the
output gate's, an untied head, no rotation anywhere (``use_rope:
false``): ``h = x + mixer(norm1(x)); y = h + experts(norm2(h))``; a final
norm; ``logits = y W_head``. Layer ``l`` is grouped-query attention where
``gqa_layers`` lists it (0, 4, 8, ...), else Kimi delta attention.

**KDA mixer** on ``x [T, E]``, ``H`` heads of ``D = Dk = Dv``:

    q~, k~, v   = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))
    q, k        = l2norm(q~) D^-1/2, l2norm(k~)            (a head)
    g           = -exp(A_log_h) softplus((x Wfa) Wfb + dt_bias)   in R^D
    beta        = 2 sigmoid(x Wb)        (kda_allow_neg_eigval: in (0, 2))
    S'          = diag(exp(g_t)) S_{t-1},         S_0 = 0
    S_t         = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t         = S_t^T q_t
    out         = (RMSNorm_D(o; w_o) sigmoid((x Wga) Wgb + b_g)) Wo

the convolutions depthwise and causal, the last tap on the current token.

**GQA mixer**: causal softmax at ``head_dim ** -0.5``, no rotation, no q/k
norm, ``attn * sigmoid(x Wg)`` element-wise before ``Wo``.

**Experts**, in every layer: ``s = sigmoid(x Wr)`` over the router's ``R``
experts; the ``num_experts_per_tok`` largest of ``s +
e_score_correction_bias``; weights ``s`` at those over their sum
(``norm_topk_prob``) times ``routed_scaling_factor``; expert ``e``:
``(silu(x Wg) * (x Wu)) Wd``; one shared expert of the same form, ungated,
added once.

**What the row leaves open**, each reading one function here and one
field of the program's preset (``assumed`` in the configuration file):
:func:`gate_pair` (``kda_use_full_proj: false`` as low-rank pairs of rank
``head_dim``), :func:`gqa_gate` (``use_gqa_gate`` element-wise from the
block's input), :func:`routing_scores` (sigmoid with a selection bias, no
groups), :func:`decay`, :func:`write_strength` and :func:`output_gate`
(the KDA gates as Kimi Linear has them), no q/k norm in the GQA layers.

**The share.** The tree holds ``E`` of the router's ``R`` experts (both
read from its shapes), those from ``first_held_expert`` on (a key of the
file; 0 where absent), and a slice of the vocabulary (the table's and the
head's own rows). The router scores, chooses and renormalises over all
``R``; only the held experts are evaluated, each densely over every token
with a zero weight where it was not chosen. What the absent experts would
add is left out: it is computed on the chips that hold them, and by
neither program nor reference. The shared expert is whole.

Which layer is which, and every size of the mixer, is read from the tree:
a layer holds a ``kda`` sub-tree or a ``self_attn`` one; ``H`` is the
length of ``A_log``, ``D`` of the mixer's norm weight, the taps the shape
of the convolution's weight. Where the sizes it is given carry the
family's own keys (the configuration file, at the real size) it asserts
that the tree agrees with every one. ``build.hf_view`` at the tiny size
carries none of them: ``kda_allow_neg_eigval``, ``use_gqa_gate`` and
``routed_scaling_factor`` are then the published ones (true, true, 1).

Departures from the published description, none of which changes the
mathematics at seeded weights:

- the published mixer keeps three convolution modules (``q_conv1d``,
  ``k_conv1d``, ``v_conv1d``); the tree keeps one depthwise convolution
  over the ``3 H D`` joined channels, whose thirds they are;
- the experts are evaluated densely, one at a time (no sort, no gather),
  and gate and up are two matrices;
- the recurrence runs a token at a time where the published kernels run
  chunks of 64;
- ``intermediate_size``, ``rope_theta``, ``partial_rotary_factor`` and
  ``max_position_embeddings`` are read by nothing: there is no dense
  layer (``first_k_dense_replace`` 0) and no rotation.
"""

import jax
import jax.numpy as jnp

from . import qwen3_moe as plain

F32 = jnp.float32


def family(cfg: dict) -> dict:
    """The family's switches and constants: the file's where it has them."""
    return {
        "neg_eigval": cfg.get("kda_allow_neg_eigval", True),
        "gqa_gate": cfg.get("use_gqa_gate", True),
        "routed_scaling": cfg.get("routed_scaling_factor", 1),
    }


def layer_kinds(model: dict, n_layers: int) -> list[str]:
    return [
        "kda" if "kda" in model[f"layers_{i}"] else "attention"
        for i in range(n_layers)
    ]


def check_sizes(params: dict, cfg: dict) -> None:
    """The tree against the family's keys, where ``cfg`` has them."""
    if "gqa_layers" not in cfg:
        return
    model = params["model"]
    n, e = cfg["num_hidden_layers"], cfg["hidden_size"]
    assert layer_kinds(model, n) == [
        "attention" if i in cfg["gqa_layers"] else "kda" for i in range(n)]
    assert not cfg["use_rope"] and not cfg["tie_word_embeddings"]
    assert not cfg["kda_use_full_proj"] and cfg["first_k_dense_replace"] == 0
    assert cfg["n_shared_experts"] == 1 and cfg["norm_topk_prob"]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    linear = cfg["linear_attn_config"]
    lh, ld, taps = (linear["num_heads"], linear["head_dim"],
                    linear["short_conv_kernel_size"])
    assert linear["num_kv_heads"] is None  # k and v have the q heads
    rank, wide = ld, cfg["moe_intermediate_size"]
    published = cfg.get("share", {}).get("published", {})
    routed = published.get("n_routed_experts", cfg["n_routed_experts"])
    for i in range(n):
        layer = model[f"layers_{i}"]
        if "kda" in layer:
            m = layer["kda"]
            for name in "qkv":
                assert m[f"{name}_proj"]["kernel"].shape == (e, lh * ld)
            assert m["qkv_conv1d"]["weight"].shape == (3 * lh * ld, taps)
            assert m["A_log"].shape == (lh,)
            assert m["dt_bias"].shape == (lh, ld)
            assert m["b_proj"]["kernel"].shape == (e, lh)
            for pair in "fg":
                assert m[f"{pair}_a_proj"]["kernel"].shape == (e, rank)
                assert m[f"{pair}_b_proj"]["kernel"].shape == (rank, lh * ld)
            assert m["g_b_proj"]["bias"].shape == (lh * ld,)
            assert "bias" not in m["f_b_proj"]
            assert m["o_norm"]["weight"].shape == (ld,)
            assert m["o_proj"]["kernel"].shape == (lh * ld, e)
        else:
            attn = layer["self_attn"]
            assert attn["q_proj"]["kernel"].shape == (e, h * d)
            assert attn["k_proj"]["kernel"].shape == (e, hkv * d)
            assert attn["v_proj"]["kernel"].shape == (e, hkv * d)
            assert attn["gate_proj"]["kernel"].shape == (e, h * d)
            assert attn["o_proj"]["kernel"].shape == (h * d, e)
            assert "q_norm" not in attn and "bias" not in attn["q_proj"]
        mlp = layer["mlp"]
        assert mlp["router"]["gate"]["kernel"].shape == (e, routed)
        assert mlp["router"]["e_score_correction_bias"].shape == (routed,)
        assert mlp["grouped_experts"]["gate_proj"].shape == (
            cfg["n_routed_experts"], e, wide)
        shared = mlp["shared_expert_module"]
        assert "gate" not in shared
        assert shared["expert"]["gate_proj"]["kernel"].shape == (e, wide)
    assert model["embed_tokens"]["embedding_default"].shape == (
        cfg["vocab_size"], e)
    assert params["lm_head"]["head_default"].shape == (cfg["vocab_size"], e)


def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gate_pair(x, p, name):
    """``(x W_a) W_b [+ b]``: a low-rank pair, rank the first matrix's."""
    out = (x @ p[f"{name}_a_proj"]["kernel"].astype(F32)) @ (
        p[f"{name}_b_proj"]["kernel"].astype(F32))
    if "bias" in p[f"{name}_b_proj"]:
        out = out + p[f"{name}_b_proj"]["bias"].astype(F32)
    return out


def decay(x, p, heads):
    """Log decay ``g [B, T, H, D] <= 0``: a number a key channel."""
    b, t, _ = x.shape
    raw = gate_pair(x, p, "f").reshape(b, t, heads, -1)
    return -jnp.exp(p["A_log"].astype(F32))[:, None] * jax.nn.softplus(
        raw + p["dt_bias"].astype(F32))


def write_strength(x, p, cfg):
    beta = jax.nn.sigmoid(x @ p["b_proj"]["kernel"].astype(F32))
    return 2.0 * beta if family(cfg)["neg_eigval"] else beta


def output_gate(o, x, p, eps):
    """``RMSNorm_D(o; w) * sigmoid(gate)``: the gate inside the norm's
    product, a head at a time; ``o [B, T, H, D]`` -> ``[B, T, H D]``."""
    b, t, heads, d = o.shape
    normed = plain.rms_norm(o, p["o_norm"]["weight"], eps)
    gate = jax.nn.sigmoid(gate_pair(x, p, "g"))
    return normed.reshape(b, t, heads * d) * gate


def kda_mixer(x, p, cfg):
    heads = p["A_log"].shape[0]
    d = p["o_norm"]["weight"].shape[0]
    conv_w = p["qkv_conv1d"]["weight"].astype(F32)  # [3 H D, K]
    taps = conv_w.shape[1]
    b, t, _ = x.shape

    qkv = jnp.concatenate(
        [x @ p[f"{n}_proj"]["kernel"].astype(F32) for n in "qkv"], axis=-1)
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(
        sum(padded[:, j:j + t] * conv_w[:, j] for j in range(taps)))
    q, k, v = (
        part.reshape(b, t, heads, d) for part in jnp.split(qkv, 3, axis=-1))
    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    g = decay(x, p, heads)
    beta = write_strength(x, p, cfg)

    def step(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs  # [B, H, D] x 4, [B, H]
        state = jnp.exp(g_t)[..., None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + beta_t[..., None, None] * (
            k_t[..., None] * (v_t - seen)[..., None, :])
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    time_major = lambda u: jnp.swapaxes(u, 0, 1)  # noqa: E731
    _, o = jax.lax.scan(
        step, jnp.zeros((b, heads, d, d), F32),
        tuple(map(time_major, (q, k, v, g, beta))),
    )
    gated = output_gate(time_major(o), x, p, cfg["rms_norm_eps"])
    return gated @ p["o_proj"]["kernel"].astype(F32)


def gqa_gate(attn, x, p, cfg):
    """``attn [B, T, H D]`` times the sigmoid of a projection of the
    block's input, a number an element."""
    if not family(cfg)["gqa_gate"]:
        return attn
    return attn * jax.nn.sigmoid(x @ p["gate_proj"]["kernel"].astype(F32))


def attention(x, p, cfg):
    b, t, _ = x.shape
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = (x @ p["q_proj"]["kernel"].astype(F32)).reshape(b, t, h, d)
    k = (x @ p["k_proj"]["kernel"].astype(F32)).reshape(b, t, hkv, d)
    v = (x @ p["v_proj"]["kernel"].astype(F32)).reshape(b, t, hkv, d)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    out = plain.causal_attention(q, k, v, d ** -0.5).reshape(b, t, h * d)
    return gqa_gate(out, x, p, cfg) @ p["o_proj"]["kernel"].astype(F32)


def routing_scores(x, router):
    """``x [N, E]`` -> ``(scores, what the choice is made by) [N, R]``."""
    scores = jax.nn.sigmoid(x @ router["gate"]["kernel"].astype(F32))
    return scores, scores + router["e_score_correction_bias"].astype(F32)


def routing_weights(x, router, cfg):
    """``x [N, E]`` -> ``[N, R]``: each token's weight on every published
    expert, zero where the router did not choose it."""
    scores, choice = routing_scores(x, router)
    _, chosen = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * family(cfg)["routed_scaling"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(weights)


def routed_experts(x, p, cfg):
    """The held experts' part of the routed output, ``x [B, T, E]``."""
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
    return routed.reshape(b, t, d)


def shared_expert(x, p):
    w = p["shared_expert_module"]["expert"]
    return plain.swiglu(
        x, w["gate_proj"]["kernel"], w["up_proj"]["kernel"],
        w["down_proj"]["kernel"],
    )


def sparse_block(x, p, cfg):
    return routed_experts(x, p, cfg) + shared_expert(x, p)


def hidden_states(params, cfg, tokens):
    check_sizes(params, cfg)
    model = params["model"]
    eps = cfg["rms_norm_eps"]
    x = model["embed_tokens"]["embedding_default"][tokens].astype(F32)
    for layer in range(cfg["num_hidden_layers"]):
        p = model[f"layers_{layer}"]
        u = plain.rms_norm(x, p["input_layernorm"]["weight"], eps)
        if "kda" in p:
            x = x + kda_mixer(u, p["kda"], cfg)
        else:
            x = x + attention(u, p["self_attn"], cfg)
        h = plain.rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        x = x + sparse_block(h, p["mlp"], cfg)
    return plain.rms_norm(x, model["norm"]["weight"], eps)


def logits(params, cfg, tokens):
    """``tokens [B, T]`` int -> logits ``[B, T, V]`` float32."""
    return plain.logits(params, cfg, tokens, hidden_states)


def loss(params, cfg, tokens, labels):
    """Mean next-token cross-entropy over ``labels [B, T]``."""
    return plain.loss(params, cfg, tokens, labels, hidden_states)
