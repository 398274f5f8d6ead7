"""Plain reference for the ``mimo_v2_flash`` family
(XiaomiMiMo/MiMo-V2-Flash), as one chip of an expert-parallel deployment
sees it.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
no kernels, no cache, nothing imported from the program under test (the
norm, rotation, feed-forward, head and loss helpers are the Qwen3
reference's, the router and the held share the Xing4.0 reference's).
Written from the layer equations of the published ``config.json`` and
the family's description; the weights are the program's parameter tree,
read by its leaf names.

Layer ``l``: ``h = x + Attn_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``;
a final RMSNorm; an untied head. No bias anywhere.

**Attention**, two kinds in one stack (``hybrid_layer_pattern[l]``: 0
full, 1 window). Both: ``q = x W_q`` as 64 heads of 192, ``k = x W_k`` as
``H_kv`` heads of 192, ``v = attention_value_scale x W_v`` as ``H_kv``
heads of 128 (``v_head_dim``); the first 64 of the 192 numbers of q and
k (``partial_rotary_factor`` 0.334 x 192, truncated) rotated in pairs
``(i, i + 32)`` at frequencies ``theta ** (-2 i / 64)``, the other 128 as
they are; ``a_ij = q_i . k_j / sqrt(192)``; output ``concat_h(o_h) W_o``.
Full: ``H_kv`` 4, theta ``rope_theta``, key ``j`` visible to query ``i``
iff ``j <= i``. Window: ``H_kv`` 8, theta ``swa_rope_theta``, visible iff
``i - sliding_window < j <= i``, and a learned scalar ``s_h`` a query head
that joins the softmax's denominator only:
``p_ij = exp(a_ij) / (sum_j' exp(a_ij') + exp(s_h))``.

**Feed-forward.** A dense SwiGLU where ``moe_layer_freq[l]`` is 0 (layer
0). Elsewhere ``n_routed_experts`` experts, no shared one: ``s =
sigmoid(x W_r)`` in float32, the ``num_experts_per_tok`` experts with the
largest ``s + b`` (``noaux_tc``, ``n_group`` 1), weights ``s`` at those
divided by their sum + 1e-20 (``norm_topk_prob``), no further scale
(``routed_scaling_factor`` null).

**The share.** The tree holds ``E`` of the router's ``R`` experts (both
read from its shapes), those from ``first_held_expert`` on (a key of the
file; 0 where absent), and a slice of the vocabulary (the table's own
rows). The router scores, chooses and renormalises over all ``R``; only
the held experts are evaluated, each densely over every token with a
zero weight where it was not chosen. What the absent experts would add
is left out: it is computed on the chips that hold them, and by neither
program nor reference.

Which layer is which, and each kind's sizes, are read from the tree: a
window layer's attention holds a ``sinks`` leaf; ``H_kv`` and the value
width are the shapes of ``k_proj`` and ``v_proj``; a sparse layer's
feed-forward holds a ``router``. Where the sizes it is given carry the
family's own keys (the configuration file, at the real size) it asserts
that the tree agrees with every one. ``build.hf_view`` at the tiny size
carries none of them: the constants are then the published ones (0.707,
0.334, ``swa_rope_theta`` 10,000) and the window is ``TINY_WINDOW``, the
tiny preset's, which nothing in the tree or the view can say.

Assumptions, each also under ``assumed`` in the configuration file: no
q/k norm (the config has no key for one); the value scale multiplies v
in both kinds; rotation in pairs ``(i, i + 32)`` on the first 64 numbers
(rotate_half); ``attention_chunk_size`` 128 is the window restated for
an engine, not a second, chunk-local mask; ``b`` is float32. Departures
from the published code, none of which changes the mathematics at seeded
weights: the experts are evaluated densely; the multi-token-prediction
layers the family ships are not built (no key of the catalog's config,
and they do not feed the next-token logits).
"""

import jax
import jax.numpy as jnp

from . import qwen3_moe as plain
from . import xing4_0

F32 = jnp.float32

# the tiny preset's window (d9d_tpu.models.mimo.mimo_v2_flash_tiny)
TINY_WINDOW = 6


def family(cfg: dict) -> dict:
    """The family's constants: the file's where it has them."""
    return {
        "eps": cfg.get("layernorm_epsilon", cfg.get("rms_norm_eps")),
        "window": cfg.get("sliding_window", TINY_WINDOW),
        "swa_theta": cfg.get("swa_rope_theta", 10_000),
        "value_scale": cfg.get("attention_value_scale", 0.707),
        "rotary_factor": cfg.get("partial_rotary_factor", 0.334),
    }


def is_window(attn: dict) -> bool:
    return "sinks" in attn


def kv_heads(attn: dict, cfg: dict) -> int:
    return attn["k_proj"]["kernel"].shape[1] // cfg["head_dim"]


def check_sizes(params: dict, cfg: dict) -> None:
    """The tree against the family's keys, where ``cfg`` has them."""
    if "hybrid_layer_pattern" not in cfg:
        return
    model = params["model"]
    n, e = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, d, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    assert (cfg["swa_num_attention_heads"], cfg["swa_head_dim"],
            cfg["swa_v_head_dim"]) == (h, d, dv)
    assert cfg["sliding_window"] == cfg["sliding_window_size"]
    assert cfg["add_swa_attention_sink_bias"]
    assert not cfg["add_full_attention_sink_bias"]
    assert not cfg["attention_bias"] and not cfg["tie_word_embeddings"]
    assert cfg["scoring_func"] == "sigmoid" and cfg["n_group"] == 1
    assert cfg["n_shared_experts"] is None
    assert cfg["routed_scaling_factor"] is None and cfg["norm_topk_prob"]
    published = cfg.get("share", {}).get("published", {})
    routed = published.get("n_routed_experts", cfg["n_routed_experts"])
    for i in range(n):
        p = model[f"layers_{i}"]
        attn, mlp = p["self_attn"], p["mlp"]
        assert is_window(attn) == bool(cfg["hybrid_layer_pattern"][i]), i
        hkv = cfg[
            "swa_num_key_value_heads" if is_window(attn)
            else "num_key_value_heads"
        ]
        assert attn["q_proj"]["kernel"].shape == (e, h * d)
        assert attn["k_proj"]["kernel"].shape == (e, hkv * d)
        assert attn["v_proj"]["kernel"].shape == (e, hkv * dv)
        assert attn["o_proj"]["kernel"].shape == (h * dv, e)
        assert "q_norm" not in attn and "bias" not in attn["q_proj"]
        if is_window(attn):
            assert attn["sinks"].shape == (h,)
        assert ("router" in mlp) == bool(cfg["moe_layer_freq"][i]), i
        if "router" not in mlp:
            assert mlp["gate_proj"]["kernel"].shape == (
                e, cfg["intermediate_size"])
            continue
        assert "shared_expert_module" not in mlp
        assert mlp["router"]["gate"]["kernel"].shape == (e, routed)
        assert mlp["router"]["e_score_correction_bias"].dtype == F32
        assert mlp["grouped_experts"]["gate_proj"].shape == (
            cfg["n_routed_experts"], e, cfg["moe_intermediate_size"])
    assert params["lm_head"]["head_default"].shape == (cfg["vocab_size"], e)
    assert model["embed_tokens"]["embedding_default"].shape == (
        cfg["vocab_size"], e)


def attention(x, p, cfg, positions):
    b, t, _ = x.shape
    fam = family(cfg)
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    hkv = kv_heads(p, cfg)
    dv = p["v_proj"]["kernel"].shape[1] // hkv
    window = is_window(p)
    theta = fam["swa_theta"] if window else cfg["rope_theta"]
    rot = int(fam["rotary_factor"] * d)

    q = (x @ p["q_proj"]["kernel"].astype(F32)).reshape(b, t, h, d)
    k = (x @ p["k_proj"]["kernel"].astype(F32)).reshape(b, t, hkv, d)
    v = (x @ p["v_proj"]["kernel"].astype(F32)).reshape(b, t, hkv, dv)
    v = fam["value_scale"] * v

    inv_freq = plain.rotary_inv_freq(rot, theta)

    def partly_rotated(u):
        return jnp.concatenate(
            [plain.rotate(u[..., :rot], positions, inv_freq), u[..., rot:]],
            axis=-1,
        )

    q, k = partly_rotated(q), partly_rotated(k)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    i, j = positions[:, None], positions[None, :]
    seen = j <= i
    if window:
        seen &= i - fam["window"] < j
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    under = 0.0
    if window:
        sink = p["sinks"].astype(F32)[None, :, None, None]
        top = jnp.maximum(top, sink)
        under = jnp.exp(sink - top)
    weights = jnp.exp(scores - top)
    probs = weights / (jnp.sum(weights, axis=-1, keepdims=True) + under)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, h * dv)
    return out @ p["o_proj"]["kernel"].astype(F32)


def sparse_block(x, p, cfg):
    """The held experts' part of the routed output: the Xing4.0
    reference's block (the same ``noaux_tc`` router and the same share),
    with no shared expert and no further scale."""
    return xing4_0.sparse_block(
        x, p, {**cfg, "routed_scaling_factor": 1.0, "n_shared_experts": 0}
    )


def feed_forward(x, p, cfg):
    if "router" in p:
        return sparse_block(x, p, cfg)
    return plain.swiglu(
        x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
        p["down_proj"]["kernel"],
    )


def hidden_states(params, cfg, tokens):
    check_sizes(params, cfg)
    model = params["model"]
    eps = family(cfg)["eps"]
    x = model["embed_tokens"]["embedding_default"][tokens].astype(F32)
    positions = jnp.arange(tokens.shape[1])
    for layer in range(cfg["num_hidden_layers"]):
        p = model[f"layers_{layer}"]
        x = x + attention(
            plain.rms_norm(x, p["input_layernorm"]["weight"], eps),
            p["self_attn"], cfg, positions,
        )
        x = x + feed_forward(
            plain.rms_norm(x, p["post_attention_layernorm"]["weight"], eps),
            p["mlp"], cfg,
        )
    return plain.rms_norm(x, model["norm"]["weight"], eps)


def logits(params, cfg, tokens):
    """``tokens [B, T]`` int -> logits ``[B, T, V]`` float32."""
    return plain.logits(params, cfg, tokens, hidden_states)


def loss(params, cfg, tokens, labels):
    """Mean next-token cross-entropy over ``labels [B, T]``."""
    return plain.loss(params, cfg, tokens, labels, hidden_states)
