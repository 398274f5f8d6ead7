"""Plain reference for the ``laguna`` family (poolside/Laguna-XS.2), as
one chip of an expert-parallel job sees it.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
no kernels, no cache, nothing imported from the program under test (the
norm, rotate-half, SwiGLU, head and loss helpers are the Qwen3
reference's). Written from the layer equations of the published
``config.json``; the weights are the program's parameter tree, read by
its leaf names.

Layer ``l``: ``h = x + Attn_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``;
RMSNorm with weights ``w`` (not ``1 + w``) and epsilon ``rms_norm_eps``; a
final RMSNorm; an untied head. No bias anywhere.

**Attention**, two kinds in one stack (``layer_types[l]``). With ``n =
RMSNorm(x)``: ``q = n W_q`` as ``H`` heads of 128, ``k = n W_k`` and ``v =
n W_v`` as 8 heads of 128, ``H = num_attention_heads_per_layer[l]``: 48 in
a ``full_attention`` layer, 64 in a ``sliding_attention`` one. The first
``r`` numbers of every head of q and k are rotated in pairs ``(i, i +
r/2)`` (rotate-half), the rest kept (``rope_parameters`` of the kind):

- full: ``r = 64`` (``partial_rotary_factor`` 0.5), frequencies ``f_i =
  theta ** (-2 i / r)`` at ``theta`` 500,000 under YaRN: ``f_i`` where ``i``
  is below ``low``, ``f_i / factor`` where it is above ``high``, blended
  linearly between, with ``low`` and ``high`` the (floored, ceiled)
  indices whose wavelengths turn ``beta_fast`` 64 and ``beta_slow`` 1
  times in ``original_max_position_embeddings`` 4,096 positions; cos and
  sin times ``attention_factor``. The blend does not depend on the
  position, so it holds at 4,096 positions as at 262,144.
- sliding: ``r = 128``, ``theta`` 10,000, no scaling.

``s_ij = q_i . k_j / sqrt(128)`` for ``j <= i``, in a sliding layer only
for ``i - sliding_window < j``; softmax in float32; query head ``h`` on
key/value head ``h // (H / 8)``. Every head's output is gated, ``g =
sigmoid(n W_g)`` with ``W_g`` in ``R^{d x H}`` (``gating``: one logit a
head), ``o = concat_h(g_h a_h) W_o``.

**Feed-forward** on ``m = RMSNorm(h)``. A dense SwiGLU where
``mlp_layer_types[l]`` is ``dense`` (layer 0). Elsewhere ``p = softmax(m
W_r)`` in float32 over all ``num_experts`` published, the
``num_experts_per_tok`` largest taken, their weights ``p_e`` over the sum
of those taken; ``y = moe_routed_scaling_factor x sum_e w_e E_e(m) +
S(m)``, ``E_e`` and the shared ``S`` SwiGLUs of width 512, ``S`` ungated.

**The share.** The tree holds ``E`` of the router's ``R`` experts (both
read from its shapes), those from ``first_held_expert`` on (a key of the
file; 0 where absent), and a slice of the vocabulary (the table's own
rows). The router scores, chooses and renormalises over all ``R``; only
the held experts are evaluated, each densely over every token with a zero
weight where it was not chosen. What the absent experts would add is left
out: it is computed on the chips that hold them, and by neither program
nor reference. The shared expert is every chip's, and is computed whole.

A layer's kind is read from the tree: a full layer's ``q_proj`` is
``num_attention_heads`` heads wide (the file's plain key), a sliding
layer's is not; a sparse layer's feed-forward holds a ``router``. Where
the sizes it is given carry the family's own keys (the configuration
file, at the real size) it asserts that the tree agrees with every one.
``build.hf_view`` at the tiny size carries none of them: the constants
are then the published ones (the two rotations, 2.5) and the window is
``TINY_WINDOW``, the tiny preset's, which nothing in the tree or the view
can say.

Assumptions, each also under ``assumed`` in the configuration file: the
router's score is a softmax with renormalised top-k weights; the shared
expert has no gate; no q/k norm; the gate is one logit a head. Departures
from a published implementation, none of which changes the mathematics:
the experts are evaluated densely; queries are taken in blocks of
``QUERY_BLOCK`` so that 64 heads at 4,096 positions fit beside the
Trainer's state (each block sees every key under a dense mask).
"""

import math

import jax
import jax.numpy as jnp

from . import qwen3_moe as plain

F32 = jnp.float32

# the tiny preset's window (d9d_tpu.models.laguna.laguna_tiny)
TINY_WINDOW = 16
QUERY_BLOCK = 512

# ``rope_parameters`` and ``moe_routed_scaling_factor`` as published: what
# the tiny preset runs too
PUBLISHED = {
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5,
        },
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1,
        },
    },
    "moe_routed_scaling_factor": 2.5,
}


def family(cfg: dict) -> dict:
    """The family's constants: the file's where it has them."""
    return {
        "window": cfg.get("sliding_window", TINY_WINDOW),
        "rope": cfg.get("rope_parameters", PUBLISHED["rope_parameters"]),
        "routed_scale": cfg.get(
            "moe_routed_scaling_factor",
            PUBLISHED["moe_routed_scaling_factor"],
        ),
    }


def query_heads(attn: dict, cfg: dict) -> int:
    return attn["q_proj"]["kernel"].shape[1] // cfg["head_dim"]


def layer_type(attn: dict, cfg: dict) -> str:
    full = query_heads(attn, cfg) == cfg["num_attention_heads"]
    return "full_attention" if full else "sliding_attention"


def check_sizes(params: dict, cfg: dict) -> None:
    """The tree against the family's keys, where ``cfg`` has them."""
    if "layer_types" not in cfg:
        return
    model = params["model"]
    n, e, d = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["head_dim"]
    hkv = cfg["num_key_value_heads"]
    assert cfg["gating"] and not cfg["attention_bias"]
    assert not cfg["tie_word_embeddings"]
    assert not cfg["moe_apply_router_weight_on_input"]
    published = cfg.get("share", {}).get("published", {})
    routed = published.get("num_experts", cfg["num_experts"])
    for i in range(n):
        p = model[f"layers_{i}"]
        attn, mlp = p["self_attn"], p["mlp"]
        h = cfg["num_attention_heads_per_layer"][i]
        assert layer_type(attn, cfg) == cfg["layer_types"][i], i
        assert attn["q_proj"]["kernel"].shape == (e, h * d)
        assert attn["k_proj"]["kernel"].shape == (e, hkv * d)
        assert attn["v_proj"]["kernel"].shape == (e, hkv * d)
        assert attn["gate_proj"]["kernel"].shape == (e, h)
        assert attn["o_proj"]["kernel"].shape == (h * d, e)
        assert "q_norm" not in attn and "bias" not in attn["q_proj"]
        assert ("router" in mlp) == (cfg["mlp_layer_types"][i] == "sparse"), i
        if "router" not in mlp:
            assert mlp["gate_proj"]["kernel"].shape == (
                e, cfg["intermediate_size"])
            continue
        assert mlp["router"]["gate"]["kernel"].shape == (e, routed)
        assert mlp["grouped_experts"]["gate_proj"].shape == (
            cfg["num_experts"], e, cfg["moe_intermediate_size"])
        shared = mlp["shared_expert_module"]
        assert "gate" not in shared
        assert shared["expert"]["gate_proj"]["kernel"].shape == (
            e, cfg["shared_expert_intermediate_size"])
    assert params["lm_head"]["head_default"].shape == (cfg["vocab_size"], e)
    assert model["embed_tokens"]["embedding_default"].shape == (
        cfg["vocab_size"], e)


def rotary(law: dict, head_dim: int):
    """``(rotated width r, frequencies [r / 2], factor on cos and sin)``
    of one kind's ``rope_parameters``."""
    r = int(law["partial_rotary_factor"] * head_dim)
    theta = law["rope_theta"]
    freq = plain.rotary_inv_freq(r, theta)
    if law["rope_type"] == "default":
        return r, freq, 1.0
    assert law["rope_type"] == "yarn"

    def index_turning(turns: float) -> float:
        """The (real) index whose wavelength turns ``turns`` times in the
        original context."""
        span = law["original_max_position_embeddings"] / (2 * math.pi * turns)
        return r * math.log(span) / (2 * math.log(theta))

    low = max(math.floor(index_turning(law["beta_fast"])), 0)
    high = min(math.ceil(index_turning(law["beta_slow"])), r // 2 - 1)
    blend = jnp.clip(
        (jnp.arange(r // 2, dtype=F32) - low) / max(high - low, 1e-3), 0, 1
    )
    freq = freq * (1 - blend) + freq / law["factor"] * blend
    return r, freq, law["attention_factor"]


def attention(x, p, cfg, positions):
    b, t, _ = x.shape
    fam = family(cfg)
    d, hkv = cfg["head_dim"], cfg["num_key_value_heads"]
    h = query_heads(p, cfg)
    kind = layer_type(p, cfg)
    r, freq, factor = rotary(fam["rope"][kind], d)

    q = (x @ p["q_proj"]["kernel"].astype(F32)).reshape(b, t, h, d)
    k = (x @ p["k_proj"]["kernel"].astype(F32)).reshape(b, t, hkv, d)
    v = (x @ p["v_proj"]["kernel"].astype(F32)).reshape(b, t, hkv, d)

    def partly_rotated(u):
        return jnp.concatenate(
            [plain.rotate(u[..., :r], positions, freq, factor), u[..., r:]],
            axis=-1,
        )

    q, k = partly_rotated(q), partly_rotated(k)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)

    def attend(block):
        """One block of queries ``(q [B, n, H, D], their positions [n])``
        against every key, under a dense mask."""
        q_block, i = block
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) * d ** -0.5
        i, j = i[:, None], positions[None, :]
        seen = j <= i
        if kind == "sliding_attention":
            seen &= i - fam["window"] < j
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v
        )

    n = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = jax.lax.map(attend, (
        jnp.moveaxis(q.reshape(b, t // n, n, h, d), 1, 0),
        positions.reshape(t // n, n),
    ))
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, t, h, d)
    gate = jax.nn.sigmoid(x @ p["gate_proj"]["kernel"].astype(F32))
    out = (out * gate[..., None]).reshape(b, t, h * d)
    return out @ p["o_proj"]["kernel"].astype(F32)


def routing_weights(flat, router, cfg):
    """``flat [N, C]`` -> ``[N, R]``: each token's weight on each of the
    router's ``R`` experts, zero where it was not chosen."""
    probs = jax.nn.softmax(flat @ router["gate"]["kernel"].astype(F32), -1)
    top_p, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(flat.shape[0])[:, None], top_i
    ].set(top_p)


def dense_swiglu(x, p):
    return plain.swiglu(
        x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
        p["down_proj"]["kernel"],
    )


def routed_part(x, p, cfg, first=None):
    """The held experts' part of the routed output, scaled: the experts
    of the tree, which the router knows as ``first`` onwards."""
    b, t, c = x.shape
    flat = x.reshape(b * t, c)
    weights = routing_weights(flat, p["router"], cfg)
    experts = p["grouped_experts"]
    held = experts["gate_proj"].shape[0]
    first = cfg.get("first_held_expert", 0) if first is None else first
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
    return family(cfg)["routed_scale"] * routed.reshape(b, t, c)


def shared_part(x, p):
    return dense_swiglu(x, p["shared_expert_module"]["expert"])


def feed_forward(x, p, cfg):
    if "router" in p:
        return routed_part(x, p, cfg) + shared_part(x, p)
    return dense_swiglu(x, p)


def hidden_states(params, cfg, tokens):
    check_sizes(params, cfg)
    model = params["model"]
    eps = cfg["rms_norm_eps"]
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
