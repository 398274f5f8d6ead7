"""Plain reference for the ``granitemoehybrid`` family
(ibm-granite/granite-4.0-h-small), as one chip of an expert-parallel
deployment sees it.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
the recurrence one step at a time in a ``lax.scan``: no kernels, no
cache, no chunking, nothing imported from the program under test (the
norm, attention and feed-forward helpers are the Qwen3 reference's).
Written from the layer equations of the published ``config.json`` and
``modeling_granitemoehybrid.py``; the weights are the program's parameter
tree, read by its leaf names.

Model: ``x = embedding_multiplier · E[ids]``; for each layer
``x += residual_multiplier · mixer(RMSNorm(x))``, then with ``h =
RMSNorm(x)``: ``x += residual_multiplier · (experts(h) + shared(h))``; a
final RMSNorm; ``logits = (h @ E^T) / logits_scaling`` on the embedding
table (``tie_word_embeddings``). No bias but the convolution's.

**Mamba-2 mixer** on ``u`` (``layer_types[i] == "mamba"``), with ``Di =
mamba_expand · hidden = H · P`` channels in ``H`` heads, ``N`` state
numbers a channel and ``G`` groups: ``[z, xBC, dt] = u W_in`` (widths
``Di``, ``Di + 2 G N``, ``H``); ``xBC = silu(conv(xBC) + bias)``,
depthwise and causal, the last tap on the current token; ``[x, B, C] =
xBC`` (``Di``, ``G N``, ``G N``); ``dt = softplus(dt + dt_bias)`` and ``A
= -exp(A_log)``, one number a head; for head ``h`` of group ``g``:
``S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] ⊗ B_t[g]`` from
``S_0 = 0``, ``y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]``; ``out =
RMSNorm_Di(y · silu(z)) W_out``, the norm over all ``Di`` channels with
its weight applied after.

**Attention** (``"attention"``, one layer in ten): grouped-query, no
bias, no q/k norm, **no rotation** (``position_embedding_type: nope``),
causal softmax at ``attention_multiplier`` (1/128, not ``head_dim **
-0.5``).

**Experts**, in every layer: ``l = h W_r`` over the router's ``R``
experts in float32; the ``num_experts_per_tok`` largest; weights
``softmax`` over those logits alone; expert ``e``: ``(silu(h W_g) · (h
W_u)) W_d``; the shared expert the same at its own width, ungated, added
once.

**The share.** The tree holds ``E`` of the router's ``R`` experts (both
read from its shapes), those from ``first_held_expert`` on (a key of the
file; 0 where absent), and a slice of the vocabulary (the table's own
rows). The router scores and chooses over all ``R``; only the held
experts are evaluated, each densely over every token with a zero weight
where it was not chosen. What the absent experts would add is left out:
it is computed on the chips that hold them, and by neither program nor
reference. The shared expert is whole.

Which layer is which, and every size of the mixer, is read from the
tree: a layer holds a ``mamba`` sub-tree or a ``self_attn`` one; ``H``
is the length of ``A_log``, ``Di`` of the gated norm's weight, the
kernel's taps and ``Di + 2 G N`` the shape of ``conv1d``. Where the
sizes it is given carry the family's own keys (the configuration file,
at the real size) it asserts that the tree agrees with every one.
``build.hf_view`` at the tiny size carries none of them: the four
multipliers are then the published ones (12, 0.22, 16, 0.0078125) and
``G`` is 1.

Departures from the published code, none of which changes the
mathematics at seeded weights:

- the published experts keep gate and up as one ``input_linear`` of
  twice the width and chunk its output; the tree keeps two matrices;
- the experts are evaluated densely, one at a time (no sort, no gather);
- the published router takes the top-k of the logits and then the
  softmax of those: written so here; the program takes the softmax over
  all experts, the top-k, and renormalises, which is the same numbers;
- ``head_dim`` is not a key of the source: the sizes' own where they
  state one (the file does, under ``assumed``), else ``hidden_size /
  num_attention_heads`` (128), as the published code computes it;
- the published mixer multiplies by the attention mask before the
  in-projection and after the convolution and clamps ``dt`` to
  ``time_step_limit`` (0, inf); the reference is only ever given whole,
  unpadded sequences, and the clamp changes nothing;
- ``mamba_chunk_size`` selects the published chunked kernel for the same
  recurrence; ``rope_theta`` and ``rope_scaling`` rotate nothing under
  ``nope``.
"""

import jax
import jax.numpy as jnp

from . import qwen3_moe as plain

F32 = jnp.float32


def family(cfg: dict) -> dict:
    """The family's constants: the file's where it has them."""
    return {
        "embedding": cfg.get("embedding_multiplier", 12),
        "residual": cfg.get("residual_multiplier", 0.22),
        "logits": cfg.get("logits_scaling", 16),
        "attention": cfg.get("attention_multiplier", 0.0078125),
        "groups": cfg.get("mamba_n_groups", 1),
    }


def layer_kinds(model: dict, n_layers: int) -> list[str]:
    return [
        "mamba" if "mamba" in model[f"layers_{i}"] else "attention"
        for i in range(n_layers)
    ]


def check_sizes(params: dict, cfg: dict) -> None:
    """The tree against the family's keys, where ``cfg`` has them."""
    if "layer_types" not in cfg:
        return
    model = params["model"]
    n, e = cfg["num_hidden_layers"], cfg["hidden_size"]
    assert layer_kinds(model, n) == cfg["layer_types"][:n]
    assert cfg["tie_word_embeddings"] and "lm_head" not in params
    assert cfg["position_embedding_type"] == "nope"
    assert not cfg["attention_bias"] and not cfg["mamba_proj_bias"]
    assert cfg["mamba_conv_bias"] and cfg["hidden_act"] == "silu"
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim", e // h)
    heads, p, state = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                       cfg["mamba_d_state"])
    d_inner = cfg["mamba_expand"] * e
    assert d_inner == heads * p
    conv = d_inner + 2 * cfg["mamba_n_groups"] * state
    published = cfg.get("share", {}).get("published", {})
    routed = published.get("num_local_experts", cfg["num_local_experts"])
    for i in range(n):
        layer = model[f"layers_{i}"]
        if "mamba" in layer:
            m = layer["mamba"]
            assert m["in_proj"]["kernel"].shape == (e, d_inner + conv + heads)
            assert m["conv1d"]["weight"].shape == (conv, cfg["mamba_d_conv"])
            assert m["conv1d"]["bias"].shape == (conv,)
            for leaf in ("A_log", "D", "dt_bias"):
                assert m[leaf].shape == (heads,), leaf
            assert m["norm"]["weight"].shape == (d_inner,)
            assert m["out_proj"]["kernel"].shape == (d_inner, e)
        else:
            attn = layer["self_attn"]
            assert attn["q_proj"]["kernel"].shape == (e, h * d)
            assert attn["k_proj"]["kernel"].shape == (e, hkv * d)
            assert attn["v_proj"]["kernel"].shape == (e, hkv * d)
            assert attn["o_proj"]["kernel"].shape == (h * d, e)
            assert "q_norm" not in attn and "bias" not in attn["q_proj"]
        mlp = layer["mlp"]
        assert mlp["router"]["gate"]["kernel"].shape == (e, routed)
        assert mlp["grouped_experts"]["gate_proj"].shape == (
            cfg["num_local_experts"], e, cfg["intermediate_size"])
        shared = mlp["shared_expert_module"]
        assert "gate" not in shared
        assert shared["expert"]["gate_proj"]["kernel"].shape == (
            e, cfg["shared_intermediate_size"])
    assert model["embed_tokens"]["embedding_default"].shape == (
        cfg["vocab_size"], e)


def mamba2_mixer(u, p, cfg):
    heads = p["A_log"].shape[0]
    d_inner = p["norm"]["weight"].shape[0]
    head_dim = d_inner // heads
    conv_w = p["conv1d"]["weight"].astype(F32)  # [Di + 2 G N, K]
    k = conv_w.shape[1]
    groups = family(cfg)["groups"]
    n = (conv_w.shape[0] - d_inner) // (2 * groups)
    batch, t, _ = u.shape

    zxbcdt = u @ p["in_proj"]["kernel"].astype(F32)
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + conv_w.shape[0]]
    dt = zxbcdt[..., d_inner + conv_w.shape[0]:]
    assert dt.shape[-1] == heads

    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(padded[:, j:j + t] * conv_w[:, j] for j in range(k))
    xbc = jax.nn.silu(xbc + p["conv1d"]["bias"].astype(F32))
    x = xbc[..., :d_inner].reshape(batch, t, heads, head_dim)
    per_head = lambda v: jnp.repeat(  # noqa: E731
        v.reshape(batch, t, groups, n), heads // groups, axis=2)
    b = per_head(xbc[..., d_inner:d_inner + groups * n])
    c = per_head(xbc[..., d_inner + groups * n:])

    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))  # [B, T, H]
    a = -jnp.exp(p["A_log"].astype(F32))  # [H]

    def step(state, inputs):
        dt_t, x_t, b_t, c_t = inputs  # [B, H], [B, H, P], [B, H, N] x 2
        state = (
            jnp.exp(dt_t * a)[..., None, None] * state
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        )
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    time_major = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731
    s0 = jnp.zeros((batch, heads, head_dim, n), F32)
    _, y = jax.lax.scan(
        step, s0, (time_major(dt), time_major(x), time_major(b),
                   time_major(c)),
    )
    y = time_major(y) + p["D"].astype(F32)[:, None] * x
    gated = y.reshape(batch, t, d_inner) * jax.nn.silu(z)
    normed = plain.rms_norm(gated, p["norm"]["weight"], cfg["rms_norm_eps"])
    return normed @ p["out_proj"]["kernel"].astype(F32)


def attention(u, p, cfg):
    b, t, e = u.shape
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim", e // h)
    q = (u @ p["q_proj"]["kernel"].astype(F32)).reshape(b, t, h, d)
    k = (u @ p["k_proj"]["kernel"].astype(F32)).reshape(b, t, hkv, d)
    v = (u @ p["v_proj"]["kernel"].astype(F32)).reshape(b, t, hkv, d)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    out = plain.causal_attention(q, k, v, family(cfg)["attention"])
    return out.reshape(b, t, h * d) @ p["o_proj"]["kernel"].astype(F32)


def routing_weights(x, router, cfg):
    """``x [N, D]`` -> ``[N, R]``: the softmax of the chosen experts'
    logits at those experts, zero elsewhere."""
    logits = x @ router["gate"]["kernel"].astype(F32)
    top, chosen = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, chosen].set(
        jax.nn.softmax(top, axis=-1))


def routed_experts(x, p, cfg):
    """The held experts' part of the routed output, ``x [B, T, D]``."""
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
    fam, eps = family(cfg), cfg["rms_norm_eps"]
    table = model["embed_tokens"]["embedding_default"]
    x = fam["embedding"] * table[tokens].astype(F32)
    for layer in range(cfg["num_hidden_layers"]):
        p = model[f"layers_{layer}"]
        u = plain.rms_norm(x, p["input_layernorm"]["weight"], eps)
        if "mamba" in p:
            mixed = mamba2_mixer(u, p["mamba"], cfg)
        else:
            mixed = attention(u, p["self_attn"], cfg)
        x = x + fam["residual"] * mixed
        h = plain.rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        x = x + fam["residual"] * sparse_block(h, p["mlp"], cfg)
    return plain.rms_norm(x, model["norm"]["weight"], eps)


def logits(params, cfg, tokens):
    """``tokens [B, T]`` int -> logits ``[B, T, V]`` float32."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, cfg, tokens)
        table = params["model"]["embed_tokens"]["embedding_default"]
        return h @ table.astype(F32).T / family(cfg)["logits"]


def loss(params, cfg, tokens, labels):
    """Mean next-token cross-entropy over ``labels [B, T]``."""
    logp = jax.nn.log_softmax(logits(params, cfg, tokens), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)
