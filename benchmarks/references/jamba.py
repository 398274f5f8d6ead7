"""Plain reference for the ``jamba`` family (ai21labs/AI21-Jamba2-3B).

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
no kernels, no cache, no chunking, nothing imported from the program
under test (the norm, attention, feed-forward and loss helpers are the
Qwen3 reference's). The layer equations are those of the published
``modeling_jamba.py``, written down here from them; the weights are the
program's parameter tree, read by its leaf names.

Layer ``i``: ``x += mixer_i(RMSNorm(x))``, then ``x += SwiGLU(RMSNorm(x))``
(``num_experts`` 1: a dense feed-forward in every layer); a final
RMSNorm; logits ``h @ E^T`` on the embedding table ``E``
(``tie_word_embeddings``).

Mamba-1 mixer on ``u``: ``[xs, z] = u W_in``; ``xs = silu(conv(xs) +
bias)``, depthwise and causal, the last tap on the current token;
``[dt_low, B, C] = xs W_x``, each through its own RMSNorm (the family's
``dt_layernorm``, ``b_layernorm``, ``c_layernorm``); ``dt =
softplus(dt_low W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(dt_t A)
h_{t-1} + dt_t xs_t B_t`` from ``h_0 = 0``, one step at a time in a
``lax.scan``; ``y_t = h_t C_t + D xs_t``; ``out = (y silu(z)) W_out``.

Attention (one layer a period): multi-query, no bias, no q/k norm, **no
rotation** (the family uses no positional encoding), causal softmax at
``head_dim ** -0.5``.

Which layer is which, and every size of the mixer, is read from the
tree: a layer holds a ``mamba`` sub-tree or a ``self_attn`` one;
``d_inner``, ``d_state``, ``dt_rank`` and the kernel width are the
shapes of ``A_log``, ``dt_proj`` and ``conv1d``; the head is tied when
the tree has no ``lm_head``. Where the sizes it is given carry the
family's own keys (the configuration file, at the real size; the tiny
size's view carries none of them) it asserts that the tree agrees with
every one.

Departures from the published code, none of which changes the
mathematics at seeded weights:

- ``head_dim`` is not a key of the source: ``hidden_size /
  num_attention_heads`` (128), as the published code computes it;
- the published code multiplies by the attention mask after ``W_in`` and
  after the convolution; the reference is only ever given whole,
  unpadded sequences and has no mask;
- ``use_mamba_kernels`` selects fused CUDA kernels for the same
  equations, ``num_logits_to_keep`` and ``sliding_window: null`` change
  nothing here, and the expert period and offset select nothing at
  ``num_experts`` 1.
"""

import jax
import jax.numpy as jnp

from . import qwen3_moe as plain

F32 = jnp.float32


def layer_kinds(model: dict, n_layers: int) -> list[str]:
    return [
        "mamba" if "mamba" in model[f"layers_{i}"] else "attention"
        for i in range(n_layers)
    ]


def check_sizes(params: dict, cfg: dict) -> None:
    """The tree against the family's keys, where ``cfg`` has them."""
    if "attn_layer_period" not in cfg:
        return
    model = params["model"]
    n = cfg["num_hidden_layers"]
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    assert layer_kinds(model, n) == [
        "attention" if i % period == offset else "mamba" for i in range(n)
    ]
    assert ("lm_head" not in params) == cfg["tie_word_embeddings"]
    assert cfg["num_experts"] == 1 and not cfg["mamba_proj_bias"]
    e, inter = cfg["hidden_size"], cfg["intermediate_size"]
    d_inner = cfg["mamba_expand"] * e
    for i in range(n):
        p = model[f"layers_{i}"]
        assert p["mlp"]["gate_proj"]["kernel"].shape == (e, inter)
        if "mamba" not in p:
            continue
        m = p["mamba"]
        assert m["in_proj"]["kernel"].shape == (e, 2 * d_inner)
        assert m["A_log"].shape == (d_inner, cfg["mamba_d_state"])
        assert m["dt_proj"]["kernel"].shape == (cfg["mamba_dt_rank"], d_inner)
        assert m["conv1d"]["weight"].shape == (d_inner, cfg["mamba_d_conv"])
        assert ("bias" in m["conv1d"]) == cfg["mamba_conv_bias"]


def mamba_mixer(u, p, eps):
    a_log = p["A_log"].astype(F32)
    d_inner, n = a_log.shape
    rank = p["dt_proj"]["kernel"].shape[0]
    conv_w = p["conv1d"]["weight"].astype(F32)  # [d_inner, K]
    k = conv_w.shape[1]
    t = u.shape[1]

    xz = u @ p["in_proj"]["kernel"].astype(F32)
    xs, z = xz[..., :d_inner], xz[..., d_inner:]
    padded = jnp.pad(xs, ((0, 0), (k - 1, 0), (0, 0)))
    xs = sum(padded[:, j:j + t] * conv_w[:, j] for j in range(k))
    xs = jax.nn.silu(xs + p["conv1d"]["bias"].astype(F32))

    low = xs @ p["x_proj"]["kernel"].astype(F32)
    dt_low = plain.rms_norm(
        low[..., :rank], p["dt_layernorm"]["weight"], eps)
    b = plain.rms_norm(
        low[..., rank:rank + n], p["b_layernorm"]["weight"], eps)
    c = plain.rms_norm(low[..., rank + n:], p["c_layernorm"]["weight"], eps)
    dt = jax.nn.softplus(
        dt_low @ p["dt_proj"]["kernel"].astype(F32)
        + p["dt_proj"]["bias"].astype(F32)
    )
    a = -jnp.exp(a_log)  # [d_inner, N]

    def step(h, inputs):
        dt_t, x_t, b_t, c_t = inputs  # [B, d_inner] x 2, [B, N] x 2
        h = (jnp.exp(dt_t[..., None] * a) * h
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    time_major = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731
    h0 = jnp.zeros((u.shape[0], d_inner, n), F32)
    _, y = jax.lax.scan(
        step, h0, (time_major(dt), time_major(xs), time_major(b),
                   time_major(c)),
    )
    y = time_major(y) + p["D"].astype(F32) * xs
    return (y * jax.nn.silu(z)) @ p["out_proj"]["kernel"].astype(F32)


def attention(u, p, cfg):
    b, t, e = u.shape
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = e // h
    q = (u @ p["q_proj"]["kernel"].astype(F32)).reshape(b, t, h, d)
    k = (u @ p["k_proj"]["kernel"].astype(F32)).reshape(b, t, hkv, d)
    v = (u @ p["v_proj"]["kernel"].astype(F32)).reshape(b, t, hkv, d)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    out = plain.causal_attention(q, k, v, d ** -0.5).reshape(b, t, h * d)
    return out @ p["o_proj"]["kernel"].astype(F32)


def hidden_states(params, cfg, tokens):
    model = params["model"]
    eps = cfg["rms_norm_eps"]
    x = model["embed_tokens"]["embedding_default"][tokens].astype(F32)
    for layer in range(cfg["num_hidden_layers"]):
        p = model[f"layers_{layer}"]
        u = plain.rms_norm(x, p["input_layernorm"]["weight"], eps)
        if "mamba" in p:
            x = x + mamba_mixer(u, p["mamba"], eps)
        else:
            x = x + attention(u, p["self_attn"], cfg)
        mlp = p["mlp"]
        x = x + plain.swiglu(
            plain.rms_norm(x, p["post_attention_layernorm"]["weight"], eps),
            mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
            mlp["down_proj"]["kernel"],
        )
    return plain.rms_norm(x, model["norm"]["weight"], eps)


def logits(params, cfg, tokens):
    """``tokens [B, T]`` int -> logits ``[B, T, V]`` float32."""
    check_sizes(params, cfg)
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, cfg, tokens)
        if "lm_head" in params:
            table = params["lm_head"]["head_default"]
        else:
            table = params["model"]["embed_tokens"]["embedding_default"]
        return h @ table.astype(F32).T


def loss(params, cfg, tokens, labels):
    """Mean next-token cross-entropy over ``labels [B, T]``."""
    logp = jax.nn.log_softmax(logits(params, cfg, tokens), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)
