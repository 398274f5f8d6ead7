"""Plain reference for the ``zaya`` family (Zyphra/ZAYA1-8B).

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
no kernels, no cache, no batching of experts (a loop over them, each
densely over every token), the convolutions as explicit shifted sums,
nothing imported from the program under test (the norm, the rotation and
the expert are the Qwen3 reference's). Written from the catalog row's
``config.json`` keys and, where those leave a reading open, from the
family's papers (Zyphra, "Compressed Convolutional Attention",
arXiv:2510.04476; the ZAYA1 report, arXiv:2511.17127) and the row's
``described_as``; the weights are the program's parameter tree, read by
its leaf names.

One layer, on the stream ``x [T, E]`` and the previous layer's router
state ``r_prev [T, R]`` (none for layer 0). **(K)** a key of the
configuration fixes it; **(A)** a reading, one function here and one
field of the program's preset (``assumed`` in the configuration file).

Attention sublayer, ``u = RMSNorm(x)`` at ``rms_norm_eps`` (K):

1. ``q0 = u Wq`` (``h`` heads of ``d``), ``k0 = u Wk`` (``g`` heads), no
   bias (K); ``c = [q0 ; k0]``.
2. :func:`first_convolution` (A): depthwise, causal, ``cca_time0`` taps
   (K), with a bias: ``c1[t] = a0 c[t-1] + a1 c[t] + b``, ``c[-1] = 0``.
3. :func:`second_convolution` (A): causal, ``cca_time1`` taps (K),
   grouped by head: ``c2[t, j] = c1[t-1, j] B0_j + c1[t, j] B1_j + b'_j``.
4. :func:`qk_mean` (A): ``m_q[i] = (q0[i] + k0[i // (h/g)]) / 2``, ``m_k[j]``
   the mean of its group's ``m_q``; ``q = c2[:h] + m_q``, ``k = c2[h:] + m_k``.
5. :func:`shifted_values` (A): the first half of the key/value heads take
   ``u[t] Wv``, the second half ``u[t-1] Wv'`` (zero before the first
   token).
6. :func:`normalise` (A): a head, ``q <- sqrt(d) q / |q|``, ``k <- sqrt(d)
   exp(theta_j) k / |k|``, ``theta`` one learned number a key/value head.
7. rotation of the first ``partial_rotary_factor`` of each head at
   ``rope_theta`` (K), pairs ``(i, i + rot/2)``, after 6 (A).
8. causal softmax at ``d ** -0.5``, ``h`` query heads on ``g`` key/value
   heads (K); ``a = heads Wo``.
9. :func:`scaled_residual` (A): ``x <- s_r (x + b_r) + s_h (a + b_h)``.

Expert sublayer, ``y = RMSNorm(x)``:

10. ``z = y W_D + b_D`` (``router_hidden_size`` (K)); :func:`depth_carry`
    (A): ``z <- z + gamma r_prev`` for layer > 0; the layer hands on ``z``.
11. :func:`router_scores` (A): ``s = W3 gelu(W2 gelu(W1 RMSNorm(z) + b1) +
    b2)``, exact GELU, over the experts and one skip.
12. :func:`select` (A): ``p = softmax(s)``; ``e = argmax(p + beta)``,
    ``beta`` the selection-only bias; top-1 (K); the weight is ``p[e]``,
    not renormalised.
13. ``p[e] Wdown_e (silu(y Wgate_e) (y Wup_e))`` (K) for an expert,
    nothing for the skip (:func:`expert_outputs`).
14. :func:`scaled_residual` with its own four vectors.

Then RMSNorm and ``logits = x E^T`` on the tied table (K).

``readings`` turns single readings off for the tests that ask what the
comparison's tolerance can tell (``tests/models/test_zaya.py``): every
entry defaults to the reading above.

Every size is read from the tree (the heads from the configuration's
counts and the projections' shapes, the taps and the router's width from
the weights' shapes); where the sizes it is given carry the family's own
keys (the configuration file, at the real size) it asserts that the tree
agrees with every one. Departures from the published description, none
of which changes the mathematics: experts evaluated densely one at a
time; the l2 norms carry an epsilon of 1e-6 under the root, as the
program's do; ``sliding_window``, ``max_position_embeddings`` and the
``hybrid_sliding`` rotation are read by nothing (no window layer in the
8B).
"""

import jax
import jax.numpy as jnp

from . import qwen3_moe as plain

F32 = jnp.float32

READINGS = {
    "conv1_grouped": True, "qk_mean": True, "value_shift": True,
    "temperature": True, "depth_carry": True, "skip": True,
}
L2_EPS = 1e-6


def family(cfg: dict) -> dict:
    """The family's constants: the file's where it has them (the tiny
    view of ``build.hf_view`` carries ``rope_theta`` and nothing else of
    these)."""
    rope = cfg.get("rope_parameters", {}).get("hybrid", {})
    return {
        "rotary_factor": cfg.get("partial_rotary_factor", 0.5),
        "rope_theta": rope.get("rope_theta", cfg.get("rope_theta")),
    }


def f32(x):
    return jnp.asarray(x).astype(F32)


def shifted(x, by: int = 1):
    """``x [B, T, ...]`` a token late: ``out[t] = x[t - by]``, zero before."""
    pad = [(0, 0)] * x.ndim
    pad[1] = (by, 0)
    return jnp.pad(x, pad)[:, : x.shape[1]]


def first_convolution(c, p):
    """(A) depthwise over every channel, with a bias; the last tap on the
    current token."""
    w = f32(p["conv0"]["weight"])  # [C, K]
    taps = w.shape[1]
    out = sum(shifted(c, taps - 1 - j) * w[:, j] for j in range(taps))
    return out + f32(p["conv0"]["bias"])


def second_convolution(c1, p, groups: int, grouped: bool = True):
    """(A) a ``d x d`` matrix a head a tap. ``grouped`` off: depthwise,
    each matrix's diagonal as the channel's tap (the reading the test
    sets against it)."""
    w = f32(p["conv1_weight"])  # [K, G, D, D]
    taps, _, width, _ = w.shape
    b, t, _ = c1.shape
    heads = c1.reshape(b, t, groups, width)
    if grouped:
        out = sum(
            jnp.einsum("btgd,gde->btge", shifted(heads, taps - 1 - j), w[j])
            for j in range(taps)
        )
    else:
        diagonal = jnp.diagonal(w, axis1=2, axis2=3)  # [K, G, D]
        out = sum(
            shifted(heads, taps - 1 - j) * diagonal[j] for j in range(taps)
        )
    return out.reshape(b, t, -1) + f32(p["conv1_bias"])


def qk_mean(q0, k0):
    """(A) ``q0 [B,T,h,d]``, ``k0 [B,T,g,d]`` -> what is added to the
    convolved queries and keys."""
    h, g = q0.shape[2], k0.shape[2]
    mean_q = 0.5 * (q0 + jnp.repeat(k0, h // g, axis=2))
    mean_k = mean_q.reshape(*q0.shape[:2], g, h // g, -1).mean(axis=3)
    return mean_q, mean_k


def shifted_values(u, p, g: int, shift: bool = True):
    """(A) ``[B, T, g, d]``: the second half of the heads a token late."""
    now = u @ f32(p["v_proj"]["kernel"])
    late = u @ f32(p["v_prev_proj"]["kernel"])
    if shift:
        late = shifted(late)
    b, t, _ = u.shape
    return jnp.concatenate([now, late], axis=-1).reshape(b, t, g, -1)


def normalise(q, k, p, temperature: bool = True):
    """(A) unit heads times ``sqrt(d)``, the keys times ``exp(theta)``."""

    def unit(x):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + L2_EPS
        )

    q, k = unit(q), unit(k)
    if temperature:
        k = k * jnp.exp(f32(p["key_temperature"]))[:, None]
    return q, k


def attention_sublayer(u, p, cfg, positions, readings=READINGS):
    """Steps 1 to 8 on ``u [B, T, E]`` -> ``[B, T, E]``."""
    b, t, _ = u.shape
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    fam = family(cfg)
    q0 = u @ f32(p["q_proj"]["kernel"])
    k0 = u @ f32(p["k_proj"]["kernel"])
    c = jnp.concatenate([q0, k0], axis=-1)
    c2 = second_convolution(
        first_convolution(c, p), p, h + g, readings["conv1_grouped"]
    )
    q = c2[..., : h * d].reshape(b, t, h, d)
    k = c2[..., h * d:].reshape(b, t, g, d)
    if readings["qk_mean"]:
        mean_q, mean_k = qk_mean(
            q0.reshape(b, t, h, d), k0.reshape(b, t, g, d)
        )
        q, k = q + mean_q, k + mean_k
    v = shifted_values(u, p, g, readings["value_shift"])
    q, k = normalise(q, k, p, readings["temperature"])

    rot = int(fam["rotary_factor"] * d)
    inv_freq = plain.rotary_inv_freq(rot, fam["rope_theta"])

    def partly_rotated(x):
        return jnp.concatenate(
            [plain.rotate(x[..., :rot], positions, inv_freq), x[..., rot:]],
            axis=-1,
        )

    q, k = partly_rotated(q), partly_rotated(k)
    k = jnp.repeat(k, h // g, axis=2)
    v = jnp.repeat(v, h // g, axis=2)
    out = plain.causal_attention(q, k, v, d ** -0.5).reshape(b, t, h * d)
    return out @ f32(p["o_proj"]["kernel"])


def scaled_residual(x, branch, p):
    """(A) learned scales and biases on both sides of the addition."""
    return f32(p["stream_scale"]) * (x + f32(p["stream_bias"])) + f32(
        p["branch_scale"]
    ) * (branch + f32(p["branch_bias"]))


def depth_carry(z, carried, p, carry: bool = True):
    """(A) the previous layer's router state joins this layer's."""
    if carried is None or not carry:
        return z
    return z + f32(p["carry_scale"]) * carried


def router_scores(z, p, eps):
    """(A) three small matrices, exact GELU between."""
    a = plain.rms_norm(z, p["norm"]["weight"], eps)
    for name in ("fc1", "fc2"):
        a = jax.nn.gelu(
            a @ f32(p[name]["kernel"]) + f32(p[name]["bias"]),
            approximate=False,
        )
    return a @ f32(p["gate"]["kernel"])


def select(scores, p):
    """(A) softmax over the experts and the skip; top-1 by the scores
    plus the selection-only bias; the weight is the chosen score itself."""
    probs = jax.nn.softmax(scores, axis=-1)
    chosen = jnp.argmax(probs + f32(p["e_score_correction_bias"]), axis=-1)
    weight = jnp.take_along_axis(probs, chosen[..., None], axis=-1)[..., 0]
    return chosen, weight


def expert_outputs(y, experts, chosen, weight, skip: bool = True):
    """Each expert over every token, kept where it was chosen; the id
    past the experts is the skip and adds nothing (``skip`` off: its rows
    go through expert 0, the reading the test sets against it)."""
    n_experts = experts["gate_proj"].shape[0]
    if not skip:
        chosen = jnp.where(chosen == n_experts, 0, chosen)
    out = jnp.zeros_like(y)
    for e in range(n_experts):
        one = plain.swiglu(
            y, experts["gate_proj"][e], experts["up_proj"][e],
            experts["down_proj"][e],
        )
        out = out + jnp.where((chosen == e)[..., None], one, 0.0)
    return out * weight[..., None]


def expert_sublayer(y, carried, p, cfg, readings=READINGS):
    """Steps 10 to 13: ``(output, this layer's router state)``."""
    router = p["router"]
    z = y @ f32(router["down"]["kernel"]) + f32(router["down"]["bias"])
    z = depth_carry(z, carried, router, readings["depth_carry"])
    chosen, weight = select(
        router_scores(z, router, cfg["rms_norm_eps"]), router
    )
    out = expert_outputs(
        y, p["grouped_experts"], chosen, weight, readings["skip"]
    )
    return out, z


def check_sizes(params: dict, cfg: dict) -> None:
    """The tree against the family's keys, where ``cfg`` has them."""
    if "cca_time0" not in cfg:
        return
    model = params["model"]
    e, h, g, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"], cfg["head_dim"])
    n, wide, r = (cfg["num_experts"], cfg["moe_intermediate_size"],
                  cfg["router_hidden_size"])
    assert cfg["tie_word_embeddings"] and "lm_head" not in params
    assert not cfg["attention_bias"] and cfg["num_experts_per_tok"] == 1
    assert cfg["hidden_act"] == "silu" and cfg["sliding_window"] is None
    assert model["embed_tokens"]["embedding_default"].shape == (
        cfg["vocab_size"], e)
    for i in range(cfg["num_hidden_layers"]):
        assert cfg["layer_types"][i] == "hybrid"
        layer = model[f"layers_{i}"]
        a = layer["self_attn"]
        assert a["q_proj"]["kernel"].shape == (e, h * d)
        assert a["k_proj"]["kernel"].shape == (e, g * d)
        assert a["v_proj"]["kernel"].shape == (e, g // 2 * d)
        assert a["v_prev_proj"]["kernel"].shape == (e, g // 2 * d)
        assert a["o_proj"]["kernel"].shape == (h * d, e)
        assert a["conv0"]["weight"].shape == ((h + g) * d, cfg["cca_time0"])
        assert a["conv1_weight"].shape == (cfg["cca_time1"], h + g, d, d)
        assert a["key_temperature"].shape == (g,)
        router = layer["mlp"]["router"]
        assert router["down"]["kernel"].shape == (e, r)
        assert router["fc1"]["kernel"].shape == (r, r)
        assert router["gate"]["kernel"].shape == (r, n + 1)
        assert ("carry_scale" in router) == (i > 0)
        assert layer["mlp"]["grouped_experts"]["gate_proj"].shape == (
            n, e, wide)


def hidden_states(params, cfg, tokens, readings=READINGS):
    check_sizes(params, cfg)
    readings = {**READINGS, **readings}
    model = params["model"]
    eps = cfg["rms_norm_eps"]
    x = f32(model["embed_tokens"]["embedding_default"][tokens])
    positions = jnp.arange(tokens.shape[1])
    carried = None
    for layer in range(cfg["num_hidden_layers"]):
        p = model[f"layers_{layer}"]
        branch = attention_sublayer(
            plain.rms_norm(x, p["input_layernorm"]["weight"], eps),
            p["self_attn"], cfg, positions, readings,
        )
        x = scaled_residual(x, branch, p["attn_residual"])
        branch, carried = expert_sublayer(
            plain.rms_norm(x, p["post_attention_layernorm"]["weight"], eps),
            carried, p["mlp"], cfg, readings,
        )
        x = scaled_residual(x, branch, p["mlp_residual"])
    return plain.rms_norm(x, model["norm"]["weight"], eps)


def logits(params, cfg, tokens, readings=READINGS):
    """``tokens [B, T]`` int -> logits ``[B, T, V]`` float32."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, cfg, tokens, readings)
        table = params["model"]["embed_tokens"]["embedding_default"]
        return h @ f32(table).T


def loss(params, cfg, tokens, labels, readings=READINGS):
    """Mean next-token cross-entropy over ``labels [B, T]``."""
    logp = jax.nn.log_softmax(logits(params, cfg, tokens, readings), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)
