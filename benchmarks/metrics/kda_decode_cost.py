"""Operations and bytes the Kimi delta attention mixer's decode step
needs, from the configuration file's Hugging Face keys (``solar_open2``'s),
the serving slots and the number of decode steps; nothing is taken from
the program. It is what ``kernel.kda_decode_roofline`` divides by
(listed for the Solar cell since PR 53, beside
``model.decode_kda_device_pct``). Mamba-2's count (``ssm2_decode_cost``)
is another recurrence under other keys and is not borrowed.

One decode step of one KDA layer processes all ``slots`` rows, busy or
not (the shapes are static), with ``H = linear_attn_config.num_heads``
heads of ``D = linear_attn_config.head_dim`` key and value channels (``HD
= H D``), three convolutions of ``K = short_conv_kernel_size`` taps over
``HD`` channels each, and two low-rank gate pairs of rank ``D``:

- the recurrent state, ``slots x H x D x D`` float32 (a matrix a head),
  is read and written whole, once: ``S' = diag(e^g) S``, ``S = S' + beta
  k (v - S'^T k)^T``, ``o = S^T q``;
- the convolutions' tail, ``slots x (K - 1) x 3 HD`` in bf16, is read
  and written (shifted by the new input);
- the step's own operands are read once: ``q``, ``k`` and ``v`` as the
  projections write them in bf16 (``3 HD``), the decay's and the output
  gate's rows as their second products leave them in float32 (``2
  HD``), the write strength (``H``, bf16).

Operations: for each state number the decay product, the product with
``k`` and its sum, the write's product and sum, the product with ``q``
and its sum: seven (the exponential is one a key channel, not one a
state number). The convolutions add ``2 K`` a channel they cover, the
gates, the l2 norms and the gated norm twelve a channel. All
element-wise: against the chip's matmul peak they are nothing, and the
step is bound by the state's bytes.

The mixer's projections ride along (``projections``), for the reason
``ssm_decode_cost`` gives: the compiler brings operands into VMEM under
the neighbouring matmuls, so the time of the state's traffic cannot be
told from theirs. Each weight (``q``, ``k``, ``v`` ``E x HD``, the pairs
``E x D`` and ``D x HD`` twice, ``b_proj`` ``E x H``, ``o_proj`` ``HD x
E``, the taps, ``A_log``, ``dt_bias``, the gate's bias, the norm's
weight, all bf16) is read once a step, the mixer's input and output rows
(``slots x E`` bf16) once each, and a matmul is two operations a weight a
row.
"""

F32, BF16 = 4, 2  # bytes
STATE_OPS = 7  # per state number
CHANNEL_OPS = 12  # the gates, the l2 norms and the gated norm, per channel


def kda_layers(cfg: dict) -> int:
    n = cfg["num_hidden_layers"]
    return n - sum(1 for layer in cfg["gqa_layers"] if layer < n)


def widths(cfg: dict) -> tuple[int, int, int, int]:
    """``(HD, H, D, K)``."""
    linear = cfg["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]
    # the mixers are as wide as the attention layers' heads together
    assert heads * d == cfg["num_attention_heads"] * cfg["head_dim"]
    return heads * d, heads, d, linear["short_conv_kernel_size"]


def state_bytes(cfg: dict, slots: int) -> int:
    """Bytes of one layer's recurrent state for ``slots`` rows."""
    hd, _, d, _ = widths(cfg)
    return slots * hd * d * F32


def layer_step(cfg: dict, slots: int) -> dict:
    """One KDA layer, one decode step, all ``slots`` rows."""
    hd, heads, d, k = widths(cfg)
    tail = slots * (k - 1) * 3 * hd * BF16
    operands = slots * (3 * hd * BF16 + 2 * hd * F32 + heads * BF16)
    return {
        "flops": float(slots * (
            hd * (d * STATE_OPS + CHANNEL_OPS) + 3 * hd * 2 * k
        )),
        "bytes": float(2 * state_bytes(cfg, slots) + 2 * tail + operands),
    }


def matmul_params(cfg: dict) -> int:
    e = cfg["hidden_size"]
    hd, heads, d, _ = widths(cfg)
    return 3 * e * hd + 2 * (e * d + d * hd) + e * heads + hd * e


def small_params(cfg: dict) -> int:
    """Taps; ``A_log``, ``dt_bias``, the gate's bias; the norm's weight."""
    hd, heads, d, k = widths(cfg)
    return 3 * hd * k + heads + 2 * hd + d


def projections(cfg: dict, slots: int) -> dict:
    """One KDA layer's projections and small parameters, one decode
    step, all ``slots`` rows."""
    e = cfg["hidden_size"]
    matmuls = matmul_params(cfg)
    return {
        "flops": float(2 * slots * matmuls),
        "bytes": float(
            (matmuls + small_params(cfg)) * BF16 + 2 * slots * e * BF16
        ),
    }


def kda_decode_work(cfg: dict, slots: int, steps: int) -> dict:
    """Every KDA mixer's work over ``steps`` decode steps: the state's
    step and the projections around it."""
    parts = (layer_step(cfg, slots), projections(cfg, slots))
    scale = kda_layers(cfg) * steps
    return {key: scale * sum(p[key] for p in parts) for key in parts[0]}
