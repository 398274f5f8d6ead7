"""Operations and bytes the Mamba-2 mixer's decode step needs, from the
configuration file's Hugging Face keys (``granitemoehybrid``'s), the
serving slots and the number of traced steps. What
``kernel.ssm2_decode_roofline`` divides by; nothing is taken from the
program. Mamba-1's count (``ssm_decode_cost``) is another recurrence
under other keys and is not borrowed.

One decode step of one Mamba-2 layer processes all ``slots`` rows, busy
or not (the shapes are static), with ``H = mamba_n_heads`` heads of ``P =
mamba_d_head`` channels (``Di = H P``), ``N = mamba_d_state`` state
numbers a channel, ``G = mamba_n_groups`` groups and a convolution of
``K = mamba_d_conv`` taps over ``Di + 2 G N`` channels:

- the recurrent state, ``slots x H x P x N`` float32 (a matrix a head),
  is read and written whole: ``S = exp(dt A) S + dt x (x) B``, ``y = S C
  + D x``;
- the convolution's tail, ``slots x (K - 1) x (Di + 2 G N)`` in bf16, is
  read and written (shifted by the new input);
- the step's own operands are read once, as the in-projection writes
  them in bf16: ``z`` (``Di``), ``x``, ``B`` and ``C`` together (``Di + 2
  G N``) and the step size (``H``).

Operations: for each state number the decay product, the drive ``(dt x)
B`` and its sum, the product with ``C`` and its sum: five (the
exponential is one a head, not one a state number). The convolution
adds ``2 K`` a channel it covers, the gate and the gated norm six a
channel. All element-wise: against the chip's matmul peak they are
nothing, and the step is bound by the state's bytes.

The mixer's projections ride along (``projections``), for the reason
``ssm_decode_cost`` gives: the compiler brings the state into VMEM under
the neighbouring matmuls, so the time of the state's traffic cannot be
told from theirs. Each weight (``in_proj`` ``E x (2 Di + 2 G N + H)``,
``out_proj`` ``Di x E``, the convolution's taps and bias, ``A_log``,
``D``, ``dt_bias``, the gated norm's weight, all bf16) is read once a
step, the mixer's input and output rows (``slots x E`` bf16) once each,
and a matmul is two operations a weight a row.
"""

F32, BF16 = 4, 2  # bytes
STATE_OPS = 5  # per state number
CHANNEL_OPS = 6  # the gate and the gated norm, per channel


def mamba_layers(cfg: dict) -> int:
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count("mamba")


def widths(cfg: dict) -> tuple[int, int, int]:
    """``(Di, the convolution's channels Di + 2 G N, H)``."""
    heads = cfg["mamba_n_heads"]
    d_inner = heads * cfg["mamba_d_head"]
    assert d_inner == cfg["mamba_expand"] * cfg["hidden_size"]
    conv = d_inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return d_inner, conv, heads


def state_bytes(cfg: dict, slots: int) -> int:
    """Bytes of one layer's recurrent state for ``slots`` rows."""
    d_inner, _, _ = widths(cfg)
    return slots * d_inner * cfg["mamba_d_state"] * F32


def layer_step(cfg: dict, slots: int) -> dict:
    """One Mamba-2 layer, one decode step, all ``slots`` rows."""
    d_inner, conv, heads = widths(cfg)
    n, k = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    tail = slots * (k - 1) * conv * BF16
    operands = slots * (d_inner + conv + heads) * BF16
    return {
        "flops": float(slots * (
            d_inner * (n * STATE_OPS + CHANNEL_OPS) + conv * 2 * k
        )),
        "bytes": float(2 * state_bytes(cfg, slots) + 2 * tail + operands),
    }


def projections(cfg: dict, slots: int) -> dict:
    """One Mamba-2 layer's projections and small parameters, one decode
    step, all ``slots`` rows."""
    e, k = cfg["hidden_size"], cfg["mamba_d_conv"]
    d_inner, conv, heads = widths(cfg)
    matmuls = e * (d_inner + conv + heads) + d_inner * e
    small = conv * (k + 1) + 3 * heads + d_inner  # taps, bias; A, D, dt; norm
    return {
        "flops": float(2 * slots * matmuls),
        "bytes": float((matmuls + small) * BF16 + 2 * slots * e * BF16),
    }


def ssm2_decode_work(cfg: dict, slots: int, steps: int) -> dict:
    """Every Mamba-2 mixer's work over ``steps`` decode steps: the
    state's step and the projections around it."""
    parts = (layer_step(cfg, slots), projections(cfg, slots))
    scale = mamba_layers(cfg) * steps
    return {key: scale * sum(p[key] for p in parts) for key in parts[0]}
