"""Operations and bytes the Mamba-1 mixer's decode step needs, from the
configuration file's Hugging Face keys, the serving slots and the number
of traced steps. What ``kernel.ssm_decode_roofline`` divides by; nothing
is taken from the program.

One decode step of one Mamba layer processes all ``slots`` rows, busy
or not (the shapes are static), with ``Di = mamba_expand x hidden_size``
channels, ``N = mamba_d_state`` state numbers a channel and a
convolution of ``K = mamba_d_conv`` taps:

- the recurrent state, ``slots x Di x N`` float32, is read and written
  whole: ``h = exp(dt A) h + dt x B``, ``y = h C + D x``;
- the convolution's tail, ``slots x (K - 1) x Di`` in bf16, is read and
  written (shifted by the new input);
- the step's own operands are read once: ``xs`` and ``z`` in bf16 (the
  in-projection's halves), ``dt`` in float32, ``B`` and ``C`` (``N``
  float32 numbers a row each).

Operations: for each state number ``dt x A``, one exponential, the decay
product, the drive ``(dt x) B`` and its sum, the product with ``C`` and
its sum: seven, the exponential counted as one. The convolution and the
gate add ``2 K + 4`` a channel. All element-wise: against the chip's
matmul peak they are nothing, and the step is bound by the state's
bytes.

The mixer's projections ride along (``projections``): the chip's
compiler brings the state into VMEM under the neighbouring matmuls, so
the time of the state's traffic cannot be told from theirs (PERF.md, PR
32: the ops between the projections alone took 99 us a layer a step for
168 MB). Each weight (``in_proj`` ``E x 2 Di``, ``x_proj`` ``Di x (R +
2 N)``, ``dt_proj`` ``R x Di`` with its bias, ``out_proj`` ``Di x E``,
the convolution's taps and bias, ``A_log``, ``D``, the three inner
norms, all bf16) is read once a step, the mixer's input and output rows
(``slots x E`` bf16) once each, and a matmul is two operations a weight
a row.
"""

F32, BF16 = 4, 2  # bytes
STATE_OPS = 7  # per state number, the exponential counted as one


def mamba_layers(cfg: dict) -> int:
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return sum(
        i % period != offset for i in range(cfg["num_hidden_layers"])
    )


def state_bytes(cfg: dict, slots: int) -> int:
    """Bytes of one layer's recurrent state for ``slots`` rows."""
    d_inner = cfg["mamba_expand"] * cfg["hidden_size"]
    return slots * d_inner * cfg["mamba_d_state"] * F32


def layer_step(cfg: dict, slots: int) -> dict:
    """One Mamba layer, one decode step, all ``slots`` rows."""
    d_inner = cfg["mamba_expand"] * cfg["hidden_size"]
    n, k = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    tail = slots * (k - 1) * d_inner * BF16
    operands = slots * (d_inner * (BF16 + BF16 + F32) + 2 * n * F32)
    return {
        "flops": float(slots * d_inner * (n * STATE_OPS + 2 * k + 4)),
        "bytes": float(2 * state_bytes(cfg, slots) + 2 * tail + operands),
    }


def projections(cfg: dict, slots: int) -> dict:
    """One Mamba layer's projections and small parameters, one decode
    step, all ``slots`` rows."""
    e, n, k = cfg["hidden_size"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    d_inner, rank = cfg["mamba_expand"] * e, cfg["mamba_dt_rank"]
    matmuls = e * 2 * d_inner + d_inner * (rank + 2 * n) + rank * d_inner \
        + d_inner * e
    small = d_inner * (1 + k + 1 + n + 1) + rank + 2 * n  # biases, taps, A, D, norms
    return {
        "flops": float(2 * slots * matmuls),
        "bytes": float((matmuls + small) * BF16 + 2 * slots * e * BF16),
    }


def ssm_decode_work(cfg: dict, slots: int, steps: int) -> dict:
    """Every Mamba mixer's work over ``steps`` decode steps: the state's
    step and the projections around it."""
    parts = (layer_step(cfg, slots), projections(cfg, slots))
    scale = mamba_layers(cfg) * steps
    return {key: scale * sum(p[key] for p in parts) for key in parts[0]}
