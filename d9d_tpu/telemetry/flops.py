"""Model-FLOPs inventory for live MFU.

The trainer's live MFU gauge and ``chip_smoke.py`` share one convention
on the step path: 6 FLOPs per token per active parameter plus the exact
quadratic-attention term (MFU counts remat recompute as overhead, so the
multiplier stays 6 regardless of remat policy).
"""

from typing import Any

__all__ = [
    "model_flops_per_token",
    "gdn_flops_per_token",
    "keys_per_query",
    "active_param_count",
    "device_peak_flops",
]

# Peak bf16 FLOPs per chip by device-kind substring (Google Cloud TPU
# documentation, per-generation system pages). The table chip_smoke.py
# and the trainer's MFU gauge read (the benchmark keeps its own,
# benchmarks/harness/peaks.py: ROADMAP `two-peak-tables`); a TPU that is
# not in it is an error, never a default.
PEAK_FLOPS = {
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6": 918e12,
}


def model_flops_per_token(
    active_param_count: int,
    *,
    seq_len: int,
    config: Any | None = None,
) -> float:
    """Model FLOPs per trained token.

    When ``config`` exposes transformer geometry (``num_layers``,
    ``num_heads``, ``head_dim`` — the Qwen3/deepseek config shape) the
    causal-attention term ``6 * H * D * T`` a layer is added; hybrid
    stacks restrict it to the quadratic layers via
    ``linear_attention_layers``, and a stack that mixes attention kinds
    (``layer_kind`` / ``attention_kind``) counts each layer at its own
    kind's query heads and, under a window, at the keys a query sees
    (:func:`keys_per_query`). Without a recognizable config the 6N term
    alone is reported (an underestimate for long sequences —
    documented, not guessed at).
    """
    flops = 6.0 * active_param_count
    if config is not None:
        layers = getattr(config, "num_layers", None)
        heads = getattr(config, "num_heads", None)
        head_dim = getattr(config, "head_dim", None)
        if layers and heads and head_dim:
            linear = getattr(config, "linear_attention_layers", None) or ()
            for layer in range(layers):
                if layer in linear:
                    continue
                h, window = _layer_attention(config, layer, heads)
                flops += (
                    12.0 * h * head_dim * keys_per_query(seq_len, window)
                )
            flops += gdn_flops_per_token(config)
    return flops


def keys_per_query(seq_len: int, window: int | None = None) -> float:
    """Keys a query attends on average over a causal sequence: half the
    sequence, or under a window ``W`` shorter than it ``W - W (W - 1) /
    2S`` (the first ``W`` queries see fewer)."""
    if window is None or window >= seq_len:
        return seq_len / 2
    return window - window * (window - 1) / (2 * seq_len)


def _layer_attention(config: Any, layer: int, heads: int):
    """``(query heads, window)`` of one layer: its kind's where the
    config names kinds, else the plain count and no window."""
    if not hasattr(config, "attention_kind"):
        return heads, None
    kind = config.attention_kind(config.layer_kind(layer))
    return kind.num_heads, kind.window_size


def gdn_flops_per_token(config: Any, chunk: int = 64) -> float:
    """Chunked-WY gated-delta FLOPs per token across the GDN layers
    (ops/gated_delta.py matmul inventory): per head per token the forward
    costs ≈ 2·2·C·dk (k·kᵀ, q·kᵀ) + C·dv (triangular solve) + 2·C·dv
    (attn·u) + 3·2·dk·dv (state read ×2 + state update); fwd+bwd ≈ 3×."""
    linear = getattr(config, "linear_attention_layers", None) or ()
    if not linear:
        return 0.0
    dk = getattr(config, "gdn_head_qk_dim", None) or config.head_dim
    dv = getattr(config, "gdn_head_v_dim", None) or config.head_dim
    hv = getattr(config, "gdn_v_heads", None) or config.num_heads
    per_head = 3 * (4 * chunk * dk + 3 * chunk * dv + 6 * dk * dv)
    return len(linear) * hv * per_head


def active_param_count(trees, config: Any | None = None) -> float:
    """Parameters that compute per token, summed over ``trees`` (pytrees
    of arrays): MoE expert weights — any leaf whose path contains
    ``grouped_experts`` — scaled by ``num_experts_per_tok`` over the
    router's width (``num_routed_experts``, else ``num_experts``) from
    ``config``, everything else counted once. The single accounting
    behind the trainer's live-MFU gauge."""
    import jax  # deferred: the telemetry package core stays jax-free
    import numpy as np

    # a chip's share of a wider router holds ``num_experts`` of
    # ``num_routed_experts``: a token's top-k fall on them in proportion
    n_exp = (
        getattr(config, "num_routed_experts", None)
        or getattr(config, "num_experts", None)
    )
    top_k = getattr(config, "num_experts_per_tok", None)
    expert_scale = (top_k / n_exp) if (n_exp and top_k) else 1.0
    total = 0.0
    for tree in trees:
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            n = int(np.prod(leaf.shape))
            if expert_scale != 1.0 and "grouped_experts" in "/".join(
                str(p) for p in path
            ):
                n *= expert_scale
            total += n
    return total


def device_peak_flops() -> float | None:
    """Peak bf16 FLOPs of the first local device, from :data:`PEAK_FLOPS`.

    None on a backend that is not a TPU (the CPU rig): there is no peak
    to divide by, so callers emit no utilisation there. A TPU whose kind
    is missing from the table raises — add the kind with its source
    rather than measure against another chip's peak."""
    import jax  # deferred: the telemetry package core stays jax-free

    device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower()
    for key, peak in PEAK_FLOPS.items():
        if key in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s recorded for TPU kind {device.device_kind!r}; "
        "add it to d9d_tpu.telemetry.flops.PEAK_FLOPS with its source"
    )
