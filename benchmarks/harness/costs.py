"""Operations and bytes the algorithm needs, from a cell's shapes.

The arithmetic behind ``train.mfu_pct`` and the ``*_roofline`` metrics.
It reads the configuration file's Hugging Face keys and the traffic
mix's sizes; nothing is taken from the program, and recomputed
(rematerialised) operations are never counted.
"""

BF16 = 2  # bytes


def is_mla(cfg: dict) -> bool:
    return "kv_lora_rank" in cfg


# the keys a source may count its routed experts under, in the order read
# (of the catalog's rows the Qwen-MoE and Jamba families use the first, the
# DeepSeek family the second, Granite and MiniMax the third, Yuan the last)
EXPERT_COUNTS = (
    "num_experts", "n_routed_experts", "num_local_experts", "moe_num_experts"
)


def _expert_count(counts: dict) -> int | None:
    """The first of ``EXPERT_COUNTS`` that ``counts`` states."""
    return next((counts[k] for k in EXPERT_COUNTS if counts.get(k)), None)


def n_routed_experts(cfg: dict) -> int:
    """Routed experts held here: the file's own count."""
    held = _expert_count(cfg)
    if held is None:
        raise KeyError(f"none of {', '.join(EXPERT_COUNTS)}")
    return held


def published_experts(cfg: dict) -> int:
    """Routed experts of the deployment, which the router scores: the
    ``share`` block's published count where this chip holds a share of
    a layer (``reduced`` lists the key), else the count held."""
    published = cfg.get("share", {}).get("published", {})
    return _expert_count(published) or n_routed_experts(cfg)


def routed_per_token(cfg: dict) -> float:
    """Of a token's top-k experts, how many are held here on average:
    ``top_k x held / published``. The rest would be computed on the
    chips that share the layer, and neither program nor reference
    computes them."""
    held = cfg["num_experts_per_tok"] * n_routed_experts(cfg)
    return held / published_experts(cfg)


def n_dense_layers(cfg: dict) -> int:
    return min(cfg.get("first_k_dense_replace", 0), cfg["num_hidden_layers"])


def n_sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - n_dense_layers(cfg)


def n_mtp_modules(cfg: dict) -> int:
    """Multi-token-prediction modules (DeepSeek-V3, arXiv:2412.19437
    section 2.2): each is one more block of the kind that follows the
    dense layers, a ``2d x d`` merge of the hidden state with the next
    token's embedding, and one more pass through the shared head. They
    are trained, so only the training costs count them: a decode chunk
    runs the stack alone, whatever the file states."""
    return cfg.get("num_nextn_predict_layers") or 0


def n_trained_attention_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] + n_mtp_modules(cfg)


def n_trained_sparse_layers(cfg: dict) -> int:
    return n_sparse_layers(cfg) + n_mtp_modules(cfg)


def attention_matmul_params(cfg: dict) -> int:
    """Projection parameters of one attention layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if is_mla(cfg):
        d_qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        rank, q_rank = cfg["kv_lora_rank"], cfg.get("q_lora_rank")
        # q compression multiplies d x r_q and r_q x (h x d_qk) weights
        query = d * q_rank + q_rank * h * d_qk if q_rank else d * h * d_qk
        return (
            query
            + d * (rank + cfg["qk_rope_head_dim"])
            + rank * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d
        )
    hd, hkv = cfg["head_dim"], cfg["num_key_value_heads"]
    return d * h * hd + 2 * d * hkv * hd + h * hd * d


def active_matmul_params(cfg: dict) -> float:
    """Weights one token is multiplied by on this chip in a training
    step: attention projections, the router at its published width,
    those of its top-k experts that are held here and the shared ones,
    dense layers, the output head over the vocabulary held, and per MTP
    module a block, its merge and a second pass through the head. The
    embedding is a lookup and is not counted."""
    d = cfg["hidden_size"]
    dense, mtp = n_dense_layers(cfg), n_mtp_modules(cfg)
    per_expert = 3 * d * cfg["moe_intermediate_size"]
    sparse = (
        d * published_experts(cfg)
        + (routed_per_token(cfg) + cfg.get("n_shared_experts", 0))
        * per_expert
    )
    return (
        n_trained_attention_layers(cfg) * attention_matmul_params(cfg)
        + dense * 3 * d * cfg["intermediate_size"]
        + n_trained_sparse_layers(cfg) * sparse
        + mtp * 2 * d * d
        + (1 + mtp) * d * cfg["vocab_size"]
    )


def attention_score_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward FLOPs per token of QK^T and PV under a causal mask (a token
    attends to half the sequence on average), all trained layers."""
    h = cfg["num_attention_heads"]
    if is_mla(cfg):
        d_qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        d_v = cfg["v_head_dim"]
    else:
        d_qk = d_v = cfg["head_dim"]
    return n_trained_attention_layers(cfg) * h * seq_len * (d_qk + d_v)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward = 3 x forward; forward = 2 FLOPs a weight."""
    return 3.0 * (
        2.0 * active_matmul_params(cfg)
        + attention_score_flops_per_token(cfg, seq_len)
    )


def expert_mm_train(cfg: dict, tokens: int) -> dict:
    """Expert matmuls of ONE sparse layer in one training step, forward
    and backward: gate, up and down, each once forward and twice
    backward (to the input and to the weight). Rows: the routed pairs
    that land on the experts held. Bytes: every held expert's weights
    read forward and backward and their gradient written, rows in and
    out of each matmul."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = tokens * routed_per_token(cfg)
    weights = n_routed_experts(cfg) * 3 * d * f
    flops = 3 * 2.0 * rows * 3 * d * f
    row_bytes = rows * (2 * d + 4 * f) * BF16  # x, g, u, h, y
    return {
        "flops": flops,
        "bytes": 3.0 * weights * BF16 + 3.0 * row_bytes,
    }


def expert_mm_decode(cfg: dict, slots: int, experts_touched: float) -> dict:
    """Expert matmuls of ONE sparse layer in one decode step over
    ``slots`` tokens. Bytes count only the experts the step's routing
    touched (their three matrices once) plus the rows that land on the
    experts held."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = slots * routed_per_token(cfg)
    return {
        "flops": 2.0 * rows * 3 * d * f,
        "bytes": experts_touched * 3 * d * f * BF16
        + rows * (2 * d + 4 * f) * BF16,
    }


def expected_experts_touched(cfg: dict, slots: int) -> float:
    """Distinct held experts hit by ``slots`` tokens x top-k draws over
    the published experts when the router is near uniform, as it is at
    seeded init."""
    miss = 1.0 - cfg["num_experts_per_tok"] / published_experts(cfg)
    return n_routed_experts(cfg) * (1.0 - miss ** slots)


def flash_train(cfg: dict, sequences: int, seq_len: int) -> dict:
    """The attention kernels of ONE layer in one step. Forward is
    QK^T and PV over the causal half; the backward kernels recompute the
    scores and form dQ, dK, dV: 2.5 x the forward's matmul work (the
    recomputation is part of the algorithm FlashAttention defines, not
    rematerialisation of the model). Bytes: q, k, v, o read or written
    forward; q, k, v, o, do read and dq, dk, dv written backward."""
    h = cfg["num_attention_heads"]
    if is_mla(cfg):
        d_qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        d_v = cfg["v_head_dim"]
        hkv = h
    else:
        d_qk = d_v = cfg["head_dim"]
        hkv = cfg["num_key_value_heads"]
    tokens = sequences * seq_len
    fwd_flops = 2.0 * tokens * h * (seq_len / 2) * (d_qk + d_v)
    q_bytes = tokens * h * d_qk * BF16
    k_bytes = tokens * hkv * d_qk * BF16
    v_bytes = tokens * hkv * d_v * BF16
    o_bytes = tokens * h * d_v * BF16
    fwd_bytes = q_bytes + k_bytes + v_bytes + o_bytes
    bwd_bytes = 2 * (q_bytes + k_bytes + v_bytes) + 2 * o_bytes
    return {
        "flops": 3.5 * fwd_flops,
        "bytes": float(fwd_bytes + bwd_bytes),
    }


def roofline_seconds(cost: dict, peak) -> tuple[float, str]:
    """Least time the chip could take, and which bound binds."""
    compute = cost["flops"] / peak.bf16_flops
    memory = cost["bytes"] / peak.hbm_bytes_per_s
    return (compute, "compute") if compute >= memory else (memory, "memory")
