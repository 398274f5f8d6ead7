"""Analytic roofline attribution for the bench configs (no chip needed).

VERDICT r3 item 1 asks for the MoE north-star to reach MFU >= 0.25 *or a
backed explanation of the ceiling*. This tool supplies the analytic
half of that explanation, which needs no chip: a per-component
FLOPs/bytes inventory of one training step (the same geometry bench.py
runs), pushed through a two-resource roofline (MXU peak, HBM bandwidth)
to predict step time, tokens/s and MFU — and, more usefully, to rank
WHERE the non-MXU time goes and what each queued optimization can
recover.

Method: every component of the step contributes
``time = max(flops / (peak * mxu_eff), bytes / (bw * hbm_eff))``
summed serially (XLA overlaps some of this; the serial sum is the
pessimistic bound, the max over totals the optimistic one — both are
reported). Efficiencies are calibrated once against the MEASURED dense
row (48,127 tok/s on v5e, BASELINE.md): with mxu_eff=0.55 / hbm_eff=0.8
the dense prediction lands within a few percent, and the same constants
are then applied unchanged to the MoE/hybrid geometries, so relative
attributions are apples-to-apples.

Anchors (BASELINE.md measured rows, TPU v5e):
- dense 256M: 48,127 tok/s, MFU 0.412 -> calibration target
- Qwen3-MoE north-star: 25,280 tok/s, MFU 0.136 -> the row to explain

Prints one JSON line per scenario with the component table under
``detail.components`` (ms and binding resource each).
"""

import argparse
import json

# TPU v5e (one chip)
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 820e9  # bytes/s
# calibrated on the measured dense row (see module docstring); the point
# is not absolute accuracy but a consistent yardstick across scenarios
MXU_EFF = 0.55
HBM_EFF = 0.80


def _t(flops: float, bytes_: float) -> tuple[float, str]:
    tc = flops / (PEAK_FLOPS * MXU_EFF)
    tm = bytes_ / (HBM_BW * HBM_EFF)
    return (tc, "mxu") if tc >= tm else (tm, "hbm")


class Inventory:
    """Accumulates (flops, bytes) per named component for ONE step."""

    def __init__(self):
        self.rows: dict[str, list[float]] = {}

    def add(self, name: str, flops: float = 0.0, bytes_: float = 0.0):
        f, b = self.rows.setdefault(name, [0.0, 0.0])
        self.rows[name] = [f + flops, b + bytes_]

    def report(self, tokens_per_step: int, model_flops_per_token: float):
        comps = {}
        serial_s = 0.0
        tot_f = tot_b = 0.0
        for name, (f, b) in sorted(self.rows.items()):
            t, bind = _t(f, b)
            serial_s += t
            tot_f += f
            tot_b += b
            comps[name] = {
                "ms": round(t * 1e3, 3),
                "bound": bind,
                "gflops": round(f / 1e9, 1),
                "mbytes": round(b / 1e6, 1),
            }
        # optimistic bound: perfect overlap of compute and memory streams
        overlap_s = max(tot_f / (PEAK_FLOPS * MXU_EFF),
                        tot_b / (HBM_BW * HBM_EFF))
        tok_s = tokens_per_step / serial_s
        return {
            "predicted_tokens_per_sec": round(tok_s, 0),
            "predicted_mfu": round(
                tok_s * model_flops_per_token / PEAK_FLOPS, 4
            ),
            "step_ms_serial": round(serial_s * 1e3, 2),
            "step_ms_overlapped": round(overlap_s * 1e3, 2),
            "components": comps,
        }


def _attention_layer(inv, n, h, heads, kv_heads, head_dim, seq, dtype_b,
                     passes, param_dtype_b=None):
    """One attention layer, one microbatch. ``passes`` scales fwd(+bwd,
    +remat-recompute): fwd counts 1, bwd 2, recompute 1. Weight reads are
    charged at ``param_dtype_b`` (fp32 masters cast per traversal)."""
    param_dtype_b = param_dtype_b or dtype_b
    q_dim = heads * head_dim
    kv_dim = kv_heads * head_dim
    proj_in = h * (q_dim + 2 * kv_dim)
    proj_out = q_dim * h
    inv.add(
        "attn.proj",
        flops=passes * 2 * n * (proj_in + proj_out),
        bytes_=passes * param_dtype_b * (proj_in + proj_out)  # weights
        + passes * dtype_b * n * (h + q_dim + 2 * kv_dim + q_dim),
    )
    # flash attention, causal half: QK^T + PV
    inv.add(
        "attn.flash",
        flops=passes * 2 * 2 * (n * seq / 2) * q_dim,
        bytes_=passes * dtype_b * n * (q_dim + 2 * kv_dim) * 2,
    )


def _dense_ffn_layer(inv, n, h, inter, dtype_b, passes, param_dtype_b=None):
    param_dtype_b = param_dtype_b or dtype_b
    w = h * inter * 3  # gate, up, down
    inv.add(
        "ffn",
        flops=passes * 2 * n * h * inter * 3,
        bytes_=passes * param_dtype_b * w
        + passes * dtype_b * n * (h * 2 + inter * 3),
    )


def _norms_rope(inv, n, h, layers, dtype_b, passes):
    # RMSNorm x2 per layer + rope: bandwidth-only elementwise traffic
    inv.add(
        "norms_rope",
        bytes_=passes * layers * dtype_b * n * h * 2 * 2,
    )


def _moe_layer(inv, n, h, inter, n_experts, topk, dtype_b, passes,
               param_dtype_b, fused_gate_up=True, sortfree=True):
    m = n * topk
    # router: h -> E matmul + softmax/topk (VPU, counted as bytes)
    inv.add(
        "moe.router",
        flops=passes * 2 * n * h * n_experts,
        bytes_=passes * dtype_b * n * (h + n_experts) * 2,
    )
    # grouping permutation: one-hot+cumsum traffic (sort-free) or sort
    grouping = n * n_experts * 4 * (2 if sortfree else 4)
    inv.add("moe.grouping", bytes_=passes * grouping)
    # permute gather: read N*K source rows + write; combine mirror
    inv.add(
        "moe.permute_combine",
        bytes_=passes * dtype_b * m * h * 2 * 2,
    )
    # grouped matmuls; when param_dtype is fp32 the weights are read at
    # 4 B/elem (the cast is on the traversal path); the fused gate+up
    # concat additionally writes+reads the bf16 copy (ADVICE r3 caveat)
    w_gu = h * inter * 2 * n_experts
    w_down = inter * h * n_experts
    gu_bytes = param_dtype_b * w_gu + (2 * 2 * w_gu if fused_gate_up else 0)
    inv.add(
        "moe.experts_gate_up",
        flops=passes * 2 * m * h * inter * 2,
        bytes_=passes * (gu_bytes + dtype_b * m * (h + inter * 2)),
    )
    inv.add(
        "moe.experts_down",
        flops=passes * 2 * m * inter * h,
        bytes_=passes * (param_dtype_b * w_down + dtype_b * m * (inter + h)),
    )
    inv.add("moe.silu_mul", bytes_=passes * dtype_b * m * inter * 3)


def _embed_head_ce(inv, n_step, h, vocab, dtype_b, passes, ce_chunk,
                   param_dtype_b=None):
    # LM head matmul dominates; CCE runs it chunked (never [N, V]),
    # logits traffic = chunk-sized tiles streamed once per pass
    param_dtype_b = param_dtype_b or dtype_b
    inv.add(
        "head.cce",
        flops=passes * 2 * n_step * h * vocab,
        bytes_=passes * (param_dtype_b * h * vocab + dtype_b * n_step * h
                         + 4 * n_step * vocab / max(n_step // ce_chunk, 1)),
    )
    inv.add("embed", bytes_=passes * dtype_b * n_step * h * 2)


def _optimizer(inv, params, moment_dtype_b, param_dtype_b, zero_n=1):
    # AdamW: read p, m, v, g; write p, m, v (fp32 grads accumulated).
    # Under ZeRO-sharded state (zero_n > 1, parallel/zero.py) the chip
    # reads its 1/N param shard + 1/N of both moments (read+write) + the
    # 1/N grad shard, and writes the FULL all-gathered new params (the
    # gather itself is ICI traffic, not HBM)
    b = params * (
        param_dtype_b            # new params written full (post-gather)
        + param_dtype_b / zero_n  # param shard read
        + moment_dtype_b * 4 / zero_n  # m, v read+write on the shard
        + 4 / zero_n             # grad shard read (fp32)
    )
    inv.add("optimizer", bytes_=b)


def _grad_accum(inv, params, microbatches, zero_n=1):
    if microbatches > 1:
        # fp32 accumulator read+write per microbatch; ZeRO pins the
        # scan carry to the dp_r-sharded layout so the accumulator —
        # BASELINE.md's 66 ms/step row — shrinks to 1/N per chip
        inv.add("grad_accum", bytes_=params * 4 * 2 * microbatches / zero_n)


def dense_scenario():
    h, layers, heads, kvh, hd, inter, vocab = 1024, 12, 16, 8, 64, 4096, 32768
    seq, batch, ub = 2048, 8, 8
    n = ub * seq
    microbatches = batch // ub
    dtype_b = 2
    passes = 4  # fwd 1 + bwd 2 + full-remat recompute 1
    params = (
        vocab * h
        + layers * (h * (heads * hd + 2 * kvh * hd) + heads * hd * h
                    + 3 * h * inter + 2 * h)
        + h * vocab + h
    )
    inv = Inventory()
    param_b = 4  # fp32 master weights (AdamWProvider), cast per traversal
    for _ in range(microbatches):
        for _ in range(layers):
            _attention_layer(inv, n, h, heads, kvh, hd, seq, dtype_b, passes,
                             param_b)
            _dense_ffn_layer(inv, n, h, inter, dtype_b, passes, param_b)
        _norms_rope(inv, n, h, layers, dtype_b, passes)
        # head not rematted
        _embed_head_ce(inv, n, h, vocab, dtype_b, 3, 512, param_b)
    _optimizer(inv, params, 4, 4)
    _grad_accum(inv, params, microbatches)
    tokens = batch * seq
    attn_f = 6 * layers * heads * hd * seq
    model_fpt = 6 * params + attn_f
    return "dense_256m", inv.report(tokens, model_fpt)


def moe_scenario(ub=1, param_dtype_b=4, fused_gate_up=True, sortfree=True,
                 hybrid=False, zero_n=1):
    """Qwen3-MoE north-star geometry; ``hybrid=True`` swaps 12 of the 16
    attention layers for GatedDeltaNet (bench.py run_bench_moe(hybrid=
    True) — BASELINE config 5). ``zero_n`` predicts the
    ``D9D_BENCH_MOE_ZERO=1`` leg on an N-chip dp_replicate mesh at
    constant per-chip load: compute terms are per-chip and unchanged,
    only the optimizer stream and the fp32 grad accumulator divide by N
    (parallel/zero.py; pre-registered BEFORE the chip window)."""
    h, layers, heads, kvh, hd = 768, 16, 12, 4, 64
    inter, n_experts, topk, vocab = 256, 64, 8, 32768
    seq, batch = 2048, 8
    chunk = 64
    n = ub * seq
    microbatches = batch // ub
    dtype_b = 2
    passes = 4
    n_attn = 4 if hybrid else layers
    n_gdn = layers - n_attn
    expert_params = layers * n_experts * 3 * h * inter
    attn_layer_params = h * (heads * hd + 2 * kvh * hd) + heads * hd * h
    # GDN block (nn/linear_attention.py): qkv_proj + conv + decay/b gates
    # + output gate g_proj + o_proj (+ per-head norm, negligible)
    gdn_dim = kvh * hd * 2 + heads * hd
    gdn_layer_params = (
        h * gdn_dim + gdn_dim * 4
        + 2 * h * heads + h * heads * hd + heads * hd * h
    )
    dense_params = (
        vocab * h
        + n_attn * attn_layer_params
        + n_gdn * gdn_layer_params
        + layers * (h * n_experts + 2 * h)
        + h * vocab + h
    )
    params = expert_params + dense_params
    inv = Inventory()
    for _ in range(microbatches):
        for _ in range(n_attn):
            _attention_layer(inv, n, h, heads, kvh, hd, seq, dtype_b, passes,
                             param_dtype_b)
        for _ in range(n_gdn):
            _gdn_layer(inv, n, h, kvh, heads, hd, hd, dtype_b, passes,
                       param_dtype_b, chunk)
        for _ in range(layers):
            _moe_layer(inv, n, h, inter, n_experts, topk, dtype_b, passes,
                       param_dtype_b, fused_gate_up, sortfree)
        _norms_rope(inv, n, h, layers, dtype_b, passes)
        _embed_head_ce(inv, n, h, vocab, dtype_b, 3,
                       2048 if n <= 2048 else 512, param_dtype_b)
    moment_b = 4 if param_dtype_b == 4 else 2  # bf16 params -> SR moments
    _optimizer(inv, params, moment_b, param_dtype_b, zero_n=zero_n)
    _grad_accum(inv, params, microbatches, zero_n=zero_n)
    tokens = batch * seq
    active = dense_params + expert_params * topk / n_experts
    attn_f = 6 * n_attn * heads * hd * seq
    # telemetry/flops.py gdn_flops_per_token convention (fwd+bwd ~ 3x)
    gdn_f = 3 * n_gdn * heads * (
        4 * chunk * hd + 3 * chunk * hd + 6 * hd * hd
    )
    model_fpt = 6 * active + attn_f + gdn_f
    base = "hybrid" if hybrid else "qwen3_moe"
    name = f"{base}_ub{ub}_{'fp32' if param_dtype_b == 4 else 'bf16'}"
    if not fused_gate_up:
        name += "_unfused_gate_up"
    if not sortfree:
        name += "_argsort"
    if zero_n > 1:
        name += f"_zero{zero_n}"
    return name, inv.report(tokens, model_fpt)


def _gdn_layer(inv, n, h, qk_heads, v_heads, dk, dv, dtype_b, passes,
               param_dtype_b, chunk=64):
    """One GatedDeltaNet layer (nn/linear_attention.py): projections +
    causal conv + chunked WY delta rule. The WY matmuls run in fp32
    (ops/gated_delta.py), i.e. at roughly half the bf16 MXU rate — the
    model charges their FLOPs x2 to reflect it."""
    proj_in = h * (qk_heads * dk * 2 + v_heads * dv * 2 + 2 * v_heads)
    proj_out = v_heads * dv * h
    inv.add(
        "gdn.proj",
        flops=passes * 2 * n * (proj_in + proj_out),
        bytes_=passes * param_dtype_b * (proj_in + proj_out)
        + passes * dtype_b * n * (h * 2 + qk_heads * dk * 2
                                  + v_heads * dv * 2),
    )
    conv_ch = qk_heads * dk * 2 + v_heads * dv
    inv.add("gdn.conv", bytes_=passes * dtype_b * n * conv_ch * 2)
    # chunked delta rule per head per token (telemetry/flops.py gdn_flops_per_token
    # inventory), fp32 -> x2 FLOPs-equivalent on the bf16 roofline
    per_tok = v_heads * (4 * chunk * dk + 3 * chunk * dv + 6 * dk * dv)
    inv.add(
        "gdn.delta_rule",
        flops=passes * 2 * n * per_tok,
        bytes_=passes * 4 * n * (qk_heads * dk * 2 + v_heads * dv * 2),
    )


def decode_scenario():
    """run_bench_generate geometry: greedy KV-cache decode on the dense
    256M model (batch 8). Decode is weight-stream-bound: every step
    streams ALL params — at fp32 width, because run_bench_generate only
    sets the compute dtype to bf16 and the modules keep fp32 master
    params (cast per traversal) — plus the full static-length cache
    (eager decode attends every slot, masked); MXU work is negligible at
    batch 8. Per-step costs are constant, so one add scaled by ``gen``
    covers the whole run."""
    h, layers, heads, kvh, hd, inter, vocab = 1024, 12, 16, 8, 64, 4096, 32768
    batch, prompt, gen = 8, 128, 256
    dtype_b = 2
    params = (
        vocab * h
        + layers * (h * (heads * hd + 2 * kvh * hd) + heads * hd * h
                    + 3 * h * inter + 2 * h)
        + h * vocab + h
    )
    inv = Inventory()
    s_max = prompt + gen
    # the embedding is a GATHER (batch rows, nn/embedding.py), not a
    # streamed matmul operand — exclude it from the per-step weight stream
    streamed = params - vocab * h
    inv.add("decode.weights", bytes_=gen * streamed * 4,
            flops=gen * 2 * batch * streamed)
    inv.add(
        "decode.kv_cache",
        # eager decode attends every static slot, masked: bytes AND flops
        # both scale with s_max
        bytes_=gen * batch * layers * s_max * 2 * kvh * hd * dtype_b,
        flops=gen * 2 * batch * layers * heads * hd * s_max * 2,
    )
    tokens = batch * gen
    rep = inv.report(tokens, 1.0)  # MFU meaningless for decode
    rep.pop("predicted_mfu")
    return "dense_256m_decode", rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--top", type=int, default=6,
                    help="components to list per scenario")
    args = ap.parse_args()
    scenarios = [
        dense_scenario(),
        moe_scenario(ub=1, param_dtype_b=4),
        moe_scenario(ub=2, param_dtype_b=2),
        moe_scenario(ub=4, param_dtype_b=2),
        moe_scenario(ub=1, param_dtype_b=4, fused_gate_up=False),
        moe_scenario(ub=1, param_dtype_b=4, hybrid=True),
        moe_scenario(ub=2, param_dtype_b=2, hybrid=True),
        # ZeRO pre-registrations (D9D_BENCH_MOE_ZERO=1 on a 4-chip
        # dp_replicate slice, constant per-chip load): the optimizer
        # stream + fp32 grad accumulator divide by N
        moe_scenario(ub=1, param_dtype_b=4, zero_n=4),
        moe_scenario(ub=2, param_dtype_b=2, zero_n=4),
        moe_scenario(ub=4, param_dtype_b=2, zero_n=4),
        decode_scenario(),
    ]
    for name, rep in scenarios:
        comps = rep.pop("components")
        top = sorted(comps.items(), key=lambda kv: -kv[1]["ms"])[: args.top]
        rep["top_components"] = {k: v for k, v in top}
        print(json.dumps({"scenario": name, **rep}))


if __name__ == "__main__":
    main()
