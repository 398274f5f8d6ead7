"""Per-kernel latency harness: time each op's providers against each other.

TPU analogue of the reference's triton-bench helper
(test/d9d_test/kernel/helper/benchmark.py:15-29: latency curves for d9d vs
torch-eager vs torch.compile vs liger). Here the providers are the repo's
kernel variants:

- sdpa:        pallas flash kernel vs the eager jnp oracle (fwd, fwd+bwd)
- linear_ce:   chunked CCE, fp32 vs bf16-in/fp32-accum einsum x chunk sizes,
               vs the naive full-logits path
- rms_norm:    jnp/XLA-fused implementation
- silu_mul:    jnp/XLA-fused implementation
- gated_delta: linear-attention chunked WY form vs recurrent oracle
- stochastic:  bf16 stochastic-rounding copy, jnp bit-twiddle vs pallas prng

Run on the TPU chip:   python tools/bench_kernels.py
CPU smoke:             JAX_PLATFORMS=cpu python tools/bench_kernels.py --tiny
Prints one JSON line per (bench, provider, config): mean ms/call timed
around ``block_until_ready`` (tools/benchtime.timeit), or an error line if
the case OOMs.
"""

import argparse
import functools
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from tools.benchtime import timeit  # noqa: E402 — needs the path bootstrap


def emit(bench, provider, config, ms):
    print(
        json.dumps(
            {"bench": bench, "provider": provider, "config": config,
             "ms": round(ms, 4)}
        ),
        flush=True,
    )


def emit_timed(bench, provider, config, fn, *args, **kw):
    """emit() a timing, or an error line if this case doesn't fit the chip
    (e.g. eager SDPA at t=8192 materializes >16 GB of score tensors and
    OOMs HBM — that's a result worth recording, not a harness crash)."""
    try:
        ms = timeit(fn, *args, **kw)
    except Exception as e:  # noqa: BLE001 — record chip-side failures
        print(
            json.dumps(
                {"bench": bench, "provider": provider, "config": config,
                 "error": f"{type(e).__name__}: {str(e)[:200]}"}
            ),
            flush=True,
        )
        return
    emit(bench, provider, config, ms)


def bench_sdpa(tiny):
    import jax
    import jax.numpy as jnp

    from d9d_tpu.ops.attention.eager import eager_sdpa

    shapes = (
        [(1, 128, 4, 2, 64)]
        if tiny
        else [(4, 2048, 16, 8, 64), (2, 8192, 16, 8, 64), (1, 4096, 32, 8, 128)]
    )
    providers = {"eager": eager_sdpa}
    if jax.default_backend() == "tpu":
        from d9d_tpu.ops.attention.pallas_flash import make_pallas_flash_sdpa

        providers["pallas_flash"] = make_pallas_flash_sdpa()
        # r4: one-pass backward (dq+dk+dv from a single logit recompute)
        providers["pallas_flash_fused_bwd"] = make_pallas_flash_sdpa(
            fused_bwd=True
        )
        # block-size sweep around the adopted 1024x512 default (r3); the
        # biggest tilings stay within VMEM: fp32 scores 2048x1024 = 8 MB
        for bq, bkv in ((512, 512), (256, 512), (512, 256), (1024, 512),
                        (1024, 1024), (2048, 1024)):
            providers[f"pallas_flash_q{bq}_kv{bkv}"] = make_pallas_flash_sdpa(
                block_q=bq, block_kv=bkv
            )

    for b, t, hq, hkv, d in shapes:
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (b, t, hq, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, t, hkv, d), jnp.bfloat16)
        v = jax.random.normal(kv, (b, t, hkv, d), jnp.bfloat16)
        cfg = f"b{b}_t{t}_h{hq}:{hkv}_d{d}"
        for name, sdpa in providers.items():
            if name == "pallas_flash_fused_bwd":
                # the fused backward silently falls back to the split
                # kernels when its dq VMEM state doesn't fit — mark the
                # row instead of recording a meaningless duplicate
                from d9d_tpu.ops.attention.pallas_flash import (
                    fused_bwd_applies,
                )

                if not fused_bwd_applies(
                    t=t, num_heads=hq, num_kv_heads=hkv, head_dim=d,
                    itemsize=q.dtype.itemsize,
                ):
                    print(json.dumps(
                        {"bench": "sdpa_fwd_bwd", "provider": name,
                         "config": cfg,
                         "error": "fused dq state exceeds VMEM budget; "
                                  "would run the split kernels"}
                    ), flush=True)
                    continue
            fwd = jax.jit(lambda q, k, v, f=sdpa: f(q, k, v, causal=True))
            emit_timed("sdpa_fwd", name, cfg, fwd, q, k, v)

            def loss(q, k, v, f=sdpa):
                return jnp.sum(f(q, k, v, causal=True).astype(jnp.float32))

            bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            emit_timed("sdpa_fwd_bwd", name, cfg, bwd, q, k, v)


def bench_linear_ce(tiny):
    import jax
    import jax.numpy as jnp

    from d9d_tpu.ops.linear_ce import linear_cross_entropy

    if tiny:
        n, d, v = 256, 64, 512
        chunks = [128]
    else:
        n, d, v = 16384, 1024, 32768
        chunks = [512, 2048, 8192]
    h = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (v, d), jnp.bfloat16)
    labels = jnp.arange(n) % v

    def naive(h, w, labels):
        logits = h.astype(jnp.float32) @ w.astype(jnp.float32).T
        lse = jax.nn.logsumexp(logits, axis=-1)
        corr = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return lse - corr

    variants = {"naive_full_logits": jax.jit(naive)}
    for chunk in chunks:
        for dtype in ("fp32", "bf16"):
            variants[f"cce_{dtype}_c{chunk}"] = jax.jit(
                lambda h, w, l, c=chunk, dt=dtype: linear_cross_entropy(
                    h, w, l, chunk_size=c, matmul_dtype=dt
                )
            )
    cfg = f"n{n}_d{d}_v{v}"
    for name, fn in variants.items():
        emit_timed("linear_ce_fwd", name, cfg, fn, h, w, labels)
        grad = jax.jit(
            jax.grad(lambda h, w, l, f=fn: jnp.sum(f(h, w, l)), argnums=(0, 1))
        )
        emit_timed("linear_ce_fwd_bwd", name, cfg, grad, h, w, labels)


def bench_elementwise(tiny):
    import jax
    import jax.numpy as jnp

    from d9d_tpu.ops import rms_norm, silu_mul

    n, d = (256, 64) if tiny else (16384, 4096)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.bfloat16)
    y = jax.random.normal(jax.random.PRNGKey(1), (n, d), jnp.bfloat16)
    w = jnp.ones((d,), jnp.float32)
    emit_timed("rms_norm", "jnp_fused", f"n{n}_d{d}",
               jax.jit(lambda x, w: rms_norm(x, w)), x, w)
    emit_timed("silu_mul", "jnp_fused", f"n{n}_d{d}",
               jax.jit(silu_mul), x, y)


def bench_gated_delta(tiny):
    """Linear-attention (GDN) providers: chunked WY form vs the recurrent
    oracle, fwd and fwd+bwd — the hybrid model family's hot op."""
    import jax
    import jax.numpy as jnp

    from d9d_tpu.ops.gated_delta import (
        gated_delta_rule_chunked,
        gated_delta_rule_recurrent,
    )

    b, t, h, dk, dv = (1, 128, 2, 16, 16) if tiny else (2, 2048, 8, 96, 128)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (b, t, h, dk), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h, dk), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h, dv), jnp.float32)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, h), jnp.float32))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h), jnp.float32))
    cfg = f"b{b}_t{t}_h{h}_dk{dk}_dv{dv}"

    providers = {"recurrent": gated_delta_rule_recurrent}
    for chunk in ([32] if tiny else [32, 64, 128]):
        providers[f"chunked_c{chunk}"] = (
            lambda *a, c=chunk, **kw: gated_delta_rule_chunked(
                *a, chunk_size=c, **kw
            )
        )
    for name, fn in providers.items():
        fwd = jax.jit(lambda q, k, v, g, beta, f=fn: f(q, k, v, g, beta)[0])
        emit_timed("gated_delta_fwd", name, cfg, fwd, q, k, v, g, beta)
        bwd = jax.jit(
            jax.grad(
                lambda q, k, v, g, beta, f=fn: jnp.sum(
                    f(q, k, v, g, beta)[0].astype(jnp.float32)
                ),
                argnums=(0, 1, 2),
            )
        )
        emit_timed("gated_delta_fwd_bwd", name, cfg, bwd, q, k, v, g, beta)


def bench_ring_blocks(tiny):
    """Ring-attention per-step block compute, simulated on one chip.

    Reproduces exactly what the busiest ring device (my_idx = cp-1, which
    attends every chunk under causal masking) computes per step — cp
    chunked attention calls + the online combine — without needing a
    multi-chip mesh. Providers: the Pallas flash block (r4 default inside
    ``ring_attention``) vs the fp32 einsum oracle the ring used through r3.
    The flash row is the evidence for VERDICT r3 item 2: CP block compute
    no longer materializes [T_loc, S_loc] logits and tracks flash
    throughput."""
    import jax
    import jax.numpy as jnp

    from d9d_tpu.ops.attention.pallas_flash import (
        combine_attention_chunks,
        flash_attention_block,
    )

    shapes = (
        [(1, 128, 4, 2, 16, 4)]
        if tiny
        else [(1, 8192, 16, 8, 64, 4), (1, 16384, 16, 8, 64, 8)]
    )

    def flash_sim(q, ks, vs, t_loc, cp):
        o = jnp.zeros(q.shape, jnp.float32)
        lse = jnp.full((q.shape[0], q.shape[2], q.shape[1]), -1e30, jnp.float32)
        for i in range(cp):
            o_b, lse_b = flash_attention_block(
                q, ks[i], vs[i],
                q_offset=(cp - 1) * t_loc, k_offset=i * t_loc, causal=True,
            )
            o, lse = combine_attention_chunks(o, lse, o_b, lse_b)
        return o

    def eager_sim(q, ks, vs, t_loc, cp):
        b, t, hq, d = q.shape
        hkv = ks[0].shape[2]
        g = hq // hkv
        qf = q.astype(jnp.float32).reshape(b, t, hkv, g, d) * (d**-0.5)
        q_pos = (cp - 1) * t_loc + jnp.arange(t_loc)[:, None]
        o = jnp.zeros((b, t, hkv, g, d), jnp.float32)
        m = jnp.full((b, hkv, g, t), -1e30, jnp.float32)
        l = jnp.zeros((b, hkv, g, t), jnp.float32)
        for i in range(cp):
            logits = jnp.einsum(
                "bthgd,bshd->bhgts", qf, ks[i].astype(jnp.float32)
            )
            k_pos = i * t_loc + jnp.arange(t_loc)[None, :]
            logits = jnp.where(k_pos <= q_pos, logits, -1e30)
            new_m = jnp.maximum(m, jnp.max(logits, axis=-1))
            alpha = jnp.exp(m - new_m)
            p = jnp.exp(logits - new_m[..., None])
            o = o * alpha.transpose(0, 3, 1, 2)[..., None] + jnp.einsum(
                "bhgts,bshd->bthgd", p, vs[i].astype(jnp.float32)
            )
            l = l * alpha + jnp.sum(p, axis=-1)
            m = new_m
        return (o / jnp.maximum(l.transpose(0, 3, 1, 2)[..., None], 1e-30)
                ).reshape(b, t, hq, d)

    for b, t_glob, hq, hkv, d, cp in shapes:
        t_loc = t_glob // cp
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (b, t_loc, hq, d), jnp.bfloat16)
        ks = list(jax.random.normal(kk, (cp, b, t_loc, hkv, d), jnp.bfloat16))
        vs = list(jax.random.normal(kv, (cp, b, t_loc, hkv, d), jnp.bfloat16))
        cfg = f"b{b}_T{t_glob}_cp{cp}_h{hq}:{hkv}_d{d}"
        for name, sim in (("flash_block", flash_sim), ("eager_block", eager_sim)):
            fwd = jax.jit(
                lambda q, ks, vs, f=sim: f(q, ks, vs, t_loc, cp)
            )
            emit_timed("ring_cp_blocks_fwd", name, cfg, fwd, q, ks, vs)
            bwd = jax.jit(jax.grad(
                lambda q, ks, vs, f=sim: jnp.sum(f(q, ks, vs, t_loc, cp)),
                argnums=(0,),
            ))
            emit_timed("ring_cp_blocks_fwd_bwd", name, cfg, bwd, q, ks, vs)


def bench_moe_ffn(tiny):
    """XLA grouped chain vs the fused aligned-layout Pallas kernel
    (ops/moe_pallas.py) at the north-star MoE geometry — fwd and
    fwd+bwd (the bwd is shared, so fwd is where the A/B decides)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from d9d_tpu.ops.moe import sort_tokens_by_expert
    from d9d_tpu.ops.moe_pallas import _reference_apply, fused_moe_ffn_apply

    if tiny:
        n, h, inter, e, k = 96, 64, 32, 8, 2
        block_ms = [16]
    else:
        # bench geometry (bench.py run_bench_moe): h768 i256 E64 top-8,
        # one microbatch of 2048 tokens. block_m tops out at 128 here:
        # the aligned layout's static pad is E*block_m rows, so larger
        # blocks mostly measure padding at M = n*k = 16384
        n, h, inter, e, k = 2048, 768, 256, 64, 8
        block_ms = [64, 128]
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, h), jnp.bfloat16)
    wg = jnp.asarray(rng.randn(e, h, inter) * 0.1, jnp.bfloat16)
    wu = jnp.asarray(rng.randn(e, h, inter) * 0.1, jnp.bfloat16)
    wd = jnp.asarray(rng.randn(e, inter, h) * 0.1, jnp.bfloat16)
    ids = jnp.asarray(
        np.stack([rng.choice(e, size=k, replace=False) for _ in range(n)]),
        jnp.int32,
    )
    probs = jnp.asarray(rng.rand(n, k).astype(np.float32))

    def xla_chain(x, probs, ids, wg, wu, wd):
        # the production chain itself (moe_pallas keeps it as the single
        # source of truth for its own fallback + custom_vjp backward)
        sort = sort_tokens_by_expert(ids, e)
        return _reference_apply(x, probs, sort, wg, wu, wd, jnp.bfloat16)

    variants = {"xla_chain": jax.jit(xla_chain)}
    for bm in block_ms:
        variants[f"pallas_fused_bm{bm}"] = jax.jit(
            lambda x, probs, ids, wg, wu, wd, bm=bm: fused_moe_ffn_apply(
                x, probs, sort_tokens_by_expert(ids, e), wg, wu, wd,
                jnp.bfloat16, num_experts=e, block_m=bm,
            )
        )
        # r5: in-kernel row gather (x resident in VMEM) — the aligned
        # activation buffer never round-trips HBM. combine pinned OFF so
        # this row keeps measuring the r5 kernel (cross-round
        # comparability); the r7 combine fusion gets its own variant
        variants[f"pallas_gather_bm{bm}"] = jax.jit(
            lambda x, probs, ids, wg, wu, wd, bm=bm: fused_moe_ffn_apply(
                x, probs, sort_tokens_by_expert(ids, e), wg, wu, wd,
                jnp.bfloat16, num_experts=e, block_m=bm, gather=True,
                combine=False,
            )
        )
        # r7: gather + in-kernel combine — token-major [N, h] output
        # accumulated in VMEM, expert-sorted y never touches HBM
        variants[f"pallas_gather_combine_bm{bm}"] = jax.jit(
            lambda x, probs, ids, wg, wu, wd, bm=bm: fused_moe_ffn_apply(
                x, probs, sort_tokens_by_expert(ids, e), wg, wu, wd,
                jnp.bfloat16, num_experts=e, block_m=bm, gather=True,
                combine=True,
            )
        )
    cfg = f"n{n}_h{h}_i{inter}_e{e}_k{k}"
    for name, fn in variants.items():
        emit_timed("moe_ffn_fwd", name, cfg, fn, x, probs, ids, wg, wu, wd)
        grad = jax.jit(
            jax.grad(
                lambda x, probs, wg, wu, wd, f=fn: jnp.sum(
                    f(x, probs, ids, wg, wu, wd).astype(jnp.float32)
                ),
                argnums=(0, 2, 3, 4),
            )
        )
        emit_timed("moe_ffn_fwd_bwd", name, cfg, grad, x, probs, wg, wu, wd)


def bench_mla_decode(tiny):
    """MLA single-token decode: absorbed (rank-space) vs decompressed.

    The absorbed form folds kv_up into q/o so each step skips
    decompressing all cache slots; this times one decode step at a
    DeepSeek-V2-ish geometry with a warm cache. 'decompressed_t1' is the
    TRUE non-absorbed decode (``decode_absorbed=False``): every step
    decompresses all s_max cached latents through kv_up and attends over
    the slot cache — the per-step cost the absorbed trick removes
    (ADVICE r4 replaced the old warm-cache-t2 proxy leg, which measured
    neither a valid decode nor the decompression)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from d9d_tpu.nn.attention import MultiHeadLatentAttention
    from d9d_tpu.ops.attention.eager import eager_sdpa
    from d9d_tpu.ops.rope import compute_rope_frequencies, make_rope_cos_sin

    if tiny:
        h, heads, d_nope, d_rope, d_v, rank, s_max, b = 64, 4, 16, 8, 12, 32, 32, 2
    else:
        h, heads, d_nope, d_rope, d_v, rank, s_max, b = (
            2048, 16, 128, 64, 128, 512, 4096, 8
        )
    blk = MultiHeadLatentAttention(
        hidden_size=h, num_heads=heads, qk_nope_head_dim=d_nope,
        qk_rope_head_dim=d_rope, v_head_dim=d_v, kv_lora_rank=rank,
        sdpa=eager_sdpa, dtype=jnp.bfloat16, decode_max_length=s_max,
    )
    inv, sc = compute_rope_frequencies(d_rope, 10000.0)
    rng = np.random.RandomState(0)
    prefill_t = s_max // 2
    x = jnp.asarray(rng.randn(b, prefill_t, h), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(prefill_t), (b, prefill_t))
    cos, sin = make_rope_cos_sin(pos, inv, sc, dtype=jnp.bfloat16)
    params = blk.init(jax.random.PRNGKey(0), x, cos, sin)["params"]
    _, state = blk.apply(
        {"params": params}, x, cos, sin, mutable=["cache"]
    )
    cache = state["cache"]

    blk_dec = blk.clone(decode_absorbed=False)

    def step(tokens_t, block=blk):
        t = tokens_t.shape[1]
        p2 = jnp.broadcast_to(jnp.arange(prefill_t, prefill_t + t), (b, t))
        c2, s2 = make_rope_cos_sin(p2, inv, sc, dtype=jnp.bfloat16)
        out, _ = block.apply(
            {"params": params, "cache": cache}, tokens_t, c2, s2,
            mutable=["cache"],
        )
        return out

    one = jnp.asarray(rng.randn(b, 1, h), jnp.bfloat16)
    cfg = f"h{h}_heads{heads}_r{rank}_s{s_max}_b{b}"
    emit_timed("mla_decode_step", "absorbed_t1", cfg, jax.jit(step), one)
    emit_timed(
        "mla_decode_step", "decompressed_t1", cfg,
        jax.jit(functools.partial(step, block=blk_dec)), one,
    )


def bench_decode_attn(tiny):
    """Per-step decode attention at serving shapes: eager slot-mask path
    vs the Pallas flash-decode kernel (ops/attention/pallas_decode.py).

    The kernel streams each (batch, kv-head) cache slice once and skips
    slots past the write index, so its cost should scale with the warm
    fraction; the eager path materializes [B,Hq,1,S] logits and reads
    the full cache regardless. Rows at start = S/2 and S-1 expose the
    skip win; a windowed row models sliding-window serving."""
    import jax
    import jax.numpy as jnp

    from d9d_tpu.nn.attention import _decode_slot_mask
    from d9d_tpu.ops.attention.eager import eager_sdpa
    from d9d_tpu.ops.attention.pallas_decode import flash_decode_attention

    if tiny:
        shapes = [(2, 4, 2, 16, 64)]
    else:
        # (b, hq, hkv, d, s): Qwen3-ish serving geometries, batch >= 32
        shapes = [(32, 16, 8, 128, 4096), (64, 16, 8, 128, 2048),
                  (8, 32, 8, 128, 8192)]
    interpret = jax.default_backend() != "tpu"
    for b, hq, hkv, d, s in shapes:
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (b, 1, hq, d), jnp.bfloat16)
        # heads-major [B, Hkv, S, D]: the decode cache's storage layout;
        # the eager fallback pays its read-side transpose (as the module
        # path does), the kernel streams it natively
        k = jax.random.normal(kk, (b, hkv, s, d), jnp.bfloat16)
        v = jax.random.normal(kv, (b, hkv, s, d), jnp.bfloat16)

        def eager_step(q, k, v, start, s=s):
            mask = _decode_slot_mask(start, 1, s, None, None)
            return eager_sdpa(
                q,
                jnp.transpose(k, (0, 2, 1, 3)),
                jnp.transpose(v, (0, 2, 1, 3)),
                causal=False, mask=mask,
            )

        def pallas_step(q, k, v, start, window=None):
            return flash_decode_attention(
                q, k, v, start=start, window_size=window,
                interpret=interpret,
            )

        cfg_base = f"b{b}_h{hq}:{hkv}_d{d}_s{s}"
        for frac, tag in ((s // 2, "warm50"), (s - 1, "full")):
            start = jnp.asarray(frac, jnp.int32)
            cfg = f"{cfg_base}_{tag}"
            emit_timed("decode_attn_step", "eager", cfg,
                       jax.jit(eager_step), q, k, v, start)
            emit_timed("decode_attn_step", "pallas_decode", cfg,
                       jax.jit(pallas_step), q, k, v, start)
        emit_timed(
            "decode_attn_step", "pallas_decode_window1k",
            f"{cfg_base}_full",
            jax.jit(functools.partial(pallas_step, window=1024)),
            q, k, v, jnp.asarray(s - 1, jnp.int32),
        )


def bench_stochastic(tiny):
    import jax
    import jax.numpy as jnp

    from d9d_tpu.ops.stochastic import (
        stochastic_round_to_bf16,
        stochastic_round_to_bf16_pallas,
    )

    n = 4096 if tiny else 1 << 24
    x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
    key = jax.random.PRNGKey(1)
    emit_timed("stochastic_round", "jnp_bit_twiddle", f"n{n}",
               jax.jit(stochastic_round_to_bf16), x, key)
    if jax.default_backend() == "tpu":
        seed = jnp.uint32(7)
        emit_timed("stochastic_round", "pallas_prng", f"n{n}",
                   jax.jit(stochastic_round_to_bf16_pallas), x, seed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument(
        "--only",
        choices=["sdpa", "linear_ce", "elementwise", "gated_delta",
                 "ring", "stochastic", "moe_ffn", "mla_decode",
                 "decode_attn"],
        default=None,
    )
    args = ap.parse_args()
    import jax

    if args.tiny:
        # --tiny is the CPU smoke, whatever backend the machine has
        jax.config.update("jax_platforms", "cpu")

    from d9d_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()

    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "backend": jax.default_backend()}), flush=True)
    benches = {
        "sdpa": bench_sdpa,
        "linear_ce": bench_linear_ce,
        "elementwise": bench_elementwise,
        "gated_delta": bench_gated_delta,
        "ring": bench_ring_blocks,
        "stochastic": bench_stochastic,
        "moe_ffn": bench_moe_ffn,
        "mla_decode": bench_mla_decode,
        "decode_attn": bench_decode_attn,
    }
    for name, fn in benches.items():
        if args.only is None or args.only == name:
            fn(args.tiny)


if __name__ == "__main__":
    main()
