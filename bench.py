"""Benchmark: training throughput on one TPU chip (dense LM + Qwen3-MoE).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} for the
dense headline row, with the Qwen3-MoE north-star row (BASELINE.json:
tokens/sec/chip + MFU on Qwen3-MoE pretrain) under ``detail.moe``.
The reference publishes no absolute numbers (BASELINE.md), so the baseline
is this repo's own best recorded measurement (RECORDED below, mirrored in
BASELINE.md's measured-rows table); vs_baseline = value / recorded.

MFU convention (VERDICT r2 Weak #3): ``mfu`` is MODEL-flop utilisation —
6N FLOPs per token per active param plus exact attention FLOPs, regardless
of remat — and ``hfu`` (detail) counts the remat forward as useful work
(8N). For MoE, "active params" counts dense/shared weights once and expert
weights scaled by top_k/num_experts.

Uses only the public Trainer API (``Trainer.run_step``); covered by
tests/test_bench.py so it cannot silently rot against loop refactors.
"""

import json
import time

# Peak-FLOPs table and FLOPs-per-token inventory shared with the
# trainer's live-MFU gauge (d9d_tpu/telemetry/flops.py) — one convention,
# so live and bench-reported MFU cannot drift apart
from d9d_tpu.telemetry.flops import (  # noqa: E402
    device_peak_flops,
    model_flops_per_token,
)

# Best previously recorded results (BASELINE.md measured rows).
RECORDED_DENSE = {"v5 lite": 48163.0, "v5e": 48163.0}
RECORDED_MOE = {"v5 lite": 25280.0, "v5e": 25280.0}
RECORDED_HYBRID: dict[str, float] = {}  # no chip row yet (BASELINE cfg 5)


def _flops_accounting(cfg, *, seq_len, active_param_count):
    """(model_flops_per_token, hardware_flops_per_token).

    The model term is telemetry/flops.py's shared inventory: 6N_active +
    quadratic attention on the non-linear layers (causal QK^T + PV
    fwd+bwd = 12·L·H·D·T/2 per token) + the GDN chunked delta rule on
    ``linear_attention_layers``. HFU additionally counts the remat
    forward recompute as useful work (8N)."""
    model = model_flops_per_token(
        active_param_count, seq_len=seq_len, config=cfg
    )
    hardware = model + (2.0 * active_param_count if cfg.remat else 0.0)
    return model, hardware


def _measure(trainer, data_iter, *, warmup, steps, batch, seq_len,
             profile_tag=None):
    # run_step is one jitted executable, so its metrics are ready only
    # when the whole step has run (chip_smoke.py's sync check shows that
    # block_until_ready waits on the chip)
    import os

    import jax

    # telemetry JSONL alongside the bench row (per-step dispatch spans +
    # tokens/s gauge) when D9D_TELEMETRY_DIR is set; a span costs ~µs
    # against multi-ms steps, so the recorded numbers stay honest
    from d9d_tpu.telemetry import attached_jsonl_sink

    tele_dir = os.environ.get("D9D_TELEMETRY_DIR")
    with attached_jsonl_sink(
        tele_dir, run_name=f"bench_{profile_tag or 'train'}"
    ) as (tele, tele_sink):
        if tele_sink is not None:
            # each leg (dense/moe/hybrid) gets its own file; clear the
            # shared hub's instruments so this leg's flush doesn't report
            # the previous legs' cumulative counters/histograms
            tele.reset_instruments()
        try:
            for _ in range(warmup):
                m = trainer.run_step(next(data_iter))
            jax.block_until_ready(m)
            t0 = time.perf_counter()
            for k in range(steps):
                # host dispatch only: run_step returns before the device
                # finishes (async dispatch), so this span is NOT step wall
                # time — named bench/dispatch to keep it distinct from the
                # trainer's synchronous train/step timeline
                with tele.span("bench/dispatch", step=k):
                    m = trainer.run_step(next(data_iter))
            jax.block_until_ready(m)
            dt = time.perf_counter() - t0
            tok_s = steps * batch * seq_len / dt
            tele.counter("train/tokens").add(steps * batch * seq_len)
            tele.gauge("train/tokens_per_s").set(tok_s)
        finally:
            # a raising step must not leave this leg's events unflushed
            if tele_sink is not None:
                tele.flush(step=steps)

    # optional SEPARATE traced pass (after timing, so trace collection
    # can't inflate the recorded numbers): set D9D_BENCH_PROFILE_DIR and
    # feed the capture to tools/trace_summary.py
    profile_root = os.environ.get("D9D_BENCH_PROFILE_DIR")
    if profile_root and profile_tag:
        from d9d_tpu.core.tracing import trace

        with trace(os.path.join(profile_root, profile_tag)):
            for _ in range(2):
                m = trainer.run_step(next(data_iter))
            jax.block_until_ready(m)
    return tok_s


def _utilisation(tok_per_s, flops_per_token):
    """tokens/s x FLOPs/token over the chip's peak, or None off the TPU
    (the --tiny CPU rig has no peak: the number is not measured there)."""
    peak = device_peak_flops()
    if peak is None:
        return None
    return round(tok_per_s * flops_per_token / peak, 4)


def run_bench(*, tiny: bool = False) -> dict:
    """Dense-LM row (the recorded headline)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from d9d_tpu.core import MeshParameters
    from d9d_tpu.loop import (
        AdamWProvider,
        CausalLMTask,
        DatasetProvider,
        ModelProvider,
        Trainer,
        TrainerConfig,
    )
    from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
    from d9d_tpu.nn.sdpa import build_sdpa_backend
    from d9d_tpu.parallel import replicate_plan

    if tiny:
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 256),),
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            intermediate_size=128,
            remat=False,
        )
        seq_len, batch = 64, 4
        steps_warmup, steps_measure = 1, 2
        dtype = jnp.float32
    else:
        import os

        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 32_768),),
            hidden_size=1024,
            num_layers=12,
            num_heads=16,
            num_kv_heads=8,
            head_dim=64,
            intermediate_size=4096,
            remat=True,
            # tuning knob for on-chip sweeps (BASELINE.md methodology)
            remat_policy=os.environ.get("D9D_BENCH_REMAT_POLICY", "full"),
            # r4 MFU lever: q/k/v as one matmul (single chip: no TP axis
            # to reshard). A/B with D9D_BENCH_FUSED_QKV=0.
            fused_qkv=os.environ.get("D9D_BENCH_FUSED_QKV", "1") == "1",
        )
        # batch knob for on-chip sweeps: more rows per step amortize
        # per-kernel overheads if HBM allows (full remat leaves plenty)
        seq_len, batch = 2048, int(os.environ.get("D9D_BENCH_BATCH", "8"))
        steps_warmup, steps_measure = 3, 10
        dtype = jnp.bfloat16

    class Provider(ModelProvider):
        def build_module(self, stage):
            return Qwen3DenseCausalLM(
                config=cfg, sdpa=build_sdpa_backend(), stage=stage,
                dtype=dtype,
            )

        def build_plan(self, c):
            return replicate_plan(c)

        def sample_inputs(self, batch_size, seq_len):
            z = jnp.zeros((batch_size, seq_len), jnp.int32)
            return (z, z, z)

    class Data(DatasetProvider):
        def build(self):
            rng = np.random.RandomState(0)
            while True:
                yield {
                    "input_ids": rng.randint(
                        0, cfg.vocab_size, size=(batch, seq_len + 1)
                    )
                }

    ctx = MeshParameters().build(jax.devices()[:1])
    trainer = Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=batch,
            microbatch_size=batch,
            seq_len=seq_len,
            total_steps=steps_warmup + steps_measure,
            log_every=10_000,
        ),
        model_provider=Provider(),
        dataset_provider=Data(),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(weight_decay=0.0),
    )

    tok_per_s = _measure(
        trainer, iter(Data().build()), warmup=steps_warmup,
        steps=steps_measure, batch=batch, seq_len=seq_len,
        profile_tag=None if tiny else "dense",
    )
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(trainer.params)
    )
    model_fpt, hw_fpt = _flops_accounting(
        cfg, seq_len=seq_len, active_param_count=n_params
    )
    kind = jax.devices()[0].device_kind.lower()
    recorded = next(
        (v for k, v in RECORDED_DENSE.items() if k in kind), None
    )
    vs_baseline = round(tok_per_s / recorded, 4) if (
        recorded is not None and not tiny
    ) else 1.0

    return {
        "metric": "dense_lm_tokens_per_sec_per_chip",
        "value": round(tok_per_s, 1),
        "unit": "tokens/s",
        "vs_baseline": vs_baseline,
        "detail": {
            "mfu": _utilisation(tok_per_s, model_fpt),
            "hfu": _utilisation(tok_per_s, hw_fpt),
            "params": n_params,
            "seq_len": seq_len,
            "batch": batch,
            "steps": steps_measure,
            "device": jax.devices()[0].device_kind,
        },
    }


def run_bench_moe(*, tiny: bool = False, hybrid: bool = False) -> dict:
    """Qwen3-MoE pretrain row — the BASELINE.json north-star metric.

    Single chip: local MoE path (no EP axes), auto SDPA (pallas flash on
    TPU), fused CCE, remat — target-config shape per the reference example
    (example/qwen3_moe/pretrain.json:57-80: 16 layers, 128 experts, top-8,
    hidden 768), sized to fit one chip's HBM.

    ``hybrid=True`` benches the Qwen3-Next-style family instead (BASELINE
    config 5): the same MoE stack with GatedDeltaNet on 3 of every 4
    layers (3:1 GDN:attention), sigmoid attention output gates, partial
    RoPE and zero-centered norms — the linear-attention hot path running
    through ops/gated_delta.py's chunked WY form.
    """
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from d9d_tpu.core import MeshParameters
    from d9d_tpu.loop import (
        AdamWProvider,
        CausalLMTask,
        DatasetProvider,
        ModelProvider,
        Trainer,
        TrainerConfig,
    )
    from d9d_tpu.loop.control.providers import OptimizerProvider
    from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig
    from d9d_tpu.nn.sdpa import build_sdpa_backend
    from d9d_tpu.optim import StochasticAdamW
    from d9d_tpu.parallel import replicate_plan

    class StochasticAdamWProvider(OptimizerProvider):
        def build(self, learning_rate):
            return StochasticAdamW(
                learning_rate,
                weight_decay=0.0,
                moment_dtype=jnp.bfloat16,
            )

    def hybrid_overrides(n_layers):
        """Qwen3-Next-style geometry: GDN everywhere except every 4th
        layer (3:1 ratio), gated attention, partial RoPE, zero-centered
        norms — ONE definition so the tiny CI config and the benched chip
        config can't drift apart."""
        if not hybrid:
            return {}
        return {
            "linear_attention_layers": tuple(
                i for i in range(n_layers) if i % 4 != 3
            ),
            "use_output_gate": True,
            "rope_fraction": 0.25,
            "zero_centered_norms": True,
        }

    if tiny:
        cfg = Qwen3MoeConfig(
            vocab_ranges=(("default", 256),),
            hidden_size=64,
            num_layers=2 if not hybrid else 4,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            moe_intermediate_size=64,
            num_experts=8,
            num_experts_per_tok=2,
            remat=False,
            **hybrid_overrides(4),
        )
        seq_len, batch = 64, 4
        steps_warmup, steps_measure = 1, 2
        dtype = jnp.float32
    else:
        # reference example shape (pretrain.json: 16L, 128 experts, top-8,
        # h768) scaled to one chip's HBM: 64 experts x i256 keeps total
        # params + fp32 AdamW moments ~8 GB (fits a 16 GB v5e; 128E x i384
        # would need ~22 GB)
        cfg = Qwen3MoeConfig(
            vocab_ranges=(("default", 32_768),),
            hidden_size=768,
            num_layers=16,
            num_heads=12,
            num_kv_heads=4,
            head_dim=64,
            moe_intermediate_size=256,
            num_experts=64,
            num_experts_per_tok=8,
            remat=True,
            # tuning knob for on-chip sweeps, like the dense row's
            remat_policy=os.environ.get("D9D_BENCH_REMAT_POLICY", "full"),
            # r4 MFU lever, as in the dense row
            fused_qkv=os.environ.get("D9D_BENCH_FUSED_QKV", "1") == "1",
            **hybrid_overrides(16),
        )
        seq_len, batch = 2048, 8
        steps_warmup, steps_measure = 3, 10
        dtype = jnp.bfloat16

    # dropless MoE expands each token top_k x before the grouped matmuls:
    # at microbatch 8 the [B*T*top_k, D] ragged-dot temps alone are
    # ~20 x 192 MB and blow a 16 GB chip's HBM; with fp32 AdamW moments
    # even microbatch 2 needs 16.56G (params+moments 7.6G, temps 8.95G
    # incl. the fp32 grad accumulator — measured r3). StochasticAdamW with
    # bf16 moments (the reference's own optimizer family) cuts optimizer
    # state to 2.7G, which fits microbatch 2 — set D9D_BENCH_MOE_UB=2 to
    # run that variant; the recorded row is the validated microbatch-1 one.
    microbatch = batch if tiny else int(os.environ.get("D9D_BENCH_MOE_UB", "1"))

    # D9D_BENCH_MOE_ZERO=1: ZeRO-style optimizer-state sharding over
    # dp_replicate (parallel/zero.py) — the mesh spans every visible
    # chip as dp_r and each chip streams 1/N of the fp32 masters/Adam
    # moments per step (docs/design/zero_sharding.md). On one chip this
    # is dp_r=1 (the code path still runs; the 1/N claim needs the
    # four-chip host). The per-chip global batch is held constant:
    # tokens/s/chip stays the recorded metric.
    zero = os.environ.get("D9D_BENCH_MOE_ZERO", "0") == "1"
    n_dev = len(jax.devices())
    dp_replicate = (min(n_dev, 4) if tiny else n_dev) if zero else 1
    if zero and not tiny:
        # constant per-chip load: global batch AND the (DP-global)
        # microbatch scale by the replica count, so per-chip µBS and
        # num_microbatches match the single-chip leg exactly
        batch = batch * dp_replicate
        microbatch = microbatch * dp_replicate
    # per-chip µBS drives the fp32-vs-bf16-master recipe choice below
    ub_chip = microbatch // dp_replicate

    class Provider(ModelProvider):
        def build_module(self, stage):
            return Qwen3MoeCausalLM(
                config=cfg, sdpa=build_sdpa_backend(), stage=stage,
                dtype=dtype,
                # the microbatch>=2 variant runs the reference's flagship
                # recipe — bf16 master weights + stochastic-rounding AdamW
                # — which also removes the per-traversal fp32->bf16 cast
                # of every weight (2.7G of fp32 reads per pass)
                param_dtype=jnp.float32 if ub_chip <= 1 or tiny
                else jnp.bfloat16,
                # "auto" (the r4 default) encodes the r3 sweep: one
                # chunk at n<=2048 (the µBS=1 win: 25.3k vs 24.5k tok/s),
                # 512 beyond — no per-config pin needed anymore
                ce_chunk_size="auto",
            )

        def build_plan(self, c):
            return replicate_plan(c)

        def sample_inputs(self, batch_size, seq_len):
            z = jnp.zeros((batch_size, seq_len), jnp.int32)
            return (z, z, z)

    class Data(DatasetProvider):
        def build(self):
            rng = np.random.RandomState(0)
            while True:
                yield {
                    "input_ids": rng.randint(
                        0, cfg.vocab_size, size=(batch, seq_len + 1)
                    )
                }

    ctx = MeshParameters(dp_replicate=dp_replicate).build(
        jax.devices()[:dp_replicate]
    )
    trainer = Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=batch,
            microbatch_size=microbatch,
            seq_len=seq_len,
            total_steps=steps_warmup + steps_measure,
            log_every=10_000,
            zero_sharding=zero,
        ),
        model_provider=Provider(),
        dataset_provider=Data(),
        task=CausalLMTask(),
        # microbatch 1 (the recorded row) fits fp32-moment AdamW; larger
        # microbatches only fit with bf16 moments (see note above)
        optimizer_provider=AdamWProvider(weight_decay=0.0)
        if ub_chip <= 1 or tiny
        else StochasticAdamWProvider(),
    )
    opt_state_bytes_per_chip = trainer.opt_state_bytes_per_chip()

    tok_per_s = _measure(
        trainer, iter(Data().build()), warmup=steps_warmup,
        steps=steps_measure, batch=batch, seq_len=seq_len,
        profile_tag=None if tiny else ("hybrid" if hybrid else "moe"),
    )
    # the recorded metric is tokens/sec/CHIP: the multi-replica ZeRO leg
    # measures whole-mesh throughput over dp_replicate chips
    tok_per_s /= dp_replicate

    # active params: experts scaled by top_k/num_experts, everything else
    # 1x — the same shared accounting the trainer's live-MFU gauge uses
    from d9d_tpu.telemetry.flops import active_param_count

    total_params = sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(trainer.params)
    )
    active = active_param_count([trainer.params], cfg)
    # hybrid: quadratic-attention FLOPs only on the attention layers; the
    # GDN layers' chunked delta rule counted from its matmul inventory
    model_fpt, hw_fpt = _flops_accounting(
        cfg, seq_len=seq_len, active_param_count=active,
    )
    kind = jax.devices()[0].device_kind.lower()
    recorded_tbl = RECORDED_HYBRID if hybrid else RECORDED_MOE
    recorded = next((v for k, v in recorded_tbl.items() if k in kind), None)
    if recorded is not None and not tiny:
        vs_baseline = round(tok_per_s / recorded, 4)
    else:
        # no recorded row yet (or tiny CI config): report null rather
        # than fabricating parity; the dense headline keeps the driver's
        # numeric contract
        vs_baseline = None if hybrid else 1.0
    return {
        "metric": (
            "qwen3_next_hybrid_tokens_per_sec_per_chip"
            if hybrid else "qwen3_moe_tokens_per_sec_per_chip"
        ),
        "value": round(tok_per_s, 1),
        "unit": "tokens/s",
        "vs_baseline": vs_baseline,
        "detail": {
            "mfu": _utilisation(tok_per_s, model_fpt),
            "hfu": _utilisation(tok_per_s, hw_fpt),
            "total_params": total_params,
            "active_params": int(active),
            "seq_len": seq_len,
            "batch": batch,
            "steps": steps_measure,
            "device": jax.devices()[0].device_kind,
            # ZeRO observability (docs/design/zero_sharding.md): the 1/N
            # optimizer-state claim as an executable number — mirrors
            # the opt/state_bytes_per_chip telemetry gauge
            "zero_sharding": zero,
            "dp_replicate": dp_replicate,
            "opt_state_bytes_per_chip": opt_state_bytes_per_chip,
        },
    }


def run_bench_input_pipeline(*, tiny: bool = False) -> dict:
    """Input-pipeline overlap check (VERDICT r3 item 4 done-criterion).

    Three step-time measurements on the same dense model:

    - ``synthetic``: one pre-staged device batch reused every step — the
      floor with zero input work;
    - ``sync``: a REAL tokenized dataset (host-side doc packing per batch)
      fetched + staged on the step path (``Trainer.run_step``);
    - ``prefetch``: the same dataset through ``BatchPrefetcher`` (the
      ``train()`` loop's default) — fetch/prepare/stage on a producer
      thread, ``depth=2``.

    Overlap is proven when ``prefetch`` ≈ ``synthetic`` while ``sync``
    carries the data cost. Matches the reference's worker-backed loader
    (d9d/loop/component/data_loader_factory.py:102).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from d9d_tpu.core import MeshParameters
    from d9d_tpu.loop import (
        AdamWProvider,
        CausalLMTask,
        DatasetProvider,
        ModelProvider,
        Trainer,
        TrainerConfig,
    )
    from d9d_tpu.loop.components.prefetch import BatchPrefetcher
    from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
    from d9d_tpu.nn.sdpa import build_sdpa_backend
    from d9d_tpu.parallel import replicate_plan
    from tools.benchtime import timeit

    if tiny:
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 256),), hidden_size=64, num_layers=2,
            num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128,
            remat=False,
        )
        seq_len, batch = 64, 4
        warmup, steps = 1, 2
        dtype = jnp.float32
    else:
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 32_768),), hidden_size=1024,
            num_layers=12, num_heads=16, num_kv_heads=8, head_dim=64,
            intermediate_size=4096, remat=True,
        )
        seq_len, batch = 2048, 8
        warmup, steps = 3, 10
        dtype = jnp.bfloat16

    class Provider(ModelProvider):
        def build_module(self, stage):
            return Qwen3DenseCausalLM(
                config=cfg, sdpa=build_sdpa_backend(), stage=stage,
                dtype=dtype,
            )

        def build_plan(self, c):
            return replicate_plan(c)

        def sample_inputs(self, batch_size, seq_len):
            z = jnp.zeros((batch_size, seq_len), jnp.int32)
            return (z, z, z)

    def tokenized_stream():
        """Real input-pipeline work per batch: variable-length 'documents'
        packed into fixed [batch, seq+1] rows (the tokenize-and-pack host
        cost a production loader pays)."""
        rng = np.random.RandomState(0)
        need = batch * (seq_len + 1)
        while True:
            docs = []
            have = 0
            while have < need:
                doc = rng.randint(
                    0, cfg.vocab_size, size=rng.randint(64, 512)
                ).astype(np.int32)
                docs.append(doc)
                have += len(doc)
            stream = np.concatenate(docs)[:need]
            yield {"input_ids": stream.reshape(batch, seq_len + 1)}

    class Data(DatasetProvider):
        def build(self):
            return tokenized_stream()

    ctx = MeshParameters().build(jax.devices()[:1])
    trainer = Trainer(
        ctx=ctx,
        config=TrainerConfig(
            global_batch_size=batch, microbatch_size=batch, seq_len=seq_len,
            total_steps=10_000, log_every=10_000,
        ),
        model_provider=Provider(),
        dataset_provider=Data(),
        task=CausalLMTask(),
        optimizer_provider=AdamWProvider(weight_decay=0.0),
    )

    # synthetic floor: one staged batch reused, no input work at all
    staged = trainer._stage_batch(next(tokenized_stream()))
    synthetic_ms = timeit(
        lambda: trainer._optimizer_step(staged), reps=steps, warmup=warmup
    )

    # sync: real dataset fetched + staged on the step path
    sync_iter = tokenized_stream()
    sync_ms = timeit(
        lambda: trainer.run_step(next(sync_iter)), reps=steps, warmup=warmup
    )

    # prefetch: same dataset through the producer thread (train() default)
    pf = BatchPrefetcher(tokenized_stream(), trainer._stage_batch, depth=2)
    try:
        prefetch_ms = timeit(
            lambda: trainer._optimizer_step(next(pf)), reps=steps,
            warmup=warmup,
        )
    finally:
        pf.close()

    return {
        "metric": "input_pipeline_step_ms",
        "synthetic_ms": round(synthetic_ms, 2),
        "sync_ms": round(sync_ms, 2),
        "prefetch_ms": round(prefetch_ms, 2),
        "overlap_recovered": round(
            (sync_ms - prefetch_ms) / max(sync_ms - synthetic_ms, 1e-9), 3
        ),
        "steps": steps,
    }


def run_bench_generate(*, tiny: bool = False) -> dict:
    """Autoregressive decode throughput (loop/generate.py) on the dense
    headline geometry: batch rows decode greedily from a KV cache; the
    metric is generated tokens/sec/chip (decode is HBM-bound — each token
    re-reads the weights — so this row tracks effective weight-stream
    bandwidth, not MXU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from d9d_tpu.loop.generate import generate
    from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
    from d9d_tpu.nn.sdpa import build_sdpa_backend
    from d9d_tpu.ops.attention.pallas_decode import decode_attention_backend

    if tiny:
        cfg = Qwen3DenseConfig.tiny()
        batch, prompt, gen = 2, 8, 8
        dtype = jnp.float32
    else:
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 32_768),),
            hidden_size=1024,
            num_layers=12,
            num_heads=16,
            num_kv_heads=8,
            head_dim=64,
            intermediate_size=4096,
            remat=False,
        )
        batch, prompt, gen = 8, 128, 256
        dtype = jnp.bfloat16
    model = Qwen3DenseCausalLM(
        config=cfg, sdpa=build_sdpa_backend(), dtype=dtype,
        decode_max_length=prompt + gen,
    )
    z = jnp.zeros((batch, prompt), jnp.int32)
    pos = jnp.broadcast_to(
        jnp.arange(prompt, dtype=jnp.int32), (batch, prompt)
    )
    params = model.init(jax.random.PRNGKey(0), z, pos, z)["params"]
    # inference-weight width A/B: tools/roofline.py attributes most of the
    # decode step (~92%) to streaming fp32 master weights; D9D_BENCH_DECODE_BF16
    # casts the params once up front (what a deployment would serve)
    import os as _os

    infer_bf16 = _os.environ.get("D9D_BENCH_DECODE_BF16", "0") == "1"
    if infer_bf16:
        params = jax.tree.map(
            lambda p: p.astype(jnp.bfloat16)
            if jnp.issubdtype(p.dtype, jnp.floating) else p,
            params,
        )
    rng = np.random.RandomState(0)
    prompt_ids = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (batch, prompt)), jnp.int32
    )

    run = jax.jit(
        lambda prm, p_ids: generate(model, prm, p_ids, max_new_tokens=gen)
    )
    jax.block_until_ready(run(params, prompt_ids))  # compile + warmup
    reps = 1 if tiny else 3
    t0 = time.perf_counter()
    for _ in range(reps):
        out = run(params, prompt_ids)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    tok_s = reps * batch * gen / dt
    return {
        "metric": "dense_lm_decode_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": 1.0,  # first recorded decode row
        "detail": {
            "batch": batch,
            "prompt": prompt,
            "new_tokens": gen,
            "weights": "bf16" if infer_bf16 else "fp32_masters",
            "decode_attn": decode_attention_backend(),
            "device": jax.devices()[0].device_kind,
        },
    }


def run_bench_serving(*, tiny: bool = False) -> dict:
    """Steady-state serving throughput through ``ContinuousBatcher``
    (VERDICT r5 Weak #5: serving had no throughput story).

    Drives a Poisson-ish arrival queue through the fused K-step decode
    loop on the dense decode geometry and reports generated tokens/sec,
    slot-utilization %, and host dispatches per 1k tokens, with the
    per-token stepping mode as the pinned before/after comparison
    (tools/bench_serve.py is the CPU-runnable sweep this leg mirrors).
    Decode is HBM-bound per token like run_bench_generate; what this row
    adds is the HOST side — whether dispatch latency can starve the chip
    between chunks at serving batch sizes.
    """
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.bench_serve import build_model, make_workload, run_mode

    model, params, cfg = build_model(tiny)
    batch = 2 if tiny else 8
    n_req = 4 if tiny else 24
    gen_hi = 12 if tiny else 128
    workload = make_workload(
        vocab=cfg.vocab_size, requests=n_req, seed=0,
        prompt_lo=2, prompt_hi=6 if tiny else 32,
        gen_lo=4, gen_hi=gen_hi, mean_interarrival=gen_hi / batch,
    )
    k = int(os.environ.get("D9D_BENCH_SERVE_K", "8"))
    from d9d_tpu.telemetry import attached_jsonl_sink

    # one sink across both modes; run_mode's post-warmup
    # reset_instruments() isolates each mode's flush snapshot
    with attached_jsonl_sink(
        os.environ.get("D9D_TELEMETRY_DIR"), run_name="bench_serving"
    ) as (hub, sink):

        def _timed_mode(mode_index, **kw):
            try:
                return run_mode(
                    model, params, workload, batch_size=batch, **kw
                )
            finally:
                if sink is not None:
                    hub.flush(step=mode_index)

        fused, fused_out = _timed_mode(0, chunk_size=k, overlap=True)
        per_tok, per_tok_out = _timed_mode(1, chunk_size=None, overlap=False)
    return {
        "metric": "serving_tokens_per_sec_per_chip",
        "value": round(fused["tok_per_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": 1.0,  # first recorded serving row
        "detail": {
            "chunk_k": k,
            "slot_utilization": round(fused["slot_utilization"], 4),
            "dispatches_per_1k_tokens": round(
                fused["dispatches_per_1k_tokens"], 2
            ),
            "per_token_tok_per_s": round(per_tok["tok_per_s"], 1),
            "per_token_dispatches_per_1k_tokens": round(
                per_tok["dispatches_per_1k_tokens"], 2
            ),
            # introspection columns (telemetry/introspect.py): a warmed
            # steady-state serving loop must not compile at all
            "steady_state_compiles": fused["steady_state_compiles"],
            "recompiles": fused["recompiles"],
            "speedup_vs_per_token": round(
                fused["tok_per_s"] / max(per_tok["tok_per_s"], 1e-9), 3
            ),
            "exact_vs_per_token": fused_out == per_tok_out,
            "requests": n_req,
            "batch": batch,
            "device": __import__("jax").devices()[0].device_kind,
        },
    }


def run_bench_pp_fused() -> dict:
    """Fused-PP dispatch tax row (ISSUE 16): the tiny 1F1B schedule
    through the legacy per-action interpreter vs the compiled-run
    executor, counting real executable dispatches at the one point both
    runtimes share — ``TrackedJit.__call__``.

    Both counts are structural (what the host enqueues per step), not
    wall-clock, so the row is exactly reproducible on any backend; the
    same leg is pinned by tools/bench_compare.py's ``pp_micro.*`` gate
    on CPU. What running it on the chip adds is the proof that the
    fused programs compile and execute there.
    """
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.bench_compare import PP_MICRO, run_pp_micro

    m = run_pp_micro()
    return {
        "metric": "pp/dispatches_per_step",
        "value": m["pp_micro.dispatches_per_step"],
        "unit": "dispatches",
        "vs_baseline": 1.0,  # first recorded fused-PP row
        "detail": {
            "pp/fused_programs": m["pp_micro.fused_programs"],
            "legacy_dispatches_per_step":
                m["pp_micro.legacy_dispatches_per_step"],
            "dispatch_reduction_x": m["pp_micro.dispatch_reduction_x"],
            "exact_vs_legacy": m["pp_micro.exact_vs_legacy"],
            "multirank_dispatches_per_step":
                m["pp_micro.multirank_dispatches_per_step"],
            "multirank_fused_programs":
                m["pp_micro.multirank_fused_programs"],
            "multirank_dispatch_reduction_x":
                m["pp_micro.multirank_dispatch_reduction_x"],
            "multirank_exact_vs_legacy":
                m["pp_micro.multirank_exact_vs_legacy"],
            "num_microbatches": PP_MICRO["num_microbatches"],
            "stages_per_rank": PP_MICRO["stages_per_rank"],
            "multirank_pp": PP_MICRO["multirank_pp"],
            "device": __import__("jax").devices()[0].device_kind,
        },
    }


def _nested(row: dict) -> dict:
    """A leg's result flattened for the headline's ``detail``."""
    return {
        "metric": row["metric"],
        "value": row["value"],
        "unit": row["unit"],
        "vs_baseline": row["vs_baseline"],
        **row["detail"],
    }


def main():
    import os
    import sys

    # tools/ sits next to this file; anchor the import so bench.py works
    # when invoked from any cwd
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from d9d_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    tiny = "--tiny" in sys.argv[1:]
    if tiny:
        # harness check at toy widths on whatever backend is there; its
        # numbers are not device measurements
        print(json.dumps(run_bench(tiny=True)))
        return
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py measures on the TPU; found {device.platform!r} "
            f"({device.device_kind}). Use --tiny to check the harness "
            "on another backend."
        )
    # every leg runs in this process (one process holds the chip), and a
    # leg that raises ends the run non-zero: a partial result is no result
    out = run_bench()
    out["detail"]["moe"] = _nested(run_bench_moe())
    # BASELINE config 5: the hybrid (Qwen3-Next/GDN) family
    out["detail"]["hybrid"] = _nested(run_bench_moe(hybrid=True))
    # steady-state serving row (fused K-step ContinuousBatcher decode
    # loop vs per-token stepping)
    out["detail"]["serving"] = _nested(run_bench_serving())
    # fused-PP dispatch row (ISSUE 16): structural counts
    out["detail"]["pp"] = _nested(run_bench_pp_fused())
    print(json.dumps(out))


if __name__ == "__main__":
    main()
