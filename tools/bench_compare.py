"""The CPU count gate: diff a summary of structural counts against the
committed baseline snapshot, exit nonzero on regression.

A tripwire that needs no chip and states no speed (a speed is a row of
``benchmarks/run.py`` on the chip; ``BENCHMARK.json``, ``PERF.md``):

- ``--run-micro`` drives a tiny ``ContinuousBatcher`` workload on CPU
  (seconds, deterministic seed) and collects the counts that mean the
  same on any backend: host dispatches per 1k tokens, readbacks,
  emitted tokens, pages, compile counts and recompiles (from the
  ``telemetry/introspect.py`` inventory). No wall clock is read: a CPU
  timing says nothing about the device and is never written under a
  device metric's name. Mid-bench the workload PUBLISHES the model's
  own weights back into the live batcher (``install_weights`` — the
  elastic train→serve handoff, docs/design/elasticity.md): a publish
  must add zero
  steady-state compiles and zero dispatches, so the same exact-count
  gates that catch a dispatch regression also catch a publish-induced
  recompile.
- ``--current FILE`` compares an existing summary instead of running.

Baseline format (``BENCH_BASELINE.json`` at the repo root, committed):

    {"metrics": {"serve_micro.dispatches_per_1k_tokens":
        {"value": 31.25, "direction": "lower", "rel_tol": 0.0}, ...}}

``direction: higher`` fails when ``current < value * (1 - rel_tol)``;
``direction: lower`` fails when ``current > value * (1 + rel_tol)``.
Every metric is a deterministic count at ``rel_tol 0``: any increase is
a real regression. A metric present in the baseline but missing from
the current summary fails (a deleted metric is how a regression hides).

Exit codes: 0 ok, 1 regression, 2 usage/baseline error.

Refresh the baseline after an intended change of a count with:
    python tools/bench_compare.py --run-micro --write-baseline
"""

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
DEFAULT_BASELINE = REPO_ROOT / "BENCH_BASELINE.json"

# micro-workload shape: small enough to compile + run in seconds on the
# 2-core CI rig, big enough that the fused path's dispatch contract
# (1 dispatch per K tokens + boundary resets) is exercised across
# multiple chunks and an admission wave
MICRO = dict(batch_size=2, requests=6, chunk_k=4, gen_lo=4, gen_hi=10)


def _drive_micro(
    batcher,
    workload,
    params,
    publish: bool = True,
    *,
    front=None,
    publish_fn=None,
) -> None:
    """Drive the deterministic micro workload (after warmup/reset).

    ONE loop serves every leg, so the byte-identical structural gates
    always compare the same arrival/clock/drain semantics: ``front``
    swaps the submit/step/drain surface (the autopilot leg passes its
    1-replica ``ServingFleet``, whose ``step()`` polls the control loop
    at every round boundary) while ``batcher`` stays the stats/clock
    source. ``publish_fn`` swaps the mid-bench publish action (the
    autopilot leg canary-publishes so its decision loop replaces the
    direct ``install_weights`` — the same exact-count gates that catch
    a dispatch regression then also catch a control-loop action that
    dispatches). ``publish=False`` skips the mid-bench publish — the
    prefix leg uses it because a publish correctly INVALIDATES the
    prefix cache (cached KV is weights-dependent), and that leg gates
    steady-state hit economics, not publish cost."""
    if front is None:
        front = batcher
    pending = list(workload)
    clock = 0
    publishes = 0 if publish else 1
    while pending:
        while pending and pending[0][0] <= clock:
            _, prompt, gen = pending.pop(0)
            front.submit(prompt, max_new_tokens=gen)
        if publishes == 0 and len(pending) <= MICRO["requests"] // 2:
            # live weight publish mid-bench: re-installing the same tree
            # exercises the full swap path (stage → boundary apply →
            # generation bump) without changing emissions — the
            # steady_state_compiles/host_dispatches gates then prove a
            # publish is dispatch- and recompile-free
            if publish_fn is not None:
                publish_fn(params)
            else:
                batcher.install_weights(params)
            publishes += 1
        if batcher.active:
            before = batcher.stats.device_steps
            if front is batcher:
                batcher.step_chunk()
            else:
                front.step()
            clock += batcher.stats.device_steps - before
        elif pending:
            clock = pending[0][0]
    front.drain()


def _scrape_and_check(server) -> tuple[int, str]:
    """One /metrics scrape: returns (ok, text). ok=1 requires the body
    to parse as Prometheus text exposition (every sample line is
    ``name{labels} value``) and to carry the serving counters."""
    import re
    import urllib.request

    with urllib.request.urlopen(server.url("/metrics"), timeout=10) as r:
        text = r.read().decode()
    sample = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9eE+.infNa-]+$"
    )
    ok = all(
        sample.match(line)
        for line in text.splitlines()
        if line and not line.startswith("#")
    )
    ok = ok and "d9d_serve_tokens" in text
    return (1 if ok else 0), text


def run_micro() -> dict:
    """The CPU serving microbench: returns ``{"metrics": {name: value}}``.

    Deterministic given the seed: the arrival schedule is released
    against the batcher's own device-step clock, sampling is greedy,
    and compile counts come from the introspection inventory.

    Five legs: **plain** (the historical gate), **exporter-enabled** —
    a replica-labeled batcher with the live /metrics endpoint up, an
    SLO monitor attached, and one mid-run scrape — **paged** (the SAME
    workload through a paged-KV batcher: its structural counts must be
    byte-identical to the plain leg's and its tokens exactly equal —
    paging adds zero dispatches/readbacks/steady-state compiles per
    token), **prefix** (a shared-system-prompt workload through a
    paged batcher with the content-hashed prefix cache on: gates the
    hit rate, the HBM-bytes-per-concurrent-request reduction vs the
    dense layout, and its own structural counts), and **autopilot**
    (the same workload through a 1-replica ``ServingFleet`` with the
    SLO autopilot control loop attached and the mid-run publish
    upgraded to a canaried publish the autopilot promotes: structural
    counts must stay byte-identical to the plain leg — the control
    loop acts only at round boundaries). The exporter leg's
    structural counts must be IDENTICAL to the plain leg's (the
    monitoring plane adds zero dispatches, zero readbacks, zero
    steady-state compiles). What the plane costs in wall clock is a
    chip run's to say (not measured yet).
    """
    import os

    from tools.bench_serve import (
        build_model,
        make_shared_prefix_workload,
        make_workload,
    )

    from d9d_tpu.loop.serve import ContinuousBatcher
    from d9d_tpu.telemetry import (
        MetricsServer,
        SloMonitor,
        SloPolicy,
        get_telemetry,
        introspect,
    )

    model, params, cfg = build_model()
    workload = make_workload(
        vocab=cfg.vocab_size, requests=MICRO["requests"], seed=0,
        prompt_lo=2, prompt_hi=6, gen_lo=MICRO["gen_lo"],
        gen_hi=MICRO["gen_hi"],
        mean_interarrival=MICRO["gen_hi"] / MICRO["batch_size"],
    )
    k = MICRO["chunk_k"]
    # scope every inventory-derived metric to THIS bench's records: the
    # in-process tier-1 gate runs after other tests whose executables
    # (and deliberate recompiles) share the process-wide inventory
    mark_bench = len(introspect.inventory())
    batcher = ContinuousBatcher(
        model, params, batch_size=MICRO["batch_size"],
        chunk_size=k,
    )
    # warmup compiles both fused variants (admit + steady-state) before
    # the measurement window, like the real serving benches
    batcher.submit(workload[0][1], max_new_tokens=2 * k + 2)
    batcher.drain()
    batcher.reset_measurement()
    mark_window = len(introspect.inventory())
    _drive_micro(batcher, workload, params)
    st = batcher.stats
    # snapshot the plain leg's inventory slices BEFORE the exporter leg
    # warms its own batcher (whose warmup compiles must not read as the
    # plain leg's steady-state compiles)
    bench_records = introspect.inventory()[mark_bench:]
    window_records = introspect.inventory()[mark_window:]

    # -- exporter-enabled leg (monitoring-plane overhead contract) -----
    exp = ContinuousBatcher(
        model, params, batch_size=MICRO["batch_size"],
        chunk_size=k, replica_label="r0",
    )
    monitor = SloMonitor([
        SloPolicy(name="bench_ttft_p99", metric="serve/ttft_s",
                  quantile=0.99, target=60.0, window_s=60.0),
        SloPolicy(name="bench_miss_rate", kind="rate",
                  bad="serve/expired", good=("serve/requests_finished",),
                  target=0.01, window_s=60.0),
    ]).attach(get_telemetry())
    server = MetricsServer(port=0).start()
    scrape: dict = {"ok": 0, "text": ""}

    def mid_scrape():
        scrape["ok"], scrape["text"] = _scrape_and_check(server)

    try:
        exp.submit(workload[0][1], max_new_tokens=2 * k + 2)
        exp.drain()
        exp.reset_measurement()
        mark_exp = len(introspect.inventory())
        # the counted window carries the ALWAYS-ON plane (labels, SLO
        # observers, endpoint thread); the scrape lands right after it
        _drive_micro(exp, workload, params)
        mid_scrape()
    finally:
        server.close()
        monitor.detach()
        exp.close()
    scrape_out = os.environ.get("D9D_SCRAPE_OUT")
    if scrape_out and scrape["text"]:
        with open(scrape_out, "w") as fh:
            fh.write(scrape["text"])
    exp_window_records = introspect.inventory()[mark_exp:]

    # -- paged leg: same workload, paged KV cache ----------------------
    # prefix_cache off so the token/step schedule is EXACTLY the plain
    # leg's (warmup re-serves workload[0]'s prompt, which would
    # otherwise hit) — the byte-identical structural gate then means
    # what it says
    pg = ContinuousBatcher(
        model, params, batch_size=MICRO["batch_size"],
        chunk_size=k, page_size=16, prefix_cache=False,
    )
    pg.submit(workload[0][1], max_new_tokens=2 * k + 2)
    pg.drain()
    pg.reset_measurement()
    mark_pg = len(introspect.inventory())
    _drive_micro(pg, workload, params)
    pg_window_records = introspect.inventory()[mark_pg:]
    paged_exact = int(pg.outputs == batcher.outputs)

    # -- quant leg: same workload, int8 KV pages + int8 weight stream --
    # identical schedule to the paged leg (no eos_id → every request
    # runs its full budget, so lossy logits cannot perturb the step
    # clock): the structural counts must be BYTE-identical to the bf16
    # paged leg's — quantization adds zero host interactions and zero
    # steady-state compiles — while the dtype-honest per-request HBM
    # accounting (int8 pools + f32 scale pages vs wide pools) gates the
    # ≥2× page-capacity win. The mid-bench publish installs the
    # QUANTIZED tree, so a publish-induced recompile on the int8 weight
    # stream would trip the same exact-count gates
    from d9d_tpu.loop.quantize import quantize_for_serving

    qparams = quantize_for_serving(params)
    qt = ContinuousBatcher(
        model, qparams, batch_size=MICRO["batch_size"],
        chunk_size=k, page_size=16, prefix_cache=False,
        kv_quant="int8",
    )
    qt.submit(workload[0][1], max_new_tokens=2 * k + 2)
    qt.drain()
    qt.reset_measurement()
    mark_qt = len(introspect.inventory())
    _drive_micro(qt, workload, qparams)
    qt_window_records = introspect.inventory()[mark_qt:]

    # -- prefix leg: shared system prompt through the prefix cache -----
    shared = make_shared_prefix_workload(
        vocab=cfg.vocab_size, requests=MICRO["requests"], seed=0,
        prefix_len=2 * 16 + 2, tail_lo=2, tail_hi=6,
        gen_lo=MICRO["gen_lo"], gen_hi=MICRO["gen_hi"],
        mean_interarrival=MICRO["gen_hi"] / MICRO["batch_size"],
    )
    px = ContinuousBatcher(
        model, params, batch_size=MICRO["batch_size"],
        chunk_size=k, page_size=16,
    )
    # warmup ALSO primes the prefix cache (deliberate: the measured
    # window then shows the steady-state hit rate a shared system
    # prompt reaches, not the one-time cold fill)
    px.submit(shared[0][1], max_new_tokens=2 * k + 2)
    px.drain()
    px.reset_measurement()
    mark_px = len(introspect.inventory())
    _drive_micro(px, shared, params, publish=False)
    px_window_records = introspect.inventory()[mark_px:]
    # dense-layout bytes the same concurrency would have pinned
    px_dense_equiv = px._cache_mgr.kv_bytes_static / max(1, px._cache_mgr.peak_running)

    # -- autopilot leg: same workload through a 1-replica fleet with the
    # FULL control loop attached (SLO monitor + FleetAutopilot polled
    # every scheduling round) and the mid-run publish upgraded to a
    # CANARY publish decided by the autopilot. The contract this gates:
    # the control loop acts only at flush/round boundaries — zero added
    # per-token dispatches/readbacks/compiles, byte-identical structural
    # counts and tokens vs the plain leg (docs/design/elasticity.md
    # "SLO autopilot").
    from d9d_tpu.resilience import (
        AutopilotConfig,
        FleetAutopilot,
        ServingFleet,
        WeightPublisher,
    )

    hub = get_telemetry()
    promotes_before = hub.registry.counter(
        "autopilot/canary_promotes"
    ).value
    ap_pub = WeightPublisher()
    ap_fleet = ServingFleet(publisher=ap_pub)
    ap_b = ContinuousBatcher(
        model, params, batch_size=MICRO["batch_size"],
        chunk_size=k,
    )
    ap_fleet.add_replica(ap_b)
    ap_pub.publish(params)
    ap_monitor = SloMonitor([
        # unreachable targets: the leg gates the always-on control-loop
        # cost, not a scale action (min==max replicas forbids one too)
        SloPolicy(name="bench_ap_ttft_p99", metric="serve/ttft_s",
                  quantile=0.99, target=60.0, window_s=60.0),
    ]).attach(hub)
    autopilot = FleetAutopilot(
        ap_fleet, ap_monitor,
        config=AutopilotConfig(
            # epsilon decision window: promote at the first poll after
            # the canary install (this leg gates control-loop COST, the
            # verdict quality legs live in tests/resilience)
            min_replicas=1, max_replicas=1, canary_window_s=1e-6,
            canary_min_samples=0, eval_interval_s=1.0,
        ),
    ).attach()
    try:
        ap_fleet.submit(workload[0][1], max_new_tokens=2 * k + 2)
        ap_fleet.drain()
        ap_b.reset_measurement()
        mark_ap = len(introspect.inventory())
        _drive_micro(
            ap_b, workload, params,
            front=ap_fleet, publish_fn=autopilot.publish_canary,
        )
    finally:
        autopilot.detach()
        ap_monitor.detach()
        ap_fleet.close()
    ap_window_records = introspect.inventory()[mark_ap:]
    ap_promotes = (
        hub.registry.counter("autopilot/canary_promotes").value
        - promotes_before
    )
    ap_exact = int(ap_b.outputs == batcher.outputs)
    return {
        "schema": 1,
        "workload": dict(MICRO),
        "metrics": {
            # structural (deterministic) — tight thresholds
            "serve_micro.emitted_tokens": st.emitted_tokens,
            "serve_micro.host_dispatches": st.host_dispatches,
            "serve_micro.readbacks": st.readbacks,
            "serve_micro.dispatches_per_1k_tokens": round(
                st.dispatches_per_1k_tokens, 4
            ),
            # compiles in the MEASUREMENT window (a warmed steady-state
            # serve loop must not compile at all) + this bench's recompiles
            "serve_micro.steady_state_compiles": len(window_records),
            "serve_micro.recompiles": sum(
                1 for r in bench_records if r.recompile
            ),
            # the mid-bench publish actually applied (weights generation
            # advanced); its dispatch/compile cost is gated by the
            # exact-count metrics above
            "serve_micro.weight_publishes": batcher.weights_version,
            # exporter leg: same workload with the monitoring plane UP
            # (live /metrics endpoint + replica labels + SLO monitor +
            # one mid-run scrape): identical structural counts — zero
            # added dispatches/readbacks/compiles with the exporter enabled
            "serve_micro.exporter_emitted_tokens": exp.stats.emitted_tokens,
            "serve_micro.exporter_host_dispatches": (
                exp.stats.host_dispatches
            ),
            "serve_micro.exporter_readbacks": exp.stats.readbacks,
            "serve_micro.exporter_steady_state_compiles": len(
                exp_window_records
            ),
            # scrape parsed as Prometheus text and carried the serving
            # counters (a broken exporter must fail the gate, not
            # silently stop exporting)
            "serve_micro.exporter_scrape_ok": scrape["ok"],
            # paged leg: byte-identical structural counts + exact
            # tokens vs the plain (contiguous) leg — paging must add
            # zero host interactions per token
            "serve_micro.paged_emitted_tokens": pg.stats.emitted_tokens,
            "serve_micro.paged_host_dispatches": pg.stats.host_dispatches,
            "serve_micro.paged_readbacks": pg.stats.readbacks,
            "serve_micro.paged_steady_state_compiles": len(
                pg_window_records
            ),
            "serve_micro.paged_added_dispatches": (
                pg.stats.host_dispatches - st.host_dispatches
            ),
            "serve_micro.paged_exact_vs_contiguous": paged_exact,
            # quant leg: int8 KV + int8 weights must keep the paged
            # leg's structural counts byte-identical and at least halve
            # the per-request KV HBM claim (docs/design/generation.md
            # "Low-precision serving")
            "serve_micro.quant_emitted_tokens": qt.stats.emitted_tokens,
            "serve_micro.quant_host_dispatches": qt.stats.host_dispatches,
            "serve_micro.quant_readbacks": qt.stats.readbacks,
            "serve_micro.quant_steady_state_compiles": len(
                qt_window_records
            ),
            "serve_micro.quant_added_dispatches": (
                qt.stats.host_dispatches - pg.stats.host_dispatches
            ),
            # dtype-honest per-request KV bytes (int8 pool + f32 scale
            # pages) against the wide paged leg under the SAME schedule
            "serve_micro.quant_kv_hbm_frac_vs_paged": round(
                qt.hbm_bytes_per_request()
                / max(pg.hbm_bytes_per_request(), 1e-9), 4
            ),
            # requests a fixed HBM pool budget holds, vs wide pages
            "serve_micro.quant_kv_capacity_x": round(
                pg._cache_mgr.page_bytes / qt._cache_mgr.page_bytes, 2
            ),
            # prefix leg: the shared-system-prompt economics, all
            # deterministic accounting (exact thresholds)
            "serve_micro.prefix_host_dispatches": px.stats.host_dispatches,
            "serve_micro.prefix_readbacks": px.stats.readbacks,
            "serve_micro.prefix_steady_state_compiles": len(
                px_window_records
            ),
            "serve_micro.prefix_hit_rate": round(px.prefix_hit_rate(), 4),
            "serve_micro.prefix_hbm_bytes_per_request": round(
                px.hbm_bytes_per_request(), 1
            ),
            "serve_micro.prefix_hbm_reduction_x": round(
                px_dense_equiv / max(px.hbm_bytes_per_request(), 1e-9), 2
            ),
            # autopilot leg: the control loop (SLO monitor + autopilot
            # polled per round + canaried publish decided by it) must
            # keep every structural count byte-identical to the plain
            # leg — it acts only at round boundaries, never per token
            "serve_micro.autopilot_emitted_tokens": (
                ap_b.stats.emitted_tokens
            ),
            "serve_micro.autopilot_host_dispatches": (
                ap_b.stats.host_dispatches
            ),
            "serve_micro.autopilot_readbacks": ap_b.stats.readbacks,
            "serve_micro.autopilot_steady_state_compiles": len(
                ap_window_records
            ),
            "serve_micro.autopilot_added_dispatches": (
                ap_b.stats.host_dispatches - st.host_dispatches
            ),
            # the canary actually flowed through the decision loop (a
            # silently skipped canary would let a decision-path dispatch
            # hide) and the emissions stayed exact
            "serve_micro.autopilot_canary_promotes": ap_promotes,
            "serve_micro.autopilot_exact_vs_plain": ap_exact,
            # numerics tiny-train leg: the training-side structural gate
            # (zero added dispatches/readbacks with the numerics plane
            # compiled in; off-cadence steps transfer-guard-clean)
            **run_train_micro(),
            # fused-PP leg: dispatches-per-step is a pinned metric (the
            # ISSUE 16 acceptance: ≥5× drop at the tiny 1F1B config)
            # and fused results must stay bit-identical to the legacy
            # action-loop executor
            **run_pp_micro(),
            # disaggregated serving leg: 1-prefill + 1-decode fleet vs a
            # unified replica over the same shared-prefix workload —
            # handoffs must be token-invisible (exact_vs_unified) and
            # checksum-clean, and every cross-replica prefix shipment
            # attempt must land (docs/design/elasticity.md
            # "Disaggregated serving")
            **run_disagg_micro(),
        },
    }


def run_disagg_micro() -> dict:
    """The disaggregated-serving leg (docs/design/elasticity.md
    "Disaggregated serving"): the SAME shared-prefix workload through a
    single unified replica and through a 1-prefill + 1-decode
    role-split fleet. Gated facts: the split fleet's tokens are EXACTLY
    the unified replica's (a prefill→decode handoff is invisible in the
    token stream), every full-page prompt actually handed off, zero
    continuation fallbacks, zero checksum failures, and every fleet
    prefix-directory shipment attempt landed."""
    from tools.bench_serve import (
        build_model,
        make_shared_prefix_workload,
        run_fleet,
    )

    model, params, cfg = build_model()
    shared = make_shared_prefix_workload(
        vocab=cfg.vocab_size, requests=MICRO["requests"], seed=0,
        prefix_len=2 * 16 + 2, tail_lo=2, tail_hi=6,
        gen_lo=MICRO["gen_lo"], gen_hi=MICRO["gen_hi"],
        mean_interarrival=MICRO["gen_hi"] / MICRO["batch_size"],
    )
    rows = {}
    outs = {}
    for label, roles in (
        ("unified", ("unified",)),
        ("split", ("prefill", "decode")),
    ):
        rows[label], outs[label] = run_fleet(
            model, params, shared, roles=roles,
            batch_size=MICRO["batch_size"], chunk_size=MICRO["chunk_k"],
            page_size=16,
        )
    split = rows["split"]
    attempts = split["fleet_prefix_hits"] + split["fleet_prefix_misses"]
    return {
        "disagg_micro.exact_vs_unified": int(
            outs["split"] == outs["unified"]
        ),
        "disagg_micro.emitted_tokens": split["tokens"],
        "disagg_micro.handoffs": split["handoffs"],
        "disagg_micro.handoff_fallbacks": split["handoff_fallbacks"],
        "disagg_micro.handoff_pages": split["handoff_pages"],
        "disagg_micro.checksum_failures": split["checksum_failures"],
        "disagg_micro.fleet_prefix_hit_rate": (
            round(split["fleet_prefix_hits"] / attempts, 4)
            if attempts else 1.0
        ),
    }


TRAIN_MICRO = dict(steps=6, cadence=3, num_microbatches=2)


def run_train_micro() -> dict:
    """The numerics-enabled tiny-train leg (docs/design/observability.md
    "Training numerics plane"): the SAME toy training loop twice — plain
    vs ``numerics=True`` at a cadence — counting host dispatches and
    metric readbacks. The contract gated here: the numerics plane rides
    the existing step program and the existing metric readback, so every
    structural count is BYTE-IDENTICAL to the plain leg, and off-cadence
    steps run to completion under ``jax.transfer_guard_device_to_host(
    "disallow")`` — any readback the stats added would raise.
    """
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from d9d_tpu.loop.control.task import TrainTask
    from d9d_tpu.loop.train_step import build_train_step
    from d9d_tpu.telemetry import introspect
    from d9d_tpu.telemetry import numerics as numerics_mod

    class _Toy(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.Dense(8, name="l0")(x)
            numerics_mod.tap("l0", h)
            h = nn.Dense(4, name="l1")(jax.nn.relu(h))
            numerics_mod.tap("l1", h)
            return h

    class _Task(TrainTask):
        def prepare_batch(self, batch):
            return batch

        def loss_fn(self, module, params, mb, rng):
            y = module.apply(params, mb["x"])
            return (
                jnp.sum((y - mb["y"]) ** 2),
                jnp.float32(mb["x"].shape[0]),
                {},
            )

    module = _Toy()
    n_mb = TRAIN_MICRO["num_microbatches"]
    x = jnp.ones((n_mb, 4, 8))
    y = jnp.zeros((n_mb, 4, 4))
    batch = {"x": x, "y": y}
    opt = optax.adam(1e-2)

    def drive(numerics: bool) -> dict:
        step = build_train_step(
            module=module, task=_Task(), optimizer=opt,
            num_microbatches=n_mb, numerics=numerics,
        )
        # fresh per leg: the step donates params/opt_state buffers
        params = module.init(jax.random.PRNGKey(0), x[0])
        opt_state = opt.init(params)
        dispatches = 0
        inner = step.fn

        def counting(*args):
            nonlocal dispatches
            dispatches += 1
            return inner(*args)

        step.fn = counting
        # warmup: the one legitimate compile, outside the window
        step.numerics_next = True
        params, opt_state, m = step(
            params, opt_state, batch, jax.random.PRNGKey(10**6)
        )
        jax.block_until_ready(m["loss"])
        dispatches = 0
        readbacks = 0
        mark = len(introspect.inventory())
        for i in range(TRAIN_MICRO["steps"]):
            s = i + 1
            on_cadence = s % TRAIN_MICRO["cadence"] == 0
            step.numerics_next = on_cadence
            rng = jax.random.fold_in(jax.random.PRNGKey(1), s)
            if on_cadence:
                params, opt_state, m = step(params, opt_state, batch, rng)
                # the log-cadence metric fetch — the ONE readback, which
                # the numerics vector rides
                host = {k: np.asarray(v) for k, v in m.items()}
                readbacks += 1
                assert np.isfinite(host["loss"])
            else:
                # off-cadence: any device→host transfer raises — the
                # numerics leg must be as silent as the plain one
                with jax.transfer_guard_device_to_host("disallow"):
                    params, opt_state, m = step(params, opt_state, batch, rng)
        jax.block_until_ready(m["loss"])
        spec = step.numerics_spec
        return {
            "host_dispatches": dispatches,
            "readbacks": readbacks,
            "steady_state_compiles": len(introspect.inventory()) - mark,
            "rows": spec.n_rows if spec is not None else 0,
        }

    plain = drive(numerics=False)
    num = drive(numerics=True)
    return {
        # structural counts, exact: the numerics leg must be
        # byte-identical to the plain leg
        "train_micro.host_dispatches": plain["host_dispatches"],
        "train_micro.readbacks": plain["readbacks"],
        "train_micro.steady_state_compiles": plain["steady_state_compiles"],
        "train_micro.numerics_host_dispatches": num["host_dispatches"],
        "train_micro.numerics_readbacks": num["readbacks"],
        "train_micro.numerics_steady_state_compiles": (
            num["steady_state_compiles"]
        ),
        "train_micro.numerics_added_dispatches": (
            num["host_dispatches"] - plain["host_dispatches"]
        ),
        "train_micro.numerics_added_readbacks": (
            num["readbacks"] - plain["readbacks"]
        ),
        # the off-cadence transfer guard held (the loop would have raised
        # otherwise) AND the stats rows actually materialized — a
        # silently-empty spec would let a regression hide
        "train_micro.numerics_rows": num["rows"],
    }


# the tiny 1F1B config from tools/bench_pp_overhead.py --tiny: ONE rank
# with two virtual stages, so the wavefront partitioner can fuse the
# whole step — the config the ≥5× dispatch-drop acceptance is pinned at.
# The secondary 2-rank config keeps an honest multi-rank number next to
# it (cross-rank edges seal runs, so the reduction is smaller there).
PP_MICRO = dict(num_microbatches=8, stages_per_rank=2, multirank_pp=2)


def run_pp_micro() -> dict:
    """The fused-PP dispatch leg (docs/design/pipelining.md): the SAME
    tiny dense-stage schedule through the legacy per-action interpreter
    and the fused compiled-run executor, counting real executable
    dispatches at the one point both runtimes share —
    ``TrackedJit.__call__``. Gated facts: the tiny 1F1B step fuses into
    ONE program, dispatches drop ≥5× (the measured ratio is pinned
    exactly — both counts are structural, not wall-clock), the fused
    loss/grads are BIT-identical to the legacy executor's, and a
    ``timeline=True`` step (the pp timeline plane's cadence step,
    docs/design/observability.md "Pipeline timeline & profiling")
    dispatches EXACTLY the same programs as a plain step — the
    attribution is pure host-side timing, zero added executables.
    """
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from d9d_tpu.pipelining import (
        FusedPipelineExecutor,
        PipelineScheduleExecutor,
        PipelineStageInfo,
        PipelineStageRuntime,
    )
    from d9d_tpu.pipelining.program import add_communication_ops
    from d9d_tpu.pipelining.program.builders import (
        Interleaved1F1BProgramBuilder,
    )
    from d9d_tpu.telemetry.introspect import TrackedJit

    hid = 8

    class _Stage(nn.Module):
        @nn.compact
        def __call__(self, x):
            return jnp.tanh(nn.Dense(hid, use_bias=True)(x))

    class _Task:
        def split_microbatch(self, micro):
            return micro["x"], {}, {"y": micro["y"], "w": micro["w"]}

        def stage_forward(self, module, params, carry, kwargs):
            return module.apply(params, carry)

        def last_stage_loss(self, module, params, carry, kwargs, state):
            out = module.apply(params, carry)
            err = ((out - state["y"]) ** 2).sum(-1)
            return (err * state["w"]).sum(), state["w"].sum(), {}

    def make_stages(num_stages):
        key = jax.random.PRNGKey(0)
        stages = {}
        for s in range(num_stages):
            key, sub = jax.random.split(key)
            module = _Stage()
            stages[s] = PipelineStageRuntime(
                info=PipelineStageInfo(stage_index=s, num_stages=num_stages),
                module=module,
                params=module.init(sub, jnp.zeros((1, hid))),
                task=_Task(),
            )
        return stages

    m = PP_MICRO["num_microbatches"]
    key = jax.random.PRNGKey(1)
    mbs = []
    for _ in range(m):
        key, k1, k2 = jax.random.split(key, 3)
        mbs.append({
            "x": jax.random.normal(k1, (4, hid)),
            "y": jax.random.normal(k2, (4, hid)),
            "w": jnp.ones((4,)),
        })

    counter = {"n": 0}
    orig_call = TrackedJit.__call__

    def counting(tj, *args, **kwargs):
        counter["n"] += 1
        return orig_call(tj, *args, **kwargs)

    def drive(builder):
        program = add_communication_ops(
            builder.compose(m), num_stages=builder.num_stages,
            stage_owner=builder.stage_owner,
        )
        legacy = PipelineScheduleExecutor(
            stages=make_stages(builder.num_stages), program=program,
            stage_owner=builder.stage_owner, num_microbatches=m,
        )
        fused = FusedPipelineExecutor(
            stages=make_stages(builder.num_stages), program=program,
            stage_owner=builder.stage_owner, num_microbatches=m,
        )
        # warm both (compiles happen out of the counting window), then
        # count one steady-state step each
        rl = legacy.step(list(mbs))
        rf = fused.step(list(mbs))
        exact = int(
            np.array_equal(np.asarray(rl.loss_sum), np.asarray(rf.loss_sum))
            and all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for s in rl.grads
                for a, b in zip(
                    jax.tree.leaves(rl.grads[s]),
                    jax.tree.leaves(rf.grads[s]),
                )
            )
        )
        TrackedJit.__call__ = counting
        try:
            counter["n"] = 0
            legacy.step(list(mbs))
            legacy_n = counter["n"]
            counter["n"] = 0
            fused.step(list(mbs))
            fused_n = counter["n"]
            # a timeline (cadence) step times runs on the host and
            # blocks between them — it must dispatch the SAME programs
            counter["n"] = 0
            fused.step(list(mbs), timeline=True)
            timeline_extra = counter["n"] - fused_n
        finally:
            TrackedJit.__call__ = orig_call
        return (
            legacy_n, fused_n, fused.num_fused_programs, exact,
            timeline_extra,
        )

    tiny = Interleaved1F1BProgramBuilder(1, PP_MICRO["stages_per_rank"])
    legacy_n, fused_n, programs, exact, timeline_extra = drive(tiny)
    multi = Interleaved1F1BProgramBuilder(PP_MICRO["multirank_pp"])
    ml_n, mf_n, m_programs, m_exact, _ = drive(multi)
    return {
        "pp_micro.dispatches_per_step": fused_n,
        "pp_micro.fused_programs": programs,
        "pp_micro.legacy_dispatches_per_step": legacy_n,
        "pp_micro.dispatch_reduction_x": round(legacy_n / max(fused_n, 1), 2),
        "pp_micro.exact_vs_legacy": exact,
        "pp_micro.multirank_dispatches_per_step": mf_n,
        "pp_micro.multirank_fused_programs": m_programs,
        "pp_micro.multirank_dispatch_reduction_x": round(
            ml_n / max(mf_n, 1), 2
        ),
        "pp_micro.multirank_exact_vs_legacy": m_exact,
        # timeline-on step vs plain step: the per-run wall attribution
        # is host-side only, so a cadence step adds ZERO dispatches
        # (zero-baseline at rel_tol 0 — any positive count fails)
        "pp_micro.timeline_extra_dispatches": timeline_extra,
    }


def compare(current: dict, baseline: dict) -> tuple[bool, list[str]]:
    """→ (ok, report lines). Gates every baseline metric against the
    current summary with its direction + relative tolerance."""
    lines = []
    ok = True
    cur = current.get("metrics", {})
    base = baseline.get("metrics", {})
    if not base:
        return True, ["baseline has no metrics: nothing to gate"]
    for name in sorted(base):
        spec = base[name]
        value, direction = spec["value"], spec.get("direction", "lower")
        rel_tol = spec.get("rel_tol", 0.0)
        have = cur.get(name)
        if have is None:
            ok = False
            lines.append(f"FAIL {name}: missing from current summary "
                         f"(baseline {value})")
            continue
        if direction == "higher":
            bound = value * (1.0 - rel_tol)
            bad = have < bound
            rel = "<" if bad else ">="
        else:
            bound = value * (1.0 + rel_tol)
            bad = have > bound
            rel = ">" if bad else "<="
        status = "FAIL" if bad else "ok  "
        lines.append(
            f"{status} {name}: {have:g} {rel} bound {bound:g} "
            f"(baseline {value:g}, {direction} is better, "
            f"rel_tol {rel_tol:g})"
        )
        ok = ok and not bad
    extra = sorted(set(cur) - set(base))
    for name in extra:
        lines.append(f"note {name}: {cur[name]:g} (no baseline)")
    return ok, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="CPU count gate vs the committed baseline"
    )
    ap.add_argument(
        "--baseline", default=str(DEFAULT_BASELINE),
        help=f"baseline snapshot (default {DEFAULT_BASELINE.name})",
    )
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument(
        "--run-micro", action="store_true",
        help="run the CPU serving microbench and gate its summary",
    )
    src.add_argument(
        "--current", help="compare an existing summary JSON file"
    )
    ap.add_argument(
        "--write-baseline", action="store_true",
        help="with --run-micro: (re)write the baseline from this run "
        "instead of gating (default thresholds)",
    )
    ap.add_argument(
        "--write-current", metavar="OUT.json",
        help="also write the current summary to OUT.json",
    )
    args = ap.parse_args(argv)

    if args.run_micro:
        from d9d_tpu.core.compile_cache import enable_compile_cache

        enable_compile_cache()
        current = run_micro()
    else:
        with open(args.current) as fh:
            current = json.load(fh)

    if args.write_current:
        with open(args.write_current, "w") as fh:
            json.dump(current, fh, indent=2, sort_keys=True)

    if args.write_baseline:
        if not args.run_micro:
            print("--write-baseline requires --run-micro", file=sys.stderr)
            return 2
        baseline = {
            "comment": "CPU count-gate baseline "
                       "(tools/bench_compare.py); refresh with "
                       "--run-micro --write-baseline after an intended "
                       "change of a count",
            "metrics": default_thresholds(current["metrics"]),
        }
        with open(args.baseline, "w") as fh:
            json.dump(baseline, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"wrote baseline {args.baseline}")
        return 0

    try:
        with open(args.baseline) as fh:
            baseline = json.load(fh)
    except (OSError, ValueError) as e:
        print(f"cannot read baseline {args.baseline}: {e}", file=sys.stderr)
        return 2

    ok, lines = compare(current, baseline)
    for line in lines:
        print(line)
    print(json.dumps({
        "bench_compare": {
            "ok": ok,
            "baseline": str(args.baseline),
            "gated_metrics": len(baseline.get("metrics", {})),
        }
    }))
    return 0 if ok else 1


def default_thresholds(metrics: dict) -> dict:
    """Per-metric gate specs for a fresh baseline: every metric is an
    exact count (any extra dispatch/compile/byte is a real regression);
    only the direction and two contract values differ."""
    specs = {}
    for name, value in metrics.items():
        if name.endswith(".quant_kv_hbm_frac_vs_paged"):
            # the CONTRACT value (int8+scales must at least halve the
            # per-request KV bytes), not the measured one — robust to
            # head-dim drift in the tiny model config
            specs[name] = {
                "value": 0.5, "direction": "lower", "rel_tol": 0.0,
            }
        elif name.endswith(".quant_kv_capacity_x"):
            # contract: a fixed HBM pool budget holds ≥2× the requests
            specs[name] = {
                "value": 2.0, "direction": "higher", "rel_tol": 0.0,
            }
        elif name.endswith((
            ".exporter_scrape_ok",
            ".paged_exact_vs_contiguous",
            ".prefix_hit_rate",
            ".prefix_hbm_reduction_x",
            ".autopilot_canary_promotes",
            ".autopilot_exact_vs_plain",
            ".numerics_rows",
            # disaggregated serving: token identity across the handoff,
            # the handoff traffic actually flowing (a silently-degraded
            # fleet that re-prefills everything would otherwise pass),
            # and every prefix shipment attempt landing
            ".exact_vs_unified",
            ".handoffs",
            ".handoff_pages",
            ".fleet_prefix_hit_rate",
            # fused PP: bit-exactness vs the legacy oracle and the
            # structural dispatch reduction must never fall below the
            # measured (deterministic) values — the ISSUE 16 ≥5× gate
            # rides the pinned reduction
            # (no leading dot: the multirank_ variants share the suffix)
            "exact_vs_legacy",
            "dispatch_reduction_x",
        )):
            specs[name] = {
                "value": value, "direction": "higher", "rel_tol": 0.0,
            }
        elif name.endswith(("emitted_tokens", ".weight_publishes")):
            # the publish leg must keep RUNNING (a silently skipped
            # publish would let a publish-induced recompile hide)
            specs[name] = {
                "value": value, "direction": "higher", "rel_tol": 0.0,
            }
        else:
            specs[name] = {
                "value": value, "direction": "lower", "rel_tol": 0.0,
            }
    return specs


if __name__ == "__main__":
    sys.exit(main())
