"""Steady-state serving microbenchmark: fused vs per-token stepping.

VERDICT r5 Weak #5: continuous batching was exactness-verified but
"steps from Python per token and no bench leg measures steady-state
slot-utilization tok/s". This harness drives a Poisson-ish arrival
queue through ``ContinuousBatcher`` and reports, per stepping mode:

- generated tokens/sec (wall clock over the drain),
- slot-utilization % (busy slot-steps / total slot-steps — busy
  includes prompt consumption),
- host dispatches and token readbacks per 1k generated tokens (the
  quantity the fused K-step loop divides by K),

with an exactness cross-check: every mode must emit identical tokens
per request (greedy). CPU-runnable by design — the host-interaction
ratio is hardware-independent, so the dispatch-reduction claim can be
pinned on the CPU rig; the tok/s column is a device number only when
the run is on the chip (bench.py's serving leg does that).

Run:   JAX_PLATFORMS=cpu python tools/bench_serve.py --tiny
TPU:   python tools/bench_serve.py

Prints one JSON line per (mode, K) plus a "summary" line with the
fused-vs-per-token ratios; BASELINE.md records the measured numbers.
With ``--telemetry-out DIR`` (or ``$D9D_TELEMETRY_DIR``) the run also
emits the schema-versioned telemetry JSONL event log — TTFT/TPOT/
queue-wait/slot-util histograms, one flush event per mode
(docs/design/observability.md).
"""

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def build_model(tiny: bool):
    import jax
    import jax.numpy as jnp

    from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
    from d9d_tpu.nn.sdpa import build_sdpa_backend

    if tiny:
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 256),),
            hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
            head_dim=16, intermediate_size=128, remat=False,
        )
        dml = 96
        dtype = jnp.float32
    else:
        cfg = Qwen3DenseConfig(
            vocab_ranges=(("default", 32_768),),
            hidden_size=1024, num_layers=12, num_heads=16, num_kv_heads=8,
            head_dim=64, intermediate_size=4096, remat=False,
        )
        dml = 512
        dtype = jnp.bfloat16
    model = Qwen3DenseCausalLM(
        config=cfg, sdpa=build_sdpa_backend(), dtype=dtype,
        decode_max_length=dml,
    )
    z = jnp.zeros((2, 8), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
    params = model.clone(decode_max_length=0).init(
        jax.random.PRNGKey(0), z, pos, z
    )["params"]
    return model, params, cfg


def make_workload(*, vocab, requests, seed, prompt_lo, prompt_hi,
                  gen_lo, gen_hi, mean_interarrival):
    """Poisson-ish open-loop arrivals: each request carries an arrival
    offset (in decode steps) drawn from an exponential, so the queue
    alternates between bursts and lulls like real traffic."""
    import numpy as np

    rng = np.random.RandomState(seed)
    arrivals, t = [], 0.0
    for _ in range(requests):
        t += rng.exponential(mean_interarrival)
        arrivals.append((
            int(t),
            rng.randint(0, vocab, rng.randint(prompt_lo, prompt_hi)).tolist(),
            int(rng.randint(gen_lo, gen_hi)),
        ))
    return arrivals


def make_ramp_workload(*, vocab, schedule, seed=0, prompt_lo=2,
                       prompt_hi=8, gen_lo=4, gen_hi=24):
    """Scripted arrival-RATE ramp — phases of (steps, arrivals/step)
    with exactly deterministic arrival times. Delegates to
    ``resilience.chaos.ramp_arrivals`` so ONE injector shapes both the
    SLO-autopilot chaos legs and this bench's overload workloads (the
    same schedule reproduces the same queue depths and shed/scale
    decisions either place)."""
    from d9d_tpu.resilience.chaos import ramp_arrivals

    return ramp_arrivals(
        schedule, vocab=vocab, seed=seed, prompt_lo=prompt_lo,
        prompt_hi=prompt_hi, gen_lo=gen_lo, gen_hi=gen_hi,
    )


def make_shared_prefix_workload(*, vocab, requests, seed, prefix_len,
                                tail_lo, tail_hi, gen_lo, gen_hi,
                                mean_interarrival):
    """The million-user shape: every request opens with ONE shared
    system prefix and differs only in a short tail — the paged leg's
    prefix cache should prefill the prefix once and map it into every
    later request copy-on-write."""
    import numpy as np

    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, vocab, prefix_len).tolist()
    arrivals, t = [], 0.0
    for _ in range(requests):
        t += rng.exponential(mean_interarrival)
        tail = rng.randint(
            0, vocab, rng.randint(tail_lo, tail_hi)
        ).tolist()
        arrivals.append((
            int(t), prefix + tail, int(rng.randint(gen_lo, gen_hi)),
        ))
    return arrivals


def run_mode(model, params, workload, *, batch_size, chunk_size, overlap,
             reset_telemetry=True, **batcher_kwargs):
    """Drive the arrival schedule through one batcher; arrivals are
    released against the batcher's own device-step clock.

    ``reset_telemetry`` (default on, for the bench harnesses) clears the
    PROCESS-GLOBAL telemetry hub's instruments after the warmup request,
    so each mode's flush snapshot is warmup-free and per-mode — pass
    False when embedding run_mode next to other instrumented components
    whose counters must survive."""
    from d9d_tpu.loop.serve import ContinuousBatcher
    from d9d_tpu.telemetry import get_telemetry, introspect

    # scope inventory-derived columns to THIS mode's records: the
    # process-wide inventory may carry other components' compiles (and
    # deliberate recompiles) when run_mode is embedded
    mode_mark = len(introspect.inventory())
    batcher = ContinuousBatcher(
        model, params, batch_size=batch_size,
        chunk_size=chunk_size, overlap=overlap, **batcher_kwargs,
    )
    # warmup: compile every executable this run will use — the budget
    # spans at least two chunks so BOTH fused variants (the admit-
    # boundary one and the steady-state no-admit one) trace before the
    # timed window — then reset counters AND telemetry instruments so
    # neither the stats row nor the flushed histograms carry the warmup
    # request's compile-dominated latencies (or a previous mode's data)
    batcher.submit(
        workload[0][1], max_new_tokens=2 * (chunk_size or 1) + 2
    )
    batcher.drain()
    batcher.reset_measurement()
    if reset_telemetry:
        get_telemetry().reset_instruments()
    # introspection inventory marker: executables compiled AFTER this
    # point compiled inside the measurement window — a warmed steady
    # state must report 0 (the compile-count column the perf-regression
    # gate pins via tools/bench_compare.py)
    inventory_mark = len(introspect.inventory())

    pending = list(workload)
    rids = {}
    clock = 0  # decode-step clock the arrival offsets are drawn against
    t0 = time.perf_counter()
    while pending:
        # release every arrival whose offset has passed the step clock
        while pending and pending[0][0] <= clock:
            _, prompt, gen = pending.pop(0)
            rids[len(rids)] = batcher.submit(prompt, max_new_tokens=gen)
        if batcher.active:
            # arrivals still due: step synchronously so the clock stays
            # exact against the release schedule
            before = batcher.stats.device_steps
            if chunk_size is None:
                batcher.step()
            else:
                batcher.step_chunk()
            clock += batcher.stats.device_steps - before
        elif pending:
            clock = pending[0][0]  # idle gap: jump to the next arrival
    # arrivals exhausted: the tail runs through drain(), which is where
    # the fused path's double-buffered readback (dispatch chunk N+1
    # before fetching chunk N) actually engages
    batcher.drain()
    dt = time.perf_counter() - t0
    st = batcher.stats
    outputs = {i: batcher.outputs[r] for i, r in rids.items()}
    return {
        "tok_per_s": st.emitted_tokens / dt,
        "tokens": st.emitted_tokens,
        "wall_s": dt,
        "host_dispatches": st.host_dispatches,
        "readbacks": st.readbacks,
        "dispatches_per_1k_tokens": st.dispatches_per_1k_tokens,
        "slot_utilization": st.slot_utilization,
        "steady_state_compiles": len(introspect.inventory())
        - inventory_mark,
        "recompiles": sum(
            1 for r in introspect.inventory()[mode_mark:] if r.recompile
        ),
        # KV residency economics (deterministic accounting, not a
        # device measurement — valid on any backend)
        "hbm_bytes_per_request": batcher.hbm_bytes_per_request(),
        "prefix_hit_rate": batcher.prefix_hit_rate(),
    }, outputs


def run_fleet(model, params, workload, *, roles, batch_size, chunk_size,
              page_size, **batcher_kwargs):
    """Drive the arrival schedule through a ``ServingFleet`` with one
    replica per entry of ``roles`` — the disaggregated counterpart of
    ``run_mode`` (arrivals released against the fleet's scheduling
    round, outputs keyed by arrival index for cross-leg identity)."""
    from d9d_tpu.loop.serve import ContinuousBatcher
    from d9d_tpu.resilience import ServingFleet
    from d9d_tpu.telemetry import get_telemetry

    def make():
        return ContinuousBatcher(
            model, dict(params), batch_size=batch_size,
            chunk_size=chunk_size, page_size=page_size, **batcher_kwargs,
        )

    fleet = ServingFleet()
    for role in roles:
        fleet.add_replica(make(), role=role)
    # warmup: compile the chunk executables outside the timed window
    warm = fleet.submit(
        workload[0][1], max_new_tokens=2 * (chunk_size or 1) + 2
    )
    fleet.drain()
    get_telemetry().reset_instruments()

    pending = list(workload)
    frids = {}
    clock = 0
    t0 = time.perf_counter()
    while pending or not all(fleet.finished(f) for f in frids.values()):
        while pending and pending[0][0] <= clock:
            _, prompt, gen = pending.pop(0)
            frids[len(frids)] = fleet.submit(prompt, max_new_tokens=gen)
        fleet.step()
        clock += chunk_size or 1
    dt = time.perf_counter() - t0
    outputs = {i: fleet.outputs(f) for i, f in frids.items()}
    tokens = sum(len(t) for t in outputs.values())
    snap = get_telemetry().registry.snapshot()["counters"]
    for i in fleet.live_replicas:
        fleet._replicas[i]._kv.check_invariants()
    fleet.close()
    del warm
    return {
        "roles": "+".join(roles),
        "tok_per_s": tokens / dt,
        "tokens": tokens,
        "wall_s": dt,
        "handoffs": int(snap.get("serve/fleet_handoffs", 0)),
        "handoff_fallbacks": int(
            snap.get("serve/fleet_handoff_fallbacks", 0)
        ),
        "handoff_pages": int(snap.get("serve/handoff_pages", 0)),
        "handoff_bytes": int(snap.get("serve/handoff_bytes", 0)),
        "checksum_failures": int(
            snap.get("serve/handoff_checksum_failures", 0)
        ),
        "fleet_prefix_hits": int(snap.get("serve/fleet_prefix_hits", 0)),
        "fleet_prefix_misses": int(
            snap.get("serve/fleet_prefix_misses", 0)
        ),
    }, outputs


def run_disagg(args, model, cfg, params):
    """``--disagg``: the SAME shared-prefix mixed-length workload
    through a single unified replica and through a 1-prefill +
    1-decode role-split fleet. The split fleet must emit identical
    tokens (handoffs and cross-replica prefix shipments are invisible
    in the token stream) — the printed summary carries the identity
    bit, the handoff traffic, and the fleet prefix hit rate."""
    k = args.ks[-1] if args.ks else 8
    page_size = 16 if args.tiny else 64
    n_req = args.requests or (8 if args.tiny else 24)
    gen_hi = 24 if args.tiny else 128
    shared = make_shared_prefix_workload(
        vocab=cfg.vocab_size, requests=n_req, seed=1,
        prefix_len=(3 * page_size) + 2, tail_lo=2,
        tail_hi=8 if args.tiny else 32,
        gen_lo=4, gen_hi=gen_hi,
        mean_interarrival=gen_hi / args.batch_size,
    )
    legs = {}
    outs = {}
    for label, roles in (
        ("disagg_unified", ("unified",)),
        ("disagg_split", ("prefill", "decode")),
    ):
        row, out = run_fleet(
            model, params, shared, roles=roles,
            batch_size=args.batch_size, chunk_size=k,
            page_size=page_size,
        )
        legs[label], outs[label] = row, out
        print(json.dumps({"mode": label, **{
            kk: (round(v, 3) if isinstance(v, float) else v)
            for kk, v in row.items()
        }}), flush=True)
    split = legs["disagg_split"]
    attempts = split["fleet_prefix_hits"] + split["fleet_prefix_misses"]
    print(json.dumps({
        "disagg_summary": {
            "exact_vs_unified": outs["disagg_split"]
            == outs["disagg_unified"],
            "handoffs": split["handoffs"],
            "handoff_fallbacks": split["handoff_fallbacks"],
            "checksum_failures": split["checksum_failures"],
            "fleet_prefix_hit_rate": round(
                split["fleet_prefix_hits"] / attempts, 3
            ) if attempts else 1.0,
            "speedup_vs_unified": round(
                split["tok_per_s"]
                / max(legs["disagg_unified"]["tok_per_s"], 1e-9), 3
            ),
        }
    }), flush=True)


def main():
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized model + workload (CPU-friendly)")
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--ks", type=int, nargs="*", default=[1, 8, 16])
    ap.add_argument(
        "--quant", action="store_true",
        help="add the low-precision serving rows (int8 KV pages, then "
        "int8 weights + int8 KV) against the wide paged leg",
    )
    ap.add_argument(
        "--disagg", action="store_true",
        help="run ONLY the disaggregated serving leg: one unified "
        "replica vs a 1-prefill + 1-decode fleet over the same "
        "shared-prefix workload (token identity + handoff traffic)",
    )
    ap.add_argument(
        "--telemetry-out", default=os.environ.get("D9D_TELEMETRY_DIR"),
        help="directory for the schema-versioned telemetry JSONL event "
        "log (TTFT/TPOT/queue-wait/slot-util histograms per mode); "
        "defaults to $D9D_TELEMETRY_DIR, off when unset",
    )
    args = ap.parse_args()

    from d9d_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    model, params, cfg = build_model(args.tiny)
    if args.disagg:
        run_disagg(args, model, cfg, params)
        return
    n_req = args.requests or (8 if args.tiny else 24)
    gen_hi = 24 if args.tiny else 128
    workload = make_workload(
        vocab=cfg.vocab_size, requests=n_req, seed=0,
        prompt_lo=2, prompt_hi=8 if args.tiny else 32,
        gen_lo=4, gen_hi=gen_hi, mean_interarrival=gen_hi / args.batch_size,
    )

    from d9d_tpu.telemetry import attached_jsonl_sink

    rows = {}
    want = None
    # one sink for the whole sweep; per-mode isolation comes from
    # run_mode's post-warmup reset_instruments(), so each mode's flush
    # event carries that mode's histograms only
    with attached_jsonl_sink(
        args.telemetry_out, run_name="bench_serve"
    ) as (tele_hub, tele_sink):
        for mode_index, (label, chunk, overlap) in enumerate(
            [("per_token", None, False)]
            + [(f"fused_k{k}", k, True) for k in args.ks]
        ):
            try:
                row, outputs = run_mode(
                    model, params, workload, batch_size=args.batch_size,
                    chunk_size=chunk, overlap=overlap,
                )
            finally:
                if tele_sink is not None:
                    # one flush event per mode: the JSONL carries the
                    # latency histograms the one-line rows summarize
                    tele_hub.flush(step=mode_index)
            if want is None:
                want = outputs
            row["exact_vs_per_token"] = outputs == want
            rows[label] = row
            print(json.dumps({"mode": label, **{
                k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in row.items()
            }}), flush=True)

    base = rows["per_token"]
    fused = [r for name, r in rows.items() if name != "per_token"]
    best = max(fused, key=lambda r: r["tok_per_s"]) if fused else base
    print(json.dumps({
        "summary": {
            "dispatch_reduction_vs_per_token": round(
                base["dispatches_per_1k_tokens"]
                / best["dispatches_per_1k_tokens"], 2
            ),
            "speedup_vs_per_token": round(
                best["tok_per_s"] / base["tok_per_s"], 3
            ),
            "all_modes_exact": all(
                r["exact_vs_per_token"] for r in rows.values()
            ),
        }
    }), flush=True)

    # -- paged KV leg: many short requests sharing one system prefix --
    # (docs/design/generation.md). Same workload contiguous vs paged:
    # the paged leg must emit identical tokens with no added host
    # dispatches/readbacks, while HBM bytes per concurrent request drop
    # to what the requests actually use and the prefix cache absorbs
    # the shared prefill.
    k = args.ks[-1] if args.ks else 8
    page_size = 16 if args.tiny else 64
    shared = make_shared_prefix_workload(
        vocab=cfg.vocab_size, requests=n_req, seed=1,
        prefix_len=(3 * page_size) + 2, tail_lo=2,
        tail_hi=8 if args.tiny else 32,
        gen_lo=4, gen_hi=gen_hi, mean_interarrival=gen_hi / args.batch_size,
    )
    contig_row, contig_out = run_mode(
        model, params, shared, batch_size=args.batch_size,
        chunk_size=k, overlap=True,
    )
    paged_row, paged_out = run_mode(
        model, params, shared, batch_size=args.batch_size,
        chunk_size=k, overlap=True, page_size=page_size,
    )
    for label, row in (("shared_contiguous", contig_row),
                       ("shared_paged", paged_row)):
        print(json.dumps({"mode": label, **{
            kk: (round(v, 3) if isinstance(v, float) else v)
            for kk, v in row.items()
        }}), flush=True)
    print(json.dumps({
        "paged_summary": {
            "exact_vs_contiguous": paged_out == contig_out,
            # ≤ 0 added host interactions per token is the gate; prefix
            # hits legitimately make these NEGATIVE (skipped prefill
            # chunks), never positive
            "added_dispatches": paged_row["host_dispatches"]
            - contig_row["host_dispatches"],
            "added_readbacks": paged_row["readbacks"]
            - contig_row["readbacks"],
            "prefix_hit_rate": round(paged_row["prefix_hit_rate"], 3),
            "hbm_bytes_per_request_contiguous": contig_row[
                "hbm_bytes_per_request"
            ],
            "hbm_bytes_per_request_paged": paged_row[
                "hbm_bytes_per_request"
            ],
            "hbm_reduction_x": round(
                contig_row["hbm_bytes_per_request"]
                / max(paged_row["hbm_bytes_per_request"], 1e-9), 2
            ),
        }
    }), flush=True)

    if not args.quant:
        return

    # -- low-precision rows (docs/design/generation.md "Low-precision
    # serving"): the SAME shared workload, first with int8 KV pages
    # only (wide weights isolate the KV attribution), then with the
    # int8 weight stream on top. Structural counts must match the wide
    # paged leg exactly; tokens are compared per request (int8 KV is
    # lossy, greedy argmax usually survives it). On chip the int8 TPU
    # tile is (32, 128), so the non-tiny page_size of 64 is required —
    # the tiny CPU rig runs the kernel in interpret mode where 16 is
    # fine.
    from d9d_tpu.loop.quantize import quantize_for_serving

    quant_rows = {}
    for label, quant_params in (
        ("quant_kv_only", params),
        ("quant_weights_kv", quantize_for_serving(params)),
    ):
        row, out = run_mode(
            model, quant_params, shared, batch_size=args.batch_size,
            chunk_size=k, overlap=True, page_size=page_size,
            kv_quant="int8",
        )
        row["token_match_frac_vs_paged"] = sum(
            out[i] == paged_out[i] for i in out
        ) / max(len(out), 1)
        quant_rows[label] = row
        print(json.dumps({"mode": label, **{
            kk: (round(v, 3) if isinstance(v, float) else v)
            for kk, v in row.items()
        }}), flush=True)
    full = quant_rows["quant_weights_kv"]
    print(json.dumps({
        "quant_summary": {
            "kv_hbm_frac_vs_paged": round(
                full["hbm_bytes_per_request"]
                / max(paged_row["hbm_bytes_per_request"], 1e-9), 4
            ),
            "added_dispatches_vs_paged": full["host_dispatches"]
            - paged_row["host_dispatches"],
            "added_readbacks_vs_paged": full["readbacks"]
            - paged_row["readbacks"],
            "steady_state_compiles": full["steady_state_compiles"],
            "token_match_frac_vs_paged": round(
                full["token_match_frac_vs_paged"], 3
            ),
            "speedup_vs_paged": round(
                full["tok_per_s"] / max(paged_row["tok_per_s"], 1e-9), 3
            ),
        }
    }), flush=True)


if __name__ == "__main__":
    main()
