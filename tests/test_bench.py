"""bench.py must keep working against the public Trainer API.

Round-1 regression: bench.py reached into Trainer internals and crashed
when the loop was refactored (VERDICT round 1, Weak #1). This test runs
the actual benchmark harness (tiny config) so any API drift fails CI
instead of the driver.
"""
import pytest

pytestmark = pytest.mark.e2e  # slow tier: full training/IO flows

import importlib.util
import pathlib
import sys


def _load_bench():
    from tests.conftest import load_repo_module

    return load_repo_module("bench", "bench.py")


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_bench_tiny_runs(devices, tmp_path, monkeypatch):
    # the bench leg emits the telemetry JSONL alongside its row when
    # D9D_TELEMETRY_DIR is set (docs/design/observability.md)
    monkeypatch.setenv("D9D_TELEMETRY_DIR", str(tmp_path))
    bench = _load_bench()
    result = bench.run_bench(tiny=True)
    assert result["metric"] == "dense_lm_tokens_per_sec_per_chip"
    assert result["value"] > 0
    assert result["unit"] == "tokens/s"
    assert "vs_baseline" in result
    # no peak FLOP/s off the TPU: utilisation is not measured on this rig
    assert result["detail"]["mfu"] is None
    from d9d_tpu.telemetry import iter_events

    (jsonl,) = tmp_path.glob("*.jsonl")
    events = list(iter_events(jsonl))  # schema-validates every line
    kinds = {e["kind"] for e in events}
    assert {"meta", "span", "flush"} <= kinds
    assert any(
        e["kind"] == "span" and e["name"] == "bench/dispatch" for e in events
    )


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_bench_pp_tiny_runs(devices):
    """tools/bench_pp.py (schedule × residual-policy microbench) must keep
    working against the PipelineTrainEngine API."""
    import subprocess

    root = pathlib.Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, str(root / "tools" / "bench_pp.py"), "--tiny"],
        capture_output=True, text=True, timeout=560,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(root)},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    import json as _json

    rows = [_json.loads(l) for l in lines]
    assert any("winner" in r for r in rows)
    assert sum("schedule" in r for r in rows) == 8
    assert sum(r.get("residual_policy") == "cache_acts" for r in rows) == 3


def test_pp_makespan_simulator():
    """tools/pp_makespan.py: the schedule-economics sim must stay
    consistent with the builders (VERDICT r3 item 5) — cache_acts matches
    1F1B total compute and never loses to it on makespan."""
    import subprocess

    root = pathlib.Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, str(root / "tools" / "pp_makespan.py"),
         "--pp", "4", "--microbatches", "8"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": str(root)},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    import json as _json

    rows = [_json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    by = {(r["schedule"], r["residual_policy"]): r
          for r in rows if "schedule" in r}
    f1 = by[("1f1b", "remat")]
    acts = by[("zb1p", "cache_acts")]
    # measured split costs: I+W = 0.999x the fused backward, so totals sit
    # just under 1F1B's (never above), and the makespan must not lose
    assert f1["total_compute"] * 0.9 < acts["total_compute"] <= f1["total_compute"]
    assert acts["makespan"] <= f1["makespan"]
    assert by[("zb1p", "remat")]["total_compute"] > f1["total_compute"]


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_bench_moe_tiny_runs(devices):
    bench = _load_bench()
    result = bench.run_bench_moe(tiny=True)
    assert result["metric"] == "qwen3_moe_tokens_per_sec_per_chip"
    assert result["value"] > 0
    assert result["detail"]["active_params"] < result["detail"]["total_params"]
    assert result["detail"]["mfu"] is None and result["detail"]["hfu"] is None


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_bench_kernels_tiny_runs(devices):
    import subprocess

    root = pathlib.Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, str(root / "tools" / "bench_kernels.py"), "--tiny"],
        capture_output=True, text=True, timeout=560,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(root)},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    import json as _json

    rows = [_json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    benches = {r["bench"] for r in rows if "bench" in r}
    assert {"sdpa_fwd", "linear_ce_fwd", "rms_norm", "stochastic_round"} <= benches


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_bench_input_pipeline_tiny_runs(devices):
    """run_bench_input_pipeline (VERDICT r3 item 4): all three variants
    produce positive step times on the CPU rig (overlap itself is a
    chip-side property; this guards the harness against loop refactors)."""
    bench = _load_bench()
    result = bench.run_bench_input_pipeline(tiny=True)
    assert result["metric"] == "input_pipeline_step_ms"
    for key in ("synthetic_ms", "sync_ms", "prefetch_ms"):
        assert result[key] > 0


def test_bench_generate_tiny_runs(devices):
    """run_bench_generate: the decode-throughput row stays runnable on
    the CPU rig (guards generate + decode models against refactors)."""
    bench = _load_bench()
    result = bench.run_bench_generate(tiny=True)
    assert result["metric"] == "dense_lm_decode_tokens_per_sec_per_chip"
    assert result["value"] > 0
    assert result["detail"]["new_tokens"] == 8


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_bench_hybrid_tiny_runs(devices):
    """run_bench_moe(hybrid=True): the Qwen3-Next/GDN family's bench row
    (BASELINE config 5) stays runnable on the CPU rig."""
    bench = _load_bench()
    result = bench.run_bench_moe(tiny=True, hybrid=True)
    assert result["metric"] == "qwen3_next_hybrid_tokens_per_sec_per_chip"
    assert result["value"] > 0
    assert result["detail"]["mfu"] is None


def test_bench_serving_tiny_runs(devices):
    """run_bench_serving: the fused continuous-batching serving row —
    exactness vs the per-token path is asserted INSIDE the leg, so a
    fused-loop regression fails here before it reaches a TPU window."""
    bench = _load_bench()
    result = bench.run_bench_serving(tiny=True)
    assert result["metric"] == "serving_tokens_per_sec_per_chip"
    assert result["value"] > 0
    assert result["detail"]["exact_vs_per_token"] is True
    # the fused loop's host contract: >= 4x fewer dispatches per token
    assert (
        result["detail"]["per_token_dispatches_per_1k_tokens"]
        >= 4 * result["detail"]["dispatches_per_1k_tokens"]
    )


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_bench_serve_tool_tiny_runs(devices, tmp_path):
    """tools/bench_serve.py: the CPU serving microbench end-to-end —
    every mode must emit identical tokens, the summary must report the
    fused dispatch reduction, and --telemetry-out must produce a
    schema-valid JSONL with the serving latency histograms."""
    import json as _json
    import subprocess

    root = pathlib.Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, str(root / "tools" / "bench_serve.py"), "--tiny",
         "--requests", "4", "--ks", "8",
         "--telemetry-out", str(tmp_path)],
        capture_output=True, text=True, timeout=560,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(root)},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [_json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    summary = next(r["summary"] for r in rows if "summary" in r)
    assert summary["all_modes_exact"] is True
    assert summary["dispatch_reduction_vs_per_token"] >= 4

    from d9d_tpu.telemetry import iter_events

    (jsonl,) = tmp_path.glob("*.jsonl")
    events = list(iter_events(jsonl))  # schema-validates every line
    flushes = [e for e in events if e["kind"] == "flush"]
    assert len(flushes) == 2  # one per mode: per_token + fused_k8
    for e in flushes:
        assert e["histograms"]["serve/ttft_s"]["count"] > 0
        assert e["histograms"]["serve/queue_wait_s"]["count"] > 0


@pytest.mark.slow  # compile-bound on the 2-core rig; e2e tier covers it
def test_bench_pp_overhead_tiny_runs(devices):
    """tools/bench_pp_overhead.py: the executor dispatch-overhead A/B
    (VERDICT r5 Weak #3) stays runnable; the naive re-dispatch loop must
    not be FASTER than the pre-compiled plan once warm."""
    import json as _json
    import subprocess

    root = pathlib.Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, str(root / "tools" / "bench_pp_overhead.py"),
         "--tiny"],
        capture_output=True, text=True, timeout=560,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(root)},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [_json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    summary = next(r["summary"] for r in rows if "summary" in r)
    # the tiny config is timing-jitter-prone on small CI boxes
    # (BASELINE.md: repeats ranged ~0.9-2.0x), so allow slack below 1.0
    # while still catching a real inversion of the A/B
    assert summary["naive_over_precompiled"] > 0.75
