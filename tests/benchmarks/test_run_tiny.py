"""``benchmarks/run.py`` end to end at tiny widths on the CPU rig: a new
process per run, as the driver starts it. It must print the contract's
last line, and no device metric may be in it."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
COUNTERS = {
    m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
    if m["source"] == "program_counter"
}


def run_py(*args, devices=1, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


CASES = [
    ("qwen3-30b-a3b-l1.train-16k", 1, 1),
    ("qwen3-30b-a3b-decode.serve-rollout-closed", 0, 1),
    ("qwen3-30b-a3b-decode.serve-rollout-closed", 1, 1),
    ("deepseek-v2-lite-l2.train-16k", 0, 1),
    ("qwen3-30b-a3b-ep4.train-16k", 0, 4),
]


@pytest.mark.parametrize("workload,trace,devices", CASES)
def test_tiny_run_prints_the_contracts_last_line(workload, trace, devices):
    line = last_line(run_py(
        "--workload", workload, "--seed", str(2**31 + 5), "--seconds", "1",
        "--trace", str(trace), "--tiny", devices=devices,
    ))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    assert line["device"]["memory_peak_bytes"] > 0
    # a CPU run gives counts only: no time, rate, share of a peak or trace
    assert set(line["metrics"]) <= COUNTERS
    assert "busy_s" not in line["device"] and "breakdown" not in line
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}
    if trace:
        compiles = [k for k in line["metrics"] if "compiles_in_window" in k]
        assert compiles and line["metrics"][compiles[0]]["value"] == 0.0


def test_no_tpu_no_result():
    proc = run_py("--workload", CASES[0][0], "--seed", "1", "--seconds", "1",
                  "--trace", "0")
    assert proc.returncode != 0
    assert "{" not in proc.stdout  # no result line of any kind


def test_unknown_workload_is_refused():
    proc = run_py("--workload", "no-such.cell", "--seed", "1",
                  "--seconds", "1", "--trace", "0", "--tiny")
    assert proc.returncode != 0 and "{" not in proc.stdout
