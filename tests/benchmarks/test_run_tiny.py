"""``benchmarks/run.py`` end to end at tiny widths on the CPU rig: a new
process per run, as the driver starts it. It must print the contract's
last line, and no device metric may be in it."""

import fcntl
import functools
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from tests.benchmarks.conftest import tiny_lines_dir

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
COUNTERS = {
    m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
    if m["source"] == "program_counter"
}


# What every serving cell reports since PR 43, in the manifest's order. A
# cell's test holds its names to this and to its own: as a subsequence or a
# subset, never as the whole list, so that a later PR can append an entry
# or list a new cell with files and entries alone
EVERY_SERVING_CELL = [
    "entry.compile_s", "entry.serve_compiles_in_window",
    "serve.slot_occupancy_pct", "serve.prompt_step_share_pct",
    "serve.ttft_p50_ms", "serve.tpot_p50_ms", "serve.host_gap_ms_per_chunk",
    "device.serve_idle_pct", "serve.phase_host_ms_per_chunk",
    "serve.longest_chunk_ms", "serve.gc_pause_ms",
    "serve.prompt_slot_steps_pct", "entry.lower_s",
    "model.decode_attention_device_pct", "serve.dispatch_key_ms_per_chunk",
    "serve.dispatch_enqueue_ms_per_chunk", "serve.dispatch_arg_leaves",
    "serve.stage_ms_per_chunk", "serve.phase_gap_ms_per_chunk",
]
# and the two more of the serving cells whose layers hold experts
EXPERT_SERVING_CELLS = ["kernel.expert_mm_decode_roofline",
                        "model.decode_experts_device_pct"]


def in_order(names, within) -> bool:
    """``names`` are all among ``within``, in this order."""
    rest = iter(within)
    return all(name in rest for name in names)


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


@functools.lru_cache(maxsize=None)
def tiny_line(workload: str, trace: int, devices: int) -> dict:
    """The last line of one tiny run; a run is made once a test session:
    once a process, and under xdist once for all the workers, which keep
    the line as a file named by the three arguments in the session's
    directory (``conftest.tiny_lines_dir``, which the last worker to end
    removes), written whole (``os.replace``) under a lock a file, so that
    a worker that asks for a run another is making waits for it and runs
    of other cells go on beside it."""
    def run() -> dict:
        return last_line(run_py(
            "--workload", workload, "--seed", str(2**31 + 5), "--seconds", "1",
            "--trace", str(trace), "--tiny", devices=devices,
        ))

    uid = os.environ.get("PYTEST_XDIST_TESTRUNUID")
    if not uid:
        return run()
    shared = tiny_lines_dir(uid)
    shared.mkdir(exist_ok=True)
    held = shared / f"{workload}.{trace}.{devices}.json"
    with open(f"{held}.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not held.exists():
            fresh = Path(f"{held}.{os.getpid()}")
            fresh.write_text(json.dumps(run()))
            os.replace(fresh, held)
        return json.loads(held.read_text())


CASES = [
    ("qwen3-30b-a3b-l1.train-16k", 1, 1),
    ("qwen3-30b-a3b-decode.serve-rollout-closed", 0, 1),
    ("qwen3-30b-a3b-decode.serve-rollout-closed", 1, 1),
    ("deepseek-v2-lite-l2.train-16k", 0, 1),
    ("qwen3-30b-a3b-ep4.train-16k", 0, 4),
]


@pytest.mark.parametrize("workload,trace,devices", CASES)
def test_tiny_run_prints_the_contracts_last_line(workload, trace, devices):
    line = tiny_line(workload, trace, devices)
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


@pytest.mark.parametrize("workload,devices", [
    ("qwen3-30b-a3b-l1.train-16k", 1),
    ("qwen3-30b-a3b-decode.serve-rollout-closed", 1),
    ("qwen3-30b-a3b-ep4.train-16k", 4),
])
def test_trace_2_measures_then_traces_in_one_run(workload, devices):
    """``--trace 2``: one line with both kinds of metric. On the CPU rig
    only counters are reported, so: every per-layer counter of the cell,
    and of the end-to-end kind exactly what ``--trace 0`` reports with
    the same seed."""
    line = tiny_line(workload, 2, devices)
    plain = tiny_line(workload, 0, devices)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"] == plain["device"]
    assert set(line["metrics"]) <= COUNTERS
    assert "busy_s" not in line["device"] and "breakdown" not in line
    end_to_end = {m["name"] for m in BENCH["end_to_end"]}
    assert {k: v for k, v in line["metrics"].items() if k in end_to_end} \
        == plain["metrics"]
    per_layer = {
        m["name"] for m in BENCH["per_layer"]
        if m["source"] == "program_counter"
        and workload in m.get("workloads", [workload])
    }
    assert per_layer and per_layer <= set(line["metrics"])
    compiles = [k for k in line["metrics"] if "compiles_in_window" in k]
    assert compiles and line["metrics"][compiles[0]]["value"] == 0.0
    if "serve" in workload:
        # the program's own count of prompt-only slot-steps agrees with
        # the benchmark's reckoning from its request table
        own = line["metrics"]["serve.prompt_slot_steps_pct"]["value"]
        reckoned = line["metrics"]["serve.prompt_step_share_pct"]["value"]
        assert own == pytest.approx(reckoned, abs=2.0)


def test_no_tpu_no_result():
    proc = run_py("--workload", CASES[0][0], "--seed", "1", "--seconds", "1",
                  "--trace", "0")
    assert proc.returncode != 0
    assert "{" not in proc.stdout  # no result line of any kind


def test_unknown_workload_is_refused():
    proc = run_py("--workload", "no-such.cell", "--seed", "1",
                  "--seconds", "1", "--trace", "0", "--tiny")
    assert proc.returncode != 0 and "{" not in proc.stdout
