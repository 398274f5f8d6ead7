"""The Xing4.0 training cell end to end at tiny widths on the CPU rig, a
new process per run as the driver starts it: the contract's last line,
``correct`` true against the family's reference (main-head logits, and
the whole loss: next token + the multi-token-prediction term), counters
only, ``moe.held_rows_pct`` read from the program; and the comparison is
shown to see the module's term: a reference that drops it fails
``loss_gap``."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from tests.conftest import load_repo_module

# the helpers of the first tiny-run tests: one run per module and case
_tiny = load_repo_module("bench_run_tiny", "tests/benchmarks/test_run_tiny.py")
COUNTERS, tiny_line = _tiny.COUNTERS, _tiny.tiny_line
ROOT = Path(__file__).resolve().parents[2]
CELL = "xing4.0-29b-a4b-share8.train-8k"


@pytest.mark.parametrize("trace", [0, 2])
def test_tiny_run_prints_the_contracts_last_line(trace):
    line = tiny_line(CELL, trace, 1)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["device"]["memory_peak_bytes"] > 0
    # a CPU run gives counts only: no time, rate, share of a peak or trace
    assert set(line["metrics"]) <= COUNTERS
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_the_cells_own_counter_is_read_from_the_program():
    metrics = tiny_line(CELL, 2, 1)["metrics"]
    assert metrics["entry.train_compiles_in_window"]["value"] == 0.0
    assert metrics["step.hbm_claim_gb"]["value"] > 0.0
    # the tiny preset holds 4 of 16 routed experts: 25 % at an even router
    held = metrics["moe.held_rows_pct"]
    assert held["unit"] == "%" and 0.0 < held["value"] < 100.0
    assert 15.0 <= held["value"] <= 35.0
    # shares of a device's time come from a trace: never on the CPU
    for name in ("kernel.mhc_train_roofline", "model.train_mtp_device_pct",
                 "model.train_residual_mix_device_pct"):
        assert name not in metrics


DRIVER = """
import json, sys, types
sys.path.insert(0, {root!r})
from benchmarks import run
from benchmarks.harness import build
from benchmarks.references import xing4_0 as whole

def loss(params, hf, tokens, labels):
    without = {{k: v for k, v in params.items() if k != "mtp"}}
    return whole.loss(without, hf, tokens, labels)

stub = types.SimpleNamespace(logits=whole.logits, loss=loss)
build.reference_module = lambda config: stub
sys.exit(run.main(["--workload", {cell!r}, "--seed", "2147483659",
                   "--seconds", "1", "--trace", "0", "--tiny"]))
"""


def test_a_reference_without_the_modules_term_fails_the_loss_gap():
    """At the tiny size the term is 0.3 x about ln(256): the comparison
    through the Trainer's task and ``reference.loss`` sees it."""
    from benchmarks.harness.correct import LOSS_TOL

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER.format(root=str(ROOT), cell=CELL)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    said, line = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    checks = said["checks"]
    assert line["correct"] is False
    assert checks["loss_gap"] > 1.0 > LOSS_TOL
    assert checks["loss_gap"] == pytest.approx(0.3 * 5.5, abs=0.35)
    # the logits are the main head's and still agree: one failure, the loss
    assert len(checks["failures"]) == 1 and "loss" in checks["failures"][0]
    # the whole reference, same seed, agrees (the run above this one)
    assert tiny_line(CELL, 0, 1)["correct"] is True
