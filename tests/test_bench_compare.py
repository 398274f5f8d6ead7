"""The CPU count gate (tools/bench_compare.py): the tier-1 tripwire.

Three layers, all pinned here:

1. pure compare() semantics (directions, tolerances, missing metrics);
2. CLI exit codes: nonzero on a synthetic regressed snapshot, zero on a
   baseline-equal one (subprocess — the rc IS the contract CI consumes);
3. the live gate: run the CPU serving microbench in-process and compare
   against the committed BENCH_BASELINE.json — every future PR that
   adds a dispatch, a readback, a steady-state compile or a recompile
   to the fused serving path fails here, with no chip needed. Counts
   only: no wall clock is read or gated.
"""

import json
import pathlib
import subprocess
import sys

import pytest

from tests.conftest import load_repo_module

ROOT = pathlib.Path(__file__).resolve().parent.parent
BASELINE = ROOT / "BENCH_BASELINE.json"

bc = load_repo_module("bench_compare", "tools/bench_compare.py")


def test_compare_directions_and_tolerances():
    baseline = {"metrics": {
        "m.higher": {"value": 100.0, "direction": "higher", "rel_tol": 0.5},
        "m.lower": {"value": 10.0, "direction": "lower", "rel_tol": 0.0},
    }}
    ok, _ = bc.compare(
        {"metrics": {"m.higher": 51.0, "m.lower": 10.0}}, baseline
    )
    assert ok
    ok, lines = bc.compare(
        {"metrics": {"m.higher": 49.0, "m.lower": 10.0}}, baseline
    )
    assert not ok and any(
        line.startswith("FAIL m.higher") for line in lines
    )
    ok, lines = bc.compare(
        {"metrics": {"m.higher": 200.0, "m.lower": 10.1}}, baseline
    )
    assert not ok and any(
        line.startswith("FAIL m.lower") for line in lines
    )


def test_compare_fails_on_missing_metric():
    baseline = {"metrics": {
        "m.gone": {"value": 1.0, "direction": "lower", "rel_tol": 0.0},
    }}
    ok, lines = bc.compare({"metrics": {}}, baseline)
    assert not ok and "missing" in lines[0]


def test_compare_empty_baseline_gates_nothing():
    ok, _ = bc.compare({"metrics": {"x": 1.0}}, {"metrics": {}})
    assert ok


def _committed_values() -> dict:
    with open(BASELINE) as fh:
        return {
            name: spec["value"]
            for name, spec in json.load(fh)["metrics"].items()
        }


def _run_cli(tmp_path, metrics) -> subprocess.CompletedProcess:
    current = tmp_path / "current.json"
    current.write_text(json.dumps({"metrics": metrics}))
    return subprocess.run(
        [sys.executable, str(ROOT / "tools" / "bench_compare.py"),
         "--current", str(current), "--baseline", str(BASELINE)],
        capture_output=True, text=True, timeout=120,
    )


def test_cli_zero_on_committed_baseline_snapshot(tmp_path):
    """A current summary EQUAL to the committed baseline passes (every
    bound is inclusive)."""
    out = _run_cli(tmp_path, _committed_values())
    assert out.returncode == 0, out.stdout + out.stderr
    assert '"ok": true' in out.stdout


def test_cli_nonzero_on_synthetic_regression(tmp_path):
    """The acceptance pin: a regressed snapshot (extra dispatches, a
    steady-state compile, a recompile) exits nonzero."""
    regressed = _committed_values()
    regressed["serve_micro.host_dispatches"] += 5
    regressed["serve_micro.steady_state_compiles"] += 1
    regressed["serve_micro.recompiles"] += 1
    out = _run_cli(tmp_path, regressed)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "FAIL serve_micro.host_dispatches" in out.stdout
    assert "FAIL serve_micro.steady_state_compiles" in out.stdout
    assert "FAIL serve_micro.recompiles" in out.stdout


@pytest.mark.e2e
def test_live_micro_gate_against_committed_baseline(devices):
    """THE tripwire: run the CPU serving microbench and gate it against
    the committed baseline. Every metric (dispatches/1k tokens,
    steady-state compiles, recompiles, emitted tokens, pages) is an
    exact count; ``compare`` is the same call the CLI makes."""
    from d9d_tpu.telemetry import Telemetry, set_telemetry, recompile_guard
    from d9d_tpu.telemetry import introspect

    set_telemetry(Telemetry())  # isolate from other tests' instruments
    recompile_guard().reset()
    current = bc.run_micro()
    with open(BASELINE) as fh:
        baseline = json.load(fh)
    ok, lines = bc.compare(current, baseline)
    assert ok, "\n".join(lines)
    # and the run itself must be introspection-clean
    assert current["metrics"]["serve_micro.steady_state_compiles"] == 0
    assert current["metrics"]["serve_micro.recompiles"] == 0
