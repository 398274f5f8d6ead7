"""A training cell's loss comparison end to end at tiny widths on the CPU
rig, with the reference stubbed: the program's loss is the Trainer's
task's, the reference's is its own ``loss``, and the two are held to
``LOSS_TOL``. A new process per case, as the driver starts a run; the
stub is put in ``build.reference_module``'s place there."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "qwen3-30b-a3b-l1.train-16k"

DRIVER = """
import json, sys, types
sys.path.insert(0, {root!r})
from benchmarks import run
from benchmarks.harness import build
from benchmarks.references import qwen3_moe as plain

asked = []

def loss(params, hf, tokens, labels):
    asked.append(tokens.shape)
    return plain.loss(params, hf, tokens, labels) + {offset}

stub = types.SimpleNamespace(logits=plain.logits, loss=loss)
build.reference_module = lambda config: stub
code = run.main(["--workload", {cell!r}, "--seed", "2147483659",
                 "--seconds", "1", "--trace", "0", "--tiny"])
print(json.dumps({{"asked": asked}}), file=sys.stderr)
sys.exit(code)
"""


def stubbed_run(offset: float):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER.format(
            root=str(ROOT), cell=CELL, offset=offset)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    said, line = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    asked = json.loads(proc.stderr.strip().splitlines()[-1])["asked"]
    return said["checks"], line, asked


@pytest.mark.parametrize("offset,correct", [
    (0.0, True),    # the reference's own loss is asked for, and agrees
    (0.01, False),  # a term the program does not train on: refused
], ids=["its_own_loss", "its_own_loss_off_by_0.01"])
def test_the_references_own_loss_is_compared_under_loss_tol(offset, correct):
    from benchmarks.harness.correct import LOSS_TOL

    checks, line, asked = stubbed_run(offset)
    # the keys every training run prints, before this PR and after it
    assert set(checks) == {"failures", "logits_rel_rms", "loss",
                           "reference_loss", "loss_gap"}
    # traced once, on the sample's tokens: 32 ids in one row of 33
    assert asked == [[1, 32]]
    assert line["correct"] is correct
    assert (checks["loss_gap"] <= LOSS_TOL) is correct
    if correct:
        assert not checks["failures"] and checks["loss_gap"] < 2e-3
    else:
        assert checks["loss_gap"] == pytest.approx(0.01, abs=2e-3)
        assert len(checks["failures"]) == 1
        assert f"beyond {LOSS_TOL}" in checks["failures"][0]


def test_a_reference_without_a_loss_of_its_own_is_refused(monkeypatch):
    """Every family's reference says what it would be trained on: there
    is no falling back on the next-token loss of its logits."""
    import types

    from benchmarks.harness import build

    bare = types.SimpleNamespace(logits=lambda params, hf, tokens: None)
    monkeypatch.setitem(sys.modules, "benchmarks.references.bare", bare)
    with pytest.raises((AttributeError, AssertionError)):
        build.reference_module({"reference": "bare"})
    for config in json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]:
        body = json.loads((ROOT / config["file"]).read_text())
        assert build.reference_module(body).loss
