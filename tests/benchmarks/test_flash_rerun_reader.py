"""``kernel.flash_rerun_ms_per_step`` on hand-made traces: the flash
custom calls of the step program whose op name lies under
``rematted_computation`` (a rematerialised layer running the forward
kernel a second time), in milliseconds a traced step; 0 for a program
that keeps the call's output and log-sum-exp (no such call), nothing for
a program with no flash call or a run with no trace. Op names as the
ledger's PR 45 breakdowns have them."""

import types

import pytest

from benchmarks.harness import layers, manifest, readers
from tests.conftest import load_repo_module

# one custom call an entry of ``scope_of``, as a compiled step program's text
hlo = load_repo_module(
    "bench_test_attention_kinds_train_cost",
    "tests/benchmarks/test_attention_kinds_train_cost.py",
).hlo

NAME = "kernel.flash_rerun_ms_per_step"
STEP = "jit(step)/jit(main)/while/body/closed_call/train/grad/"
FIRST = STEP + "jvp(LagunaCausalLM)/model/"
AGAIN = STEP + "transpose(jvp(LagunaCausalLM))/model/checkpoint/"
CALL = "self_attn/self_attn._sdpa_padded/pallas_call"
SCOPES = {
    "custom-call.1": FIRST + "layers__/" + CALL,
    "custom-call.2": AGAIN + "rematted_computation/layers__/" + CALL,
    "custom-call.3": AGAIN + "layers__/" + CALL,  # dq
    "custom-call.4": AGAIN + "layers__/" + CALL,  # dk/dv
    "custom-call.5": FIRST + "layers__/attn_window/" + CALL,
    "custom-call.6": AGAIN + "rematted_computation/layers__/attn_window/"
                     + CALL,
    # recomputed, under self_attn, and not a kernel
    "custom-call.7": AGAIN + "rematted_computation/layers__/self_attn/"
                             "q_proj/dot_general",
    "custom-call.8": AGAIN + "rematted_computation/layers__/mlp/moe/"
                             "experts/ragged_dot",
}
SECONDS = {"custom-call.1": 16e-3, "custom-call.2": 17e-3,
           "custom-call.3": 20e-3, "custom-call.4": 10e-3,
           "custom-call.5": 5e-3, "custom-call.6": 6e-3,
           "custom-call.7": 3e-3, "custom-call.8": 40e-3}


def traced_run(scope_of=SCOPES, steps=2, devices=1):
    """``steps`` executions of the step program on each device, one event
    an instruction, end to end."""
    result = "bf16[4,64,4096,128]{3,2,1,0}"
    ops, modules, t = [], [], 0.0
    for _ in range(steps):
        start = t
        for name in scope_of:
            ops.append((f"%{name} = {result} custom-call(%p0)", t,
                        SECONDS[name]))
            t += SECONDS[name]
        modules.append(("jit_step(123)", start, t - start))
    run = readers.Run(
        cell=types.SimpleNamespace(config={}),
        observed=types.SimpleNamespace(), setup_s=0.0, inventory=(),
        device_kind="TPU v5 lite",
    )
    run.trace = {
        "devices": {f"/device:TPU:{d}": {"ops": ops, "modules": modules}
                    for d in range(devices)},
        "host": [],
    }
    run.programs = (layers.compiled_program(hlo(scope_of)),)
    return run


@pytest.mark.parametrize("steps,devices", [(1, 1), (6, 1), (3, 4)])
def test_it_reads_the_repeated_kernels_a_step_and_device(steps, devices):
    got = readers.read(traced_run(steps=steps, devices=devices), NAME)
    assert got == pytest.approx(17.0 + 6.0)


def test_a_layer_that_keeps_the_pair_reads_zero():
    kept = {k: v for k, v in SCOPES.items()
            if k not in ("custom-call.2", "custom-call.6")}
    assert readers.read(traced_run(kept), NAME) == 0.0


def test_no_flash_call_or_no_trace_gives_nothing():
    eager = {k: SCOPES[k] for k in ("custom-call.7", "custom-call.8")}
    assert readers.read(traced_run(eager), NAME) is None
    run = traced_run()
    run.trace = None
    assert readers.read(run, NAME) is None


def test_the_entry_lists_the_training_cells_and_moves_their_rate():
    bench = manifest.manifest()
    entry, = (m for m in bench["per_layer"] if m["name"] == NAME)
    training, = (m for m in bench["end_to_end"]
                 if m["name"] == "train_tokens_per_s_per_chip")
    assert entry["moves"] == training["name"]
    # a later training cell appends itself to both lists
    assert set(entry["workloads"]) <= set(training["workloads"])
    assert len(entry["workloads"]) >= 5
    assert (entry["source"], entry["layer"], entry["better"]) == (
        "device_trace", "kernels", "lower")
