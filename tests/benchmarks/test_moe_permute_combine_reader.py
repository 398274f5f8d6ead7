"""``model.train_moe_permute_combine_device_pct`` on hand-made traces: the
self time of the ops under ``moe/permute`` and ``moe/combine``, forward
and transposed, over the device's busy time; a held range's fold kernel
counts by the scope its Pallas call keeps, the expert FFN never. Op names
as the ledger's PR 48 breakdowns have them."""

import pytest

from benchmarks.harness import manifest, readers
from tests.benchmarks.hand_made import STEP, ran_by

NAME = "model.train_moe_permute_combine_device_pct"
GRAD = "jit(step)/jit(main)/while/body/closed_call/train/grad/"
FORWARD = GRAD + "jvp(LagunaCausalLM)/model/layers__/mlp/"
BACKWARD = GRAD + "transpose(jvp(LagunaCausalLM))/model/checkpoint/layers__/mlp/"
HELD = "cond/branch_0_fun/"
SCOPES = {
    "fusion.1": FORWARD + HELD + "moe/permute/gather",
    "custom-call.2": FORWARD + HELD + "moe/combine/fold_held",
    "fusion.3": FORWARD + HELD + "moe/combine/gather",
    "custom-call.4": BACKWARD + HELD + "transpose(jvp(moe/permute))/fold_held",
    "fusion.5": BACKWARD + HELD + "transpose(jvp(moe/combine))/gather",
    # around the experts, not the movement of rows
    "fusion.6": FORWARD + HELD + "moe/experts/down/mul",
    "custom-call.7": FORWARD + HELD + "moe/experts/ragged_dot",
    "fusion.8": FORWARD + "router/moe/router/select/top_k",
    "fusion.9": GRAD + "jvp(LagunaCausalLM)/model/layers__/self_attn/o_proj",
}
SECONDS = {"fusion.1": 2e-3, "custom-call.2": 1e-3, "fusion.3": 0.5e-3,
           "custom-call.4": 1e-3, "fusion.5": 0.5e-3, "fusion.6": 3e-3,
           "custom-call.7": 12e-3, "fusion.8": 5e-3, "fusion.9": 25e-3}
MOVED = ("fusion.1", "custom-call.2", "fusion.3", "custom-call.4", "fusion.5")


def traced_run(scope_of=SCOPES, steps=2, devices=1, gap=0.0):
    """``steps`` executions of the step program on each device, one event
    an instruction, ``gap`` idle seconds between two."""
    ops, t = [], 0.0
    for _ in range(steps):
        for name in scope_of:
            kind = name.split(".")[0]
            ops.append((f"%{name} = bf16[16384,2048]{{1,0}} {kind}(%p0)", t,
                        SECONDS[name]))
            t += SECONDS[name] + gap
    run = readers.Run(
        cell=None, observed=None, setup_s=0.0, inventory=(),
        device_kind="TPU v5 lite",
    )
    return ran_by(run, ops, scope_of, STEP,
                  devices=[f"/device:TPU:{d}" for d in range(devices)])


@pytest.mark.parametrize("steps,devices,gap", [
    (1, 1, 0.0), (6, 1, 0.0), (3, 4, 0.0), (2, 1, 1e-3),
])
def test_it_reads_the_row_movement_over_busy_time(steps, devices, gap):
    """Forward ``moe/permute`` and ``moe/combine``, their transposes and
    the fold's kernel under either; idle time is no part of the base."""
    run = traced_run(steps=steps, devices=devices, gap=gap)
    got = readers.read(run, NAME)
    moved = sum(SECONDS[name] for name in MOVED)
    assert got == pytest.approx(100.0 * moved / sum(SECONDS.values()))
    assert got == pytest.approx(10.0)
    assert run.notes[NAME + ".device_s"] == pytest.approx(steps * moved)


def test_nothing_under_the_experts_or_the_router_counts():
    others = {k: v for k, v in SCOPES.items() if k not in MOVED}
    assert readers.read(traced_run(others), NAME) is None
    with_one = dict(others, **{"fusion.1": SCOPES["fusion.1"]})
    assert readers.read(traced_run(with_one), NAME) == pytest.approx(
        100.0 * 2e-3 / (sum(SECONDS[k] for k in others) + 2e-3))


def test_no_trace_gives_nothing():
    run = traced_run()
    run.trace = None
    assert readers.read(run, NAME) is None
    empty = traced_run()
    empty.trace = {"devices": {}, "host": []}
    assert readers.read(empty, NAME) is None


def test_the_entry_lists_the_training_cells_and_moves_their_rate():
    bench = manifest.manifest()
    entry, = (m for m in bench["per_layer"] if m["name"] == NAME)
    training, = (m for m in bench["end_to_end"]
                 if m["name"] == "train_tokens_per_s_per_chip")
    assert entry["moves"] == training["name"]
    # a later training cell appends itself to both lists
    assert set(entry["workloads"]) <= set(training["workloads"])
    assert len(entry["workloads"]) >= 5
    assert (entry["source"], entry["layer"], entry["better"], entry["unit"]) == (
        "device_trace", "model", "lower", "%")
