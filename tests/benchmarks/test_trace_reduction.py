"""The reduction from a trace to numbers: exact arithmetic on small
hand-made traces, and the same functions on a trace recorded on the v5e
(``data/``: two steps of ``qwen3-30b-a3b-l1.train-16k``, the normalised
trace ``trace.load_xplane`` gave, with the scopes of its ops and the
numbers the reduction read from it that day). Standard library only."""

import gzip
import json
from pathlib import Path

import pytest

from benchmarks.harness import costs, layers, peaks
from benchmarks.harness import trace as tr
from tests.benchmarks.hand_made import STEP, program

DATA = Path(__file__).resolve().parent / "data"

FUSION = "%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p0), kind=kLoop"
RAGGED = ('%ragged-dot-none = bf16[4096,768]{1,0} custom-call(s32[1]{0} %a, '
          'bf16[4096,2048]{1,0} %lhs), custom_call_target="tpu_custom_call"')
WHILE = "%while.3 = (s32[], bf16[8]{0}) while((s32[], bf16[8]{0}) %tuple), body=%b"
GATHER = "%all-gather-start.2 = (bf16[8]{0}, bf16[32]{0}) all-gather-start(bf16[8]{0} %p)"
GATHER_DONE = "%all-gather-done.2 = bf16[32]{0} all-gather-done((bf16[8]{0}, bf16[32]{0}) %all-gather-start.2)"
A2A = "%ragged-all-to-all.1 = bf16[64,8]{1,0} ragged-all-to-all(bf16[64,8]{1,0} %x)"


def toy(ops, async_ops=(), modules=(), host=()):
    return {"devices": {"0": {"ops": [list(o) for o in ops],
                              "async": [list(o) for o in async_ops],
                              "modules": [list(m) for m in modules]}},
            "host": [list(h) for h in host]}


def test_parse_op_names_and_opcodes():
    assert tr.parse_op(FUSION) == ("fusion.1", "fusion")
    assert tr.parse_op(RAGGED) == ("ragged-dot-none", "custom-call")
    assert tr.parse_op(WHILE) == ("while.3", "while")  # tuple-shaped result
    assert tr.parse_op(GATHER) == ("all-gather-start.2", "all-gather-start")
    assert tr.instruction(RAGGED) == (
        "ragged-dot-none", "bf16[4096,768]{1,0}", "custom-call")
    assert tr.instruction(WHILE)[1] == "(s32[], bf16[8]{0})"
    # a text the trace cut inside its result: all of it, and no opcode
    assert tr.instruction(WHILE[:20]) == ("while.3", "(s32[], b", "")
    assert tr.label(RAGGED) == "custom-call:ragged-dot-none"
    # the scope is the event's own instruction's, in the program that ran
    # it (``layers.own_scope``, tests/benchmarks/test_layers.py)
    assert tr.label(FUSION, "jit(step)/jit(main)/train/optimizer/mul") == \
        "fusion:train/optimizer/mul"


CHUNK = "jit(fused_fn)/while/body/closed_call/Qwen3MoeCausalLM.logits_last/"
GRAD = ("jit(step)/while/body/closed_call/train/microbatch_grad/"
        "transpose(jvp(Qwen3MoeCausalLM))/model/train/microbatch_grad/"
        "jvp(Qwen3MoeCausalLM)/model/")


def test_a_label_says_what_differs_in_what_the_ledger_keeps():
    """The ledger keeps 64 characters of a label. Until PR 53 the first
    62 of every serving label were the chunk's loop and the model's entry
    method (``fusion:while/body/closed_call/Qwen3M..salLM.logits_last/
    model/la``: three of the Jamba cell's ten read so)."""
    assert (tr.LABEL_LIMIT, tr.LEDGER_KEEPS) == (96, 64)
    serving = [
        "model/layers_3/mlp/down_proj/dot_general",
        "model/layers_3/mlp/gate_proj/dot_general",
        "model/layers_5/mamba/mamba/in_proj/in_proj/slice",
        "model/layers_5/mamba/mamba/state_update/reduce_sum",
        "model/layers_5/mamba/mamba/out_proj/out_proj/dot_general",
        "model/layers_7/self_attn/self_attn._decode_attend/"
        "jit(_paged_decode_call)/paged_decode_p8/pallas_call",
        "model/layers_7/attn_window/self_attn/self_attn._decode_attend/"
        "jit(_paged_decode_call)/paged_decode_p3/pallas_call",
        "model/layers_1/mlp/mlp._forward_held/mlp._all_experts/moe/experts/"
        "gate_up/all_experts/dot_general",
        "model/layers_1/mlp/mlp._forward_held/mlp._all_experts/moe/experts/"
        "down/all_experts/dot_general",
        "lm_head.logits/dot_general",
    ]
    labels = [tr.label(FUSION, CHUNK + scope) for scope in serving]
    assert len({label[:64] for label in labels}) == len(serving)
    assert all(len(label) <= 64 for label in labels)
    # the head every op of the chunk shares goes, and the layers of a
    # stack add up under one label
    assert labels[0] == "fusion:model/layers_*/mlp/down_proj/dot_general"
    assert labels[3] == \
        "fusion:model/layers_*/mamba/mamba/state_update/reduce_sum"
    assert labels[-1] == "fusion:lm_head.logits/dot_general"
    # where it must be cut, the tail stays: 64 characters with the opcode
    assert labels[5] == "fusion:.." + serving[5][-55:] == (
        "fusion:..end/jit(_paged_decode_call)/paged_decode_p8/pallas_call")
    assert labels[7].endswith("moe/experts/gate_up/all_experts/dot_general")
    assert labels[8].endswith("/moe/experts/down/all_experts/dot_general")
    # an op of the chunk outside the model's entry method keeps the loop
    assert tr.label(FUSION, CHUNK.rpartition("Qwen3")[0] + "jit(_where)/"
                    "select_n") == \
        "fusion:while/body/closed_call/jit(_where)/select_n"
    # a training step's labels (``jit_step``, no such head) are what they
    # were: whole up to 96 characters, and past that the first 36, two
    # dots and the last 58, of which the ledger's 64 keep 26
    assert tr.label(FUSION, "jit(step)/train/optimizer/convert_element_type") \
        == "fusion:train/optimizer/convert_element_type"
    assert tr.label(RAGGED, "moe/experts/ragged_dot") == \
        "custom-call:moe/experts/ragged_dot"
    backward = GRAD + "checkpoint/layers_2/attn_window/self_attn/"
    whole = "custom-call:" + backward.partition("jit(step)/")[2].replace(
        "layers_2", "layers_*") + "self_attn._sdpa_padded/pallas_call"
    assert tr.label(RAGGED, backward + "self_attn._sdpa_padded/pallas_call") \
        == whole[:36] + ".." + whole[-58:]
    assert whole[:36] == "custom-call:while/body/closed_call/t"
    assert len(whole[:36] + ".." + whole[-58:]) == 96
    at_the_limit = "x" * 30 + "/" + "y" * 58
    assert tr.label(FUSION, at_the_limit) == "fusion:" + at_the_limit
    assert tr.label(FUSION, "x" + at_the_limit) == \
        "fusion:" + "x" * 29 + ".." + "y" * 58


def test_union_subtract_and_clip():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert tr.measure(tr.union([(0, 1), (0.5, 2)])) == 2
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.clip([(0, 5), (7, 9)], 4, 8) == [(4, 5), (7, 8)]


def test_busy_union_counts_nested_ops_once_and_idle_share():
    # a while of 4 s containing two 1 s ops, then a 2 s gap, then 2 s of work
    trace = toy([(WHILE, 0.0, 4.0), (FUSION, 0.5, 1.0), (RAGGED, 2.0, 1.0),
                 (FUSION, 6.0, 2.0)])
    assert tr.window_of(trace) == (0.0, 8.0)
    busy, window = tr.busy_and_window(trace)
    assert (busy, window) == (6.0, 8.0)
    assert tr.idle_share(trace) == pytest.approx(0.25)
    # self time: the while keeps what its children leave over
    self_t = dict()
    for text, _, seconds in tr.self_times_at(trace["devices"]["0"]["ops"]):
        self_t[text] = self_t.get(text, 0) + seconds
    assert self_t[WHILE] == pytest.approx(2.0)
    assert self_t[FUSION] == pytest.approx(3.0)
    ops = tr.grouped(trace)
    assert tr.top_ops(ops, n=2) == [
        ["fusion:fusion.1", pytest.approx(3.0)],
        ["while:while.3", pytest.approx(2.0)],
    ]
    # an event belongs to the execution that covers its start, if any
    trace["devices"]["0"]["modules"] = [["jit_step(7)", 0.0, 4.0, 1]]
    assert [(m, t) for _, m, t, _ in tr.events_in_modules(trace)] == [
        ("jit_step(7)", FUSION), ("jit_step(7)", RAGGED),
        ("jit_step(7)", WHILE), (None, FUSION)]
    ops = tr.grouped(trace)
    ragged = lambda text, module: "ragged-dot" in text  # noqa: E731
    assert tr.event_seconds(ops, ragged) == {"seconds": 1.0, "events": 1}
    inside = lambda text, module: module == "jit_step(7)"  # noqa: E731
    assert tr.event_seconds(ops, inside) == {"seconds": 4.0, "events": 3}


def test_busy_is_averaged_over_devices():
    trace = toy([(FUSION, 0.0, 4.0)])
    trace["devices"]["1"] = {"ops": [[FUSION, 0.0, 2.0]], "async": [],
                             "modules": []}
    assert tr.busy_by_device(trace) == {"0": 4.0, "1": 2.0}
    assert tr.busy_and_window(trace) == (3.0, 4.0)


def test_idle_gaps_go_to_the_innermost_covering_span():
    trace = toy(
        [(FUSION, 0.0, 1.0), (FUSION, 3.0, 1.0), (FUSION, 5.0, 1.0)],
        host=[("main", "bench/step_chunk", 0.5, 3.0, None),
              ("main", "serve.dispatch", 1.0, 1.5, None),
              ("main", "serve.readback", 4.0, 0.5, None),
              ("main", "PjitFunction(f)", 1.0, 1.0, None)],
    )
    spans = tr.program_spans(trace, ("serve.", "bench/"))
    assert sorted(name for name, _, _ in spans) == [
        "bench/step_chunk", "serve.dispatch", "serve.readback"]
    gaps = dict(map(tuple, tr.idle_gaps(trace, spans)))
    # gap 1..3: 1..2.5 under serve.dispatch (inside bench/step_chunk),
    # 2.5..3 under bench/step_chunk alone; gap 4..5: half under readback
    assert gaps == {"serve.dispatch": pytest.approx(1.5),
                    "bench/step_chunk": pytest.approx(0.5),
                    "serve.readback": pytest.approx(0.5),
                    "(no span)": pytest.approx(0.5)}
    assert tr.idle_seconds_in(
        trace, spans, {"serve.dispatch", "serve.readback"}
    ) == pytest.approx(2.0)


def test_clock_offset_restores_causality_only_when_broken():
    host = [("main", "DoEnqueueProgram", 1.0005, 1e-5, 7)]
    early = toy([(FUSION, 1.0, 0.1)], modules=[("jit_f(1)", 1.0, 0.1, 7)],
                host=host)
    assert tr.clock_offset(early) == pytest.approx(0.0005)
    late = toy([(FUSION, 1.002, 0.1)], modules=[("jit_f(1)", 1.002, 0.1, 7)],
               host=host)
    assert tr.clock_offset(late) == 0.0


def test_collective_exposed_is_collective_time_without_compute():
    # async all-gather 0..4 (start op at 0, waiting done op 3..4), compute
    # 0.1..3, then a synchronous all-to-all 4..5 beside nothing, compute 5..10
    trace = toy(
        [(GATHER, 0.0, 0.1), (FUSION, 0.1, 2.9), (GATHER_DONE, 3.0, 1.0),
         (A2A, 4.0, 1.0), (FUSION, 5.0, 5.0)],
        async_ops=[(GATHER, 0.0, 4.0)],
    )
    # exposed: 0..0.1 and 3..4 of the gather, 4..5 of the all-to-all
    assert tr.collective_exposed_share(trace) == pytest.approx(2.1 / 10.0)


def test_module_seconds_in_start_order():
    trace = toy([(FUSION, 0.0, 1.0)], modules=[
        ("jit_step(1)", 2.0, 0.5, 2), ("jit_step(1)", 0.0, 0.6, 1),
        ("jit_other(2)", 1.0, 0.1, 3)])
    assert tr.module_seconds(trace, "step", window=(0, 10)) == [0.6, 0.5]


def test_roofline_share_says_which_bound_binds_and_is_never_clamped():
    peak = peaks.peak_for("TPU v5 lite")
    assert peak.bf16_flops == 197e12 and peak.hbm_bytes_per_s == 819e9
    with pytest.raises(ValueError):
        peaks.peak_for("TPU v9 imaginary")
    least, bound = costs.roofline_seconds({"flops": 197e12, "bytes": 1}, peak)
    assert (least, bound) == (1.0, "compute")
    least, bound = costs.roofline_seconds({"flops": 1, "bytes": 819e9}, peak)
    assert (least, bound) == (pytest.approx(1.0), "memory")
    assert tr.roofline_share(1.0, 4.0) == 0.25
    assert tr.roofline_share(1.2, 1.0) == 1.2  # a fault must show
    with pytest.raises(ValueError):
        tr.roofline_share(1.0, 0.0)


def test_empty_trace_is_an_error_not_a_zero():
    with pytest.raises(ValueError):
        tr.window_of(toy([]))


# -- the recorded trace ---------------------------------------------------------


def recorded(name: str) -> dict:
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def train_trace():
    return recorded("train_v5e_small.json.gz")


def test_recorded_train_trace_reduces(train_trace):
    trace, scopes = train_trace["trace"], train_trace["scopes"]
    expected = train_trace["expected"]
    busy, window = tr.busy_and_window(trace)
    assert 0 < busy <= window
    assert busy == pytest.approx(expected["busy_s"], rel=1e-9)
    assert window == pytest.approx(expected["window_s"], rel=1e-9)
    assert tr.idle_share(trace) == pytest.approx(1 - busy / window)
    steps = tr.module_seconds(trace, "train_step|jit_step")
    assert len(steps) == expected["steps"]
    # the whole text of an event names its operands too: what the expert
    # roofline summed until PR 34, the calls and the fusions that read them
    ops = tr.grouped(trace)
    ragged = tr.event_seconds(ops, lambda text, _: "ragged-dot" in text)
    assert ragged["events"] > 0
    assert ragged["seconds"] == pytest.approx(expected["ragged_dot_s"])
    step = program(scopes, STEP)
    scope_of = layers.own_scope(layers.programs_that_ran(ops, [step]))
    top = tr.top_ops(ops, scope_of, n=10)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    assert sum(s for _, s in top) <= busy * (1 + 1e-9)
    assert "custom-call:moe/experts/ragged_dot" in dict(top)
    # without the programs every label is its instruction's name
    assert all("/" not in label for label, _ in tr.top_ops(ops, n=10))
    spans = tr.program_spans(trace)
    gaps = tr.idle_gaps(trace, spans)
    assert sum(s for _, s in gaps) == pytest.approx(window - busy, rel=1e-6)
