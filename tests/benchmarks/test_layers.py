"""The per-layer readers PR 25 adds and what they share
(``benchmarks/harness/layers.py``): the ``model.*`` shares on the trace
recorded on the v5e, the clock-anchor arithmetic and the expert matmuls'
scopes on hand-made data, and the serving loop's phase readers on a
hand-made span timeline. No device."""

import gzip
import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import layers, readers
from benchmarks.harness import trace as tr
from tests.benchmarks.hand_made import STEP, program, trace_of

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA / "train_v5e_small.json.gz", "rt") as f:
        return json.load(f)


def a_run(trace=None, programs=(), observed=None, inventory=()):
    return readers.Run(
        cell=None, observed=observed, setup_s=0.0, inventory=inventory,
        device_kind="TPU v5 lite", trace=trace, programs=tuple(programs),
    )


# -- model.* on the recorded trace --------------------------------------------


def test_model_shares_on_the_recorded_trace(recorded):
    step = program(recorded["scopes"], STEP)
    run = a_run(recorded["trace"], [step])
    share = {
        layer: readers.read(run, f"model.train_{layer}_device_pct")
        for layer in ("experts", "attention", "head_loss", "optimizer")
    }
    # PERF.md section 5, same cell: ragged-dot 8.1 %, head + CE 52.8 %,
    # optimizer 16.4 %, flash 5.4 % (the attention module holds its
    # projections too). The trace predates the moe/experts scopes, so the
    # custom calls are all of the expert time it can show.
    assert share["experts"] == pytest.approx(8.03, abs=0.05)
    assert share["head_loss"] == pytest.approx(52.8, abs=1.5)
    assert 16.0 < share["optimizer"] < 22.0
    assert 5.4 < share["attention"] < 10.0
    assert 60.0 < sum(share.values()) < 100.0
    # a reader looks at an op's own name and scope, never at its operands:
    # a pattern on the whole text also takes the fusions that read a
    # ragged-dot's result (0.1549 s: what kernel.expert_mm_train_roofline
    # summed until PR 34), this takes the calls alone (0.0906 s, 58 %)
    calls = layers.own_seconds(
        run, call=layers.RAGGED_CALL.pattern, scope=r"never")["seconds"]
    busy = recorded["expected"]["busy_s"]
    assert 100 * calls / busy == pytest.approx(share["experts"])
    assert calls < recorded["expected"]["ragged_dot_s"]
    # and so do the expert rooflines: 6 calls and 2 of metadata a step
    ran = run.ran
    assert ran["jit_step(14448973072706519740)"] == [step]
    assert ran["jit__threefry_fold_in(15899896716144297254)"] == []
    # the ops no scope can be read for are in no share, and the run says
    # how much they are: 12 of the program whose text the run does not
    # hold, and 59 (5.3 ms of the 1.13 s) of an execution that had begun
    # when the capture started; and, in the step itself, those whose
    # instruction carries no scope (copies, the loop's own time)
    unread = layers.unread_seconds(run.ops, ran)
    assert run.notes["scope.unplaced_ms"] == 1e3 * unread["unplaced"]
    assert run.notes["scope.unscoped_ms"] == 1e3 * unread["unscoped"]
    assert run.notes["scope.unplaced_ms"] == pytest.approx(5.305, abs=1e-3)
    assert run.notes["scope.unscoped_ms"] == pytest.approx(6.852, abs=1e-3)
    taken = tr.event_seconds(run.ops, layers.own_instruction(
        ran, "train_step|jit_step", call="ragged-dot",
        product_scope="moe/experts/(gate_up|down)/"))
    assert taken == {"seconds": pytest.approx(calls), "events": 16}
    assert calls == pytest.approx(0.0906, abs=1e-4)
    # the flash kernels are the custom calls under a self_attn scope
    flash = tr.event_seconds(run.ops, layers.own_instruction(
        ran, "train_step|jit_step", scope="self_attn.*pallas_call"))
    assert flash["events"] == recorded["expected"]["flash_events"]
    # a kernel's events are those of the step program alone
    other = layers.own_instruction(ran, "no_such_module", call="ragged-dot")
    assert tr.event_seconds(run.ops, other)["events"] == 0


def test_model_shares_need_a_trace():
    for name in ("model.train_experts_device_pct",
                 "model.decode_attention_device_pct"):
        assert readers.read(a_run(), name) is None


# -- the clock anchor ----------------------------------------------------------


def span(name, t0, dur, step=None):
    return types.SimpleNamespace(name=name, t0=t0, dur_s=dur, step=step)


def test_clock_anchor_places_registry_spans_on_the_trace():
    # program clock 100.0 s is 7.0 s on the trace's; the first anchor's
    # annotation opened 40 us after the clock was read, the second 10 us
    trace = {"devices": {"0": {"ops": [["%f = f32[] fusion()", 7.0, 1.0],
                                       ["%g = f32[] fusion()", 9.0, 1.0]],
                               "async": [], "modules": []}},
             "host": [["main", "d9d.clock/100000000000", 7.00004, 1e-6, None],
                      ["main", "serve.dispatch", 8.2, 0.3, None],
                      ["main", "d9d.clock/103000000000", 10.00001, 1e-6, None]]}
    assert layers.clock_shift(trace) == pytest.approx(-93.0 + 1e-5, abs=1e-9)
    assert layers.clock_shift({"host": [], "devices": {}}) is None
    placed = layers.spans_on_trace(
        trace, [span("serve/phase/commit", 101.0, 0.5),
                span("serve/phase/admit", 101.5, 0.5)]
    )
    assert [p[0] for p in placed] == ["serve/phase/commit",
                                      "serve/phase/admit"]
    assert placed[0][1:] == pytest.approx((8.00001, 8.50001))
    # the device idles from 8.0 to 9.0: half under each phase
    gaps = dict(tr.idle_gaps(trace, placed))
    assert gaps["serve/phase/commit"] == pytest.approx(0.5, abs=1e-4)
    assert gaps["serve/phase/admit"] == pytest.approx(0.5, abs=1e-4)
    assert layers.spans_on_trace({"host": [], "devices": {}}, placed) == []


# -- a scope for the expert matmuls' custom calls ------------------------------

HLO = """
  %concat.1 = bf16[8,64,256]{2,1,0} fusion(%gate, %up), kind=kLoop, metadata={op_name="jit(step)/jvp(M)/mlp/moe/experts/gate_up/concatenate"}
  %ragged-dot-metadata = (s32[9]{0}) custom-call(%sizes), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none.3 = bf16[512,256]{1,0} custom-call(%meta.0, %meta.1, %rows.1, %concat.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %act.1 = bf16[512,128]{1,0} fusion(%ragged-dot-none.3), kind=kLoop, metadata={op_name="jit(step)/jvp(M)/mlp/moe/experts/act/mul"}
  ROOT %ragged-dot-none = bf16[8,64,256]{2,1,0} custom-call(%meta.0, %meta.1, %rows.1, %dgu.1), custom_call_target="tpu_custom_call"
"""


def test_every_ragged_dot_call_gets_the_experts_scope():
    plain = layers.compiled_program(
        "HloModule jit_step\n\nENTRY %main (x: bf16[8]) -> bf16[8] {" + HLO
        + "}\n")
    assert plain.scopes["ragged-dot-none.3"] == "ragged-dot-none"  # what the compiler left
    scope_of = layers.own_scope({"jit_step(1)": [plain]})
    event = lambda name: f"%{name} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %p)"  # noqa: E731
    assert {name: scope_of(event(name), "jit_step(1)")
            for name in plain.results if name.startswith("ragged-dot")} == {
        "ragged-dot-metadata": layers.RAGGED_SCOPE,
        "ragged-dot-none.3": layers.RAGGED_SCOPE,
        "ragged-dot-none": layers.RAGGED_SCOPE,  # no metadata at all
    }
    # every other instruction keeps the scope it had
    assert {name: scope_of(event(name), "jit_step(1)")
            for name in ("concat.1", "act.1")} == {
        name: plain.scopes[name] for name in ("concat.1", "act.1")}
    text = ('%ragged-dot-none.3 = bf16[512,256]{1,0} custom-call(s32[9]{0} '
            '%meta.0), custom_call_target="tpu_custom_call"')
    assert tr.label(text, plain.scopes["ragged-dot-none.3"]) == \
        "custom-call:ragged-dot-none"
    assert tr.label(text, scope_of(text, "jit_step(1)")) == \
        "custom-call:moe/experts/ragged_dot"
    # in a module the run holds no text of, a call is still the experts'
    # and any other event keeps its instruction's name
    assert scope_of(text, "jit_other(2)") == layers.RAGGED_SCOPE
    assert scope_of(event("act.1"), "jit_other(2)") is None
    # the accepted roofline readers find these calls by name, not by scope
    assert not layers.RAGGED_CALL.search(layers.RAGGED_SCOPE)


# -- which instructions are matrix products -------------------------------------

# what the v5e's compiler made of two einsums, a ragged_dot and a
# concatenation under the experts' scopes (compiled for a described chip,
# PR 34; layouts, backend configs and most operands left out): a
# dot_general is a convolution inside a kOutput fusion, the activation is
# fused into the down product as its producer, the fusion's own op_name is
# its root's
PRODUCT_HLO = """
HloModule jit_f, is_scheduled=true

%fused_computation (param_0.1: bf16[8,1024,512]) -> bf16[8,1024,1024] {
  %param_0.1 = bf16[8,1024,512]{2,1,0} parameter(0)
  %pad.3 = bf16[8,1024,1024]{2,1,0} pad(%param_0.1, %constant.11), padding=0_0x0_0x0_512, metadata={op_name="jit(f)/moe/experts/gate_up/concatenate" stack_frame_id=7}
  ROOT %convert.2 = bf16[8,1024,1024]{2,1,0} convert(%maximum.1)
}

%bitcast_fusion (bitcast_input: bf16[8,1024,512]) -> bf16[8,1024,512] {
  %bitcast_input = bf16[8,1024,512]{2,1,0} parameter(0)
  ROOT %bitcast.7 = bf16[8,1024,512]{2,1,0} bitcast(%bitcast_input)
}

%fused_computation.1 (param_0.3: bf16[8,1024,512], param_1.18: bf16[512,1024]) -> bf16[8,512,512] {
  %param_0.3 = bf16[8,1024,512]{2,1,0} parameter(0)
  %fusion.11 = bf16[8,1024,512]{2,1,0} fusion(%param_0.3), kind=kLoop, calls=%bitcast_fusion
  %convolution.2 = bf16[8,512,512]{2,1,0} convolution(%fusion.11, %fusion.7), window={size=1}, dim_labels=0fb_oi0->0bf, metadata={op_name="jit(f)/moe/experts/gate_up/td,edf->etf/dot_general" stack_frame_id=2}
  ROOT %bitcast.3 = bf16[8,512,512]{1,2,0} bitcast(%convolution.2), metadata={op_name="jit(f)/moe/experts/gate_up/td,edf->etf/transpose" stack_frame_id=2}
}

%fused_computation.12 (param_0.29: bf16[8,256,1024], param_1.24: bf16[8,512,512]) -> bf16[512,1024] {
  %slice_multiply_fusion.3 = bf16[8,512,256]{1,2,0} fusion(%param_1.24), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/moe/experts/act/mul" stack_frame_id=4}
  %convolution.5 = bf16[512,1024,1]{1,0,2} convolution(%slice_multiply_fusion.3, %fusion.12), window={size=8}, dim_labels=0bf_0io->bf0, metadata={op_name="jit(f)/moe/experts/down/etf,efd->td/dot_general" stack_frame_id=5}
  ROOT %multiply.6 = bf16[512,1024]{1,0} multiply(%convolution.5, %scale), metadata={op_name="jit(f)/moe/combine/mul" stack_frame_id=5}
}

%fused_computation.20 (param_0.40: bf16[512,1024]) -> bf16[512,1024] {
  %fusion.21 = bf16[512,1024]{1,0} fusion(%param_0.40), kind=kOutput, calls=%fused_computation.12
  ROOT %add.1 = bf16[512,1024]{1,0} add(%fusion.21, %param_0.40)
}

ENTRY %main.2 (x.1: bf16[512,1024], wg.1: bf16[8,1024,512]) -> (bf16[512,1024], bf16[512,512]) {
  %ragged-dot-none = bf16[512,512]{1,0} custom-call(%get-tuple-element, %x.1, %wg.1), custom_call_target="tpu_custom_call"
  %convolution_bitcast_fusion = bf16[8,512,512]{1,2,0} fusion(%copy-done.1, %copy-done.2), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(f)/moe/experts/gate_up/td,edf->etf/dot_general" stack_frame_id=2}
  %fusion.9 = bf16[512,1024]{1,0} fusion(%copy-done, %convolution_bitcast_fusion), kind=kOutput, calls=%fused_computation.12, metadata={op_name="jit(f)/moe/combine/mul" stack_frame_id=5}
  %fusion.30 = bf16[512,1024]{1,0} fusion(%fusion.9), kind=kLoop, calls=%fused_computation.20
  %pad_maximum_fusion = bf16[8,1024,1024]{2,1,0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/moe/experts/gate_up/concatenate" stack_frame_id=7}
  %dot.4 = f32[512,64]{1,0} dot(%x.1, %router), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/moe/router/score/dot_general"}
  ROOT %tuple.1 = (bf16[512,1024]{1,0}, bf16[512,512]{1,0}) tuple(%fusion.30, %ragged-dot-none)
}
"""


def test_matrix_products_are_found_in_the_compiled_text():
    program = layers.compiled_program(PRODUCT_HLO)
    gate_up = "jit(f)/moe/experts/gate_up/td,edf->etf/dot_general"
    down = "jit(f)/moe/experts/down/etf,efd->td/dot_general"
    assert program.module == "jit_f"
    assert program.products == {
        # the products themselves, and the fusions that hold one: under
        # the product's scope, not the root's (fusion.9's own op_name is
        # the multiply fused in behind the product)
        "convolution.2": (gate_up,), "convolution_bitcast_fusion": (gate_up,),
        "convolution.5": (down,), "fusion.9": (down,),
        "fusion.21": (down,), "fusion.30": (down,),  # a fusion nested in one
        "dot.4": ("jit(f)/moe/router/score/dot_general",),
    }
    # the concatenation under gate_up, the bitcast fusion and the custom
    # call are not matrix products
    assert not {"pad_maximum_fusion", "fusion.11", "ragged-dot-none"} \
        & set(program.products)
    # every instruction's result type and own scope, by its name
    assert program.results["fusion.9"] == "bf16[512,1024]{1,0}"
    assert program.results["tuple.1"] == "(bf16[512,1024]{1,0}, bf16[512,512]{1,0})"
    assert program.scopes["fusion.9"] == "jit(f)/moe/combine/mul"
    assert "ragged-dot-none" in program.results
    assert "ragged-dot-none" not in program.scopes
    take = layers.own_instruction(
        {"jit_f(1)": [program]}, "jit_f", call="ragged-dot",
        product_scope="moe/experts/(gate_up|down)/")
    event = lambda name: f"%{name} = bf16[8,8]{{1,0}} fusion(bf16[8,8]{{1,0}} %p)"  # noqa: E731
    assert take(event("fusion.9"), "jit_f(1)")
    assert take(event("convolution_bitcast_fusion"), "jit_f(1)")
    assert take("%ragged-dot-none = bf16[512,512]{1,0} custom-call(s32[1]{0} %m)",
                "jit_f(1)")
    assert not take(event("pad_maximum_fusion"), "jit_f(1)")  # gate_up, a copy
    assert not take(event("dot.4"), "jit_f(1)")  # a product, the router's
    # a consumer names the call among its operands only
    assert not take("%act.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %ragged-dot-none)",
                    "jit_f(1)")
    # and nothing outside the step program's executions
    assert not take(event("fusion.9"), None)
    assert not take(event("fusion.9"), "jit_other(2)")
    empty = layers.compiled_program("")
    assert empty == layers.Program("", {}, {}, {})


def test_a_fusion_that_holds_several_products_is_under_each_ones_scope():
    """The dense serving cell's SwiGLU is one fusion of three matmuls
    whose own scope is the last one's (my chip run, PR 34: the trace
    shows 0.69 s under ``mlp/down_proj`` and none under the others)."""
    program = layers.compiled_program("""
HloModule jit_fused_fn, is_scheduled=true

%fused_computation.5 (x: bf16[256,2560]) -> bf16[256,2560] {
  %convolution.1 = bf16[256,8192]{1,0} convolution(%x, %wg), dim_labels=bf_io->bf, metadata={op_name="jit(fused_fn)/mlp/gate_proj/dot_general"}
  %convolution.2 = bf16[256,8192]{1,0} convolution(%x, %wu), dim_labels=bf_io->bf, metadata={op_name="jit(fused_fn)/mlp/up_proj/dot_general"}
  %multiply.3 = bf16[256,8192]{1,0} multiply(%convolution.1, %convolution.2)
  ROOT %convolution.4 = bf16[256,2560]{1,0} convolution(%multiply.3, %wd), dim_labels=bf_io->bf, metadata={op_name="jit(fused_fn)/mlp/down_proj/dot_general"}
}

ENTRY %main (x: bf16[256,2560]) -> bf16[256,2560] {
  ROOT %fusion.7 = bf16[256,2560]{1,0} fusion(%x), kind=kOutput, calls=%fused_computation.5, metadata={op_name="jit(fused_fn)/mlp/down_proj/dot_general"}
}
""")
    assert program.products["fusion.7"] == tuple(
        f"jit(fused_fn)/mlp/{name}_proj/dot_general"
        for name in ("gate", "up", "down"))
    event = "%fusion.7 = bf16[256,2560]{1,0} fusion(bf16[256,2560]{1,0} %x)"
    for held in ("gate_proj", "up_proj", "down_proj"):
        take = layers.own_instruction(
            {"jit_fused_fn(5)": [program]}, "fused", product_scope=held)
        assert take(event, "jit_fused_fn(5)")
    other = layers.own_instruction(
        {"jit_fused_fn(5)": [program]}, "fused", product_scope="o_proj")
    assert not other(event, "jit_fused_fn(5)")


# two programs of one process name their instructions alike: ``fusion.9``
# is the down product of the decode chunk and the attention output of the
# prompt step, and both call a ``fused_computation.1`` (REVIEW, PR 34)
def two_programs():
    chunk = """
HloModule jit_fused_fn, is_scheduled=true

%fused_computation.1 (p: bf16[64,768]) -> bf16[64,2048] {
  ROOT %convolution.1 = bf16[64,2048]{1,0} convolution(%p, %w), dim_labels=bf_io->bf, metadata={op_name="jit(fused_fn)/moe/experts/down/dot_general"}
}

ENTRY %main (x: bf16[64,768]) -> bf16[64,2048] {
  ROOT %fusion.9 = bf16[64,2048]{1,0} fusion(%x), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(fused_fn)/moe/experts/down/dot_general"}
}
"""
    step = """
HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (p: bf16[64,4096]) -> bf16[64,2048] {
  ROOT %convolution.1 = bf16[64,2048]{1,0} convolution(%p, %w), dim_labels=bf_io->bf, metadata={op_name="jit(step_fn)/self_attn/o_proj/dot_general"}
}

%fused_computation.2 (p: bf16[64,2048]) -> bf16[64,2048] {
  ROOT %add.1 = bf16[64,2048]{1,0} add(%p, %p)
}

ENTRY %main (x: bf16[64,4096]) -> bf16[64,2048] {
  %fusion.9 = bf16[64,2048]{1,0} fusion(%x), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/self_attn/o_proj/dot_general"}
  ROOT %fusion.10 = bf16[64,2048]{1,0} fusion(%fusion.9), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step_fn)/moe/experts/down/add"}
}
"""
    # the chunk again as the batcher compiles it with admission: the same
    # function, so the same module name, with its fusions numbered apart
    admit = chunk.replace("fusion.9", "fusion.12").replace(
        "ENTRY %main (x: bf16[64,768]) -> bf16[64,2048] {",
        "ENTRY %main (x: bf16[64,768]) -> bf16[64,2048] {\n"
        "  %fusion.9 = s32[64]{0} fusion(%slots), kind=kLoop, "
        'calls=%fused_computation.3, metadata={op_name="jit(fused_fn)/admit/select_n"}')
    return [layers.compiled_program(t) for t in (chunk, step, admit)]


DOWN_EVENT = ("%fusion.9 = bf16[64,2048]{1,0} fusion(bf16[64,768]{1,0} %x), "
              "kind=kOutput, calls=%fused_computation.1")
ADMIT_EVENT = ("%fusion.9 = s32[64]{0} fusion(s32[64]{0} %slots), kind=kLoop, "
               "calls=%fused_computation.3")
ADMIT_DOWN = DOWN_EVENT.replace("fusion.9", "fusion.12")
EXPERTS = "moe/experts/(gate_up|down)/"


def test_an_instruction_is_looked_up_in_the_program_that_ran_it():
    chunk, step, admit = two_programs()
    # each text is resolved within itself: both call a fused_computation.1
    assert chunk.products["fusion.9"] == (
        "jit(fused_fn)/moe/experts/down/dot_general",)
    assert step.products["fusion.9"] == (
        "jit(step_fn)/self_attn/o_proj/dot_general",)
    assert "fusion.10" not in step.products  # an add under the experts' scope
    assert "fusion.9" not in admit.products
    trace = {"devices": {"0": {"async": [], "ops": [
        [DOWN_EVENT, 0.0, 1.0],   # the chunk's down product
        [DOWN_EVENT, 2.0, 0.25],  # the prompt step's attention output
        [ADMIT_EVENT, 4.0, 0.5], [ADMIT_DOWN, 5.0, 1.5],
    ], "modules": [
        ["jit_fused_fn(11)", 0.0, 1.0, 1], ["jit_step_fn(22)", 2.0, 1.0, 2],
        ["jit_fused_fn(33)", 4.0, 3.0, 3],
    ]}}, "host": []}
    ops = tr.grouped(trace)
    ran = layers.programs_that_ran(ops, [chunk, step, admit])
    # the name says which function, the events which of its two programs
    assert ran == {"jit_fused_fn(11)": [chunk], "jit_step_fn(22)": [step],
                   "jit_fused_fn(33)": [admit]}
    take = layers.own_instruction(ran, "fused", product_scope=EXPERTS)
    assert tr.event_seconds(ops, take) == {"seconds": 2.5, "events": 2}
    # with the step program asked for too, its fusion.9 is still attention
    both = layers.own_instruction(ran, "fused|step", product_scope=EXPERTS)
    assert tr.event_seconds(ops, both) == {"seconds": 2.5, "events": 2}
    # where the events do not tell two programs of one name apart and the
    # two disagree on an instruction, nothing is read rather than a guess
    blind = [p._replace(results={}) for p in (chunk, step, admit)]
    unsure = layers.programs_that_ran(ops, blind)
    assert unsure["jit_fused_fn(11)"] == [blind[0], blind[2]]
    with pytest.raises(layers.Ambiguous, match="fusion.9"):
        tr.event_seconds(ops, layers.own_instruction(
            unsure, "fused", product_scope=EXPERTS))
    # where they agree (a call the compiler names itself), it is read
    calls = layers.own_instruction(unsure, "fused", call="fusion.12")
    assert tr.event_seconds(ops, calls) == {"seconds": 1.5, "events": 1}


# -- device time by scope, each event by its own program -----------------------

HEAD = "jit(fused_fn)/while/body/closed_call/Qwen3MoeCausalLM.logits_last/"
DOWN_SCOPE = HEAD + "model/layers_0/mlp/moe/experts/down/all_experts/dot_general"
Q_SCOPE = HEAD + "model/layers_0/self_attn/q_proj/dot_general"
RESET_SCOPE = "jit(fused_fn)/serve/reset_rows/dynamic_update_slice"
WIDE, NARROW, ROWS = "bf16[64,4096]{1,0}", "bf16[64,2048]{1,0}", "s32[64]{0}"


def chunk_text(*instructions) -> str:
    """A compiled text of the serving chunk: ``(name, result, scope)``."""
    return (
        "HloModule jit_fused_fn, is_scheduled=true\n\n"
        "ENTRY %main (x: bf16[64,2048]) -> bf16[64,2048] {\n" + "".join(
            f"  %{name} = {result} fusion(%x), kind=kLoop, "
            f'calls=%fused_computation.{i}, metadata={{op_name="{scope}"}}\n'
            for i, (name, result, scope) in enumerate(instructions)) + "}\n")


# the chunk as the batcher compiles it twice, with admission and without:
# one function, one module name, and ``fusion.7`` is the experts' down
# product in one text and the attention's query projection in the other
PLAIN_TEXT = chunk_text(("fusion.7", NARROW, DOWN_SCOPE),
                        ("fusion.8", WIDE, Q_SCOPE))
ADMIT_TEXT = chunk_text(("fusion.7", WIDE, Q_SCOPE),
                        ("fusion.8", ROWS, RESET_SCOPE),
                        ("fusion.9", NARROW, DOWN_SCOPE))


def two_chunks_trace():
    """Two executions of the plain chunk and one of the admitting one."""
    event = lambda name, result: f"%{name} = {result} fusion(bf16[64,2048]{{1,0}} %x), kind=kLoop"  # noqa: E731
    ops, modules, t = [], [], 0.0
    for module, events in (
        ("jit_fused_fn(11)", [("fusion.7", NARROW, 1.0), ("fusion.8", WIDE, 0.25)]),
        ("jit_fused_fn(22)", [("fusion.7", WIDE, 0.5), ("fusion.8", ROWS, 0.125),
                              ("fusion.9", NARROW, 1.5)]),
        ("jit_fused_fn(11)", [("fusion.7", NARROW, 1.0), ("fusion.8", WIDE, 0.25)]),
    ):
        start = t
        for name, result, seconds in events:
            ops.append([event(name, result), t, seconds])
            t += seconds
        modules.append([module, start, t - start, len(modules) + 1])
        t += 0.5  # the device idles between two chunks
    return {"devices": {"0": {"ops": ops, "async": [], "modules": modules}},
            "host": []}


EXPERTS_PCT = "model.decode_experts_device_pct"
ATTENTION_PCT = "model.decode_attention_device_pct"


@pytest.mark.parametrize("texts", [(PLAIN_TEXT, ADMIT_TEXT),
                                   (ADMIT_TEXT, PLAIN_TEXT)],
                         ids=["plain_first", "admitting_first"])
def test_shares_and_labels_ask_the_program_that_ran_the_event(texts):
    """Until PR 53 an instruction's name alone gave its scope and the
    first text won: the admitting chunk's ``fusion.7`` (0.5 s of query
    projections) counted as the experts', or the plain chunk's two
    seconds of down products as attention, by the order of the texts."""
    run = a_run(two_chunks_trace(), map(layers.compiled_program, texts))
    busy = 2 * 1.25 + 2.125
    assert readers.read(run, EXPERTS_PCT) == pytest.approx(100 * 3.5 / busy)
    assert readers.read(run, ATTENTION_PCT) == pytest.approx(100 * 1.0 / busy)
    assert run.notes[EXPERTS_PCT + ".device_s"] == pytest.approx(3.5)
    assert run.notes[ATTENTION_PCT + ".device_s"] == pytest.approx(1.0)
    assert not {"scope.unplaced_ms", "scope.unscoped_ms"} & set(run.notes)
    assert [len(run.ran[m]) for m in ("jit_fused_fn(11)", "jit_fused_fn(22)")] \
        == [1, 1]
    # a scope only the admitting chunk has, over one module or all
    assert layers.own_seconds(run, scope="serve/reset_rows") == {
        "seconds": 0.125, "events": 1}
    assert layers.own_seconds(run, "step", scope="serve/reset_rows") == {
        "seconds": 0.0, "events": 0}
    # the breakdown names each event by its own program's scope
    assert tr.top_ops(run.ops, layers.own_scope(run.ran)) == [
        ["fusion:..l/layers_*/mlp/moe/experts/down/all_experts/dot_general",
         pytest.approx(3.5)],
        ["fusion:model/layers_*/self_attn/q_proj/dot_general",
         pytest.approx(1.0)],
        ["fusion:serve/reset_rows/dynamic_update_slice", 0.125],
    ]


def test_two_candidates_that_disagree_give_nothing_and_a_note():
    """Events that do not tell the two chunks apart (a trace that cut
    their result types away): a share that would be a guess is not read,
    and the breakdown falls back on the instruction's name."""
    blind = [layers.compiled_program(t)._replace(results={})
             for t in (PLAIN_TEXT, ADMIT_TEXT)]
    run = a_run(two_chunks_trace(), blind)
    assert [len(c) for c in run.ran.values()] == [2, 2]
    assert readers.read(run, EXPERTS_PCT) is None
    assert readers.read(run, ATTENTION_PCT) is None
    assert "fusion.7 of jit_fused_fn" in run.notes[EXPERTS_PCT + ".ambiguous"]
    assert ATTENTION_PCT + ".ambiguous" in run.notes
    assert EXPERTS_PCT + ".device_s" not in run.notes
    top = dict(tr.top_ops(run.ops, layers.own_scope(run.ran)))
    assert top["fusion:fusion.7"] == pytest.approx(2.5)
    assert top["fusion:fusion.8"] == pytest.approx(0.625)
    # fusion.9 is the admitting chunk's alone; the plain one does not
    # have it, so the two candidates say two things of it as well
    assert top["fusion:fusion.9"] == pytest.approx(1.5)
    # a kernel roofline's files leave the same kind of note
    assert readers.read(run, "model.decode_window_attention_device_pct") is None


STATE_SCOPE = HEAD + "model/layers_0/mamba/state_update/mul"
STAGED_TEXT = (
    "HloModule jit_fused_fn, is_scheduled=true\n\n"
    "%body (p: (f32[64,16], bf16[64,2048])) -> (f32[64,16], bf16[64,2048]) {\n"
    "  %p = (f32[64,16]{1,0}, bf16[64,2048]{1,0}) parameter(0)\n"
    "  %get-tuple-element.1 = f32[64,16]{1,0} get-tuple-element(%p), index=0\n"
    "  %get-tuple-element.2 = bf16[64,2048]{1,0} get-tuple-element(%p), index=1\n"
    # a weight brought into fast memory for the product that reads it
    "  %copy-start.1 = (bf16[64,2048]{1,0:S(1)}, bf16[64,2048]{1,0}, u32[]) "
    "copy-start(%get-tuple-element.2)\n"
    "  %copy-done.1 = bf16[64,2048]{1,0:S(1)} copy-done(%copy-start.1)\n"
    "  %slice-start.1 = ((f32[64,16]{1,0}), f32[64,8]{1,0:S(1)}, s32[]) "
    "async-start(%get-tuple-element.1), calls=%async_slice\n"
    "  %slice-done.1 = f32[64,8]{1,0:S(1)} async-done(%slice-start.1)\n"
    "  %copy.4 = bf16[64,2048]{0,1} copy(%copy-done.1)\n"
    f"  %fusion.7 = {NARROW} fusion(%copy.4, %slice-done.1), kind=kOutput, "
    f'calls=%fused_computation.1, metadata={{op_name="{DOWN_SCOPE}"}}\n'
    f"  %fusion.8 = f32[64,16]{{1,0}} fusion(%fusion.7), kind=kLoop, "
    f'calls=%fused_computation.2, metadata={{op_name="{STATE_SCOPE}"}}\n'
    # the loop's carried state copied out: nothing with a scope reads it
    "  %copy-start.2 = (f32[64,16]{1,0}, f32[64,16]{1,0}, u32[]) "
    "copy-start(%fusion.8)\n"
    "  %copy-done.2 = f32[64,16]{1,0} copy-done(%copy-start.2)\n"
    # what the compiler made of nothing the program names
    "  %copy.5 = bf16[64,2048]{0,1} copy(%get-tuple-element.2)\n"
    "  %bitcast.6 = bf16[64,2048]{1,0} bitcast(%get-tuple-element.2)\n"
    "  ROOT %tuple.9 = (f32[64,16]{1,0}, bf16[64,2048]{1,0}) "
    "tuple(%copy-done.2, %copy.5)\n}\n")


def test_a_staging_instruction_takes_the_scope_it_stages_for():
    """The compiler brings an instruction's operands to it by copies and
    slices of its own, which it runs beside the compute, waits for under
    a ``-done`` and gives no ``op_name``: 19 % of the Jamba chunk's device
    time, which no share took and ``kernel.ssm_decode_roofline`` read
    89.9 without (my chip run, PR 53). Such an instruction is its
    reader's work, through other such instructions; a carried state's
    copy, which only the loop's tuple reads, is its operand's."""
    staged = layers.compiled_program(STAGED_TEXT)
    for name in ("copy-start.1", "copy-done.1", "copy.4", "slice-start.1",
                 "slice-done.1"):
        assert staged.scopes[name] == DOWN_SCOPE, name
    assert staged.scopes["copy-start.2"] == STATE_SCOPE
    assert staged.scopes["copy-done.2"] == STATE_SCOPE
    # a copy that neither reads nor feeds a scope, and an instruction
    # that is no staging, have none
    assert "copy.5" not in staged.scopes and "bitcast.6" not in staged.scopes
    event = lambda name, result, opcode: f"%{name} = {result} {opcode}(%x)"  # noqa: E731
    ops = [[event("copy-done.1", "bf16[64,2048]{1,0:S(1)}", "copy-done"), 0.0, 0.25],
           [event("slice-done.1", "f32[64,8]{1,0:S(1)}", "async-done"), 0.25, 0.125],
           [event("fusion.7", NARROW, "fusion"), 0.375, 1.0],
           [event("fusion.8", "f32[64,16]{1,0}", "fusion"), 1.375, 0.5],
           [event("copy-done.2", "f32[64,16]{1,0}", "copy-done"), 1.875, 0.0625],
           [event("copy.5", "bf16[64,2048]{0,1}", "copy"), 1.9375, 0.0625]]
    run = a_run(trace_of(ops), [staged])
    assert readers.read(run, EXPERTS_PCT) == pytest.approx(100 * 1.375 / 2.0)
    assert readers.read(run, "model.decode_ssm_device_pct") == \
        pytest.approx(100 * 0.5625 / 2.0)
    assert run.notes["scope.unscoped_ms"] == pytest.approx(62.5)
    top = dict(tr.top_ops(run.ops, layers.own_scope(run.ran)))
    assert top["copy-done:..ayers_*/mlp/moe/experts/down/all_experts/"
               "dot_general"] == 0.25
    assert top["copy:copy.5"] == 0.0625


def test_device_time_no_scope_can_be_read_for_is_said():
    """An op of a module whose compiled text the run does not hold (a
    sampling program beside the chunk), an op outside any execution and
    an op whose instruction carries no scope in its program (an
    asynchronous copy of an operand into fast memory) are in no share;
    two notes say how much they are."""
    trace = two_chunks_trace()
    lanes = trace["devices"]["0"]
    lanes["ops"] += [
        ["%copy.1 = bf16[8]{0} copy(bf16[8]{0} %y)", 7.0, 0.25],
        ["%fusion.7 = u32[64]{0} fusion(u32[2]{0} %key)", 8.0, 0.125],
        ["%copy-done.3 = bf16[8,64]{1,0} copy-done((bf16[8,64]{1,0}) %c)",
         9.0, 0.0625]]
    lanes["modules"] += [["jit__threefry_fold_in(5)", 8.0, 0.125, 4],
                         ["jit_fused_fn(11)", 9.0, 0.0625, 5]]
    run = a_run(trace, map(layers.compiled_program, (PLAIN_TEXT, ADMIT_TEXT)))
    assert run.ran["jit__threefry_fold_in(5)"] == []
    assert readers.read(run, EXPERTS_PCT) == pytest.approx(
        100 * 3.5 / (4.625 + 0.375 + 0.0625))
    assert run.notes["scope.unplaced_ms"] == pytest.approx(375.0)
    assert run.notes["scope.unscoped_ms"] == pytest.approx(62.5)
    # the other program's fusion.7 keeps its name in the breakdown
    top = dict(tr.top_ops(run.ops, layers.own_scope(run.ran)))
    assert top["fusion:fusion.7"] == 0.125 and top["copy:copy.1"] == 0.25
    assert top["copy-done:copy-done.3"] == 0.0625
    none_held = a_run(two_chunks_trace(), [program({}, STEP)])
    assert readers.read(none_held, EXPERTS_PCT) == 0.0
    assert none_held.notes["scope.unplaced_ms"] == pytest.approx(4625.0)
    assert "scope.unscoped_ms" not in none_held.notes


# -- the serving loop's phase readers ------------------------------------------


@pytest.fixture
def hub():
    from d9d_tpu import telemetry

    before = telemetry.get_telemetry()
    fresh = telemetry.set_telemetry(telemetry.Telemetry())
    yield fresh
    telemetry.set_telemetry(before)
    fresh.close()


def a_chunk(hub, step, t0, admit, plan, dispatch, readback, commit):
    t = t0
    for phase, dur in (("admit", admit), ("plan", plan),
                       ("dispatch", dispatch), ("readback", readback),
                       ("commit", commit)):
        hub.registry.record_span(f"serve/phase/{phase}", t, dur, step=step)
        t += dur
    hub.registry.record_span("serve/step", t0, t - t0, step=step)
    return t


def test_serving_phase_readers_on_a_hand_made_timeline(hub):
    ms = 1e-3
    t = a_chunk(hub, 0, 9.0, ms, ms, ms, 250 * ms, ms)  # before the window
    t = a_chunk(hub, 1, 10.0, 1 * ms, 2 * ms, 1 * ms, 250 * ms, 2 * ms)
    t = a_chunk(hub, 2, t, 2 * ms, 2 * ms, 1 * ms, 7000 * ms, 3 * ms)
    hub.registry.record_span("host/gc", t - 7.0, 0.4,
                             meta={"generation": 2, "collected": 5})
    hub.registry.record_span("host/gc", 9.5, 0.2)  # before the window
    end = a_chunk(hub, 3, t, 1 * ms, 2 * ms, 1 * ms, 250 * ms, 2 * ms)
    a_chunk(hub, 4, end, ms, ms, ms, 250 * ms, ms)  # the drain: after it
    observed = types.SimpleNamespace(opened_at=10.0, closed_at=end)
    run = a_run(observed=observed)

    # (6 + 8 + 6) ms of host work over three chunks
    assert readers.read(run, "serve.phase_host_ms_per_chunk") == \
        pytest.approx(20 / 3)
    assert readers.read(run, "serve.longest_chunk_ms") == pytest.approx(7008)
    assert run.notes["serve.longest_chunk"] == {
        "chunk": 2, "seconds": pytest.approx(7.008),
        "held_by": "serve/phase/readback", "held_seconds": pytest.approx(7.0),
    }
    assert "serve.longest_traced_chunk" not in run.notes
    assert readers.read(run, "serve.gc_pause_ms") == pytest.approx(400.0)

    # a traced run says the same of its traced seconds (here: the drain)
    observed.traced = (end, end + 1.0)
    readers.read(run, "serve.longest_chunk_ms")
    assert run.notes["serve.longest_traced_chunk"] == {
        "chunk": 4, "seconds": pytest.approx(0.254),
        "held_by": "serve/phase/readback", "held_seconds": pytest.approx(0.25),
    }


def test_serving_phase_readers_find_nothing_without_the_clock(hub):
    run = a_run(observed=types.SimpleNamespace(opened_at=0.0, closed_at=1.0))
    assert readers.read(run, "serve.phase_host_ms_per_chunk") is None
    assert readers.read(run, "serve.longest_chunk_ms") is None
    assert readers.read(run, "serve.gc_pause_ms") == 0.0


def test_lower_seconds_and_prompt_slot_steps():
    records = [types.SimpleNamespace(lower_s=1.5, compile_s=20.0),
               types.SimpleNamespace(lower_s=4.0, compile_s=0.1)]
    run = a_run(inventory=records)
    assert readers.read(run, "entry.lower_s") == 5.5
    assert readers.read(run, "entry.compile_s") == 25.6
    stats = {"slot_steps_prompt": 239, "slot_steps_busy": 1000}
    run = a_run(observed=types.SimpleNamespace(stats_window=stats))
    assert readers.read(run, "serve.prompt_slot_steps_pct") == \
        pytest.approx(23.9)
