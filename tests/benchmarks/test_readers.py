"""The generic readers on hand-made observations and hand-made traces, and
the seeded init the training cells use. No device."""

import types

import pytest

from benchmarks.harness import costs, layers, readers
from tests.benchmarks.hand_made import program, trace_of

PHASES = ["data_wait", "host_dispatch", "metric_flush"]


def span(name, step, dur_s):
    return types.SimpleNamespace(name=name, step=step, dur_s=dur_s)


def observed(flush_s: float):
    """20 steps of a 1 s step after 3 of warm-up: the host spends 0.01 s
    dispatching and fetches metrics every tenth step; it runs free in
    the two steps after a fetch and waits 0.9 s for the device in
    ``data_wait`` in every other."""
    spans = [span("train/phase/host_dispatch", 0, 5.0)]  # warm-up: left out
    for step in range(3, 23):
        tenth = step % 10 == 0
        spans += [
            span("train/phase/host_dispatch", step, 0.01),
            span("train/phase/data_wait", step,
                 0.0 if step % 10 in (0, 1, 2) else 0.9),
            span("train/phase/device_block", step, 0.05),  # not asked for
        ]
        if tenth:
            spans.append(span("train/phase/metric_flush", step, flush_s))
    return types.SimpleNamespace(
        spans=spans, first_step=3, steps_in_window=20, window_s=20.0
    )


def run_of(o):
    return readers.Run(cell=None, observed=o, setup_s=0.0, inventory=(),
                       device_kind="cpu")


def test_the_lower_decile_is_a_free_step_whatever_the_periodic_stall():
    quick, slow = run_of(observed(0.1)), run_of(observed(0.9))
    free = readers.unthrottled_phase_share(quick, PHASES, decile=1)
    assert free == readers.unthrottled_phase_share(slow, PHASES, decile=1)
    assert free == pytest.approx(1.0)  # the dispatch alone, of a 1 s step
    assert readers.read(quick, "train.host_unthrottled_step_pct") == free


def test_phase_share_without_spans_reports_nothing():
    empty = types.SimpleNamespace(
        spans=[], first_step=0, steps_in_window=0, window_s=1.0)
    assert readers.unthrottled_phase_share(
        run_of(empty), PHASES, decile=1) is None


# -- the expert rooflines on hand-made traces ----------------------------------

QWEN = {"hidden_size": 2048, "moe_intermediate_size": 768, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 1}
# events as the v5e's trace names them: the instruction, cut short
CALL = ('%ragged-dot-none.3 = bf16[512,256]{1,0} custom-call(s32[9]{0} '
        '%meta.0, bf16[512,64]{1,0} %rows.1), custom_call_target="tpu_custom_call"')
CALL_META = ('%ragged-dot-metadata = (s32[9]{0}, s32[8]{0}) custom-call('
             's32[8]{0} %sizes.1), custom_call_target="tpu_custom_call"')
CONSUMER = ('%act.1 = bf16[512,128]{1,0} fusion(bf16[512,256]{1,0} '
            '%ragged-dot-none.3), kind=kLoop, calls=%fused_computation.2')
GATE_UP = ('%convolution_bitcast_fusion = bf16[8,512,512]{1,2,0} fusion('
           'bf16[8,1024,512]{2,1,0} %copy-done.1), kind=kOutput, '
           'calls=%fused_computation.1')
DOWN = ('%fusion.9 = bf16[512,1024]{1,0} fusion(bf16[8,256,1024]{2,1,0} '
        '%copy-done), kind=kOutput, calls=%fused_computation.12')
CONCAT = ('%pad_maximum_fusion = bf16[8,1024,1024]{2,1,0} fusion('
          'bf16[8,1024,512]{2,1,0} %copy-done.1), kind=kLoop, '
          'calls=%fused_computation')
Q_PROJ = ('%fusion.4 = bf16[64,4096]{1,0} fusion(bf16[64,2048]{1,0} %x), '
          'kind=kOutput, calls=%fused_computation.4')
SCOPES = {
    "convolution_bitcast_fusion": "jit(f)/moe/experts/gate_up/dot_general",
    "fusion.9": "jit(f)/moe/experts/down/dot_general",
    "pad_maximum_fusion": "jit(f)/moe/experts/gate_up/concatenate",
    "act.1": "jit(f)/moe/experts/act/mul",
    "fusion.4": "jit(f)/layers_0/self_attn/q_proj/dot_general",
}
# what ``layers.compiled_program`` finds in such a program: the fusions
# that hold a dot are products, under the dot's scope; the concatenation
# is not one
PRODUCTS = {k: (SCOPES[k],) for k in
            ("convolution_bitcast_fusion", "fusion.9", "fusion.4")}
FLASH = ('%custom-call.7 = bf16[4,4096,32,128]{3,2,1,0} custom-call('
         'bf16[4,4096,32,128]{3,2,1,0} %q), custom_call_target="tpu_custom_call"')
FLASH_CONSUMER = ('%fusion.8 = bf16[16384,2048]{1,0} fusion('
                  'bf16[4,4096,32,128]{3,2,1,0} %custom-call.7), kind=kOutput')

EXPERT_TRACES = {
    # (a) custom calls, the fusion that reads a call's result and the
    # concatenation under the experts' scope: the calls alone, 2.25 s
    "calls_and_their_consumers": (
        [(CALL, 0.0, 2.0), (CALL_META, 2.0, 0.25), (CONSUMER, 3.0, 1.0),
         (CONCAT, 4.0, 0.5), (Q_PROJ, 5.0, 0.5)], 2.25),
    # (b) no ragged-dot: the experts are plain dot_generals under
    # moe/experts/{gate_up,down}; a number, not None
    "plain_dot_generals_by_scope": (
        [(GATE_UP, 0.0, 1.0), (DOWN, 1.0, 0.5), (CONCAT, 2.0, 0.5),
         (Q_PROJ, 3.0, 0.5)], 1.5),
    # both kinds in one program count together
    "calls_and_dot_generals": (
        [(CALL, 0.0, 2.0), (DOWN, 2.0, 0.5), (CONSUMER, 3.0, 1.0)], 2.5),
    # (c) neither: nothing to read
    "no_expert_matmul": ([(CONCAT, 0.0, 0.5), (Q_PROJ, 1.0, 0.5)], None),
}


def kernel_run(ops, module: str, hf=QWEN, scopes=SCOPES, products=PRODUCTS):
    """A traced run of one program: its executable is ``module`` with a
    fingerprint, as a trace names it, and covers all the ops."""
    seen = types.SimpleNamespace(
        tokens_per_step=16_384, seq_len=4_096, chips=1, slots=64, chunk_k=8)
    return readers.Run(
        cell=types.SimpleNamespace(config=hf), observed=seen, setup_s=0.0,
        inventory=(), device_kind="TPU v5 lite",
        trace=trace_of(ops, module, devices=("0",)),
        programs=(program(scopes, module, products=products),),
    )


@pytest.mark.parametrize("case", EXPERT_TRACES)
@pytest.mark.parametrize("metric,module,cost", [
    ("kernel.expert_mm_train_roofline", "jit_step", "_expert_mm_train"),
    ("kernel.expert_mm_decode_roofline", "jit_fused_fn", "_expert_mm_decode"),
])
def test_expert_rooflines_take_an_event_by_its_own_instruction(
        case, metric, module, cost):
    """Until PR 34 the reader matched an event's whole text: it took the
    consumers of a call's result (3.25 s in the first case) and nothing
    without a call (``None`` in the second)."""
    ops, seconds = EXPERT_TRACES[case]
    run = kernel_run(ops, module)
    share = readers.read(run, metric)
    if seconds is None:
        assert share is None
        return
    least, bound = costs.roofline_seconds(getattr(readers, cost)(run), run.peak)
    assert share == pytest.approx(100.0 * least / seconds)
    assert run.notes[metric + ".bound"] == bound


@pytest.mark.parametrize("ops,seconds", [
    ([(FLASH, 0.0, 2.0), (FLASH_CONSUMER, 2.0, 1.0), (Q_PROJ, 3.0, 1.0)], 2.0),
    ([(FLASH_CONSUMER, 2.0, 1.0), (Q_PROJ, 3.0, 1.0)], None),
], ids=["the_kernels_alone", "no_kernel"])
def test_flash_roofline_takes_the_custom_calls_under_self_attn(ops, seconds):
    hf = dict(QWEN, num_attention_heads=32, num_key_value_heads=4,
              head_dim=128)
    scopes = {"custom-call.7": "jit(f)/layers_0/self_attn/pallas_call",
              "fusion.8": "jit(f)/layers_0/self_attn/o_proj/dot_general",
              **SCOPES}
    run = kernel_run(ops, "jit_train_step", hf, scopes)
    share = readers.read(run, "kernel.flash_train_roofline")
    if seconds is None:
        assert share is None
        return
    least, _ = costs.roofline_seconds(readers._flash_train(run), run.peak)
    assert share == pytest.approx(100.0 * least / seconds)


def test_a_kernel_roofline_needs_a_trace_and_an_execution():
    run = kernel_run(EXPERT_TRACES["calls_and_their_consumers"][0], "other")
    assert readers.read(run, "kernel.expert_mm_train_roofline") is None
    run.trace = None
    assert readers.read(run, "kernel.expert_mm_decode_roofline") is None
    assert readers.read(run, "kernel.flash_train_roofline") is None


def test_a_kernel_roofline_reads_nothing_where_two_programs_disagree():
    """Two programs of one name that the events cannot tell apart and
    that disagree on an instruction: a note, and no number."""
    run = kernel_run(EXPERT_TRACES["plain_dot_generals_by_scope"][0],
                     "jit_fused_fn")
    twin = layers.Program("jit_fused_fn", {}, SCOPES, {})
    run.programs = (*run.programs, twin)
    assert readers.read(run, "kernel.expert_mm_decode_roofline") is None
    assert "jit_fused_fn(17)" in run.notes["kernel.expert_mm_decode_roofline.ambiguous"]
    # the twin told apart by what its instructions return: read again
    run.programs = (run.programs[0]._replace(
        results={"fusion.9": "bf16[512,1024]{1,0}"}), twin)
    assert readers.read(run, "kernel.expert_mm_decode_roofline") is not None


def test_seeded_params_follow_the_seed_and_not_the_program():
    """One init program for every seed: the key is an argument, so two
    seeds lower to the same text, and the weights follow the seed."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import build
    from d9d_tpu.core import MeshParameters
    from d9d_tpu.parallel import replicate_plan

    ctx = MeshParameters().build(jax.devices()[:1])
    module, sample = nn.Dense(4), (jnp.zeros((2, 3)),)
    make = lambda seed: build.seeded_params(  # noqa: E731
        module, sample, seed, ctx.mesh, replicate_plan(ctx))
    a, b, big = make(7), make(7), make(2**31 + 11)
    kernel = lambda p: np.asarray(p["params"]["kernel"])  # noqa: E731
    assert np.array_equal(kernel(a), kernel(b))
    assert not np.array_equal(kernel(a), kernel(big))
    assert not np.array_equal(kernel(a), kernel(make(8)))
