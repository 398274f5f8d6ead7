"""The serving loop's always-on phase clock and its prompt-step counter:
``serve/phase/*`` partition every ``serve/step`` gap-free, one set per
chunk, with the fused path's one dispatch and one readback per chunk
untouched; ``ServeStats.slot_steps_prompt`` counts exactly the steps in
which a row only consumed a prompt token."""

import pytest

pytestmark = pytest.mark.e2e  # whole-model serving loops

from tests.loop.test_serve import _dense, _params, _prompts

from d9d_tpu.core.tracing import annotations_enabled
from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.telemetry import Telemetry

PHASES = ["admit", "plan", "dispatch", "readback", "commit"]
K = 4


@pytest.fixture(scope="module")
def model_and_params():
    model = _dense()
    return model, _params(model)


def spans_of(hub, prefix="serve/"):
    return [s for s in hub.registry.spans if s.name.startswith(prefix)]


def test_phases_partition_every_chunk_gap_free(model_and_params):
    model, params = model_and_params
    hub = Telemetry()
    b = ContinuousBatcher(
        model, params, batch_size=2, chunk_size=K, telemetry=hub
    )
    for p in _prompts(3, 3):
        b.submit(p, max_new_tokens=6)
    assert not annotations_enabled()  # no profiler: the clock is on anyway
    chunks = 0
    while b.active:
        b.step_chunk()
        chunks += 1
    assert b.step_chunk() == {}  # idle: no chunk, no spans

    # the fused path's host contract is what it was
    assert b.stats.chunks == chunks
    assert b.stats.host_dispatches == chunks and b.stats.readbacks == chunks

    steps = [s for s in spans_of(hub) if s.name == "serve/step"]
    assert [s.step for s in steps] == list(range(chunks))
    for step in steps:
        mine = [
            s for s in spans_of(hub, "serve/phase/") if s.step == step.step
        ]
        # one set per chunk, in order
        assert [s.name for s in mine] == [f"serve/phase/{p}" for p in PHASES]
        # gap-free: each phase starts where the last one ended, the first
        # with the chunk and the last ends with it
        assert mine[0].t0 == step.t0
        for a, nxt in zip(mine, mine[1:]):
            assert a.t0 + a.dur_s == pytest.approx(nxt.t0, abs=1e-9)
        assert sum(s.dur_s for s in mine) == pytest.approx(
            step.dur_s, abs=1e-6
        )
        assert all(s.dur_s >= 0 for s in mine)


def test_single_step_and_overlapped_drain_emit_the_same_phases(
    model_and_params,
):
    model, params = model_and_params
    hub = Telemetry()
    b = ContinuousBatcher(
        model, params, batch_size=2, chunk_size=K, telemetry=hub
    )
    b.submit(_prompts(4, 1)[0], max_new_tokens=3)
    b.step()  # a K=1 chunk under the same clock
    names = [s.name for s in spans_of(hub)]
    assert names == [f"serve/phase/{p}" for p in PHASES] + ["serve/step"]

    hub.registry.spans.clear()
    for p in _prompts(5, 3):
        b.submit(p, max_new_tokens=9)
    b.drain()
    names = [s.name for s in spans_of(hub)]
    # there one chunk's harvest overlaps the next one's compute: the
    # phases per dispatch and per harvest, and no per-chunk partition
    assert "serve/step" not in names
    dispatched = b.stats.chunks - 1
    for phase, per in (("admit", dispatched), ("plan", dispatched),
                       ("dispatch", dispatched), ("readback", dispatched),
                       ("commit", dispatched)):
        assert names.count(f"serve/phase/{phase}") == per, phase
    assert b.stats.readbacks == b.stats.chunks == b.stats.host_dispatches


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_slot_steps_prompt_is_exact(model_and_params, paged):
    """A table of known requests: a request spends ``n_prompt - 1`` steps
    only consuming (the step that takes the last prompt token emits), a
    prefix-cache hit skips the cached tokens, and a row that dies
    mid-chunk is busy through the step it dies on."""
    model, params = model_and_params
    kw = {"page_size": 8, "num_pages": 9} if paged else {}
    b = ContinuousBatcher(
        model, params, batch_size=2, chunk_size=K, telemetry=Telemetry(),
        **kw,
    )
    long = _prompts(42, 1, lo=18, hi=19)[0]  # 2 full pages + a tail
    table = [
        (_prompts(6, 1, lo=6, hi=7)[0], 3),   # dies mid-chunk: 6 + 3 - 1 = 8 steps
        (_prompts(7, 1, lo=2, hi=3)[0], 5),
        ([5], 2),                             # one prompt token: none only consumed
        (long, 2),
    ]
    for prompt, n in table:
        b.submit(prompt, max_new_tokens=n)
    b.drain()
    expected = sum(len(p) - 1 for p, _ in table)
    assert b.stats.slot_steps_prompt == expected
    generation = sum(n for _, n in table)
    assert b.stats.emitted_tokens == generation
    assert b.stats.slot_steps_prompt + generation == b.stats.slot_steps_busy

    # the same long prompt again: with pages its two cached pages are
    # skipped, so only the tail is consumed
    before = b.stats.slot_steps_prompt
    b.submit(long, max_new_tokens=2)
    b.drain()
    cached = 16 if paged else 0
    if paged:
        assert b._cache_mgr.allocator.prefix_hits == 1 and b._cache_mgr.allocator.prefix_hit_tokens == cached
    assert b.stats.slot_steps_prompt - before == len(long) - cached - 1
    assert (
        b.stats.slot_steps_prompt + b.stats.emitted_tokens
        == b.stats.slot_steps_busy
    )


def test_slot_steps_prompt_a_token_a_step(model_and_params):
    """The same count through ``step()``, the single-token surface."""
    model, params = model_and_params
    b = ContinuousBatcher(
        model, params, batch_size=2, chunk_size=1, telemetry=Telemetry()
    )
    table = [(p, 3) for p in _prompts(8, 3)]
    for prompt, n in table:
        b.submit(prompt, max_new_tokens=n)
    while b.active:
        b.step()
    assert b.stats.slot_steps_prompt == sum(len(p) - 1 for p, _ in table)
    assert (
        b.stats.slot_steps_prompt + b.stats.emitted_tokens
        == b.stats.slot_steps_busy
    )
