"""What a fused chunk's dispatch cost the host, on the chunk's closing
``serve/step`` span: the jit wrapper's signature walk, the enqueue, the
argument leaves, and the seconds and count of the chunk's host-to-device
stagings; and the two annotations that cover what ``step_chunk()`` does
outside ``serve.admit``, ``serve.dispatch`` and ``serve.readback``. The
phase clock's five spans stay where they were."""

import contextlib

import jax
import pytest

pytestmark = pytest.mark.e2e  # whole-model serving loops

from tests.loop.test_serve import _dense, _params, _prompts

from d9d_tpu.loop import serve
from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.nn.decode_flags import map_page_table
from d9d_tpu.telemetry import Telemetry

PHASES = ["admit", "plan", "dispatch", "readback", "commit"]
SPLIT = {"dispatch_key_s", "dispatch_enqueue_s", "dispatch_arg_leaves",
         "stage_s", "stage_transfers"}
K = 4


@pytest.fixture(scope="module")
def model_and_params():
    model = _dense()
    return model, _params(model)


def a_batcher(model_and_params, paged, hub=None):
    model, params = model_and_params
    kw = {"page_size": 8, "num_pages": 9} if paged else {}
    return ContinuousBatcher(
        model, params, batch_size=2, chunk_size=K,
        telemetry=hub or Telemetry(), **kw,
    )


def page_table_leaves(b) -> int:
    found = []
    map_page_table(b._cache, lambda pt: found.append(pt) or pt)
    return len(found)


def chunks_of(hub):
    """Per chunk: the closing span and its phases by name."""
    steps = [s for s in hub.registry.spans if s.name == "serve/step"]
    return [
        (step, {
            s.name.rsplit("/", 1)[1]: s for s in hub.registry.spans
            if s.name.startswith("serve/phase/") and s.step == step.step
        })
        for step in steps
    ]


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_both_fused_programs_put_the_split_on_the_chunks_span(
        model_and_params, paged):
    hub = Telemetry()
    b = a_batcher(model_and_params, paged, hub)
    # a short and a long request: chunks with admission, chunks without,
    # and (with pages) a chunk after a row died and its pages were freed
    b.submit(_prompts(3, 1, lo=3, hi=4)[0], max_new_tokens=3)
    b.submit(_prompts(4, 1, lo=2, hi=3)[0], max_new_tokens=17)
    table_leaves = page_table_leaves(b)
    assert table_leaves == (2 if paged else 0)  # one a layer

    released, after_release = False, []
    while b.active:
        after_release.append(released)
        emitted = b.step_chunk()
        released = paged and any(rid in b.done for rid in emitted)

    chunks = chunks_of(hub)
    assert len(chunks) == b.stats.chunks == len(after_release) >= 4
    assert any(after_release) == paged
    param_leaves = len(jax.tree.leaves(b._params))
    cache_leaves = len(jax.tree.leaves(b._cache))
    seen_admission = set()
    for step, phases in chunks:
        meta = step.meta
        assert SPLIT <= set(meta)
        seen_admission.add(meta["rows_reset"] > 0)
        # parameters, cache, four carries, the key and the one array
        # that holds the plan, the admission and the page table: the
        # same leaves in the program with admission and the one without
        assert meta["dispatch_arg_leaves"] == (
            param_leaves + cache_leaves + 4 + 1 + 1
        )
        # one staging a chunk, whatever the chunk holds: an admission, a
        # table that an admission or a death changed on the host, or
        # neither; the RNG key is split on the device
        assert meta["stage_transfers"] == 1
        assert meta["dispatch_key_s"] >= 0 and meta["dispatch_enqueue_s"] > 0
        assert meta["stage_s"] > 0
        # the wrapper's walk and the enqueue are inside the dispatch
        # phase, the staging inside plan
        assert (meta["dispatch_key_s"] + meta["dispatch_enqueue_s"]
                <= phases["dispatch"].dur_s)
        assert meta["stage_s"] <= phases["plan"].dur_s
        # and the partition is what it was: five phases, gap-free
        assert list(phases) == PHASES
        mine = [phases[p] for p in PHASES]
        assert mine[0].t0 == step.t0
        for a, nxt in zip(mine, mine[1:]):
            assert a.t0 + a.dur_s == pytest.approx(nxt.t0, abs=1e-9)
        assert sum(s.dur_s for s in mine) == pytest.approx(
            step.dur_s, abs=1e-6)
    assert seen_admission == {True, False}
    # the host contract is what it was: one dispatch, one readback a chunk
    assert b.stats.host_dispatches == b.stats.readbacks == b.stats.chunks


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_a_steady_chunk_walks_no_argument_leaf(
        model_and_params, paged, monkeypatch):
    """The fused programs carry no unordered effect (the model is traced
    under ``caller_holds_bounds``), so jax gives them a C++ dispatch and
    the wrapper's steady call finds the executable there: once each
    program's outputs have come back as arguments, a chunk costs no
    Python walk over the parameters and the cache."""
    from d9d_tpu.telemetry import introspect

    walks = []
    key_of = introspect.TrackedJit._signature_key

    def counted(self, args, kwargs):
        walks.append(self.name)
        return key_of(self, args, kwargs)

    monkeypatch.setattr(introspect.TrackedJit, "_signature_key", counted)
    hub = Telemetry()
    b = a_batcher(model_and_params, paged, hub)
    prompts = iter(_prompts(9, 8, lo=2, hi=4))
    for _ in range(2):
        b.submit(next(prompts), max_new_tokens=6)
    per_chunk = []
    while b.active:
        before = len(walks)
        emitted = b.step_chunk()
        per_chunk.append(len(walks) - before)
        for rid in emitted:
            if rid in b.done:
                with contextlib.suppress(StopIteration):
                    b.submit(next(prompts), max_new_tokens=6)
    for fused in b._fused.values():
        (compiled,) = fused._compiled.values()
        assert not compiled._executable.unsafe_call.has_unordered_effects
        assert fused._steady is not None
    # both programs ran; each walked for its compile and once more when
    # its outputs first came back as arguments, and never after
    assert len(b._fused) == 2 and len(per_chunk) >= 8
    assert sum(per_chunk) <= 4 and not any(per_chunk[4:])
    steady = [s for s in hub.registry.spans if s.name == "serve/step"][4:]
    assert all(s.meta["dispatch_arg_leaves"] > 0 for s in steady)


def test_the_wrappers_record_adds_up_to_the_chunks(model_and_params):
    """``inventory()`` answers what the wrapper cost the process: the
    fused programs' records hold the sum of what the chunks' spans say."""
    from d9d_tpu.telemetry import introspect

    hub = Telemetry()
    b = a_batcher(model_and_params, paged=False, hub=hub)
    for p in _prompts(5, 2):
        b.submit(p, max_new_tokens=7)
    while b.active:
        b.step_chunk()
    records = {}
    for fused in b._fused.values():
        records.update(
            (id(r), r) for r in fused._records.values()
        )
    assert sum(r.calls for r in records.values()) == b.stats.chunks
    spans = [s for s in hub.registry.spans if s.name == "serve/step"]
    for key in ("key_s", "enqueue_s"):
        assert sum(getattr(r, key) for r in records.values()) == \
            pytest.approx(sum(s.meta[f"dispatch_{key}"] for s in spans))
    assert introspect.CallCost._fields == (
        "key_s", "enqueue_s", "arg_leaves")


def test_plan_and_commit_are_annotated_beside_the_three(
        model_and_params, monkeypatch):
    """With a capture live every region of a chunk has its annotation,
    none nested in another, in the order of the phases."""
    events = []

    @contextlib.contextmanager
    def recorded(label):
        events.append(("open", label))
        yield
        events.append(("close", label))

    monkeypatch.setattr(serve, "annotate", recorded)
    b = a_batcher(model_and_params, paged=True)
    b.submit(_prompts(6, 1)[0], max_new_tokens=6)
    chunks = 0
    while b.active:
        b.step_chunk()
        chunks += 1
    one_chunk = [
        (edge, f"serve.{phase}") for phase in PHASES
        for edge in ("open", "close")
    ]
    assert events == one_chunk * chunks


def test_the_overlapped_drain_annotates_its_commits_too(
        model_and_params, monkeypatch):
    opened = []

    @contextlib.contextmanager
    def recorded(label):
        opened.append(label)
        yield

    monkeypatch.setattr(serve, "annotate", recorded)
    b = a_batcher(model_and_params, paged=False)
    for p in _prompts(8, 3):
        b.submit(p, max_new_tokens=9)
    b.drain()
    assert opened.count("serve.commit") == opened.count("serve.readback") \
        == b.stats.readbacks
    assert opened.count("serve.plan") == opened.count("serve.dispatch") \
        == b.stats.host_dispatches
