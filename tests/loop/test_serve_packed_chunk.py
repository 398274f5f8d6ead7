"""A fused chunk's host-made arguments as ONE packed array
(``loop/serve.py _chunk_columns``: plan, admission and page table; the
RNG key split inside the program): the tokens are the ones the loop gave
when every piece crossed on its own. Greedy streams are held to
``generate`` and to the single-step path, which stages as it always did;
sampled streams to what the tree before the packing gave for the same
seed, and the key to the host's own chain of splits. With pages: the
table fans out inside the program and is pinned by the device's ``live``
after it, so a row that died with its death unread keeps off its pages;
a row the host zeroed is rerouted to the garbage page."""

import time

import jax
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

pytestmark = pytest.mark.e2e  # whole-model serving loops

from tests.loop.test_serve import _dense, _oracle, _params, _prompts

from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.nn.decode_flags import PAGE_TABLE_LEAF
from d9d_tpu.telemetry import Telemetry, introspect

def _kv(batcher):
    """The batcher's host page allocator (``loop/kv_paging.py``)."""
    return batcher._cache_mgr.allocator


K = 4
PAGE = 8  # decode_max_length=24 → 3 pages per row
SEED = 20260930
LAYOUTS = pytest.mark.parametrize(
    "paged", [False, True], ids=["contiguous", "paged"]
)


@pytest.fixture(scope="module")
def model_and_params():
    model = _dense()
    return model, _params(model)


def a_batcher(model_and_params, *, paged, chunk=K, temperature=0.0, **kw):
    model, params = model_and_params
    if paged:
        kw.update(page_size=PAGE, num_pages=13)
    if temperature:
        kw.update(temperature=temperature, rng=jax.random.PRNGKey(SEED))
    return ContinuousBatcher(
        model, params, batch_size=2, chunk_size=chunk, **kw
    )


def mixed_run(b, advance=None):
    """Short and long requests, a prompt served twice (with pages its
    second serving starts past two shared pages), a death followed by
    chunks that admit nothing, and a drain that sends a chunk out while
    the one a row died in is still in flight. Returns the requests as
    ``(prompt, budget, stream)`` in submission order and every dispatch
    as ``(admit, chunks in flight)``. ``advance`` steps the waves
    (``step_chunk`` unless given)."""
    advance = advance or b.step_chunk
    dispatches = []
    inner = b._dispatch_chunk

    def recorded(k, admit):
        dispatches.append((admit, len(b._pending)))
        inner(k, admit)

    b._dispatch_chunk = recorded
    shared = _prompts(42, 1, lo=18, hi=19)[0]  # two full pages and a tail
    short, long_, tail, longer_tail = _prompts(7, 4, lo=2, hi=5)
    asked = []

    def submit(prompt, n):
        asked.append((prompt, n, b.submit(prompt, max_new_tokens=n)))

    for wave in (
        [(shared, 3), (short, 2)],
        # one of the two outlives the other by chunks without admission
        [(shared, 2), (long_, 12)],
    ):
        for prompt, n in wave:
            submit(prompt, n)
        while b.active:
            advance()
    # the overlapped drain: the short tail dies in a chunk that is still
    # unread when the next one goes out for the longer tail
    submit(tail, 3)
    submit(longer_tail, 14)
    out = b.drain()
    return [(p, n, out[rid]) for p, n, rid in asked], dispatches


@LAYOUTS
def test_greedy_streams_are_generates_and_the_single_step_paths(
        model_and_params, paged):
    model, params = model_and_params
    hub = Telemetry()
    b = a_batcher(model_and_params, paged=paged, telemetry=hub)
    before = len(introspect.inventory())
    got, dispatches = mixed_run(b)
    for prompt, n, stream in got:
        assert stream == _oracle(model, params, prompt, n)
    # the single-token surface: ``step()`` waves, a K = 1 drain
    single = a_batcher(model_and_params, paged=paged, chunk=1)
    stepped, _ = mixed_run(single, single.step)
    assert got == stepped
    # the run held what it was written for: a prefix hit, so a first
    # position past 0; chunks without admission after a death; follow-up
    # chunks dispatched with one in flight
    if paged:
        assert _kv(b).prefix_hits == 1
        assert _kv(b).prefix_hit_tokens == 2 * PAGE
    steps = [s for s in hub.registry.spans if s.name == "serve/step"]
    assert {s.meta["rows_reset"] > 0 for s in steps} == {True, False}
    assert any(not admit and flying for admit, flying in dispatches)
    # one staging a chunk of either program, and two programs a K
    assert {s.meta["stage_transfers"] for s in steps} == {1}
    programs = sorted(
        r.name for r in introspect.inventory()[before:]
        if r.name.startswith(f"serve/fused_k{K}")
    )
    suffix = "_paged" if paged else ""
    assert programs == [f"serve/fused_k{K}{suffix}",
                        f"serve/fused_k{K}{suffix}_admit"]
    assert sorted(b._fused) == [(K, False), (K, True)]
    b.close()


# what the tree before the packing (commit 84eb809: the RNG split on the
# host, every array staged alone) gave for this run, temperature 0.8
SAMPLED = {
    False: [
        [20, 12, 14], [36, 45], [34, 43],
        [35, 11, 0, 40, 11, 51, 42, 52, 21, 7, 23, 61], [59, 30, 55],
        [29, 59, 0, 49, 31, 32, 45, 14, 29, 15, 24, 42, 45, 37],
    ],
    # the second serving of the shared prompt starts past two pages, so
    # its steps and every later chunk's meet other keys
    True: [
        [20, 12, 14], [36, 45], [30, 35],
        [35, 11, 0, 40, 11, 51, 42, 52, 21, 7, 23, 61], [34, 43, 30],
        [8, 32, 48, 29, 29, 53, 0, 49, 31, 32, 45, 14, 29, 15],
    ],
}


@LAYOUTS
def test_sampled_streams_keep_the_hosts_random_bits(model_and_params, paged):
    b = a_batcher(model_and_params, paged=paged, temperature=0.8)
    got, _ = mixed_run(b)
    assert [stream for _, _, stream in got] == SAMPLED[paged]
    # the key the program hands back is the host's chain of splits
    key = jax.random.PRNGKey(SEED)
    for _ in range(b.stats.chunks):
        key, _ = jax.random.split(key)
    assert np.array_equal(np.asarray(b._rng), np.asarray(key))
    b.close()


def test_the_callers_key_outlives_the_donated_carry(model_and_params):
    model, params = model_and_params
    key = jax.random.PRNGKey(SEED)
    twins = [
        ContinuousBatcher(model, params, batch_size=2, chunk_size=K,
                          temperature=0.8, rng=key)
        for _ in range(2)
    ]
    prompt = _prompts(5, 1)[0]
    streams = []
    for b in twins:
        rid = b.submit(prompt, max_new_tokens=6)
        streams.append(b.drain()[rid])
        b.close()
    assert streams[0] == streams[1]
    assert np.array_equal(np.asarray(key),
                          np.asarray(jax.random.PRNGKey(SEED)))


def pools(b) -> dict:
    return {p: np.asarray(v) for p, v in b._cache_mgr.pool_leaves(b._cache).items()}


def device_tables(b) -> list:
    return [
        np.asarray(v) for p, v in flatten_dict(b._cache).items()
        if p[-1] == PAGE_TABLE_LEAF
    ]


def test_a_row_that_died_unread_writes_into_the_garbage_page(
        model_and_params):
    """Row 0 dies inside the first chunk; the next chunk goes out with
    that chunk in flight, so the host's mirror still holds row 0's
    pages and hands them to the program: the pin by ``live`` comes
    after the fan-out, and the dead row's steps land in page 0."""
    b = a_batcher(model_and_params, paged=True)
    short, long_ = _prompts(7, 2, lo=2, hi=3)
    b.submit(short, max_new_tokens=1)
    b.submit(long_, max_new_tokens=15)
    b._dispatch_chunk(K, admit=True)
    mine = _kv(b).table[0].copy()
    assert mine[0] > 0
    held = pools(b)  # waits for the chunk, reads nothing back of it
    b._dispatch_chunk(K, admit=False)
    assert len(b._pending) == 2
    assert np.array_equal(_kv(b).table[0], mine)  # the death is unread
    for table in device_tables(b):
        assert not table[0].any() and table[1].any()
    after = pools(b)
    for path, pool in held.items():
        # the dead row's K steps wrote slot 0 of the garbage page, as
        # its last steps of the chunk before did: its own page keeps
        # the prompt's first position
        assert np.array_equal(after[path][mine[0]], pool[mine[0]])
        assert not np.array_equal(after[path][0], after[path][mine[0]])
    out = b.drain()
    model, params = model_and_params
    assert out[1] == _oracle(model, params, long_, 15)
    _kv(b).check_invariants()
    b.close()


def test_a_row_the_host_zeroed_is_rerouted_and_its_pages_wait(
        model_and_params):
    """A host-side kill with a chunk in flight: the mirror's row is
    zeroed at once and goes out with the next follow-up chunk, so the
    still-live device twin writes into page 0 from then on; its pages
    stay held until the clean boundary, as the chunk in flight may
    still write them. A released row goes the same way."""
    b = a_batcher(model_and_params, paged=True)
    doomed_prompt, long_ = _prompts(9, 2, lo=2, hi=3)
    doomed = b.submit(doomed_prompt, max_new_tokens=15, deadline_s=0.01)
    kept = b.submit(long_, max_new_tokens=15)
    b.step_chunk()
    b._dispatch_chunk(K, admit=False)  # in flight
    mine = _kv(b).table[0].copy()
    time.sleep(0.05)
    b._expire_running(time.perf_counter())
    assert b.failed[doomed] == "deadline"
    assert _kv(b)._deferred and not _kv(b).table[0].any()
    in_use = _kv(b).pages_in_use
    held = pools(b)
    b._dispatch_chunk(K, admit=False)  # the zombie steps on, rerouted
    for table in device_tables(b):
        assert not table[0].any() and table[1].any()
    after = pools(b)
    for path, pool in held.items():
        for page in mine[mine > 0]:
            assert np.array_equal(after[path][page], pool[page])
    assert _kv(b)._deferred and _kv(b).pages_in_use == in_use
    fresh = b.submit(doomed_prompt, max_new_tokens=3)
    out = b.drain()  # a clean boundary on its way: the pages free
    assert not _kv(b)._deferred
    model, params = model_and_params
    assert out[kept] == _oracle(model, params, long_, 15)
    assert out[fresh] == _oracle(model, params, doomed_prompt, 3)
    _kv(b).check_invariants()
    b.close()
