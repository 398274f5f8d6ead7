"""Degraded-mode serving: bounded-queue backpressure, per-request
deadlines (queued and running), and the drain stall watchdog."""

import time

import pytest

pytestmark = pytest.mark.chaos

from tests.resilience.conftest import toy_expected

from d9d_tpu.loop.serve import QueueFullError, ServeStalledError
from d9d_tpu.resilience.chaos import wedge_batcher


def test_bounded_queue_rejects_with_backpressure(toy_batcher_factory):
    b = toy_batcher_factory(max_queue=2)
    r1 = b.submit([3, 4], max_new_tokens=4)
    r2 = b.submit([7], max_new_tokens=3)
    b.step_chunk()  # admit r1/r2 into the two slots
    r3 = b.submit([1], max_new_tokens=2)
    b.submit([2], max_new_tokens=2)  # queue now at max_queue
    with pytest.raises(QueueFullError):
        b.submit([5], max_new_tokens=2)
    assert b.stats.rejected == 1
    # the overload shed cleanly: everything admitted still decodes right
    out = b.drain()
    assert out[r1] == toy_expected([3, 4], 4)
    assert out[r2] == toy_expected([7], 3)
    assert out[r3] == toy_expected([1], 2)
    assert not b.failed


def test_full_queue_expires_running_rows_before_rejecting(
    toy_batcher_factory,
):
    """ISSUE 11 satellite fix (running-side mirror of the PR 5
    queued-side fix): with the queue at max_queue, a deadline-expired
    RUNNING row frees a slot this boundary — the queue head will admit
    into it, so the submit must be accepted, not rejected."""
    b = toy_batcher_factory(max_queue=1, batch_size=1)
    doomed = b.submit([3], max_new_tokens=30, deadline_s=0.05)
    b.step_chunk()  # admitted into the only slot
    queued = b.submit([5], max_new_tokens=3)  # queue now at max_queue
    time.sleep(0.1)
    late = b.submit([9], max_new_tokens=3)  # pre-fix: QueueFullError
    assert b.failed[doomed] == "deadline"
    assert b.stats.rejected == 0
    out = b.drain()
    assert out[queued] == toy_expected([5], 3)
    assert out[late] == toy_expected([9], 3)
    # with nothing expirable the bounded-queue contract is unchanged
    r = b.submit([4], max_new_tokens=30)
    b.step_chunk()
    b.submit([6], max_new_tokens=2)
    with pytest.raises(QueueFullError):
        b.submit([8], max_new_tokens=2)
    assert b.stats.rejected == 1
    del r


def test_full_queue_expiry_credit_is_page_bounded_when_paged(
    toy_batcher_factory,
):
    """Paged admission is bounded by pages, not slots: a freed slot
    only counts as capacity for the full-queue check if the queue head
    can actually map onto free pages — otherwise the bounded-queue
    contract would be violated with the head still blocked."""
    b = toy_batcher_factory(
        max_queue=1, batch_size=2, page_size=4, num_pages=7,
    )
    # a long-lived row pinning 3 pages + a doomed row holding 3 more
    alive = b.submit([3], max_new_tokens=12)
    b.step_chunk()
    doomed = b.submit([4], max_new_tokens=12, deadline_s=0.05)
    b.step_chunk()
    # head of queue needs 4 pages; the expiry can only ever free 3
    head = b.submit([5], max_new_tokens=16)
    time.sleep(0.1)
    with pytest.raises(QueueFullError):
        b.submit([9], max_new_tokens=2)
    assert b.failed[doomed] == "deadline"  # the expiry itself happened
    out = b.drain()
    assert out[alive] == toy_expected([3], 12)
    assert out[head] == toy_expected([5], 16)
    b._cache_mgr.allocator.check_invariants()


def test_queued_request_past_deadline_expires_cleanly(toy_batcher_factory):
    b = toy_batcher_factory()
    ra = b.submit([3], max_new_tokens=30)
    rb = b.submit([4], max_new_tokens=30)
    rc = b.submit([5], max_new_tokens=4, deadline_s=0.01)  # will queue
    time.sleep(0.05)
    out = b.drain()
    assert b.failed[rc] == "deadline"
    assert rc in b.done and out[rc] == []
    assert b.stats.expired == 1
    # the live requests were untouched by the expiry
    assert out[ra] == toy_expected([3], 30)
    assert out[rb] == toy_expected([4], 30)


def test_running_request_past_deadline_evicted_at_boundary(
    toy_batcher_factory,
):
    b = toy_batcher_factory()
    rid = b.submit([3], max_new_tokens=30, deadline_s=0.05)
    b.step_chunk()  # admitted + decoding
    time.sleep(0.1)
    out = b.drain()
    assert b.failed[rid] == "deadline"
    # partial output up to the boundary is preserved, the row was freed
    assert 0 < len(out[rid]) < 30
    assert out[rid] == toy_expected([3], len(out[rid]))
    assert all(s.rid < 0 for s in b._slots)


def test_freed_slot_is_reusable_after_expiry(toy_batcher_factory):
    b = toy_batcher_factory(batch_size=1)
    r1 = b.submit([3], max_new_tokens=30, deadline_s=0.05)
    b.step_chunk()
    time.sleep(0.1)
    b.step_chunk()  # boundary: expire r1, free the only slot
    assert b.failed[r1] == "deadline"
    r2 = b.submit([9], max_new_tokens=3)
    out = b.drain()
    # the reused row was reset on admission: r2 decodes exactly
    assert out[r2] == toy_expected([9], 3)


def test_drain_stall_watchdog_converts_hang_to_error(toy_batcher_factory):
    b = toy_batcher_factory(stall_timeout_s=0.3)
    b.submit([3], max_new_tokens=30)
    # warm up one real chunk: the watchdog deliberately holds fire until
    # a readback has ever completed (first-call XLA compile can
    # legitimately exceed any reasonable stall timeout)
    b.step_chunk()
    wedge_batcher(b, seconds=60.0)
    t0 = time.monotonic()
    with pytest.raises(ServeStalledError):
        b.drain()
    assert time.monotonic() - t0 < 10.0  # error, not a 60 s hang
    assert b._tele.registry.counter("serve/stalls").value >= 1


def test_single_token_steps_honor_deadlines(toy_batcher_factory):
    b = toy_batcher_factory(chunk_size=1)
    rid = b.submit([3], max_new_tokens=20, deadline_s=0.05)
    for _ in range(3):
        b.step()
    time.sleep(0.1)
    b.step()  # boundary: expiry
    assert b.failed[rid] == "deadline"
    assert b.active == 0
