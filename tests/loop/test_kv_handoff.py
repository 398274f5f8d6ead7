"""Cross-replica KV page shipment (loop/kv_paging.py export/import,
loop/serve.py KVPageShipment): the allocator-level primitives must keep
refcounts exact across a ship (export is refcount-neutral, import
registers READY one-ref entries), coexist with deferred release, and
refuse partial imports; the serving-level shipment must round-trip page
payloads bit-exactly (int8 pools WITH their sibling scale pages), and
the per-page checksum must catch corruption before anything is written.
The fleet integration is pinned by tests/resilience/test_fleet_disagg.py.
"""

import numpy as np
import pytest

from d9d_tpu.loop.kv_paging import PagedKVAllocator


def _kv(batcher):
    """The batcher's host page allocator (``loop/kv_paging.py``)."""
    return batcher._cache_mgr.allocator


def _alloc(**kw):
    kw.setdefault("num_pages", 9)       # 8 allocatable + garbage
    kw.setdefault("page_size", 4)
    kw.setdefault("rows", 2)
    kw.setdefault("max_pages_per_row", 4)
    return PagedKVAllocator(**kw)


# -- allocator export ----------------------------------------------------


def test_export_pages_is_refcount_neutral():
    kv = _alloc()
    a = kv.admit(0, 0, [1, 2, 3, 4, 5], 10)
    assert kv.export_pages(0) == list(a.pages)
    assert kv.pages_in_use == 3  # unchanged: export observes, never holds
    kv.check_invariants()
    with pytest.raises(KeyError):
        kv.export_pages(7)  # no such live rid


def test_export_prefix_walks_only_ready_chain():
    kv = _alloc(rows=3)
    prompt = list(range(9))  # 2 full blocks + tail
    a = kv.admit(0, 0, prompt, 12)
    # owner still filling: nothing exportable yet
    assert kv.export_prefix(prompt) == []
    kv.mark_filled(0)
    assert kv.export_prefix(prompt) == list(a.pages[:2])
    kv.release(0)
    # entries outlive the row: the chain still exports after release
    assert kv.export_prefix(prompt) == list(a.pages[:2])
    # a diverging prompt exports only the shared leading blocks
    fork = prompt[:4] + [99, 99, 99, 99, 99]
    assert kv.export_prefix(fork) == list(a.pages[:1])
    kv.check_invariants()


# -- allocator import ----------------------------------------------------


def test_import_pages_registers_ready_entries_with_exact_refs():
    kv = _alloc(rows=3)
    prompt = list(range(8))
    placed = kv.import_pages(prompt, 2)
    assert placed is not None and [b for b, _ in placed] == [0, 1]
    assert kv.pages_in_use == 2
    kv.check_invariants()
    # the imported chain is a first-class prefix hit for admission
    a = kv.admit(0, 0, prompt + [8], 12)
    assert a.hit_tokens == 8 and a.n_shared == 2
    assert a.pages[:2] == [p for _, p in placed]
    kv.check_invariants()
    kv.release(0)
    kv.check_invariants()


def test_import_pages_skips_cached_blocks_and_refuses_partial():
    kv = _alloc(rows=3, num_pages=5)  # 4 allocatable
    prompt = list(range(12))  # 3 full blocks
    first = kv.import_pages(prompt, 1)
    assert first is not None and len(first) == 1
    # re-import over a longer run: the cached leading block is skipped
    more = kv.import_pages(prompt, 3)
    assert more is not None and [b for b, _ in more] == [1, 2]
    # full re-import of a fully-cached chain: nothing to copy
    assert kv.import_pages(prompt, 3) == []
    kv.check_invariants()
    # genuine shortfall (5 blocks > 4 allocatable even after eviction):
    # refuse WHOLESALE — no partial chain, no entries registered
    other = [77] * 20
    assert kv.import_pages(other, 5) is None
    assert kv.export_prefix(other) == []
    kv.check_invariants()


def test_import_pages_blocked_by_filling_mid_chain():
    kv = _alloc(rows=3)
    prompt = list(range(9))
    kv.admit(0, 0, prompt, 12)  # entries registered, NOT ready
    assert kv.import_pages(prompt, 2) == []  # nothing importable past it
    kv.mark_filled(0)
    assert kv.import_pages(prompt, 2) == []  # now cached: still no copies
    kv.check_invariants()


def test_import_pages_evicts_lru_on_pressure():
    kv = _alloc(rows=3, num_pages=5)  # 4 allocatable
    old = [5] * 8
    a = kv.import_pages(old, 2)
    assert a is not None and len(a) == 2
    fresh = [6] * 16
    placed = kv.import_pages(fresh, 4)
    assert placed is not None and len(placed) == 4
    # the old sole-held chain was evicted to make room
    assert kv.export_prefix(old) == []
    assert len(kv.export_prefix(fresh)) == 4
    kv.check_invariants()


def test_import_interacts_with_deferred_release():
    kv = _alloc(rows=2, num_pages=5)  # 4 allocatable
    a = kv.admit(0, 0, [9] * 9, 12)   # 3 pages, 2 prefix entries
    kv.defer_release(0)               # zombie holds all 3 until flush
    assert kv.pages_in_use == 3
    # import needs 2 pages; only 1 is free and the zombie's pages are
    # NOT reclaimable by eviction (refs > 1 via the row hold)
    assert kv.import_pages([7] * 8, 2) is None
    kv.check_invariants()
    kv.flush_deferred()
    kv.check_invariants()
    placed = kv.import_pages([7] * 8, 2)
    assert placed is not None and len(placed) == 2
    kv.check_invariants()


# -- serving-level shipment (device pools, checksums) --------------------


@pytest.mark.e2e
@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_shipment_round_trips_pool_payloads(paged_toy_factory, kv_quant):
    from tests.resilience.conftest import paged_toy_expected

    src = paged_toy_factory(kv_quant=kv_quant)
    dst = paged_toy_factory(kv_quant=kv_quant)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5]  # 2 full pages of 4 + tail
    rid = src.submit(prompt, max_new_tokens=3)
    out = src.drain()
    assert out[rid] == paged_toy_expected(prompt, 3)
    ship = src.export_kv_pages(prompt)
    assert ship is not None and ship.n_pages == 2
    if kv_quant == "int8":
        # int8 pools ship WITH their sibling scale pages
        assert any(n.endswith("_scale") for n in ship.payload)
    # payload rows are the exact device pool pages, in chain order
    pool = {n: np.asarray(leaf) for n, leaf in src._cache_mgr.pool_leaves(src._cache).items()}
    pages = _kv(src).export_prefix(prompt)
    for name, arr in ship.payload.items():
        np.testing.assert_array_equal(arr, pool[name][np.asarray(pages)])
    assert dst.import_kv_pages(ship)
    _kv(dst).check_invariants()
    dpool = {n: np.asarray(leaf) for n, leaf in dst._cache_mgr.pool_leaves(dst._cache).items()}
    dpages = _kv(dst).export_prefix(prompt)
    assert len(dpages) == 2
    for name, arr in ship.payload.items():
        np.testing.assert_array_equal(
            arr, dpool[name][np.asarray(dpages)]
        )
    # the shipped prefix decodes exactly like a cold prefill
    rid2 = dst.submit(prompt, max_new_tokens=3)
    out2 = dst.drain()
    assert out2[rid2] == paged_toy_expected(prompt, 3)
    assert _kv(dst).prefix_hits == 1
    _kv(dst).check_invariants()


@pytest.mark.e2e
def test_shipment_checksum_catches_corruption(paged_toy_factory):
    from d9d_tpu.telemetry import Telemetry, set_telemetry

    tele = Telemetry()
    set_telemetry(tele)
    src = paged_toy_factory()
    dst = paged_toy_factory()
    prompt = [2] * 9
    src.submit(prompt, max_new_tokens=2)
    src.drain()
    ship = src.export_kv_pages(prompt)
    assert ship is not None
    name = sorted(ship.payload)[0]
    raw = ship.payload[name].copy()
    raw.view(np.uint8).flat[0] ^= 0xFF
    ship.payload[name] = raw
    before = {n: np.asarray(v) for n, v in dst._cache_mgr.pool_leaves(dst._cache).items()}
    assert not dst.import_kv_pages(ship)
    # refused WHOLESALE: no entries registered, no pool bytes written
    assert len(_kv(dst)._entries) == 0
    for n, v in dst._cache_mgr.pool_leaves(dst._cache).items():
        np.testing.assert_array_equal(np.asarray(v), before[n])
    _kv(dst).check_invariants()


@pytest.mark.e2e
def test_shipment_version_mismatch_refused(paged_toy_factory):
    src = paged_toy_factory()
    dst = paged_toy_factory()
    prompt = [4] * 9
    src.submit(prompt, max_new_tokens=2)
    src.drain()
    ship = src.export_kv_pages(prompt)
    assert ship is not None
    # cached KV is weights-dependent: a shipment minted under another
    # generation must be refused (same invariant as install_weights
    # prefix invalidation)
    ship.weights_version = ship.weights_version + 1
    assert not dst.import_kv_pages(ship)
    assert len(_kv(dst)._entries) == 0
    _kv(dst).check_invariants()


@pytest.mark.e2e
def test_shipment_quant_mode_mismatch_refused(paged_toy_factory):
    src = paged_toy_factory()
    dst = paged_toy_factory(kv_quant="int8")
    prompt = [4] * 9
    src.submit(prompt, max_new_tokens=2)
    src.drain()
    ship = src.export_kv_pages(prompt)
    assert ship is not None
    assert not dst.import_kv_pages(ship)  # f32 pages into int8 pools
    assert len(_kv(dst)._entries) == 0
    _kv(dst).check_invariants()


@pytest.mark.e2e
def test_export_respects_transfer_budget_chunks(paged_toy_factory):
    src = paged_toy_factory()
    prompt = [1] * 13  # 3 full pages
    src.submit(prompt, max_new_tokens=2)
    src.drain()
    # a budget of one page's bytes forces one chunk per page
    ship = src.export_kv_pages(
        prompt, transfer_budget_bytes=src._cache_mgr.page_bytes
    )
    assert ship is not None and ship.n_pages == 3
    assert ship.chunks == 3
    big = src.export_kv_pages(prompt)
    assert big is not None and big.chunks == 1
    assert big.checksums == ship.checksums
