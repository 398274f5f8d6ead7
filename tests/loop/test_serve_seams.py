"""The serving loop's two seams (``loop/serve_cache.py``,
``loop/serve_accounting.py``): the cache manager answers the scheduler's
questions alone, over a tiny model's shapes and with no model program
compiled, and the three modules' imports point one way."""

import ast
import pathlib

import numpy as np
import pytest
from flax.traverse_util import flatten_dict, unflatten_dict

from tests.loop.test_serve import _dense, _jamba

from d9d_tpu.loop.serve_cache import CacheManager

LOOP = pathlib.Path(__file__).parents[2] / "d9d_tpu" / "loop"
PAGE = 8  # decode_max_length=24 → 3 pages a row


def _paged(**kw):
    kw.setdefault("num_pages", 7)  # 6 allocatable: two full rows
    return CacheManager(_dense(), batch_size=2, page_size=PAGE, **kw)


def test_unpaged_a_row_is_all_a_request_needs():
    mgr = CacheManager(_dense(), batch_size=2)
    assert not mgr.paged and mgr.pages_per_row == 0
    assert mgr.admit(0, 0, [1, 2, 3], 10) == 0
    mgr.check_fits(24)
    assert mgr.fits_after_flush(24)
    mgr.release_row(0, defer=False)
    assert mgr.invalidate_prefix_cache() is None
    # every row's whole context is resident whether used or not
    assert mgr.hbm_bytes_per_request() == mgr.kv_bytes_static
    assert mgr.prefix_hit_rate() == 0.0


def test_paged_admission_is_bounded_by_pages_and_release_frees_them():
    mgr = _paged()
    assert mgr.pages_per_row == 3 and mgr.table.shape == (2, 3)
    with pytest.raises(ValueError, match="could never be admitted"):
        _paged(num_pages=3).check_fits(24)
    prompt = list(range(18))  # two full pages and a tail
    assert mgr.admit(0, 0, prompt, 24) == 0  # a cold prefix: fed from 0
    assert mgr.pages_in_use == 3 and mgr.table[0].all()
    assert mgr.admit(1, 1, [5, 6], 24) == 0
    assert mgr.pages_free == 0
    assert not mgr.fits_after_flush(8)
    # a host-side kill with a chunk in flight: the table row is zeroed,
    # the pages wait for a clean boundary
    mgr.release_row(1, defer=True)
    assert not mgr.table[1].any() and mgr.pages_in_use == 6
    assert mgr.fits_after_flush(24)
    mgr.flush_deferred()
    assert mgr.pages_in_use == 3
    # the first request's prompt is dispatched, then it dies in-device:
    # its two full pages stay, as the prefix cache's
    mgr.mark_filled(0)
    mgr.release_row(0, defer=False)
    assert mgr.pages_in_use == 2
    assert mgr.admit(1, 2, prompt, 24) == 2 * PAGE  # fed past the hit
    assert mgr.prefix_hit_rate() == pytest.approx(1 / 3)
    assert (mgr.pages_in_use, mgr.pages_free) == (3, 3)
    mgr.note_running(2)
    mgr.note_running(1)  # the window's peak stays
    assert mgr.hbm_bytes_per_request() == 3 * mgr.page_bytes
    mgr.reset_window()
    assert mgr.peak_running == 0 and mgr.prefix_hit_rate() == 0.0
    assert mgr.invalidate_prefix_cache() == 2
    mgr.allocator.check_invariants()


def test_a_failed_fill_leaves_nothing_to_hit():
    mgr = _paged()
    prompt = list(range(18))
    assert mgr.admit(0, 0, prompt, 24) == 0
    mgr.drop_request(0)  # failed before its prompt was dispatched
    mgr.release_row(0, defer=False)
    assert mgr.pages_in_use == 0
    assert mgr.admit(0, 1, prompt, 24) == 0
    mgr.allocator.check_invariants()


def test_recurrent_state_refuses_the_prefix_cache():
    model = _jamba()
    auto = CacheManager(model, batch_size=2, page_size=PAGE)
    assert auto.unpageable_leaves == ["conv_tail", "ssm_state"]
    assert not auto.prefix_cache_enabled and auto.recurrent_state_bytes > 0
    assert auto.row_reset_bytes > auto.recurrent_state_bytes // 2
    with pytest.raises(ValueError, match="prefix_cache=True is unsound"):
        CacheManager(model, batch_size=2, page_size=PAGE, prefix_cache=True)


def test_a_window_layers_ring_refuses_the_prefix_cache_and_kv_quant():
    from tests.loop.test_serve_cache_kinds import MIMO

    from benchmarks.harness import build, manifest

    config = manifest.cell(MIMO).config
    cfg, _ = build.sizes(config, tiny=True)
    serving = config["tiny"]["serving"]
    model = build.decode_model(config, cfg, serving["decode_max_length"])
    kw = dict(batch_size=serving["slots"], page_size=serving["page_size"])
    mgr = CacheManager(model, **kw)
    assert dict(mgr.ring_windows) == {6: 2} and mgr.window_cache_bytes > 0
    assert {"ring_key", "ring_value"} <= set(mgr.unpageable_leaves)
    assert not mgr.prefix_cache_enabled
    with pytest.raises(ValueError, match="prefix_cache=True is unsound"):
        CacheManager(model, prefix_cache=True, **kw)
    with pytest.raises(ValueError, match="does not cover a window layer"):
        CacheManager(model, kv_quant="int8", **kw)


@pytest.mark.parametrize("kw, match", [
    (dict(page_size=0), "page_size must be >= 1"),
    (dict(num_pages=5), "need paged mode"),
    (dict(prefix_cache=False), "need paged mode"),
    (dict(kv_quant="int8"), "kv_quant needs paged mode"),
    (dict(page_size=PAGE, kv_quant="fp8"), "kv_quant must be None or"),
])
def test_the_cache_knobs_are_checked_where_the_cache_is(kw, match):
    with pytest.raises(ValueError, match=match):
        CacheManager(_dense(), batch_size=2, **kw)


@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_an_export_is_imported_page_for_page(kv_quant):
    src, dst = (_paged(kv_quant=kv_quant) for _ in range(2))
    prompt = list(range(18))
    src.admit(0, 0, prompt, 24)
    src.mark_filled(0)
    # what the steps would have written: every pool leaf's pages numbered
    cache = src.zeros()
    leaves = src.pool_leaves(cache)
    assert len(leaves) == (4 if kv_quant is None else 8)  # 2 layers' k, v
    marked = {
        name: np.arange(leaf.size).reshape(leaf.shape).astype(leaf.dtype)
        for name, leaf in leaves.items()
    }
    flat = flatten_dict(cache)
    for name, value in marked.items():
        flat[tuple(name.split("/"))] = value
    cache = unflatten_dict(flat)
    assert src.export_pages(cache, [9, 9], weights_version=0) is None
    ship = src.export_pages(
        cache, prompt, weights_version=3,
        transfer_budget_bytes=src.page_bytes,  # a page a transfer
    )
    assert (ship.n_pages, ship.chunks, ship.weights_version) == (2, 2, 3)
    assert ship.tokens == prompt[: 2 * PAGE] and ship.kv_quant == kv_quant
    assert ship.nbytes == 2 * src.page_bytes and len(ship.checksums) == 2

    empty = dst.zeros()
    assert dst.import_pages(empty, ship, weights_version=4) \
        == (None, 0, "version_mismatch")
    good = ship.payload
    name = sorted(good)[0]
    ship.payload = {**good, name: good[name] + 1}  # a flipped page
    assert dst.import_pages(empty, ship, weights_version=3) \
        == (None, 0, "checksum")
    assert dst.pages_in_use == 0  # refused whole: nothing allocated
    ship.payload = good
    other = _paged(kv_quant="int8" if kv_quant is None else None)
    assert other.import_pages(other.zeros(), ship, weights_version=3) \
        == (None, 0, None)

    cache, pages, refusal = dst.import_pages(empty, ship, weights_version=3)
    assert pages == 2 and refusal is None
    theirs = src.allocator.export_prefix(prompt)
    mine = dst.allocator.export_prefix(prompt)
    for name, pool in dst.pool_leaves(cache).items():
        np.testing.assert_array_equal(
            np.asarray(pool)[mine], marked[name][theirs]
        )
    assert dst.admit(0, 0, prompt, 24) == 2 * PAGE  # a hit on the import
    dst.allocator.check_invariants()


def _imports(module: str) -> set:
    tree = ast.parse((LOOP / f"{module}.py").read_text())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            found.add(node.module)
            found.update(f"{node.module}.{a.name}" for a in node.names)
        elif isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
    return found


def test_the_serving_modules_import_one_way():
    """``serve.py`` imports the cache manager and the accounting; neither
    imports it or the other, and the scheduler reaches the allocator and
    the leaf rules only through the cache manager."""
    serve = _imports("serve")
    assert "d9d_tpu.loop.serve_cache" in serve
    assert "d9d_tpu.loop.serve_accounting" in serve
    assert not any("kv_paging" in name for name in serve)
    leaf_rules = {
        name for name in serve if name.startswith("d9d_tpu.nn.decode_flags.")
    }
    assert leaf_rules <= {
        "d9d_tpu.nn.decode_flags.zero_rows",
        "d9d_tpu.nn.decode_flags.caller_holds_bounds",
    }
    for module in ("serve_cache", "serve_accounting"):
        back = {
            name for name in _imports(module)
            if name.split(".")[:3] == ["d9d_tpu", "loop", "serve"]
            or name.startswith(("d9d_tpu.loop.serve_", "d9d_tpu.resilience"))
        }
        assert not back, (module, back)
    elastic = _imports("../resilience/elastic")
    assert not [
        name for name in elastic
        if name.startswith("d9d_tpu.loop.") and name.split(".")[-1][0] == "_"
    ]
