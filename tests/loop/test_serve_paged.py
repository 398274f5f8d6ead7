"""Paged KV cache + prefix cache in the serving loop
(loop/serve.py page_size mode, loop/kv_paging.py, docs/design/
generation.md): greedy paged serving must be TOKEN-IDENTICAL to the
contiguous layout across K — including mid-chunk finishes and
admissions — a prefix-cache hit must decode exactly like a cold
prefill, admission must be bounded by free pages (waiting, not
rejecting), deadline evictions must recycle pages safely, and the
pool/hit telemetry must be live."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.e2e  # whole-model serving loops (slow tier)

from tests.loop.test_serve import _dense, _oracle, _params, _prompts

from d9d_tpu.loop.serve import ContinuousBatcher

def _kv(batcher):
    """The batcher's host page allocator (``loop/kv_paging.py``)."""
    return batcher._cache_mgr.allocator


PAGE = 8  # decode_max_length=24 → 3 pages per row


def _batcher(model, params, *, paged, chunk=4, batch_size=2, **kw):
    if paged:
        kw.setdefault("page_size", PAGE)
    return ContinuousBatcher(
        model, params, batch_size=batch_size, chunk_size=chunk, **kw
    )


def _staggered_run(model, params, prompts, *, n, paged, chunk, **kw):
    """Admissions landing between chunk boundaries + budgets that end
    mid-chunk: the shapes the token-identity pin must survive."""
    b = _batcher(model, params, paged=paged, chunk=chunk, **kw)
    rids = [b.submit(prompts[0], max_new_tokens=n)]
    if chunk is None:
        b.step()
    else:
        b.step_chunk()
    rids += [b.submit(p, max_new_tokens=n) for p in prompts[1:]]
    outputs = b.drain()
    if paged:
        _kv(b).check_invariants()
        assert _kv(b).pages_in_use == (
            len(_kv(b)._entries)  # only cached prefix pages stay mapped
        )
    return [outputs[r] for r in rids], b


@pytest.mark.parametrize(
    "k",
    [
        pytest.param(1, marks=pytest.mark.slow),
        4,
        pytest.param(16, marks=pytest.mark.slow),
    ],
)
def test_paged_token_identical_to_contiguous(k):
    """The tentpole pin: paged vs contiguous, K ∈ {1, 4, 16}, n=6 (not
    a K multiple → finishes land mid-chunk), staggered admission."""
    model = _dense()
    params = _params(model)
    prompts = _prompts(10, 4)
    want, _ = _staggered_run(model, params, prompts, n=6, paged=False,
                             chunk=k)
    got, pb = _staggered_run(model, params, prompts, n=6, paged=True,
                             chunk=k)
    assert got == want
    for out, prompt in zip(got, prompts):
        assert out == _oracle(model, params, prompt, 6)
    del pb


def test_prefix_hit_token_identical_and_counted():
    """A shared prompt's second serving must hit the prefix cache
    (skipping its full pages) and still emit EXACTLY the cold-prefill
    tokens; the hit/miss counters and page-sharing refcounts agree."""
    model = _dense()
    params = _params(model)
    prompt = _prompts(42, 1, lo=18, hi=19)[0]  # 2 full pages + tail
    oracle = _oracle(model, params, prompt, 5)
    b = _batcher(model, params, paged=True, num_pages=9)
    r1 = b.submit(prompt, max_new_tokens=5)
    cold = b.drain()[r1]
    assert cold == oracle
    assert _kv(b).prefix_hits == 0 and _kv(b).prefix_misses == 1
    # second serving: 2 pages (16 tokens) come from the cache
    r2 = b.submit(prompt, max_new_tokens=5)
    hit = b.drain()[r2]
    assert hit == oracle
    assert _kv(b).prefix_hits == 1 and _kv(b).prefix_hit_tokens == 2 * PAGE
    assert b.prefix_hit_rate() == 0.5
    _kv(b).check_invariants()
    # BOTH rows sharing at once: two fresh hits decode concurrently
    r3 = b.submit(prompt, max_new_tokens=5)
    r4 = b.submit(prompt, max_new_tokens=5)
    out = b.drain()
    assert out[r3] == oracle and out[r4] == oracle
    assert _kv(b).prefix_hits == 3
    _kv(b).check_invariants()


def test_paged_admission_bounded_by_free_pages():
    """A pool smaller than the slots' worst case: admission waits for
    pages (head-of-line, no rejection, no corruption) and both
    requests still decode exactly."""
    model = _dense()
    params = _params(model)
    prompts = _prompts(12, 2, lo=4, hi=6)
    # each request needs ceil((len(p)+8-1)/8) = 2 pages; pool holds 2
    # allocatable → strictly one request resident at a time
    b = _batcher(model, params, paged=True, num_pages=3,
                 prefix_cache=False)
    r1 = b.submit(prompts[0], max_new_tokens=8)
    r2 = b.submit(prompts[1], max_new_tokens=8)
    b.step_chunk()
    # only one row could be mapped: the other is still queued
    assert sum(1 for s in b._slots if s.rid >= 0) == 1
    assert _kv(b).pages_free == 0
    out = b.drain()
    assert out[r1] == _oracle(model, params, prompts[0], 8)
    assert out[r2] == _oracle(model, params, prompts[1], 8)
    _kv(b).check_invariants()
    # a request that could NEVER fit fails fast at submit
    with pytest.raises(ValueError, match="could never be admitted"):
        b.submit(list(range(10)), max_new_tokens=12)


def test_paged_deadline_eviction_recycles_pages_exactly():
    """A running row expiring at a boundary frees its pages; the next
    request reuses them and decodes exactly (the zeroed table row was
    pushed before its first chunk, so the zombie never scribbles on
    the new owner)."""
    model = _dense()
    params = _params(model)
    prompts = _prompts(13, 2, lo=3, hi=5)
    b = _batcher(model, params, paged=True, batch_size=1,
                 prefix_cache=False)
    doomed = b.submit(prompts[0], max_new_tokens=18, deadline_s=0.05)
    b.step_chunk()
    time.sleep(0.1)
    b.step_chunk()  # boundary: expire + release
    assert b.failed[doomed] == "deadline"
    assert _kv(b).pages_in_use == 0
    _kv(b).check_invariants()
    fresh = b.submit(prompts[1], max_new_tokens=6)
    assert b.drain()[fresh] == _oracle(model, params, prompts[1], 6)
    _kv(b).check_invariants()


def test_paged_pallas_backend_matches_eager(monkeypatch):
    """The gathering block-index-map kernel (interpret mode on CPU)
    must serve the same tokens as the eager gathered-view path."""
    model = _dense()
    params = _params(model)
    prompts = _prompts(14, 3)

    def run():
        b = _batcher(model, params, paged=True)
        rids = [b.submit(p, max_new_tokens=5) for p in prompts]
        return [b.drain()[r] for r in rids]

    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "eager")
    want = run()
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "pallas")
    got = run()
    assert got == want


def test_paged_gauges_and_structural_counts():
    """The page-pool gauges are live at boundaries, the HBM accounting
    shows paged < contiguous-static, and paging adds ZERO dispatches/
    readbacks vs the contiguous batcher on the same schedule (the
    bench-gate contract, pinned in-tree)."""
    from d9d_tpu.telemetry import Telemetry

    model = _dense()
    params = _params(model)
    prompts = _prompts(15, 3)
    tele = Telemetry()
    contig = ContinuousBatcher(model, params, batch_size=2, chunk_size=4)
    paged = ContinuousBatcher(
        model, params, batch_size=2, chunk_size=4, page_size=PAGE,
        prefix_cache=False, telemetry=tele,
    )
    for b in (contig, paged):
        for p in prompts:
            b.submit(p, max_new_tokens=6)
        b.drain()
    assert paged.outputs == contig.outputs
    assert paged.stats.host_dispatches == contig.stats.host_dispatches
    assert paged.stats.readbacks == contig.stats.readbacks
    # deterministic accounting: fewer resident KV bytes per request
    assert paged.hbm_bytes_per_request() < contig.hbm_bytes_per_request()
    # gauges landed in the injected hub (drain left the pool empty)
    assert tele.registry.gauge("serve/kv_pages_in_use").value == 0
    assert (
        tele.registry.gauge("serve/kv_pages_free").value
        == _kv(paged).num_pages - 1
    )


@pytest.mark.slow  # MoE hybrid compiles are the heaviest in this file
def test_paged_hybrid_gdn_token_identical_and_prefix_auto_disabled():
    """A hybrid model (GDN recurrent state + conv tail) pages its
    attention KV while the unpageable per-row state stays per-row; the
    prefix cache auto-disables (that state summarizes the whole prefix)
    and serving stays token-identical to the contiguous layout."""
    from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM, Qwen3MoeConfig
    from d9d_tpu.ops.attention.eager import eager_sdpa

    cfg = Qwen3MoeConfig(
        vocab_ranges=(("default", 64),), hidden_size=32, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, moe_intermediate_size=32,
        num_experts=4, num_experts_per_tok=2, remat=False,
        linear_attention_layers=(0,),
    )
    model = Qwen3MoeCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
        decode_max_length=24,
    )
    z = jnp.zeros((2, 8), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
    params = jax.jit(model.clone(decode_max_length=0).init)(
        jax.random.PRNGKey(0), z, pos, z
    )["params"]
    prompts = _prompts(3, 3)
    want, _ = _staggered_run(model, params, prompts, n=5, paged=False,
                             chunk=4)
    got, pb = _staggered_run(model, params, prompts, n=5, paged=True,
                             chunk=4)
    assert got == want
    assert _kv(pb).prefix_cache_enabled is False
    assert pb._cache_mgr.unpageable_leaves == ["conv_tail", "delta_state"]
    with pytest.raises(ValueError, match="unsound"):
        ContinuousBatcher(model, params, batch_size=2, chunk_size=4,
                          page_size=PAGE, prefix_cache=True)


def test_weight_publish_invalidates_prefix_cache():
    """Cached prefix KV is weights-dependent: after install_weights a
    same-prompt request must MISS (re-prefill under the new weights)
    and emit exactly the new weights' oracle tokens — a stale hit
    would silently decode the prefix under the old generation."""
    model = _dense()
    params = _params(model)
    params2 = jax.tree.map(lambda x: x * 1.03, params)
    prompt = _prompts(44, 1, lo=18, hi=19)[0]  # 2 full pages + tail
    b = _batcher(model, params, paged=True)
    r1 = b.submit(prompt, max_new_tokens=5)
    assert b.drain()[r1] == _oracle(model, params, prompt, 5)
    assert _kv(b)._entries  # the prefix is cached (old weights)
    b.install_weights(params2)
    r2 = b.submit(prompt, max_new_tokens=5)
    out = b.drain()[r2]
    assert _kv(b).prefix_hits == 0  # invalidated: no stale hit
    assert out == _oracle(model, params2, prompt, 5)
    _kv(b).check_invariants()
    # and the prompt re-cached under the new generation: now it hits
    r3 = b.submit(prompt, max_new_tokens=5)
    assert b.drain()[r3] == out
    assert _kv(b).prefix_hits == 1


def test_quant_kv_serving_exact_on_toy():
    """``kv_quant`` plumbing end to end on a model with NO poolable
    leaves (ToyDecodeLM's ``mem`` is per-row): the quantized paged
    batcher must run the identical schedule and emit exact tokens —
    nothing to quantize means nothing may drift."""
    from tests.resilience.conftest import ToyDecodeLM, toy_expected

    model = ToyDecodeLM()
    z = jnp.zeros((2, 1), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), z, z, z).get("params", {})
    b = ContinuousBatcher(model, params, batch_size=2, chunk_size=4,
                          page_size=4, num_pages=9, kv_quant="int8")
    r1 = b.submit([3], max_new_tokens=6)
    r2 = b.submit([7], max_new_tokens=6)
    out = b.drain()
    assert out[r1] == toy_expected([3], 6)
    assert out[r2] == toy_expected([7], 6)
    _kv(b).check_invariants()
    # and the mode is misuse-proof: int8 pools need a page table
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatcher(model, params, batch_size=2, chunk_size=4,
                          kv_quant="int8")


def test_quant_prefix_hit_shares_scale_pages_token_identical():
    """Prefix-hit sharing on QUANTIZED pages: a hit row reads the same
    int8 pages AND the same sibling scale pages through its table (full
    shared pages are read-only; writers append into their own pages),
    so the hit serving must emit EXACTLY the quantized batcher's own
    cold tokens. Lossiness cancels out — both servings attend the same
    quantized bytes."""
    from flax.traverse_util import flatten_dict

    from d9d_tpu.nn.decode_flags import PAGED_SCALE_SUFFIX

    model = _dense()
    params = _params(model)
    prompt = _prompts(42, 1, lo=18, hi=19)[0]  # 2 full pages + tail
    b = _batcher(model, params, paged=True, num_pages=9, kv_quant="int8")
    # the cache really is quantized: int8 pools with f32 scale siblings
    flat = flatten_dict(b._cache)
    scale_paths = [
        p for p in flat if p[-1].endswith(PAGED_SCALE_SUFFIX)
    ]
    assert scale_paths
    for p in scale_paths:
        assert flat[p].dtype == jnp.float32
        pool = flat[p[:-1] + (p[-1][: -len(PAGED_SCALE_SUFFIX)],)]
        assert pool.dtype == jnp.int8
    r1 = b.submit(prompt, max_new_tokens=5)
    cold = b.drain()[r1]
    assert _kv(b).prefix_hits == 0 and _kv(b).prefix_misses == 1
    r2 = b.submit(prompt, max_new_tokens=5)
    assert b.drain()[r2] == cold
    assert _kv(b).prefix_hits == 1 and _kv(b).prefix_hit_tokens == 2 * PAGE
    _kv(b).check_invariants()
    # two rows sharing the quantized prefix concurrently
    r3 = b.submit(prompt, max_new_tokens=5)
    r4 = b.submit(prompt, max_new_tokens=5)
    out = b.drain()
    assert out[r3] == cold and out[r4] == cold
    assert _kv(b).prefix_hits == 3
    _kv(b).check_invariants()


def test_canary_rollback_invalidation_stamp_distinct_from_publish():
    """Both a canary install AND its rollback invalidate the prefix
    cache (each swaps the weights the cached pages were computed
    under); the ``serve/prefix_cache_invalidated_version`` gauge stamps
    each with the generation that caused it — the rollback's FRESH
    stamp (3) is distinguishable from the canary publish it undoes (2),
    which is the only way an operator can tell the two apart on a
    dashboard (both just drop entries)."""
    from d9d_tpu.resilience import WeightPublisher
    from d9d_tpu.telemetry import Telemetry

    model = _dense()
    params = _params(model)
    bad = jax.tree.map(lambda x: x * 1.03, params)
    prompt = _prompts(45, 1, lo=18, hi=19)[0]
    tele = Telemetry()
    b = _batcher(model, params, paged=True, num_pages=9, telemetry=tele)
    pub = WeightPublisher(telemetry=tele)
    pub.attach(b)
    pub.publish(params)  # generation 1: the retained rollback target
    r1 = b.submit(prompt, max_new_tokens=5)
    oracle = b.drain()[r1]
    assert b.weights_version == 1
    gauge = tele.registry.gauge("serve/prefix_cache_invalidated_version")
    assert gauge.value == 1
    assert _kv(b)._entries  # the prefix is cached under generation 1
    # canary publish: the apply at the next boundary must invalidate
    # and stamp with the canary's generation
    assert pub.publish_canary(bad) == 2
    r2 = b.submit(prompt, max_new_tokens=5)
    b.drain()
    assert b.weights_version == 2
    assert gauge.value == 2
    assert _kv(b).prefix_hits == 0  # no stale hit under the canary
    # rollback: a FRESH generation, and a FRESH invalidation stamp —
    # the re-invalidation is auditable as the rollback, not a replay
    # of the publish
    assert pub.rollback_canary() == 3
    r3 = b.submit(prompt, max_new_tokens=5)
    out = b.drain()[r3]
    assert b.weights_version == 3
    assert gauge.value == 3
    assert out == oracle  # back on the retained tree, exactly
    _kv(b).check_invariants()
    del r2


@pytest.mark.slow  # full-model quantized compile on top of the wide one
def test_quant_qwen3_logits_drift_bounded():
    """Per-channel int8 weights round-tripped through the serving
    dequant must reproduce the wide logits within a tight relative
    bound on the tiny qwen3 config — the weight-stream half of the
    low-precision contract, pinned at the logits (the argmax consumer
    sees this surface)."""
    from d9d_tpu.loop.quantize import (
        dequantize_params,
        is_quantized_tree,
        quantize_for_serving,
    )

    model = _dense()
    params = _params(model)
    q = quantize_for_serving(params)
    assert is_quantized_tree(q) and not is_quantized_tree(params)
    tokens = jnp.asarray([_prompts(46, 1, lo=8, hi=9)[0]], jnp.int32)
    pos = jnp.broadcast_to(
        jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
    )
    eval_model = model.clone(decode_max_length=0)
    w = np.asarray(eval_model.apply(
        {"params": params}, tokens, pos, method="logits"
    ))
    g = np.asarray(eval_model.apply(
        {"params": dequantize_params(q)}, tokens, pos, method="logits"
    ))
    drift = np.abs(g - w).max() / max(np.abs(w).max(), 1e-9)
    assert drift < 0.02, drift


def test_paged_deferred_release_flushes_at_next_boundary():
    """White-box: a host-side expiry while a chunk is IN FLIGHT defers
    the page free (the device twin may still write); the next clean
    admit boundary flushes it and pushes the zeroed table."""
    from tests.resilience.conftest import ToyDecodeLM, toy_expected

    model = ToyDecodeLM()
    z = jnp.zeros((2, 1), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), z, z, z).get("params", {})
    b = ContinuousBatcher(model, params, batch_size=2, chunk_size=4,
                          page_size=4, num_pages=9)
    doomed = b.submit([3], max_new_tokens=12, deadline_s=0.01)
    b.step_chunk()
    b._dispatch_chunk(b._k, admit=False)  # leave one chunk in flight
    time.sleep(0.05)
    b._expire_running(time.perf_counter())
    assert b.failed[doomed] == "deadline"
    assert _kv(b)._deferred and _kv(b).pages_in_use > 0  # held for zombie
    _kv(b).check_invariants()
    b.drain()  # harvests the in-flight chunk
    fresh = b.submit([7], max_new_tokens=3)  # admit boundary: flush
    out = b.drain()
    assert out[fresh] == toy_expected([7], 3)
    assert not _kv(b)._deferred and _kv(b).pages_in_use == 0
    _kv(b).check_invariants()
