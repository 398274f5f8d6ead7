"""Continuous batching (loop/serve.py): any admission schedule must
emit, per request, exactly the greedy tokens generate() produces —
slots decode independently, rows reset cleanly on reuse, and the
per-row cache-index machinery (nn/attention.py dual-rank support,
flash-decode per-row start) stays invisible to results.

The fused K-step decode path must be token-identical to generate()
across K (K = 1 is the single-token surface), including
mid-chunk finishes (budget and EOS), mid-chunk admissions (requests
submitted between chunk boundaries), and the double-buffered drain."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.e2e  # whole-model serving loops (slow tier)

from d9d_tpu.loop.generate import generate
from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.models.qwen3 import (
    Qwen3DenseCausalLM,
    Qwen3DenseConfig,
    Qwen3MoeCausalLM,
    Qwen3MoeConfig,
)
from d9d_tpu.ops.attention.eager import eager_sdpa

VOCAB = 64


def _dense(decode_max_length=24):
    cfg = Qwen3DenseConfig(
        vocab_ranges=(("default", VOCAB),),
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=8,
        intermediate_size=64,
        remat=False,
    )
    return Qwen3DenseCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
        decode_max_length=decode_max_length,
    )


@functools.lru_cache(maxsize=None)
def _drawn(full):
    b, t = 2, 8
    z = jnp.zeros((b, t), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    return jax.jit(full.init)(jax.random.PRNGKey(0), z, pos, z)["params"]


def _params(model):
    """Seeded weights: one jitted ``init`` a model a process (this file's
    tests and the five files that import it ask some seventy times); the
    containers are the caller's own, the arrays shared."""
    return jax.tree.map(
        lambda a: a, _drawn(model.clone(decode_max_length=0)))


def _oracle(model, params, prompt, n):
    out = generate(
        model, params, jnp.asarray([prompt], jnp.int32), max_new_tokens=n
    )
    return np.asarray(out)[0].tolist()


def _prompts(seed, count, lo=2, hi=7):
    rng = np.random.RandomState(seed)
    return [
        rng.randint(0, VOCAB, rng.randint(lo, hi)).tolist()
        for _ in range(count)
    ]


@pytest.mark.slow  # >10s compile-bound on the 2-core rig; e2e tier covers it
def test_staggered_admission_matches_generate():
    model = _dense()
    params = _params(model)
    prompts = _prompts(0, 3)
    n = 6
    batcher = ContinuousBatcher(model, params, batch_size=2)
    # staggered: A at step 0, B after 2 steps, C queues until a slot frees
    rids = [batcher.submit(prompts[0], max_new_tokens=n)]
    batcher.step()
    batcher.step()
    rids.append(batcher.submit(prompts[1], max_new_tokens=n))
    rids.append(batcher.submit(prompts[2], max_new_tokens=n))
    outputs = batcher.drain()
    for rid, prompt in zip(rids, prompts):
        assert outputs[rid] == _oracle(model, params, prompt, n), rid


@pytest.mark.slow  # >10s compile-bound on the 2-core rig; e2e tier covers it
def test_slot_reuse_resets_state():
    """batch_size=1: requests run strictly sequentially through ONE slot;
    each must be unpolluted by its predecessor's cache."""
    model = _dense()
    params = _params(model)
    prompts = _prompts(1, 3)
    n = 5
    batcher = ContinuousBatcher(model, params, batch_size=1)
    rids = [batcher.submit(p, max_new_tokens=n) for p in prompts]
    outputs = batcher.drain()
    for rid, prompt in zip(rids, prompts):
        assert outputs[rid] == _oracle(model, params, prompt, n), rid


@pytest.mark.slow  # >10s compile-bound on the 2-core rig; e2e tier covers it
def test_eos_evicts_and_slot_refills():
    model = _dense()
    params = _params(model)
    prompts = _prompts(2, 4, lo=2, hi=5)
    n = 8
    # pick eos from the oracle's own output so eviction actually triggers
    first_oracle = _oracle(model, params, prompts[0], n)
    eos = first_oracle[2]
    batcher = ContinuousBatcher(model, params, batch_size=2, eos_id=eos)
    rids = [batcher.submit(p, max_new_tokens=n) for p in prompts]
    outputs = batcher.drain()
    for rid, prompt in zip(rids, prompts):
        want = _oracle(model, params, prompt, n)
        if eos in want:
            want = want[: want.index(eos) + 1]
        assert outputs[rid] == want, rid


@pytest.mark.slow  # >10s compile-bound on the 2-core rig; e2e tier covers it
def test_hybrid_gdn_serving_matches_generate():
    """GDN recurrent state + conv tail are per-row; slot resets must
    clear them (a polluted state changes every subsequent token)."""
    cfg = Qwen3MoeConfig(
        vocab_ranges=(("default", VOCAB),),
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=8,
        moe_intermediate_size=32,
        num_experts=4,
        num_experts_per_tok=2,
        remat=False,
        linear_attention_layers=(0,),
    )
    model = Qwen3MoeCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=jnp.float32,
        decode_max_length=24,
    )
    b, t = 2, 8
    z = jnp.zeros((b, t), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    params = model.clone(decode_max_length=0).init(
        jax.random.PRNGKey(0), z, pos, z
    )["params"]
    prompts = _prompts(3, 3)
    n = 5
    batcher = ContinuousBatcher(model, params, batch_size=2)
    rids = [batcher.submit(p, max_new_tokens=n) for p in prompts]
    outputs = batcher.drain()
    for rid, prompt in zip(rids, prompts):
        assert outputs[rid] == _oracle(model, params, prompt, n), rid


def test_pallas_decode_backend_serving(monkeypatch):
    """The flash-decode kernel's per-row start path (env-forced,
    interpret mode on CPU) must emit the same tokens as eager."""
    model = _dense()
    params = _params(model)
    prompts = _prompts(4, 3)
    n = 5

    def run():
        batcher = ContinuousBatcher(model, params, batch_size=2)
        rids = [batcher.submit(p, max_new_tokens=n) for p in prompts]
        return [batcher.drain()[r] for r in rids]

    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "eager")
    want = run()
    monkeypatch.setenv("D9D_TPU_DECODE_ATTN", "pallas")
    got = run()
    assert got == want


def test_capacity_and_validation():
    model = _dense(decode_max_length=8)
    params = _params(model)
    batcher = ContinuousBatcher(model, params, batch_size=1)
    with pytest.raises(ValueError, match="exceeds decode_max_length"):
        batcher.submit(list(range(6)), max_new_tokens=4)
    with pytest.raises(ValueError, match="empty prompt"):
        batcher.submit([], max_new_tokens=2)


# ---------------------------------------------------------------------
# fused K-step decode path: token-identical to generate(), across K and
# boundary cases


def _run_batch(model, params, prompts, *, n, chunk, eos=None,
               batch_size=2):
    batcher = ContinuousBatcher(
        model, params, batch_size=batch_size, eos_id=eos,
        chunk_size=chunk,
    )
    rids = [batcher.submit(p, max_new_tokens=n) for p in prompts]
    outputs = batcher.drain()
    return [outputs[r] for r in rids]


@pytest.mark.parametrize(
    "k",
    # K=16 compiles the widest chunk program for ~8s on the 2-core rig;
    # K∈{1,4} pin the same mid-chunk-finish contract in tier-1
    [1, 4, pytest.param(16, marks=pytest.mark.slow)],
)
def test_fused_matches_generate(k):
    """K-chunked decode vs generate(): budgets chosen so rows finish
    mid-chunk at K=4 and K=16."""
    model = _dense()
    params = _params(model)
    prompts = _prompts(10, 4)
    n = 6  # not a multiple of either K: finishes land mid-chunk
    got = _run_batch(model, params, prompts, n=n, chunk=k)
    assert got == [_oracle(model, params, p, n) for p in prompts]


@pytest.mark.parametrize("k", [4, 16])
def test_fused_eos_mid_chunk(k):
    """EOS fires in-device mid-chunk: the row must stop emitting at the
    step generate()'s stream first shows it (the EOS itself goes out),
    and its slot must refill."""
    model = _dense()
    params = _params(model)
    prompts = _prompts(11, 4, lo=2, hi=5)
    n = 8
    streams = [_oracle(model, params, p, n) for p in prompts]
    eos = streams[0][2]
    want = [
        s[: s.index(eos) + 1] if eos in s else s for s in streams
    ]
    assert len(want[0]) <= 3  # the EOS does cut a stream mid-chunk
    got = _run_batch(model, params, prompts, n=n, chunk=k, eos=eos)
    assert got == want


@pytest.mark.parametrize("k", [1, 4, 16])
def test_fused_mid_chunk_admission(k):
    """Requests submitted between chunk boundaries are admitted at the
    next boundary and still decode exactly."""
    model = _dense()
    params = _params(model)
    prompts = _prompts(12, 3)
    n = 6
    batcher = ContinuousBatcher(model, params, batch_size=2, chunk_size=k)
    rids = [batcher.submit(prompts[0], max_new_tokens=n)]
    batcher.step_chunk()
    rids.append(batcher.submit(prompts[1], max_new_tokens=n))
    batcher.step_chunk()
    rids.append(batcher.submit(prompts[2], max_new_tokens=n))
    outputs = batcher.drain()
    for rid, prompt in zip(rids, prompts):
        assert outputs[rid] == _oracle(model, params, prompt, n), rid


def test_step_chunk_loop_and_drain_emit_the_same_streams():
    """A ``step_chunk()`` loop (nothing in flight between calls) and
    ``drain()`` (one chunk in flight) emit the same streams."""
    model = _dense()
    params = _params(model)
    prompts = _prompts(13, 3)
    a = _run_batch(model, params, prompts, n=5, chunk=8)
    batcher = ContinuousBatcher(model, params, batch_size=2, chunk_size=8)
    rids = [batcher.submit(p, max_new_tokens=5) for p in prompts]
    while batcher.active:
        batcher.step_chunk()
        assert not batcher._pending
    assert [batcher.outputs[r] for r in rids] == a


@pytest.mark.parametrize("chunk", [1, 4])
def test_idle_slot_cache_index_stays_pinned(chunk):
    """Regression (ADVICE r5 #1): a slot left idle for more steps than
    decode_max_length must not advance its cache_index — the jitted
    step pins idle/dead rows at 0 — and must serve exactly when
    finally admitted."""
    from flax.traverse_util import flatten_dict

    model = _dense(decode_max_length=16)
    params = _params(model)
    prompt = [3, 9, 4]
    n = 12
    batcher = ContinuousBatcher(model, params, batch_size=2,
                                chunk_size=chunk)
    # requests run one at a time through slot 0; slot 1 idles for
    # 4 * (3 + 12 - 1) steps > decode_max_length = 16
    for _ in range(4):
        rid = batcher.submit(prompt, max_new_tokens=n)
        out = batcher.drain()
        assert out[rid] == _oracle(model, params, prompt, n)
    for path, leaf in flatten_dict(batcher._cache).items():
        if path[-1] == "cache_index":
            assert int(np.asarray(leaf)[1]) == 0, path
    # the long-idle slot must admit and serve cleanly
    r0 = batcher.submit(prompt, max_new_tokens=n)
    r1 = batcher.submit(prompt, max_new_tokens=n)
    out = batcher.drain()
    assert out[r0] == out[r1] == _oracle(model, params, prompt, n)


def test_fused_dispatch_counters():
    """The contract the serving bench pins: one dispatch + one readback
    per chunk, at least a 4x reduction per 1k tokens at K=8 vs stepping
    a token a dispatch (K=1)."""
    model = _dense()
    params = _params(model)
    prompts = _prompts(14, 2)
    n = 8
    per_tok = ContinuousBatcher(model, params, batch_size=2, chunk_size=1)
    fused = ContinuousBatcher(model, params, batch_size=2, chunk_size=8)
    for b in (per_tok, fused):
        for p in prompts:
            b.submit(p, max_new_tokens=n)
        b.drain()
    assert fused.stats.emitted_tokens == per_tok.stats.emitted_tokens
    assert (
        per_tok.stats.dispatches_per_1k_tokens
        >= 4 * fused.stats.dispatches_per_1k_tokens
    )
    for b in (per_tok, fused):
        assert b.stats.readbacks == b.stats.chunks == b.stats.host_dispatches


# -- a state-space hybrid: two kinds of cache in one manager ------------------
#
# jamba_tiny: a Mamba-1 mixer (per-row ssm_state + conv_tail, never paged)
# beside a multi-query attention layer (paged KV), tied table.


def _jamba(decode_max_length=32):
    from d9d_tpu.models.jamba import JambaCausalLM, jamba_tiny

    return JambaCausalLM(
        config=jamba_tiny(VOCAB), sdpa=eager_sdpa, dtype=jnp.float32,
        decode_max_length=decode_max_length,
    )


@functools.lru_cache(maxsize=None)
def _jamba_setup():
    """One model, its weights and its generate oracle for the cases below."""
    model = _jamba()
    params = _params(model)
    oracle = jax.jit(lambda prm, ids: generate(
        model, prm, ids, max_new_tokens=9
    ))

    def want(prompt):
        ids = jnp.asarray([prompt], jnp.int32)
        return np.asarray(oracle(params, ids))[0].tolist()

    return model, params, want


@pytest.mark.parametrize("page_size,chunk", [
    (8, 4), (8, 1), (None, 4),
], ids=["paged-fused", "paged-k1", "contiguous-fused"])
def test_state_space_hybrid_staggered_admission_matches_generate(
    page_size, chunk
):
    """Admission zeroes a row's ssm_state and conv_tail in the dispatch
    that starts it: requests admitted mid-flight, queued behind a full
    batch, and into slots other requests died in emit what generate
    emits for each alone."""
    model, params, want = _jamba_setup()
    # five prompts of one length each: the oracle compiles once a length
    prompts = [p[:n] for p, n in zip(_prompts(21, 5, lo=6, hi=7),
                                     (3, 5, 3, 5, 3))]
    batcher = ContinuousBatcher(
        model, params, batch_size=2, page_size=page_size, chunk_size=chunk
    )
    rids = [batcher.submit(prompts[0], max_new_tokens=9)]
    batcher.step()
    batcher.step()
    rids += [batcher.submit(p, max_new_tokens=9) for p in prompts[1:]]
    outputs = batcher.drain()
    for rid, prompt in zip(rids, prompts):
        assert outputs[rid] == want(prompt), rid
    assert batcher._cache_mgr.unpageable_leaves == ["conv_tail", "ssm_state"]
    if page_size:
        assert batcher._cache_mgr.allocator.prefix_cache_enabled is False
    batcher.close()


def test_state_space_hybrid_reused_slot_serves_the_second_request():
    """One slot, requests strictly one after another, and a dead row that
    kept stepping on token 0 to the end of its chunk: each request gets
    its own stream, not a continuation of its predecessor's state."""
    model, params, want = _jamba_setup()
    prompts = [p[:n] for p, n in zip(_prompts(22, 3, lo=6, hi=7), (5, 3, 5))]
    batcher = ContinuousBatcher(
        model, params, batch_size=1, page_size=8, chunk_size=8
    )
    rids = [batcher.submit(p, max_new_tokens=9) for p in prompts]
    outputs = batcher.drain()
    for rid, prompt in zip(rids, prompts):
        assert outputs[rid] == want(prompt), rid
    # and differ from each other: the check above is not vacuous
    assert outputs[rids[0]] != outputs[rids[1]]
    batcher.close()


def test_state_space_hybrid_counts_its_recurrent_state():
    """``recurrent_state_bytes`` from the batcher's own cache tree and
    ``rows_reset`` per admission, in ServeStats, on each ``serve/step``
    span and in the gauge; an attention-only model counts zero bytes."""
    from d9d_tpu.telemetry import Telemetry

    model, params, _ = _jamba_setup()
    tele = Telemetry()
    batcher = ContinuousBatcher(
        model, params, batch_size=2, page_size=8, chunk_size=4,
        telemetry=tele,
    )
    cfg = model.config
    d_inner = cfg.mamba_expand * cfg.hidden_size
    per_row = len(cfg.mamba_layers) * d_inner * 4 * (
        cfg.mamba_d_state + cfg.mamba_d_conv - 1
    )  # float32 state, and tails in the model's dtype: float32 here
    for p in _prompts(23, 3, lo=3, hi=4):
        batcher.submit(p, max_new_tokens=5)
    while batcher.active:
        batcher.step_chunk()
    assert batcher.stats.recurrent_state_bytes == 2 * per_row
    assert batcher.stats.rows_reset == 3
    steps = [s for s in tele.registry.spans if s.name == "serve/step"]
    assert steps and all(
        s.meta["recurrent_state_bytes"] == 2 * per_row for s in steps
    )
    assert sum(s.meta["rows_reset"] for s in steps) == 3
    assert tele.registry.gauge(
        "serve/recurrent_state_bytes").value == 2 * per_row
    batcher.close()
    dense = _dense()
    plain = ContinuousBatcher(dense, _params(dense), batch_size=2,
                              telemetry=Telemetry())
    plain.submit([1, 2], max_new_tokens=2)
    plain.drain()
    assert plain.stats.recurrent_state_bytes == 0
    assert plain.stats.rows_reset == 1
    plain.close()


def test_state_space_hybrid_refuses_prefix_cache_and_speculation():
    """One rule (``nn/decode_flags.recurrent_leaves``), two refusals: a
    state that summarizes the whole prefix cannot be rebuilt from shared
    KV pages, nor rolled back past a rejected proposal."""
    from d9d_tpu.loop.speculative import speculative_generate
    from d9d_tpu.nn.decode_flags import recurrent_leaves

    model, params, _ = _jamba_setup()
    with pytest.raises(ValueError, match="unsound"):
        ContinuousBatcher(model, params, batch_size=2, page_size=8,
                          prefix_cache=True)
    with pytest.raises(NotImplementedError, match="ssm_state|conv_tail"):
        speculative_generate(
            model, params, model, params, jnp.asarray([[1, 2, 3]], jnp.int32),
            max_new_tokens=4, speculate_k=2,
        )
    # the rule itself: by what a leaf is, not by its name
    cache = {"attn": {"cached_key": 0, "cache_index": 0, "page_table": 0,
                      "cached_key_scale": 0},
             "mixer": {"some_new_state": 0}}
    assert list(recurrent_leaves(cache)) == [("mixer", "some_new_state")]
