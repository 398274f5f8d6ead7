"""Serving telemetry: TTFT/TPOT/queue-wait stats must agree between a
token a dispatch (K=1) and eight (K=8) on identical requests, and
deriving them must add ZERO device readbacks to the
one-readback-per-chunk contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.e2e  # whole-model serving loops

from d9d_tpu.loop.serve import ContinuousBatcher
from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
from d9d_tpu.ops.attention.eager import eager_sdpa
from d9d_tpu.telemetry import JsonlSink, Telemetry, iter_events

VOCAB = 64


@pytest.fixture(scope="module")
def model_and_params():
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
    model = Qwen3DenseCausalLM(
        config=cfg, sdpa=eager_sdpa, dtype=jnp.float32, decode_max_length=24
    )
    b, t = 2, 8
    z = jnp.zeros((b, t), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    params = model.clone(decode_max_length=0).init(
        jax.random.PRNGKey(0), z, pos, z
    )["params"]
    return model, params


def _prompts(seed, count):
    rng = np.random.RandomState(seed)
    return [
        rng.randint(0, VOCAB, rng.randint(2, 7)).tolist()
        for _ in range(count)
    ]


def _serve(model, params, prompts, *, chunk, n=6, hub=None):
    hub = hub if hub is not None else Telemetry()
    batcher = ContinuousBatcher(
        model, params, batch_size=2, chunk_size=chunk, telemetry=hub
    )
    rids = [batcher.submit(p, max_new_tokens=n) for p in prompts]
    batcher.drain()
    return batcher, rids, hub


def test_ttft_tpot_agree_across_paths(model_and_params):
    """Same requests a token a dispatch (K=1) and eight (K=8): identical
    tokens (the existing parity contract) AND identical telemetry
    *shape* — every request gets one queue-wait, one TTFT, and
    (multi-token) one TPOT sample, with finite positive-or-zero values,
    at both chunk sizes."""
    model, params = model_and_params
    prompts = _prompts(0, 4)

    results = {}
    for label, chunk in (("per_token", 1), ("fused", 8)):
        batcher, rids, hub = _serve(model, params, prompts, chunk=chunk)
        snap = hub.registry.snapshot()
        results[label] = (batcher, rids, snap)

    (bt, rids_t, snap_t) = results["per_token"]
    (bf, rids_f, snap_f) = results["fused"]
    # token-identical outputs (the fused-path exactness contract)
    assert [bt.outputs[r] for r in rids_t] == [bf.outputs[r] for r in rids_f]

    for (_, rids, snap), b in ((results["per_token"], bt),
                               (results["fused"], bf)):
        hists = snap["histograms"]
        assert hists["serve/queue_wait_s"]["count"] == len(rids)
        assert hists["serve/ttft_s"]["count"] == len(rids)
        # every request emitted >= 2 tokens, so every one has a TPOT
        assert hists["serve/tpot_s"]["count"] == len(rids)
        assert hists["serve/slot_util"]["count"] > 0
        for rid in rids:
            rec = b.request_stats[rid]
            assert rec.tokens == len(b.outputs[rid])
            assert rec.queue_wait_s is not None and rec.queue_wait_s >= 0
            assert rec.ttft_s is not None and rec.ttft_s > 0
            assert rec.tpot_s is not None and rec.tpot_s >= 0
            assert rec.ttft_s >= rec.queue_wait_s

    # per-request token counts agree pairwise across the two modes
    for rt, rf in zip(rids_t, rids_f):
        assert bt.request_stats[rt].tokens == bf.request_stats[rf].tokens


def test_fused_telemetry_adds_zero_readbacks(model_and_params, tmp_path):
    """The acceptance criterion: with the JSONL sink attached, the fused
    path still performs exactly one readback per chunk (telemetry is
    derived at boundaries that already exist)."""
    model, params = model_and_params
    hub = Telemetry()
    sink = hub.add_sink(JsonlSink(tmp_path, run_name="serve"))
    batcher, rids, _ = _serve(
        model, params, _prompts(1, 3), chunk=8, hub=hub
    )
    assert batcher.stats.readbacks == batcher.stats.chunks
    assert batcher.stats.host_dispatches == batcher.stats.chunks
    hub.flush(step=0)
    hub.close()
    events = list(iter_events(sink.path))  # schema-validates
    (flush,) = [e for e in events if e["kind"] == "flush"]
    assert flush["counters"]["serve/tokens"] == sum(
        len(batcher.outputs[r]) for r in rids
    )
    assert flush["histograms"]["serve/ttft_s"]["count"] == len(rids)


def test_dropped_batcher_is_not_pinned_by_gauge_fn(model_and_params):
    """The hub's gauge_fn registration must not keep a discarded batcher
    (and its device-resident cache) alive, and a dead batcher's rate
    gauge must disappear from snapshots rather than report stale data."""
    import gc
    import weakref

    model, params = model_and_params
    batcher, _, hub = _serve(model, params, _prompts(3, 1), chunk=8)
    assert "serve/tokens_per_s" in hub.registry.snapshot()["gauges"]
    ref = weakref.ref(batcher)
    del batcher
    gc.collect()
    assert ref() is None
    assert "serve/tokens_per_s" not in hub.registry.snapshot()["gauges"]


def test_reset_measurement_restarts_the_window(model_and_params):
    """Bench warmup contract: after reset_measurement() the stats row and
    throughput clock cover only the post-reset window; resetting with
    work in flight is refused."""
    model, params = model_and_params
    batcher, rids, hub = _serve(model, params, _prompts(2, 2), chunk=8)
    assert batcher.stats.emitted_tokens > 0
    batcher.reset_measurement()
    assert batcher.stats.emitted_tokens == 0
    assert batcher.outputs == {} and batcher.request_stats == {}
    rid = batcher.submit([1, 2], max_new_tokens=2)
    with pytest.raises(RuntimeError, match="in flight"):
        batcher.reset_measurement()
    batcher.drain()
    assert batcher.stats.emitted_tokens == len(batcher.outputs[rid])


def test_single_token_request_has_no_tpot(model_and_params):
    model, params = model_and_params
    batcher, (rid,), hub = _serve(
        model, params, [[3, 5]], chunk=8, n=1
    )
    rec = batcher.request_stats[rid]
    assert rec.tokens == 1
    assert rec.ttft_s is not None
    assert rec.tpot_s is None  # TPOT undefined below 2 tokens
    hists = hub.registry.snapshot()["histograms"]
    assert hists.get("serve/tpot_s", {"count": 0})["count"] == 0
