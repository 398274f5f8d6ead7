"""The tiny serving model and the seeded workloads of the CPU count gate.

``tools/bench_compare.py`` (the micro gate against
``BENCH_BASELINE.json``) and ``tools/audit/harness.py`` (the audit's
serving scenarios) build the same toy model and drive the same arrival
schedules through ``ContinuousBatcher`` and ``ServingFleet``; this
module is where those live, once. Everything here is counts: requests,
tokens, handoffs, pages. A speed is a row of ``benchmarks/run.py`` on
the chip (``BENCHMARK.json``, ``PERF.md``), never a number from here.
"""


def build_model():
    import jax
    import jax.numpy as jnp

    from d9d_tpu.models.qwen3 import Qwen3DenseCausalLM, Qwen3DenseConfig
    from d9d_tpu.nn.sdpa import build_sdpa_backend

    cfg = Qwen3DenseConfig(
        vocab_ranges=(("default", 256),),
        hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, intermediate_size=128, remat=False,
    )
    model = Qwen3DenseCausalLM(
        config=cfg, sdpa=build_sdpa_backend(), dtype=jnp.float32,
        decode_max_length=96,
    )
    z = jnp.zeros((2, 8), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
    params = model.clone(decode_max_length=0).init(
        jax.random.PRNGKey(0), z, pos, z
    )["params"]
    return model, params, cfg


def make_workload(*, vocab, requests, seed, prompt_lo, prompt_hi,
                  gen_lo, gen_hi, mean_interarrival):
    """Poisson-ish open-loop arrivals: each request carries an arrival
    offset (in decode steps) drawn from an exponential, so the queue
    alternates between bursts and lulls like real traffic."""
    import numpy as np

    rng = np.random.RandomState(seed)
    arrivals, t = [], 0.0
    for _ in range(requests):
        t += rng.exponential(mean_interarrival)
        arrivals.append((
            int(t),
            rng.randint(0, vocab, rng.randint(prompt_lo, prompt_hi)).tolist(),
            int(rng.randint(gen_lo, gen_hi)),
        ))
    return arrivals


def make_shared_prefix_workload(*, vocab, requests, seed, prefix_len,
                                tail_lo, tail_hi, gen_lo, gen_hi,
                                mean_interarrival):
    """The million-user shape: every request opens with ONE shared
    system prefix and differs only in a short tail — the paged leg's
    prefix cache should prefill the prefix once and map it into every
    later request copy-on-write."""
    import numpy as np

    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, vocab, prefix_len).tolist()
    arrivals, t = [], 0.0
    for _ in range(requests):
        t += rng.exponential(mean_interarrival)
        tail = rng.randint(
            0, vocab, rng.randint(tail_lo, tail_hi)
        ).tolist()
        arrivals.append((
            int(t), prefix + tail, int(rng.randint(gen_lo, gen_hi)),
        ))
    return arrivals


def run_fleet(model, params, workload, *, roles, batch_size, chunk_size,
              page_size):
    """Drive the arrival schedule through a ``ServingFleet`` with one
    replica per entry of ``roles`` (arrivals released against the
    fleet's scheduling round, outputs keyed by arrival index for
    cross-leg identity). Returns the fleet's counters and the outputs."""
    from d9d_tpu.loop.serve import ContinuousBatcher
    from d9d_tpu.resilience import ServingFleet
    from d9d_tpu.telemetry import get_telemetry

    fleet = ServingFleet()
    for role in roles:
        fleet.add_replica(
            ContinuousBatcher(
                model, dict(params), batch_size=batch_size,
                chunk_size=chunk_size, page_size=page_size,
            ),
            role=role,
        )
    # warmup: compile the chunk executables before the counted window
    fleet.submit(workload[0][1], max_new_tokens=2 * chunk_size + 2)
    fleet.drain()
    get_telemetry().reset_instruments()

    pending = list(workload)
    frids = {}
    clock = 0
    while pending or not all(fleet.finished(f) for f in frids.values()):
        while pending and pending[0][0] <= clock:
            _, prompt, gen = pending.pop(0)
            frids[len(frids)] = fleet.submit(prompt, max_new_tokens=gen)
        fleet.step()
        clock += chunk_size
    outputs = {i: fleet.outputs(f) for i, f in frids.items()}
    snap = get_telemetry().registry.snapshot()["counters"]
    for i in fleet.live_replicas:
        fleet._replicas[i]._cache_mgr.allocator.check_invariants()
    fleet.close()
    return {
        "tokens": sum(len(t) for t in outputs.values()),
        "handoffs": int(snap.get("serve/fleet_handoffs", 0)),
        "handoff_fallbacks": int(
            snap.get("serve/fleet_handoff_fallbacks", 0)
        ),
        "handoff_pages": int(snap.get("serve/handoff_pages", 0)),
        "checksum_failures": int(
            snap.get("serve/handoff_checksum_failures", 0)
        ),
        "fleet_prefix_hits": int(snap.get("serve/fleet_prefix_hits", 0)),
        "fleet_prefix_misses": int(
            snap.get("serve/fleet_prefix_misses", 0)
        ),
    }, outputs
