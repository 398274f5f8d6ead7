"""Traffic kind ``closed_loop``: a serving cell, a fixed pool of callers
on a ``ContinuousBatcher``.

One caller per slot. A caller submits its next request at the chunk
boundary at which its last one finished, so the batcher always has as
many requests as slots and never a queue. The loop is the batcher's own
``step_chunk()``: one dispatch and one readback per call.

Traffic runs for ``preroll_tables`` passes of the length table before
the window opens (the slots are then out of step with each other, as in
steady state), for ``--seconds`` seconds inside it, and on after it
closes until every request submitted inside has finished. Latencies are
of the requests submitted inside the window; throughput is of the tokens
emitted between the first and the last chunk boundary inside it.

A traced run (``--trace 2``) is that run and then ``TRACE_SECONDS`` more
of the same traffic under the profiler, once the drain is over and every
number of the window is taken: the closed loop keeps refilling, so these
are more ``boundary()`` calls. ``--trace 1`` records them before the
window opens.
"""

import dataclasses
import itertools
import os
import time

import jax

from d9d_tpu.telemetry import introspect

from benchmarks.harness import build, correct, traffic
from benchmarks.harness import trace as tr

# seconds of traffic a traced run records
TRACE_SECONDS = 4.0


@dataclasses.dataclass
class Finished:
    rid: int
    n_prompt: int
    n_out: int
    submit_t: float
    first_tok_t: float
    finish_t: float
    tokens: int

    @property
    def ttft_s(self) -> float:
        return self.first_tok_t - self.submit_t

    @property
    def tpot_s(self) -> float:
        return (self.finish_t - self.first_tok_t) / (self.tokens - 1)


@dataclasses.dataclass
class ServeObserved:
    kind: str
    chips: int
    slots: int
    chunk_k: int
    opened_at: float
    closed_at: float
    boundaries: list  # (time, tokens emitted in the chunk) per chunk
    requests: list  # Finished, submitted inside the window
    unfinished: int
    stats_window: dict  # ServeStats deltas over the window
    prompt_steps_window: int
    compiles_in_window: int
    marks: dict
    hlo_texts: list
    checks: dict
    traced: tuple | None = None  # (start, end) of the capture, host clock


def stats_snapshot(stats) -> dict:
    return {f.name: getattr(stats, f.name) for f in dataclasses.fields(stats)}


def run(cell, seed: int, seconds: float, trace_dir, tiny: bool,
        devices, trace_after: bool = False) -> ServeObserved:
    config = cell.config
    mix = traffic.sized(cell.traffic, tiny)
    serving = (config["tiny"] if tiny else config)["serving"]
    cfg, hf = build.sizes(config, tiny)
    reference = build.reference_module(config)
    slots = serving["slots"]
    work = traffic.table_work(mix)
    if work["longest_request_steps"] > serving["decode_max_length"]:
        raise ValueError("the table's longest request exceeds the cache")

    marks = {"cell_start": time.perf_counter()}
    model = build.decode_model(config, cfg, serving["decode_max_length"])
    params = build.seeded_weights(model, seed)
    jax.block_until_ready(params)
    marks["weights"] = time.perf_counter()
    batcher = build.build_batcher(model, params, serving)

    requests = traffic.closed_loop_requests(mix, seed, cfg.vocab_size)
    by_rid, live, finished, served = {}, {}, [], {}

    def submit(n: int):
        for req in itertools.islice(requests, n):
            rid = batcher.submit(
                req.prompt, max_new_tokens=req.max_new_tokens
            )
            by_rid[rid] = live[rid] = req

    def boundary(keep_streams: bool = False):
        """One chunk; then each caller whose request finished in it
        submits its next one. Returns (time of the boundary, tokens the
        chunk emitted)."""
        with tr.span("bench/step_chunk"):
            emitted = batcher.step_chunk()
        now = time.perf_counter()
        with tr.span("bench/refill"):
            done = [r for r in emitted if r in batcher.done]
            for rid in done:
                req, rec = live.pop(rid), batcher.request_stats[rid]
                finished.append(Finished(
                    rid=rid, n_prompt=len(req.prompt),
                    n_out=req.max_new_tokens, submit_t=rec.submit_t,
                    first_tok_t=rec.first_tok_t, finish_t=rec.finish_t,
                    tokens=rec.tokens,
                ))
                if keep_streams:
                    served[rid] = list(batcher.outputs[rid])
            submit(len(done))
        return now, sum(len(t) for t in emitted.values())

    # one request of a little over two chunks compiles both fused programs
    # (with and without admission), whatever the traffic will bring
    batcher.submit([0], max_new_tokens=2 * build.CHUNK_K + 2)
    batcher.drain()
    batcher.reset_measurement()
    marks["warmed"] = time.perf_counter()

    submit(slots)  # one caller per slot

    # pre-roll: leaves the slots out of step with each other, as in
    # steady state
    while len(finished) < mix["preroll_tables"] * work["requests"]:
        boundary(keep_streams=True)

    marks["preroll"] = time.perf_counter()
    checks = serving_checks(
        model, params, reference, hf, mix, by_rid, served
    )
    marks["compared"] = time.perf_counter()

    def trace_chunks():
        """(start, end) of TRACE_SECONDS of the same traffic under the
        profiler. Stopping it stalls the loop for seconds, which no
        metric may include: the capture lies wholly before the window
        (--trace 1) or wholly after its drain (--trace 2)."""
        with tr.capture(trace_dir):
            start = time.perf_counter()
            until = start + TRACE_SECONDS
            while time.perf_counter() < until:
                boundary()
            return start, time.perf_counter()

    traced = None
    if trace_dir is not None and not trace_after:
        traced = trace_chunks()
        marks["traced"] = time.perf_counter()

    mark = len(introspect.inventory())
    stats0 = stats1 = stats_snapshot(batcher.stats)
    opened_at = closed_at = time.perf_counter()
    boundaries = []
    while True:
        now, tokens = boundary()
        if now - opened_at > seconds:
            break  # the chunk that crossed the deadline is drain
        closed_at = now
        boundaries.append((now, tokens))
        stats1 = stats_snapshot(batcher.stats)
    compiles = len(introspect.inventory()) - mark

    def inside(t: float) -> bool:
        return opened_at <= t <= closed_at

    # traffic goes on until every request submitted inside has finished
    wanted = {
        rid for rid in live if inside(batcher.request_stats[rid].submit_t)
    }
    give_up = time.perf_counter() + 3 * seconds + 120
    while wanted & live.keys() and time.perf_counter() < give_up:
        boundary()
    requests_in = [f for f in finished if inside(f.submit_t)]
    unfinished = len(wanted & live.keys())
    if trace_dir is not None and trace_after:
        # the profiler's first start costs seconds: it falls into a
        # capture of nothing, which is thrown away
        with tr.capture(os.path.join(trace_dir, "first_start")):
            pass
        traced = trace_chunks()
    observed = ServeObserved(
        kind=mix["kind"], chips=len(devices), slots=slots,
        chunk_k=build.CHUNK_K,
        opened_at=opened_at, closed_at=closed_at, boundaries=boundaries,
        requests=requests_in, unfinished=unfinished,
        stats_window={k: stats1[k] - stats0[k] for k in stats0},
        # the step that consumes a prompt's last token emits a token, so
        # a request spends n_prompt - 1 steps only consuming
        prompt_steps_window=sum(f.n_prompt - 1 for f in requests_in),
        compiles_in_window=compiles, marks=marks,
        hlo_texts=(
            [t for name in sorted({
                r.name for r in introspect.inventory()
                if r.name.startswith("serve/")
            }) for t in introspect.compiled_hlo(name)]
            if trace_dir is not None else []
        ),
        checks=checks, traced=traced,
    )
    batcher.close()
    return observed


def serving_checks(model, params, reference, hf, mix, by_rid, served):
    """Outside the window, on requests the pre-roll served: logits of
    prefill then cached decode against the reference's full forward, and
    the served streams against ``loop.generate``."""
    n_prompt, n_out = mix["logits_request"]
    rid = next(
        r for r in served if len(by_rid[r].prompt) == n_prompt
        and by_rid[r].max_new_tokens == n_out
    )
    checks = correct.serving_logits(
        model, params, reference, hf, (by_rid[rid].prompt, served[rid])
    )
    chosen = [
        r for r in sorted(served)
        if by_rid[r].max_new_tokens <= mix["checked_max_output"]
    ][:mix["checked_requests"]]
    prompts = [list(by_rid[r].prompt) for r in chosen]
    streams = [served[r] for r in chosen]
    width = max(mix["prompt_lengths"])
    oracle = correct.generate_streams(
        model, params, prompts, mix["checked_max_output"], width
    )
    pad_to = -(-(width + mix["checked_max_output"]) // 64) * 64
    divergences = correct.stream_divergences(
        reference, params, hf, prompts, streams, oracle, pad_to
    )
    checks.update(
        streams_checked=len(chosen),
        streams_equal_generate=len(chosen) - len(divergences),
        stream_lengths_ok=all(
            len(served[r]) == by_rid[r].max_new_tokens for r in served
        ),
        divergences=divergences,
    )
    return checks


def verdict(observed: ServeObserved) -> dict:
    c = observed.checks
    failures = list(correct.sample_failures(c))
    if not c["stream_lengths_ok"]:
        failures.append("a served stream is not as long as its budget")
    for d in c["divergences"]:
        if not d["logit_gap"] <= correct.LOGIT_TIE_TOL:
            failures.append(
                f"served stream leaves generate's beyond a "
                f"{correct.LOGIT_TIE_TOL} logit tie: {d}"
            )
    if observed.compiles_in_window:
        failures.append(
            f"{observed.compiles_in_window} compiles inside the window"
        )
    if observed.unfinished:
        failures.append(f"{observed.unfinished} requests never finished")
    return {"failures": failures, **c}


def attempts(observed: ServeObserved) -> dict:
    return {
        "attempted": len(observed.requests) + observed.unfinished,
        "failed": observed.unfinished,
    }


def samples(observed: ServeObserved) -> dict:
    """Sample counts for the line before the last."""
    gaps = sorted(
        b[0] - a[0]
        for a, b in zip(observed.boundaries, observed.boundaries[1:])
    )
    return {
        "requests": len(observed.requests),
        "chunks": len(observed.boundaries),
        "window_s": observed.closed_at - observed.opened_at,
        "chunk_s_min_med_max": (
            [gaps[0], gaps[len(gaps) // 2], gaps[-1]] if gaps else []
        ),
    }
