"""The longest chunk of the measured window and, in the notes, where
it went: a stall inside one chunk (PERF.md, PR 24: 7.3 s against 0.26)
names its phase here with no profiler live. A traced run's notes say
the same of its traced seconds: a stall caught there is in every
``device_trace`` metric of the line."""

from benchmarks.harness import layers


def longest(spans):
    """Where the longest ``serve/step`` among ``spans`` went."""
    chunks = layers.per_step(spans, "serve/step")
    if not chunks:
        return None
    index, row = max(chunks.items(), key=lambda kv: kv[1]["serve/step"])
    phases = {k: v for k, v in row.items() if k != "serve/step"}
    held = max(phases, key=phases.get)
    return {
        "chunk": index, "seconds": row["serve/step"],
        "held_by": held, "held_seconds": phases[held],
    }


def read(run):
    note = longest(layers.window_spans(run))
    if note is None:
        return None
    run.notes["serve.longest_chunk"] = note
    traced = getattr(run.observed, "traced", None)
    if traced:
        run.notes["serve.longest_traced_chunk"] = longest(
            layers.spans_between(layers.program_spans(), *traced)
        )
    return 1e3 * note["seconds"]
