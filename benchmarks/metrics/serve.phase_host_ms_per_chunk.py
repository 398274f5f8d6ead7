"""The host's own work per chunk, from the serving loop's always-on
phase clock: every phase of ``serve/step`` but ``readback`` (the wait
for the device and the transfer). The device idles for exactly this work
between two chunks of a synchronous ``step_chunk`` loop."""

from benchmarks.harness import layers

HOST_PHASES = tuple(
    f"serve/phase/{p}" for p in ("admit", "plan", "dispatch", "commit")
)


def read(run):
    chunks = layers.per_step(layers.window_spans(run), "serve/step")
    if not chunks:
        return None
    host = sum(
        row.get(name, 0.0) for row in chunks.values() for name in HOST_PHASES
    )
    return 1e3 * host / len(chunks)
