"""Device-idle milliseconds inside the host's phases of a chunk, from
the program's own names: the always-on ``serve/phase/*`` spans of the
traced seconds, placed on the trace by the clock anchor, own the idle
time (a collector pause inside a phase is that phase's), and what
``admit``, ``plan``, ``dispatch`` and ``commit`` hold is divided by the
traced chunks. ``readback`` is left out: its idle is the transfer's
tail, not host work. The inside-out twin of
``serve.host_gap_ms_per_chunk``, which reads the annotations."""

from benchmarks.harness import layers
from benchmarks.harness import trace as tr

PHASES = "serve/phase/"
HOST_PHASES = {PHASES + p for p in ("admit", "plan", "dispatch", "commit")}


def read(run):
    traced = getattr(run.observed, "traced", None)
    if run.trace is None or not run.trace["devices"] or traced is None:
        return None
    chunks = len(tr.module_seconds(run.trace, "fused"))
    spans = [
        s for s in layers.phase_spans_on_trace(run.trace, *traced)
        if s[0].startswith(PHASES)
    ]
    if not chunks or not spans:
        return None
    return 1e3 * tr.idle_seconds_in(run.trace, spans, HOST_PHASES) / chunks
