"""What the per-layer readers of PR 25 share: the program's span timeline
inside a window, registry spans placed on a trace by the clock anchor,
device time by scope, and one scope for the expert matmuls' custom calls.

Arithmetic on plain data, like ``trace.py``: the program is only asked
for its span timeline and for its own reading of the clock anchor, so
all of it is tested on hand-made and recorded data with no TPU.
"""

import re

from . import trace as tr

# scopes of the model's layers as the program names them: flax module
# paths (self_attn, lm_head), jax.named_scope (moe/*, train/optimizer)
EXPERTS = r"moe/experts/"
ATTENTION = r"/self_attn/|decoder/attn/"
HEAD_LOSS = r"/lm_head/"
OPTIMIZER = r"train/optimizer/"

# the program's spans that partition a step or a chunk, and the
# collector's pauses: what idle time on the device is attributed to
PHASE_SPANS = ("serve/phase/", "train/phase/", "host/gc")

# the TPU compiler rewrites lax.ragged_dot into custom calls of this name
# and gives them no op_name but their own
RAGGED_CALL = re.compile(r"ragged-dot")
RAGGED_SCOPE = "moe/experts/ragged_dot"


# -- the program's span timeline ----------------------------------------------


def program_spans():
    """The process hub's span timeline (8,192 deep), oldest first."""
    from d9d_tpu.telemetry import get_telemetry

    return list(get_telemetry().registry.spans)


def spans_between(spans, lo: float, hi: float, names=None):
    """Spans that start and end inside ``[lo, hi]`` (host clock)."""
    return [
        s for s in spans
        if lo <= s.t0 and s.t0 + s.dur_s <= hi
        and (names is None or s.name in names)
    ]


def window_spans(run, names=None):
    """The program's spans of the measured window."""
    o = run.observed
    return spans_between(program_spans(), o.opened_at, o.closed_at, names)


def per_step(spans, step_name: str):
    """``{step: {name: seconds}}`` for the steps that have a ``step_name``
    span among ``spans``: a whole chunk or step, with its phases."""
    whole = {s.step for s in spans if s.name == step_name}
    out: dict = {}
    for s in spans:
        if s.step in whole:
            row = out.setdefault(s.step, {})
            row[s.name] = row.get(s.name, 0.0) + s.dur_s
    return out


# -- registry spans on a trace's clock ----------------------------------------


def clock_shift(trace: dict):
    """Seconds to ADD to a ``perf_counter`` reading to place it on the
    trace's clock, or ``None`` when the trace holds no anchor: the
    program's own reading (``core/tracing.clock_shift``) of the anchors
    it wrote among the trace's host events."""
    from d9d_tpu.core import tracing

    return tracing.clock_shift((h[1], h[2]) for h in trace["host"])


def spans_on_trace(trace: dict, spans):
    """``(name, start, end)`` on the trace's clock for registry spans:
    what ``trace.idle_gaps`` takes beside the annotations. Empty when the
    trace holds no anchor."""
    shift = clock_shift(trace)
    if shift is None:
        return []
    return [(s.name, s.t0 + shift, s.t0 + s.dur_s + shift) for s in spans]


def phase_spans_on_trace(trace: dict, lo: float, hi: float):
    """The program's phase clocks and collector pauses between ``lo`` and
    ``hi`` (host clock: the traced seconds), on the trace's clock."""
    return spans_on_trace(trace, [
        s for s in spans_between(program_spans(), lo, hi)
        if s.name.startswith(PHASE_SPANS)
    ])


# -- device time by scope -----------------------------------------------------


def scope_seconds(trace: dict, scopes: dict, pattern: str,
                  name_pattern=None, window=None) -> float:
    """Self time of the device ops whose scope (the ``op_name`` of their
    instruction) matches ``pattern``, or whose instruction name matches
    ``name_pattern``, averaged over devices. Only the instruction's own
    name and scope are looked at, never its operands."""
    rx = re.compile(pattern)
    lo, hi = window or tr.window_of(trace)
    per_device = []
    for lanes in trace["devices"].values():
        inside = [e for e in lanes["ops"] if lo <= e[1] < hi]
        total = 0.0
        for text, seconds in tr.self_times(inside):
            name = tr.parse_op(text)[0]
            if rx.search((scopes or {}).get(name, "")) or (
                name_pattern is not None and name_pattern.match(name)
            ):
                total += seconds
        per_device.append(total)
    return sum(per_device) / max(len(per_device), 1)


def scope_share(run, pattern: str, name_pattern=None):
    """Device time under a scope as a share of busy time, in percent."""
    if run.trace is None or not run.trace["devices"]:
        return None
    busy, _ = tr.busy_and_window(run.trace)
    if not busy:
        return None
    seconds = scope_seconds(run.trace, run.scopes, pattern, name_pattern)
    return 100.0 * seconds / busy


# -- a scope for the expert matmuls' custom calls ------------------------------


def with_expert_matmuls(scopes: dict, hlo_texts) -> dict:
    """``scopes`` with ``moe/experts/ragged_dot`` for every ``ragged-dot``
    custom call of the compiled programs: the TPU compiler makes them
    from ``lax.ragged_dot`` and from nothing else, and leaves them no
    ``op_name`` but their own, so the breakdown would print them
    unscoped. Which of the layer's matmuls a call is (gate|up or down,
    forward or a transpose) is not told apart here."""
    calls = re.findall(
        r"^\s*(?:ROOT )?%?(ragged-dot[\w.\-]*) = ", "\n".join(hlo_texts), re.M
    )
    return {**scopes, **dict.fromkeys(calls, RAGGED_SCOPE)}
