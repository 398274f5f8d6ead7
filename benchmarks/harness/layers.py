"""What the per-layer readers of PR 25 share: the program's span timeline
inside a window, registry spans placed on a trace by the clock anchor,
device time by scope, one scope for the expert matmuls' custom calls,
and which events of a trace are a kernel's: each by its own instruction
in the compiled program that ran it.

Arithmetic on plain data, like ``trace.py``: the program is only asked
for its span timeline and for its own reading of the clock anchor, so
all of it is tested on hand-made and recorded data with no TPU.
"""

import re
import typing

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
# what the TPU compiler makes of a dot_general
MATRIX_PRODUCTS = ("dot", "convolution")


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


# -- a kernel's events, each by its own instruction -----------------------------

_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?(%?[\w.\-]+ = .*)")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
RESULT_LIMIT = 160  # of a result type: a trace cuts an event's text short


class Program(typing.NamedTuple):
    """What one compiled program's text says of its instructions, by
    name (a name is the program's own: ``fusion.9`` is another
    instruction in every program)."""

    module: str  # the HloModule's name: ``jit_step``
    results: dict  # instruction -> its result type, cut to RESULT_LIMIT
    scopes: dict  # instruction -> the ``op_name`` of its metadata
    products: dict  # instruction -> scopes of the matrix products it holds


def compiled_program(text: str) -> Program:
    """One compiled program's instructions. A matrix product is a
    ``dot`` or a ``convolution`` under its own ``op_name``, and a fusion
    whose computation holds some (fusions nested in it included), under
    each such product's ``op_name`` (a fusion's own is its root's, which
    may be an activation fused in behind the product, or one of three
    projections fused together) or else its own. A copy, a concatenation
    or any other fusion is not one."""
    module, results, scopes = "", {}, {}
    inside: dict[str, list] = {}  # computation -> (name, opcode, scope, calls)
    body = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            body = inside.setdefault(head[1], [])
            continue
        found = _INSTRUCTION.match(line) if body is not None else None
        if not found:
            named = _MODULE.match(line)
            module = named[1] if named else module
            continue
        name, result, opcode = tr.instruction(found[1])
        scope, calls = _OP_NAME.search(line), _CALLS.search(line)
        results[name] = result[:RESULT_LIMIT]
        if scope:
            scopes[name] = scope[1]
        if opcode == "fusion" or opcode in MATRIX_PRODUCTS:
            body.append((name, opcode, scope[1] if scope else "",
                         calls[1] if calls else None))

    def products_of(opcode, scope, calls, seen=()) -> tuple:
        if opcode in MATRIX_PRODUCTS:
            return (scope,)
        if not calls or calls in seen:
            return ()
        return tuple(
            held or scope for _, *inner in inside.get(calls, ())
            for held in products_of(*inner, seen=(*seen, calls))
        )

    products = {}
    for body in inside.values():
        for name, *rest in body:
            held = products_of(*rest)
            if held:
                products[name] = held
    return Program(module, results, scopes, products)


def programs_that_ran(trace: dict, programs) -> dict[str, list]:
    """Module of the trace (``jit_step(1444..)``: a name with its
    fingerprint) -> the compiled programs it may be: those of that name
    in which most of the module's events are found, each by its
    instruction's name and result type. Two programs made from one
    function (the serving chunk with and without admission) share a name
    and number their fusions apart, so the name alone does not say which
    ran; the events do, unless the two agree wherever it matters."""
    seen: dict[str, set] = {}
    for _, module, text, _ in tr.events_in_modules(trace):
        if module is not None:
            name, result, _ = tr.instruction(text)
            seen.setdefault(module, set()).add((name, result[:RESULT_LIMIT]))
    out = {}
    for module, events in seen.items():
        named = [p for p in programs if p.module == module.partition("(")[0]]
        found = [sum(p.results.get(n) == r for n, r in events) for p in named]
        out[module] = [p for p, n in zip(named, found) if n == max(found)]
    return out


class Ambiguous(ValueError):
    """Two programs that a module may be disagree on an instruction."""


def own_instruction(ran: dict, module_pattern: str, call=None, scope=None,
                    product_scope=None):
    """``take(text, module)`` for ``trace.event_seconds``: is this event
    one of a kernel's? Only events of the modules matching
    ``module_pattern`` are, and of those the ones whose own instruction
    is named like ``call`` (a custom call the compiler names itself), or
    carries a scope matching ``scope``, or is or holds a matrix product
    (``compiled_program``) scoped ``product_scope``; the last two as the
    program that ran the module (``programs_that_ran``) says. Never by
    its operands' names: the activation that reads a ``ragged-dot``'s
    result is not the kernel. Never a copy or a concatenation under a
    product's scope: the serving cells concatenate ``gate|up`` every
    chunk."""
    module_rx = re.compile(module_pattern)
    call_rx = re.compile(call) if call else None

    def said_by(program: Program, name: str) -> bool:
        return bool(
            scope and re.search(scope, program.scopes.get(name, ""))
            or product_scope and any(
                re.search(product_scope, held)
                for held in program.products.get(name, ())
            )
        )

    def take(text: str, module) -> bool:
        if module is None or not module_rx.search(module):
            return False
        name = tr.parse_op(text)[0]
        if call_rx and call_rx.match(name):
            return True
        said = {said_by(p, name) for p in ran.get(module, ())}
        if len(said) > 1:
            raise Ambiguous(f"{name} of {module}")
        return said == {True}

    return take
