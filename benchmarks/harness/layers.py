"""What the per-layer readers of PR 25 share: the program's span timeline
inside a window, registry spans placed on a trace by the clock anchor,
and device time by scope, a kernel's events and the breakdown's
labels: each event by its own instruction in the compiled program that
ran it.

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


# -- an event by its own instruction --------------------------------------------

_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?(%?[\w.\-]+ = .*)")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
RESULT_LIMIT = 160  # of a result type: a trace cuts an event's text short
# what the compiler makes to bring an instruction's operand to it or take
# its result away (a copy, a slice or a copy it runs beside the compute
# and waits for) and gives no ``op_name``
STAGING = ("copy", "-start", "-done")


class Program(typing.NamedTuple):
    """What one compiled program's text says of its instructions, by
    name (a name is the program's own: ``fusion.9`` is another
    instruction in every program)."""

    module: str  # the HloModule's name: ``jit_step``
    results: dict  # instruction -> its result type, cut to RESULT_LIMIT
    scopes: dict  # instruction -> its ``op_name``, or the one it stages for
    products: dict  # instruction -> scopes of the matrix products it holds


def _operands(line: str, opcode: str) -> list:
    """Names of the instructions one reads: between its opcode's
    parentheses."""
    rest, depth = line.partition(f" {opcode}(")[2], 1
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return _OPERAND.findall(rest, 0, i)
    return []


def _nearest(name: str, scopes: dict, near: dict):
    """The ``op_name`` of the nearest instruction that has one, going
    from this one by ``near`` through those that have none (a bitcast,
    a tuple's element, a concatenation the compiler made of slices)."""
    todo, seen = [name], {name}
    while todo:
        for other in near.get(todo.pop(0), ()):
            if other in scopes:
                return scopes[other]
            if other not in seen:
                seen.add(other)
                todo.append(other)
    return None


def compiled_program(text: str) -> Program:
    """One compiled program's instructions. A matrix product is a
    ``dot`` or a ``convolution`` under its own ``op_name``, and a fusion
    whose computation holds some (fusions nested in it included), under
    each such product's ``op_name`` (a fusion's own is its root's, which
    may be an activation fused in behind the product, or one of three
    projections fused together) or else its own. A copy, a concatenation
    or any other fusion is not one. A ``STAGING`` instruction with no
    ``op_name`` is its reader's work, or (a loop's carried state, copied
    out) its operand's: it takes the scope of the first instruction that
    reads it, else of the one it reads."""
    module, results, scopes = "", {}, {}
    inside: dict[str, list] = {}  # computation -> (name, opcode, scope, calls)
    reads, readers, staging = {}, {}, set()
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
        reads[name] = _operands(found[1], opcode)
        for read in reads[name]:
            readers.setdefault(read, []).append(name)
        if scope:
            scopes[name] = scope[1]
        elif opcode.endswith(STAGING):
            staging.add(name)
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
    staged = {
        name: _nearest(name, scopes, readers) or _nearest(name, scopes, reads)
        for name in staging
    }
    scopes.update((name, scope) for name, scope in staged.items() if scope)
    return Program(module, results, scopes, products)


def programs_that_ran(ops: tr.Ops, programs) -> dict[str, list]:
    """Module of the trace (``jit_step(1444..)``: a name with its
    fingerprint) -> the compiled programs it may be: those of that name
    in which most of the module's instructions are found, each by its
    name and result type. Two programs made from one function (the
    serving chunk with and without admission) share a name and number
    their fusions apart, so the name alone does not say which ran; the
    events do, unless the two agree wherever it matters."""
    seen: dict[str, set] = {}
    for module, text in ops.by_instruction:
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


def own_scope(ran: dict):
    """``scope_of(text, module)`` for ``trace.top_ops``: the scope of an
    event's own instruction in the program that ran its module, one
    scope for the expert matmuls' custom calls (the compiler leaves them
    no ``op_name`` but their own), and ``None``, which leaves the label
    the instruction's name, where the program gives none or two
    candidates disagree."""

    def scope_of(text: str, module):
        name = tr.parse_op(text)[0]
        if RAGGED_CALL.match(name):
            return RAGGED_SCOPE
        said = {p.scopes.get(name) for p in ran.get(module, ())}
        return said.pop() if len(said) == 1 else None

    return scope_of


# -- device time by scope -----------------------------------------------------


def own_seconds(run, module_pattern: str = "", call=None, scope=None,
                product_scope=None):
    """``{"seconds", "events"}`` of a run's traced device ops that
    ``own_instruction`` takes, each asked of the program that ran it
    (``run.ran``), averaged over devices. ``None`` with no trace, and,
    with the note ``<metric>.ambiguous``, where two programs the trace
    cannot tell apart disagree on an instruction."""
    if run.trace is None or not run.trace["devices"]:
        return None
    take = own_instruction(run.ran, module_pattern, call, scope, product_scope)
    try:
        return tr.event_seconds(run.ops, take)
    except Ambiguous as which:
        run.note("ambiguous", str(which))
        return None


def scope_share(run, pattern: str, name_pattern=None,
                module_pattern: str = ""):
    """Device time of the ops whose scope (the ``op_name`` of their own
    instruction) matches ``pattern``, or whose instruction's name matches
    ``name_pattern``, in the modules matching ``module_pattern``, as a
    share of busy time in percent; the seconds stay in the note
    ``<metric>.device_s``."""
    taken = own_seconds(run, module_pattern, call=name_pattern, scope=pattern)
    if taken is None:
        return None
    busy, _ = tr.busy_and_window(run.trace)
    if not busy:
        return None
    run.note("device_s", taken["seconds"])
    return 100.0 * taken["seconds"] / busy


def unread_seconds(ops: tr.Ops, ran: dict) -> dict:
    """Device seconds that no scope's pattern can take, averaged over
    devices: ``unplaced``, the ops outside any execution or in a module
    the run holds no compiled text of, and ``unscoped``, the ops whose
    own instruction has no scope in the program that ran it (or has two,
    by two candidates)."""
    scope_of = own_scope(ran)
    out = {"unplaced": 0.0, "unscoped": 0.0}
    for (module, text), (own, _) in ops.by_instruction.items():
        if not ran.get(module):
            out["unplaced"] += own
        elif scope_of(text, module) is None:
            out["unscoped"] += own
    return {key: seconds / ops.devices for key, seconds in out.items()}
