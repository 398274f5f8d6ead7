"""What the readers' tests share: a hand-made traced run as a compiled
program ran it. A reader asks the program that ran an event for the
event's scope (``layers.own_instruction`` over ``Run.ran``), so a
hand-made trace shows an execution of a module around its ops, and the
run holds a ``layers.Program`` of that module's name which says the
scope of each instruction."""

from benchmarks.harness import layers

FUSED = "jit_fused_fn"  # the serving chunk's program
STEP = "jit_step"  # the train step's


def program(scopes: dict, module: str = FUSED, results=None,
            products=None) -> layers.Program:
    """A compiled program called ``module`` whose instructions carry
    ``scopes``, as ``layers.compiled_program`` would read it from text."""
    return layers.Program(
        module, dict(results or {}), dict(scopes), dict(products or {}))


def trace_of(ops, module: str = FUSED, executions: int = 1,
             devices=(0,), host=()) -> dict:
    """A normalised trace (``harness/trace.py``): every device ran
    ``ops``, ``(hlo text, start, seconds)``, inside ``executions``
    back-to-back executions of ``module(17)`` that cover them together
    (none with ``executions=0``: the ops then belong to no program)."""
    lo = min(op[1] for op in ops)
    each = (max(op[1] + op[2] for op in ops) - lo) / max(executions, 1)
    each *= 1 + 1e-9  # the last op ends inside the last execution
    return {"devices": {d: {
        "ops": [list(op) for op in ops], "async": [],
        "modules": [[f"{module}(17)", lo + i * each, each, i + 1]
                    for i in range(executions)],
    } for d in devices}, "host": [list(h) for h in host]}


def ran_by(run, ops, scopes: dict, module: str = FUSED, **trace):
    """``run`` with the trace of ``ops`` (``trace_of``) and the one
    program, called ``module``, that ran them under ``scopes``."""
    run.trace = trace_of(ops, module, **trace)
    run.programs = (program(scopes, module),)
    return run
