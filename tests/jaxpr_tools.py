"""Reading a traced program: every equation of a jaxpr and of the jaxprs
inside it (scans, conditionals, remats, calls, custom derivatives).

A test that asks what a program holds walks it with these and never
counts substrings of the printed jaxpr: jax prints a sub-jaxpr that an
earlier test of the same process traced once, under a name, so a count of
text depends on which tests ran before (``order-dependent-test``, closed
by PR 47)."""

import jax


def equations(jaxpr):
    """Every equation of ``jaxpr``, sub-jaxprs included, once a call site."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


def scoped_equations(jaxpr, scope=""):
    """``(equation, scope)`` of ``jaxpr`` and every jaxpr nested in it; the
    scope is the name stack the lowering joins into an op's ``op_name``."""
    for eqn in jaxpr.eqns:
        inner = f"{scope}/{eqn.source_info.name_stack}"
        yield eqn, inner
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from scoped_equations(sub, inner)


def count(jaxpr, wanted) -> int:
    """Equations of ``jaxpr``, and of the jaxprs inside it, that
    ``wanted`` accepts."""
    return sum(bool(wanted(eqn)) for eqn in equations(jaxpr))
