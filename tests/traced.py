"""What a traced program says of itself, for the tests that ask which path
a public entry took: the primitives it binds (with their parameters) and
the grids and operands of its Pallas calls, through every sub-jaxpr; and
the trips its compiled loops take. Each of the former takes ``fn, *args``
and traces it, or a closed jaxpr that the caller has
(``jax.jit(fn).trace(*args).jaxpr``, whose ``lower()`` the caller may read
too: one trace for both)."""

import re

import jax


def equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs in its equations'
    parameters, outermost first."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


def _equations(fn, args):
    closed = fn if hasattr(fn, "jaxpr") else jax.make_jaxpr(fn)(*args)
    return equations(closed.jaxpr)


def bound(fn, *args, prefix="hvd_"):
    """``(name, params)`` of every primitive ``fn(*args)`` binds whose name
    starts with ``prefix``, in the trace's order."""
    return [(eqn.primitive.name, eqn.params) for eqn in _equations(fn, args)
            if eqn.primitive.name.startswith(prefix)]


def pallas_calls(fn, *args):
    """``(grid, operands' shapes)`` of every ``pallas_call`` that
    ``fn(*args)`` traces."""
    return [(tuple(eqn.params["grid_mapping"].grid),
             [tuple(operand.aval.shape) for operand in eqn.invars])
            for eqn in _equations(fn, args)
            if eqn.primitive.name == "pallas_call"]


def pallas_grids(fn, *args):
    """The grid of every ``pallas_call`` that ``fn(*args)`` traces."""
    return [grid for grid, _ in pallas_calls(fn, *args)]


def shapes(fn, *args):
    """The shape of every array that ``fn(*args)`` traces, as a set."""
    return {tuple(out.aval.shape) for eqn in _equations(fn, args)
            for out in eqn.outvars if hasattr(out.aval, "shape")}


def loop_trips(compiled_text: str, scope: str):
    """The known trip count of every ``while`` of a compiled program's text
    whose ``op_name`` holds ``scope``: a plain scan takes a trip a chunk."""
    return [int(re.search(r'"known_trip_count":\{"n":"(\d+)"', line).group(1))
            for line in compiled_text.splitlines()
            if " while(" in line and scope in line]
