"""Counting what a traced program holds, for tests of what a
rematerialised layer runs again: counts of equations, never times."""

import collections

import jax


def _subjaxprs(value):
    if isinstance(value, (list, tuple)):
        for item in value:
            yield from _subjaxprs(item)
    elif hasattr(value, "eqns"):
        yield value
    elif hasattr(getattr(value, "jaxpr", None), "eqns"):
        yield value.jaxpr


def primitives(fn, *args):
    """How often each primitive appears in ``fn``'s jaxpr at ``args``,
    whatever it is nested in; a `pallas_call` counts under
    ``pallas_call:<name>`` as well."""
    counts = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            counts[eqn.primitive.name] += 1
            if eqn.primitive.name == "pallas_call":
                counts["pallas_call:" + str(eqn.params["name"])] += 1
            for value in eqn.params.values():
                for sub in _subjaxprs(value):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return counts
