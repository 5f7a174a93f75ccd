"""Recursive jaxpr traversal shared by the static lints.

``jax.make_jaxpr`` output nests: ``scan``/``while``/``cond``/``pjit``/
``custom_vjp_call`` equations carry their bodies as (Closed)Jaxpr values in
``eqn.params``. The lints (gradient-leak, dtype-policy) need every equation
and every abstract value in the whole program, so this module flattens the
nesting once and the lints stay simple linear scans.
"""

from __future__ import annotations

from typing import Iterator, List, Tuple

from jax.extend import core as jcore


def _sub_jaxprs(value) -> Iterator[jcore.Jaxpr]:
    """Yield any (Closed)Jaxpr reachable from one ``eqn.params`` value."""
    values = value if isinstance(value, (list, tuple)) else (value,)
    for v in values:
        if isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jcore.Jaxpr):
            yield v


def iter_eqns(jaxpr) -> Iterator:
    """Every equation in ``jaxpr`` and all nested sub-jaxprs, depth-first."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in _sub_jaxprs(param):
                yield from iter_eqns(sub)


def aval_key(aval) -> Tuple[Tuple[int, ...], str]:
    """Hashable (shape, dtype) identity of an abstract value."""
    return tuple(getattr(aval, "shape", ())), str(getattr(aval, "dtype", ""))


def out_avals(jaxpr) -> List:
    """Abstract values of every equation output across the whole program."""
    return [v.aval for eqn in iter_eqns(jaxpr) for v in eqn.outvars]
