"""Varying manual axes (``vma``) for code that runs inside ``jax.shard_map``.

With ``check_vma`` on, every value in a ``shard_map`` body carries the set of
mesh axes it varies over: per-series rows vary over the series axis, the
replicated weights do not. Two kinds of op need this set spelled out:

* a ``pallas_call`` declares it on its outputs (:func:`out_shape`);
* a ``custom_vjp`` must return a cotangent of the same type as each
  primal input, so a weight that meets per-series rows is first cast to
  varying (:func:`match_vma`). The transpose of that cast is the ``psum``
  that all-reduces the weight gradient.

Outside ``shard_map`` every set is empty and both are no-ops.
"""

from __future__ import annotations

import functools

import jax


def _vma_of(*xs) -> frozenset:
    """Union of the mesh axes that ``xs`` vary over."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def out_shape(*inputs):
    """``ShapeDtypeStruct`` factory for outputs varying like ``inputs``."""
    return functools.partial(jax.ShapeDtypeStruct, vma=_vma_of(*inputs))


def match_vma(*xs):
    """Cast each of ``xs`` to vary over the union of their axes."""
    vma = _vma_of(*xs)

    def cast(x):
        missing = tuple(sorted(vma - jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return tuple(cast(x) for x in xs)
