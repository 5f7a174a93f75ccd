"""The numbers that decide ``correct``, each compared with its limit.

Training cells compare norms leaf by leaf, by the worst leaf: the gap
between the program's norm and the reference's, over the reference's norm
of that leaf or of the median leaf, whichever is larger (some gradients are
all but zero). Cells that answer (forecasts) compare every answer kept with
the reference's, elementwise relative to the reference.
"""

from __future__ import annotations

from typing import Dict, Iterable, Optional

import numpy as np

# a leaf whose first reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone, and is left out of the
# parameter-change comparison (by this rule, never by name)
STILL_LEAF = 1e-3


def norms(leaves: Dict[str, object]) -> Dict[str, float]:
    """L2 norm of every leaf, read back to the host in one transfer."""
    import jax.numpy as jnp

    names = list(leaves)
    vals = np.asarray(jnp.stack([jnp.linalg.norm(
        jnp.ravel(jnp.asarray(leaves[k], jnp.float32))) for k in names]))
    return {k: float(v) for k, v in zip(names, vals)}


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             only: Optional[Iterable[str]] = None) -> float:
    names = list(ref if only is None else only)
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in names)


def moving_leaves(first_grad: Dict[str, float]):
    med = float(np.median(list(first_grad.values())))
    return [k for k, v in first_grad.items() if v >= STILL_LEAF * med]


def loss_gap(prog, ref) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def forecast_errors(outs, ref) -> Dict[str, float]:
    """Largest and mean elementwise |out - ref| / ref over the answers."""
    ref = np.asarray(ref, np.float64)
    worst, total, count = 0.0, 0.0, 0
    for out in outs:
        err = np.abs(np.asarray(out, np.float64) - ref) / np.abs(ref)
        err = np.where(np.isfinite(err), err, np.inf)
        worst = max(worst, float(err.max()))
        total += float(err.sum())
        count += err.size
    return {"max_rel_err": worst, "mean_rel_err": total / max(count, 1)}


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, checks): every number at or under its limit, and finite."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits[name]
        checks[name] = {"value": value, "limit": limit}
        ok = ok and bool(np.isfinite(value)) and value <= limit
    return ok, checks
