"""Production mesh construction (a function -- importing never touches jax
device state).

Meshes are built with ``AxisType.Auto`` axes, as the sharding rules here
are written for automatic (not Explicit) partitioning. The ES-RNN
series-data-parallel mesh lives in :mod:`repro.sharding.series`
(re-exported here for discoverability).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.sharding.series import make_series_mesh  # noqa: F401  (re-export)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests / single-host runs)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel), ("data", "model"),
                         (AxisType.Auto,) * 2)
