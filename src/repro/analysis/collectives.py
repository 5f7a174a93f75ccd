"""Collective audit over partitioned HLO: predict is collective-free, the
loss gradient all-reduces and nothing else.

The sharded inference claim (PR 5) is that per-series rows are device-local
under ``shard_map`` -- the partitioned predict program must contain *zero*
collectives, or scaling claims based on "embarrassingly parallel" are void.
The sharded training loss, conversely, must contain the expected psums (the
decomposed masked-mean reduction plus the shard_map transpose's replicated
weight-grad all-reduce) and **only** psums: an all-gather or
collective-permute in the gradient means a sharding spec regressed into
resharding traffic. Both properties are read off ``compiled.as_text()`` of
the partitioned module with the shared :mod:`repro.analysis.hlo_text`
helpers -- the same regexes the roofline's ICI term uses.

Collectives only exist on a multi-device mesh, and XLA pins the host device
count at first jax init, so :func:`collective_audit` runs in-process when
the current process already has enough devices (the CI sharded-smoke job)
and otherwise re-executes this module in a subprocess with
``--xla_force_host_platform_device_count`` (the CLI-on-a-laptop path) --
exactly the pattern the distributed tests use.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, Tuple

import numpy as np

from repro.analysis.gradleak import Finding
from repro.analysis.hlo_text import collective_counts

# the only collective the sharded training gradient is allowed to contain
# (psum/pmean lower to all-reduce); one is required, resharding kinds never
EXPECTED_GRAD_KINDS = frozenset({"all-reduce"})


def probe_batch(cfg, n: int, t: int = 60, seed: int = 0):
    """Deterministic strictly-positive probe series for lowering/tracing."""
    rng = np.random.default_rng(seed)
    y = np.abs(rng.lognormal(3.0, 0.5, (n, t))).astype(np.float32) + 1.0
    cats = np.eye(cfg.n_categories, dtype=np.float32)[
        rng.integers(0, cfg.n_categories, n)]
    return y, cats


def sharded_collective_counts(cfg, devices: int) -> Dict[str, Dict[str, int]]:
    """Compile the sharded predict + loss-grad and count their collectives.

    Requires ``devices`` jax devices in this process (force host devices on
    CPU); :func:`collective_audit` handles the subprocess fallback.
    """
    import jax

    from repro.core.esrnn import esrnn_init
    from repro.sharding.series import (
        esrnn_forecast_dp, esrnn_loss_dp, make_series_mesh,
    )

    mesh = make_series_mesh(devices)
    n = 2 * devices
    y, cats = probe_batch(cfg, n)
    params = esrnn_init(jax.random.PRNGKey(0), cfg, n)

    predict = jax.jit(
        lambda p, yy, cc: esrnn_forecast_dp(cfg, p, yy, cc, mesh=mesh))
    predict_hlo = predict.lower(params, y, cats).compile().as_text()

    grad = jax.jit(jax.grad(
        lambda p: esrnn_loss_dp(cfg, p, y, cats, mesh=mesh)))
    grad_hlo = grad.lower(params).compile().as_text()

    return {"devices": devices,
            "predict": collective_counts(predict_hlo),
            "loss_grad": collective_counts(grad_hlo)}


def collective_findings(
    counts: Dict[str, Dict[str, int]],
) -> Tuple[List[Finding], dict]:
    """Evaluate the zero-collective / psum-only invariants on raw counts."""
    findings: List[Finding] = []
    predict = counts.get("predict", {})
    grad = counts.get("loss_grad", {})
    if predict:
        findings.append(Finding(
            "collectives",
            f"sharded predict compiles to collectives {predict}: per-series "
            f"rows are no longer device-local (expected zero)"))
    unexpected = {k: v for k, v in grad.items()
                  if k not in EXPECTED_GRAD_KINDS}
    if unexpected:
        findings.append(Finding(
            "collectives",
            f"sharded loss gradient contains non-psum collectives "
            f"{unexpected}: a sharding spec regressed into resharding "
            f"traffic (only all-reduce is expected)"))
    if not grad.get("all-reduce"):
        findings.append(Finding(
            "collectives",
            "sharded loss gradient contains no all-reduce: the replicated "
            "weight gradients and the global masked-mean psums are missing"))
    metrics = {
        "devices": counts.get("devices"),
        "predict_collectives": sum(predict.values()),
        "grad_all_reduces": int(grad.get("all-reduce", 0)),
        "grad_other_collectives": sum(unexpected.values()),
    }
    return findings, metrics


def collective_audit(cfg, devices: int = 8) -> Dict[str, Dict[str, int]]:
    """Collective counts for ``cfg`` at ``devices``, via subprocess if needed.

    In-process when this process already sees enough devices; otherwise
    re-runs this module under ``--xla_force_host_platform_device_count``
    with the same config fields serialized on argv.
    """
    import jax

    if len(jax.devices()) >= devices:
        return sharded_collective_counts(cfg, devices)

    import dataclasses

    # the count is a static HLO walk on forced host devices: the child must
    # never reach for an accelerator this process may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + env.get("XLA_FLAGS", "")).strip()
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
    payload = json.dumps(
        {"config": dataclasses.asdict(cfg), "devices": devices})
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.collectives"],
        input=payload, capture_output=True, text=True, env=env, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded collective audit subprocess failed:\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _main() -> int:
    """Subprocess entry: read {config, devices} JSON on stdin, print counts."""
    from repro.core.esrnn import ESRNNConfig

    spec = json.loads(sys.stdin.read())
    cfg_dict = dict(spec["config"])
    cfg_dict["dilations"] = tuple(
        tuple(d) for d in cfg_dict.get("dilations", ()))
    cfg = ESRNNConfig(**cfg_dict)
    print(json.dumps(sharded_collective_counts(cfg, int(spec["devices"]))))
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
