"""Cells of the benchmark at a size a CPU test run can hold.

The widths stay those of the configurations; only the fleet, the batch,
the eval period, the request rate and the window shrink.
"""

from __future__ import annotations

import time
from pathlib import Path

from bench import harness
from bench.fleet import generate, prepare

ROOT = Path(__file__).resolve().parents[2]

SMALL = {
    "quarterly-fit": ({"data_scale": 0.03},
                      {"batch_size": 32, "probe_steps": 13, "eval_every": 5}),
    "monthly-fit": ({"data_scale": 0.01},
                    {"batch_size": 32, "probe_steps": 13, "eval_every": 5}),
    "monthly-predict": ({"data_scale": 0.01},
                        {"keep_share": 0.5, "reference_block": 128}),
    "quarterly-serve": ({"n_series": 100},
                        {"rate_per_s": 40, "reference_block": 128}),
}


def small_cell(workload: str, *, seed: int = 2**31 + 11,
               seconds: float = 0.5) -> harness.Cell:
    cell = harness.load_cell(ROOT, workload, seed=seed, seconds=seconds,
                             trace=False)
    cfg, mix = SMALL[workload]
    cell.config = {**cell.config, **cfg}
    cell.mix = {**cell.mix, **mix}
    if "data_scale" in cfg:
        c = cell.config
        c["n_series"] = prepare(
            generate(c["frequency"], scale=c["data_scale"],
                     seed=c["data_seed"]),
            min_length=c["min_length"]).n_series
    return cell


def run_small(workload: str, **kw) -> dict:
    """A whole run of a small cell on the CPU: the harness minus its look
    for a chip."""
    cell = small_cell(workload, **kw)
    return harness.run(ROOT, workload, seed=cell.seed, seconds=cell.seconds,
                       trace=False, t_start=time.perf_counter(),
                       require_chip=False, cell=cell, log=lambda s: None)
