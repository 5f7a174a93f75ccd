"""Cells of the benchmark at a size a CPU test run can hold.

The widths stay those of the configurations; only the fleet, the batch,
the eval period, the request rate and the window shrink, as each cell's
``bench/tests/small/<cell>.json`` states (``config`` and ``mix`` keys laid
over the cell's own).
"""

from __future__ import annotations

import json
import time
from pathlib import Path

from bench import harness
from bench.fleet import generate, prepare

ROOT = Path(__file__).resolve().parents[2]


def cells(*drivers: str) -> list:
    """The cells of BENCHMARK.json whose traffic mix runs one of
    ``drivers``, in the file's order."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]
            if json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json")
                          .read_text())["driver"] in drivers]


def small_cell(workload: str, *, seed: int = 2**31 + 11,
               seconds: float = 0.5, root: Path = ROOT) -> harness.Cell:
    cell = harness.load_cell(root, workload, seed=seed, seconds=seconds,
                             trace=False)
    small = json.loads((root / "bench" / "tests" / "small" /
                        f"{workload}.json").read_text())
    cfg = small["config"]
    cell.config = {**cell.config, **cfg}
    cell.mix = {**cell.mix, **small["mix"]}
    if "data_scale" in cfg:
        c = cell.config
        c["n_series"] = prepare(
            generate(c["frequency"], scale=c["data_scale"],
                     seed=c["data_seed"]),
            min_length=c["min_length"]).n_series
    return cell


def run_small(workload: str, *, root: Path = ROOT, **kw) -> dict:
    """A whole run of a small cell on the CPU: the harness minus its look
    for a chip."""
    cell = small_cell(workload, root=root, **kw)
    return harness.run(root, workload, seed=cell.seed, seconds=cell.seconds,
                       trace=False, t_start=time.perf_counter(),
                       require_chip=False, cell=cell, log=lambda s: None)
