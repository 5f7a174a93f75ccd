"""The control, the step below the precision that a configuration states,
comes out not correct in every cell of BENCHMARK.json.

A configuration names its control: for the fit cells' float32 at matmul
precision ``highest``, the precision ``high`` (three bf16 passes); for the
answering cells' float32 at JAX's default, the program's own bf16 policy.
On the CPU a matmul precision changes nothing (every float32 product is
exact there), so where the control is one, this test puts the bf16 policy,
a step further down, in its place; on a TPU it runs the control as stated.
"""

import time

import jax
import pytest

from bench import harness
from bench.compare import judge

from .conftest import cells, small_cell


@pytest.mark.parametrize("workload", cells("fit", "predict", "serve"))
def test_control_is_not_correct(workload):
    cell = small_cell(workload)
    cell.t_start = time.perf_counter()
    if (jax.default_backend() == "cpu"
            and "matmul_precision" in cell.config["control"]):
        cell.config = {**cell.config, "control": {"precision": "bf16"}}
    out = harness.drive(cell, control=True)
    correct, checks = judge(out.numbers, cell.limits)
    assert not correct, checks
