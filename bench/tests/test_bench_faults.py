"""Whole runs of small cells with the timed path broken underneath:
``correct`` comes out false for each fault the cell can have, and true
without one. The cells are those of BENCHMARK.json whose driver a test
covers. (One chip: no cell has an exchange between chips to drop.)"""

import pytest

from bench import faults

from .conftest import cells, run_small

# the fault of each answering driver: an answer altered where it is produced
ALTERED = {"predict": "altered_predict_answer",
           "serve": "altered_served_answer"}


@pytest.mark.parametrize("workload", cells("fit"))
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fit_faults_are_caught(workload, fault):
    with getattr(faults, fault)():
        line = run_small(workload)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("workload,fault", [
    (w, fault) for driver, fault in ALTERED.items() for w in cells(driver)])
def test_altered_answers_are_caught(workload, fault):
    with getattr(faults, fault)():
        line = run_small(workload)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("workload", cells("fit", "predict", "serve"))
def test_sound_runs_are_correct(workload):
    line = run_small(workload)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
