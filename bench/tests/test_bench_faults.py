"""Whole runs of small cells with the timed path broken underneath:
``correct`` comes out false for each fault the cell can have, and true
without one. (One chip: no cell has an exchange between chips to drop.)"""

import pytest

from bench import faults

from .conftest import run_small


@pytest.mark.parametrize("workload", ["quarterly-fit", "monthly-fit"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fit_faults_are_caught(workload, fault):
    with getattr(faults, fault)():
        line = run_small(workload)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("workload,fault", [
    ("monthly-predict", "altered_predict_answer"),
    ("quarterly-serve", "altered_served_answer"),
])
def test_altered_answers_are_caught(workload, fault):
    with getattr(faults, fault)():
        line = run_small(workload)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("workload", ["quarterly-fit", "quarterly-serve"])
def test_sound_runs_are_correct(workload):
    line = run_small(workload)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
