"""The operation counts behind mfu.fit and mfu.predict, derived by hand,
read through each configuration's model family as a cell reads them."""

import json

import pytest

from bench import harness

from .conftest import ROOT


def cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def family(c):
    return harness.family_module(ROOT, "reference", c["family"])


@pytest.mark.parametrize("name", ["esrnn-quarterly",
                                  "esrnn-quarterly-highest"])
def test_quarterly_per_position(name):
    c = cfg(name)
    # layer 1: x = 4 window + 6 category = 10 wide, h = 40: 2 * (10 + 40) * 160
    # layers 2-4: 2 * (40 + 40) * 160 each; readout 2*40*40 + 2*40*8
    assert family(c).cell_flops(c, 10) == 16000
    assert family(c).cell_flops(c, 40) == 25600
    assert family(c).readout_flops(c) == 3840
    assert family(c).position_flops(c) == 16000 + 3 * 25600 + 3840 == 96640


@pytest.mark.parametrize("name", ["esrnn-monthly",
                                  "esrnn-monthly-highest"])
def test_monthly_per_position(name):
    c = cfg(name)
    # layer 1: 12 + 6 = 18 wide, h = 50: 2 * 68 * 200; layers 2-4: 2 * 100 * 200
    # readout 2*50*50 + 2*50*18
    assert family(c).position_flops(c) == 27200 + 3 * 40000 + 6800 == 154000


@pytest.mark.parametrize("name,batch,count", [
    # T=72, W=4: positions 3..70 hold a target -> 68; x3 for backward
    ("esrnn-quarterly-highest", 256, 3 * 256 * 68 * 96640),
    # T=72, W=12: positions 11..70 -> 60
    ("esrnn-monthly-highest", 2048, 3 * 2048 * 60 * 154000),
])
def test_train_step(name, batch, count):
    c = cfg(name)
    assert family(c).train_step_flops(c, batch, 72) == count


def test_forecast_counts_only_what_the_last_position_reaches():
    c = cfg("esrnn-monthly")
    # T=90, W=12: 79 positions, the last is index 78. Dilations (1,3),(6,12):
    # d=12 needs 78,66,...,6 (7); d=6 needs 78,72,...,0 (14); the residual
    # block input at 78 is among them; d=3 needs 78,75,...,0 (27); d=1 all 79.
    per_series = (79 * 27200 + 27 * 40000 + 14 * 40000 + 7 * 40000
                  + 2 * 50 * 50 + 2 * 50 * 18)
    assert family(c).forecast_flops(c, 1, 90) == per_series
    assert family(c).forecast_flops(c, 35690, 90) == 35690 * per_series
    q = cfg("esrnn-quarterly")
    # T=72, W=4: index 68. (1,2),(4,8): d=8 -> 68,60,..,4 (9); d=4 -> 68,
    # 64,..,0 (18); d=2 -> 35; d=1 -> 69
    per_q = 69 * 16000 + (35 + 18 + 9) * 25600 + 3840
    assert family(q).forecast_flops(q, 8572, 72) == 8572 * per_q
