"""Fused superstep engine: trajectory equality, boundaries, resume, compose.

The contract under test: ``scan_steps=K`` changes *when the host syncs*, not
what gets computed -- the fused ``lax.scan`` superstep walks the same loss
trajectory as the per-step loop (same step math in the same order; we assert
atol=1e-6 and observe bit-identity on CPU), eval/checkpoints fire at the
same absolute steps, and a checkpoint taken mid-run resumes onto the same
trajectory from any superstep boundary. The 8-host-device data-parallel and
``use_pallas`` variants run the same assertions through their respective
loss paths (the multi-device one in a subprocess, because XLA locks the host
device count at first init).
"""

import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.esrnn import make_config
from repro.data.pipeline import batch_indices, prepare
from repro.data.synthetic_m4 import generate
from repro.train.engine import next_boundary, segment_steps
from repro.train.trainer import TrainConfig, train_esrnn


@pytest.fixture(scope="module")
def data():
    return prepare(generate("quarterly", scale=0.002, seed=3))


@pytest.fixture(scope="module")
def mcfg():
    return make_config("quarterly", hidden_size=8)


_BASE = dict(batch_size=8, lr=1e-3, seed=5)


def _fit(mcfg, data, n_steps, **kw):
    kw = {**_BASE, "eval_every": 1000, "ckpt_every": 1000, **kw}
    hooks = kw.pop("hooks", None)
    return train_esrnn(mcfg, data, TrainConfig(n_steps=n_steps, **kw),
                       hooks=hooks)


# ---------------------------------------------------------------------------
# segment planner
# ---------------------------------------------------------------------------


def test_segment_steps_land_on_every_boundary():
    segs = list(segment_steps(0, 100, 32, 50, 30))
    ends = np.cumsum([k for _, k in segs])
    assert ends[-1] == 100
    for b in (30, 50, 60, 90, 100):            # every eval/ckpt multiple
        assert b in ends, (b, ends)
    assert all(k <= 32 for _, k in segs)
    # resume from an arbitrary step realigns with the same absolute bounds
    segs_r = list(segment_steps(37, 100, 32, 50, 30))
    assert segs_r[0] == (37, 13)               # first stop: step 50
    ends_r = 37 + np.cumsum([k for _, k in segs_r])
    assert set(ends_r) <= set(ends) | {50}


def test_next_boundary():
    assert next_boundary(0, 100, 50, 30) == 30
    assert next_boundary(30, 100, 50, 30) == 50
    assert next_boundary(99, 100, 50, 30) == 100
    assert next_boundary(0, 10, 0, 0) == 10    # disabled everys -> n_steps


# ---------------------------------------------------------------------------
# trajectory equality: fused vs per-step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scan_steps", [1, 4, 32])
def test_superstep_matches_perstep_trajectory(mcfg, data, scan_steps):
    ref = _fit(mcfg, data, 20)                 # per-step engine
    out = _fit(mcfg, data, 20, scan_steps=scan_steps)
    h_ref = np.asarray(ref["history"]["loss"])
    h = np.asarray(out["history"]["loss"])
    assert h.shape == h_ref.shape == (20,)
    np.testing.assert_allclose(h, h_ref, atol=1e-6)
    for (pa, a), b in zip(
        jax.tree_util.tree_leaves_with_path(ref["params"]),
        jax.tree_util.tree_leaves(out["params"]),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   err_msg=str(pa))


def test_sparse_adam_fused_matches_perstep(mcfg, data):
    """The sparse segment optimizer is engine-invariant too."""
    ref = _fit(mcfg, data, 16, sparse_adam=True)
    out = _fit(mcfg, data, 16, sparse_adam=True, scan_steps=8)
    np.testing.assert_allclose(np.asarray(out["history"]["loss"]),
                               np.asarray(ref["history"]["loss"]), atol=1e-6)


def test_eval_fires_at_same_steps(mcfg, data):
    ref = _fit(mcfg, data, 20, eval_every=5)
    out = _fit(mcfg, data, 20, eval_every=5, scan_steps=4)
    assert [s for s, _ in ref["history"]["val_smape"]] \
        == [s for s, _ in out["history"]["val_smape"]] == [5, 10, 15, 20]
    np.testing.assert_allclose(
        [v for _, v in out["history"]["val_smape"]],
        [v for _, v in ref["history"]["val_smape"]], atol=1e-5)


def test_on_step_hook_granularity(mcfg, data):
    """Per-step: float per step. Fused: one loss array per superstep."""
    per, fused = [], []
    _fit(mcfg, data, 10,
         hooks={"on_step": lambda s, l, p: per.append((s, l))})
    _fit(mcfg, data, 10, scan_steps=4,
         hooks={"on_step": lambda s, l, p: fused.append((s, l))})
    assert [s for s, _ in per] == list(range(10))
    assert all(isinstance(l, float) for _, l in per)
    assert [s for s, _ in fused] == [3, 7, 9]  # superstep boundaries - 1
    assert [np.asarray(l).shape for _, l in fused] == [(4,), (4,), (2,)]
    np.testing.assert_allclose(
        np.concatenate([np.atleast_1d(l) for _, l in fused]),
        np.asarray([l for _, l in per]), atol=1e-6)


# ---------------------------------------------------------------------------
# per-step engine: losses read IN_FLIGHT steps late, drained where used
# ---------------------------------------------------------------------------


def _assert_params_equal(a, b):
    for (pa, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                          jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=str(pa))


def test_lagged_perstep_matches_synchronous_bitwise(mcfg, data):
    """Reading losses late (no hook: lagged, donated) walks the trajectory
    a hook's synchronous, undonated loop walks, to the bit. 23 steps with
    eval every 5: the last drain is n_steps, which is no eval multiple."""
    lagged = _fit(mcfg, data, 23, eval_every=5)
    sync = _fit(mcfg, data, 23, eval_every=5,
                hooks={"on_step": lambda s, l, p: None})
    assert len(lagged["history"]["loss"]) == 23
    assert lagged["history"]["loss"] == sync["history"]["loss"]
    assert [s for s, _ in lagged["history"]["val_smape"]] \
        == [5, 10, 15, 20, 23]
    assert lagged["history"]["val_smape"] == sync["history"]["val_smape"]
    _assert_params_equal(lagged["params"], sync["params"])


def test_preemption_mid_segment_checkpoints_the_step_it_names(
        mcfg, data, tmp_path, monkeypatch):
    """A request seen while step 7 is prepared (between the evals at 5 and
    10) stops the run after step 7 with a checkpoint of step 8 holding the
    params of an uninterrupted 8-step run, not of a later step."""
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.train import trainer

    handlers = []

    class Handler(trainer.PreemptionHandler):
        def __init__(self):
            super().__init__()
            handlers.append(self)

    def indices(n, bs, step, *, seed):
        if step == 7:
            handlers[-1].requested = True
        return batch_indices(n, bs, step, seed=seed)

    ref = _fit(mcfg, data, 8, eval_every=5)
    monkeypatch.setattr(trainer, "PreemptionHandler", Handler)
    monkeypatch.setattr(trainer, "batch_indices", indices)
    d = str(tmp_path / "preempt")
    out = _fit(mcfg, data, 23, eval_every=5, ckpt_dir=d)
    assert out["history"]["loss"] == ref["history"]["loss"]
    assert Checkpointer(d).latest_step() == 8
    step, (params, _) = Checkpointer(d).restore(
        (out["params"], out["opt_state"]))
    assert step == 8
    _assert_params_equal(ref["params"], params)
    _assert_params_equal(ref["params"], out["params"])


def test_traced_perstep_fit_reads_each_loss_once_with_its_lag(
        mcfg, data, tmp_path):
    """One ``fit.loss_sync`` per step, each with ``lag``: 2 outside drains,
    counting down to 0 as a drain reads every pending loss (at the evals
    after steps 5 and 10 and at n_steps 12)."""
    from bench import spans as bench_spans
    from repro.analysis import spans
    from repro.train.trainer import IN_FLIGHT

    _fit(mcfg, data, 12, eval_every=5)          # compiles step and eval
    spans.reset()
    try:
        with jax.profiler.trace(str(tmp_path)):
            _fit(mcfg, data, 12, eval_every=5)
        s = spans.summary()
    finally:
        spans.reset()
    assert IN_FLIGHT == 2
    assert s["fit.loss_sync"].count == 12
    assert [i["lag"] for i in s["fit.loss_sync"].ids] \
        == [2, 2, 2, 1, 0, 2, 2, 2, 1, 0, 1, 0]
    assert bench_spans.fit_steps(s) == 12


# ---------------------------------------------------------------------------
# checkpoint -> resume mid-run -> trajectory match
# ---------------------------------------------------------------------------


def test_fused_resume_mid_run_matches(mcfg, data, tmp_path):
    """20 fused steps straight vs 12 + restart + 8: same trajectory/params.

    ckpt_every=6 makes the superstep segments land on 6/12/18 (not scan_steps
    multiples), and the restart resumes from step 12 -- a mid-run superstep
    boundary -- through the stateless schedule.
    """
    kw = dict(scan_steps=4, ckpt_every=6)
    ref = _fit(mcfg, data, 20, **kw)

    d = str(tmp_path / "fused-resume")
    first = _fit(mcfg, data, 12, ckpt_dir=d, **kw)
    assert len(first["history"]["loss"]) == 12
    out = _fit(mcfg, data, 20, ckpt_dir=d, **kw)
    assert out["resumed_from"] == 12
    np.testing.assert_allclose(np.asarray(out["history"]["loss"]),
                               np.asarray(ref["history"]["loss"])[12:],
                               atol=1e-6)
    for (pa, a), b in zip(
        jax.tree_util.tree_leaves_with_path(ref["params"]),
        jax.tree_util.tree_leaves(out["params"]),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(pa))


def test_resume_rejects_flipped_sparse_adam(mcfg, data, tmp_path):
    """Dense and sparse Adam states are not interchangeable: flipping
    sparse_adam across a resume must fail with an actionable error."""
    d = str(tmp_path / "sparse-flip")
    _fit(mcfg, data, 8, ckpt_dir=d, ckpt_every=4, sparse_adam=True)
    with pytest.raises(ValueError, match="sparse_adam"):
        _fit(mcfg, data, 16, ckpt_dir=d, ckpt_every=4, sparse_adam=False)


def test_perstep_ckpt_resumes_into_fused_engine(mcfg, data, tmp_path):
    """Engines share schedule + state format: ckpt under one, resume under
    the other, land on the straight-run trajectory."""
    ref = _fit(mcfg, data, 20, scan_steps=4, ckpt_every=10)
    d = str(tmp_path / "cross-engine")
    _fit(mcfg, data, 10, ckpt_dir=d, ckpt_every=10)          # per-step
    out = _fit(mcfg, data, 20, ckpt_dir=d, ckpt_every=10, scan_steps=4)
    assert out["resumed_from"] == 10
    np.testing.assert_allclose(np.asarray(out["history"]["loss"]),
                               np.asarray(ref["history"]["loss"])[10:],
                               atol=1e-6)


# ---------------------------------------------------------------------------
# composition: use_pallas in-process, 8 host devices in a subprocess
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("scan_steps", [4, 32])
def test_superstep_matches_perstep_with_pallas(data, scan_steps):
    cfg_k = make_config("quarterly", hidden_size=8, use_pallas=True)
    ref = _fit(cfg_k, data, 12)
    out = _fit(cfg_k, data, 12, scan_steps=scan_steps)
    np.testing.assert_allclose(np.asarray(out["history"]["loss"]),
                               np.asarray(ref["history"]["loss"]), atol=1e-6)


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
from repro.core.esrnn import make_config
from repro.data.pipeline import prepare
from repro.data.synthetic_m4 import generate
from repro.train.trainer import TrainConfig, train_esrnn
import jax

data = prepare(generate("quarterly", scale=0.002, seed=3))
mcfg = make_config("quarterly", hidden_size=8)
base = dict(batch_size=8, lr=1e-3, eval_every=1000, ckpt_every=1000, seed=5)
out = {"devices": len(jax.devices())}

ref = train_esrnn(mcfg, data, TrainConfig(n_steps=12, **base))
h_ref = np.asarray(ref["history"]["loss"])
for scan_steps in (1, 4, 32):
    dp = train_esrnn(mcfg, data, TrainConfig(
        n_steps=12, scan_steps=scan_steps, data_parallel=8, **base))
    out[f"dp_scan{scan_steps}_absdiff"] = float(
        np.max(np.abs(np.asarray(dp["history"]["loss"]) - h_ref)))

# fused + data-parallel + pallas kernels, all at once
cfg_k = make_config("quarterly", hidden_size=8, use_pallas=True)
k = train_esrnn(cfg_k, data, TrainConfig(
    n_steps=12, scan_steps=4, data_parallel=8, **base))
out["dp_pallas_scan4_absdiff"] = float(
    np.max(np.abs(np.asarray(k["history"]["loss"]) - h_ref)))

# sparse per-series Adam composes with the series-sharded loss: the
# reference is the single-device sparse per-step run (sparse != dense by
# design, so it gets its own baseline)
ref_sp = train_esrnn(mcfg, data, TrainConfig(
    n_steps=12, sparse_adam=True, **base))
dp_sp = train_esrnn(mcfg, data, TrainConfig(
    n_steps=12, scan_steps=4, data_parallel=8, sparse_adam=True, **base))
out["dp_sparse_scan4_absdiff"] = float(np.max(np.abs(
    np.asarray(dp_sp["history"]["loss"])
    - np.asarray(ref_sp["history"]["loss"]))))
print(json.dumps(out))
"""


@pytest.mark.slow
def test_superstep_matches_perstep_on_8_devices():
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROC], capture_output=True, text=True,
        timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    # fused scan around the shard_map'd loss vs single-device per-step loop:
    # the documented DP tolerance (float summation order) applies per step
    for key in ("dp_scan1_absdiff", "dp_scan4_absdiff", "dp_scan32_absdiff",
                "dp_pallas_scan4_absdiff", "dp_sparse_scan4_absdiff"):
        assert out[key] <= 1e-6, (key, out)
