"""Driver ``fit``: one call of ``ESRNNForecaster.fit`` is the window.

Set-up makes the fleet and the weights from the seed and builds one
forecaster. Through the window's own call and data it then runs one step
(the first gradient is read from Adam's first moment) and three steps from
the same weights (losses and the parameters' change), which the reference
follows. A third call of ``probe_steps`` gives the step rate that sizes the
window's ``n_steps`` (a multiple of the eval period, so every window ends
on an eval). The window continues from there; it has no ``on_step`` hook,
which would turn off donation.

Mix keys: ``batch_size``, ``eval_every``, ``probe_steps``.
"""

from __future__ import annotations

import time

import numpy as np

from bench import compare, harness, sut
from bench.fleet import build_fleet

CHECK_STEPS = 3


def batch_rows(n_series: int, batch: int, step: int, seed: int) -> np.ndarray:
    """The stateless batch schedule the trainer documents: each epoch is a
    seeded permutation of the series, cut into batches (the last wraps)."""
    per_epoch = max(1, -(-n_series // batch))
    epoch, k = divmod(step, per_epoch)
    perm = np.random.default_rng(
        np.random.SeedSequence([seed, epoch])).permutation(n_series)
    rows = perm[k * batch:(k + 1) * batch]
    if len(rows) < batch:
        rows = np.concatenate([rows, perm[: batch - len(rows)]])
    return rows


def step_rate(probe_steps: int, t_probe: float, t_check: float) -> float:
    """Steps per second, from two fit calls that pay the same per-call cost
    (re-trace, evals at their end): the probe and the three-step call.

    Where the calls' difference is too small to read, the probe's own
    average (which counts the per-call cost against the steps, and so reads
    low) is the floor, and ten times it the ceiling.
    """
    floor = probe_steps / t_probe
    if t_probe <= t_check:
        return floor
    return min(max((probe_steps - CHECK_STEPS) / (t_probe - t_check), floor),
               10 * floor)


def _fit(f, data, n_steps):
    t0 = time.perf_counter()
    f.fit(data, n_steps=n_steps)
    return time.perf_counter() - t0


def run(cell, spec_overrides=None) -> harness.Outcome:
    import jax

    from repro.analysis.recompile import CompileCounter
    from repro.forecast import ESRNNForecaster

    cfg, mix = cell.config, cell.mix
    ref, adapter = cell.reference, cell.adapter
    batch, eval_every = mix["batch_size"], mix["eval_every"]
    fleet = build_fleet(cfg)
    w0 = ref.init_weights(cfg, fleet.n_series, cell.seed)
    sched_seed = harness.sub_seed(cell.seed, 3)
    spec = adapter.make_spec(cfg, batch_size=batch, eval_every=eval_every,
                             seed=sched_seed, **(spec_overrides or {}))
    data = adapter.program_data(cfg, fleet)
    p0 = adapter.program_params(cfg, w0)
    f = ESRNNForecaster(spec)

    t_made = time.perf_counter()
    with sut.capture_fit_state() as states:
        f.params_ = p0
        t_first = _fit(f, data, 1)
    b1 = cfg["adam_b1"]
    prog_grad = {k: v / (1.0 - b1) for k, v in compare.norms(
        adapter.program_leaves(states[0]["mu"])).items()}
    del states
    f.params_ = p0
    t_check = _fit(f, data, CHECK_STEPS)
    prog_losses = list(f.history_["loss"])
    p0_leaves = adapter.program_leaves(p0)
    prog_change = compare.norms({k: v - p0_leaves[k] for k, v in
                                 adapter.program_leaves(f.params_).items()})
    t_probe = _fit(f, data, mix["probe_steps"])
    rate = step_rate(mix["probe_steps"], t_probe, t_check)
    # what a call costs besides its steps (re-trace, its last eval)
    per_call = max(0.0, t_check - CHECK_STEPS / rate)
    n_steps = eval_every * max(1, round(
        (cell.window_seconds - per_call) * rate / eval_every))

    with CompileCounter() as compiles, cell.window():
        wall = _fit(f, data, n_steps)
    losses = np.asarray(f.history_["loss"])
    failed = int(np.sum(~np.isfinite(losses)))
    peak = harness.memory_peak_bytes()
    del f, data, p0, p0_leaves
    harness.free_program_state()

    sched = np.stack([batch_rows(fleet.n_series, batch, k, sched_seed)
                      for k in range(CHECK_STEPS)])
    ref_losses, ref_g1, ref_w = ref.train(cell.model, w0, fleet.train,
                                          fleet.cats, sched)
    ref_grad = compare.norms(ref.leaves(ref_g1))
    w0_leaves = ref.leaves(w0)
    ref_change = compare.norms({k: v - w0_leaves[k] for k, v in
                                ref.leaves(ref_w).items()})
    numbers = {
        "grad_gap": compare.leaf_gap(prog_grad, ref_grad),
        "update_gap": compare.leaf_gap(prog_change, ref_change,
                                       compare.moving_leaves(ref_grad)),
    }
    n_evals = -(-n_steps // eval_every)
    t_len = fleet.train.shape[1]
    work = ref.train_step_flops(cell.model, batch, t_len) * n_steps + \
        ref.forecast_flops(cell.model, fleet.n_series, t_len) * n_evals
    return harness.Outcome(
        attempted=n_steps, failed=failed, numbers=numbers,
        metrics={"fit_series_per_s": n_steps * batch / wall},
        memory_peak_bytes=peak,
        work={"steps": n_steps, "flops": work, "window_s": wall},
        detail={"grad": {k: (prog_grad[k], ref_grad[k]) for k in ref_grad},
                "change": {k: (prog_change[k], ref_change[k])
                           for k in ref_change},
                "losses": (prog_losses, np.asarray(ref_losses).tolist())},
        notes=[f"fit: {fleet.n_series} series x T={t_len}, batch {batch}, "
               f"{n_steps} steps in {wall:.3f} s ({rate:.1f} steps/s probed); "
               f"compiles in the window: {compiles.count} "
               f"({compiles.seconds:.3f} s); device {jax.devices()[0]}",
               f"fit: set-up s: fleet and weights "
               f"{t_made - cell.t_start:.3f}, first fit {t_first:.3f}, "
               f"{CHECK_STEPS}-step fit {t_check:.3f}, probe {t_probe:.3f}",
               f"fit: losses program {prog_losses} reference "
               f"{np.asarray(ref_losses).tolist()}, gap (not compared) "
               f"{compare.loss_gap(prog_losses, np.asarray(ref_losses))!r}"])
