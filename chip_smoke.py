#!/usr/bin/env python3
"""Run the ES-RNN fit, predict and serve path once on a TPU chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: sharded fit + predict only

Everything goes through the public API (``ESRNNForecaster`` and the
``ForecastServer`` it returns) in one process, at the paper's quarterly
Table-1 widths (hidden 40, dilations ((1, 2), (4, 8)), input window 8,
horizon 8, batch 256) on the full synthetic quarterly set
(``data_scale=1.0``: 8572 series of T=72), made from ``--seed``. The
weights start random from the same seed.

One chip:

1. fit ``STEPS`` steps on the default (``jax.numpy``) path;
2. the same fit through the Pallas kernels (``use_pallas=True``). The train
   step that fit compiled must hold the forward and backward kernels as
   ``tpu_custom_call``s (compiled by Mosaic, not interpreted). Against the
   default path, within ``KERNEL_RTOL``: the loss trajectory, and a
   forecast at the same weights; within ``GRAD_RTOL``: one batch's
   gradient at the initial weights, leaf by leaf;
3. predict all series and ``evaluate(split="test")``: finite forecasts,
   sMAPE and OWA;
4. two waves of ``REQUESTS`` requests through ``ForecastServer``: every
   forecast finite, and the second wave compiles nothing.

``--chips 4`` runs only a 4-way series-data-parallel fit and a sharded
predict, against a single-device fit and predict on the first of those
devices, in the same process (``DP_RTOL``).

With no TPU, or when any phase fails, the script exits non-zero and prints
no result. Its last line of output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Kernel path vs default path, relative. On a TPU v5e under JAX 0.9 an fp32
# dot rounds its operands to bfloat16 for one MXU pass (relative error
# ~2.5e-3 against fp64 at K=128) both in XLA at default precision and inside
# a Mosaic kernel, so the two forward passes agree bit for bit. The backward
# kernels sum in another order than XLA's autodiff (weight gradients
# accumulate tile by tile over the batch grid), which moves every update by
# fp32 rounding; 20 Adam steps carry that to about 1e-5 of the loss. 1e-4
# leaves a factor of ten over that.
KERNEL_RTOL = 1e-4
# One batch's gradient at the initial weights, kernel vs default path, per
# leaf: max |a - b| over max |b|. The trajectory alone cannot show a wrong
# backward kernel: 20 batches of 256 out of 8572 series revisit few rows, so
# a wrong Holt-Winters gradient barely reaches the loss, and a dropped LSTM
# cell-state cotangent moves it by less than KERNEL_RTOL. Such a fault (a
# term dropped or its sign flipped in either backward kernel) moves this
# gradient by 0.8 to 2; summation order and bf16 operand rounding move it by
# orders of magnitude less. 1e-2 lies between the two.
GRAD_RTOL = 1e-2
# Sharded vs single device, relative. Both run the same per-row math at the
# same precision; only the order of the cross-device sums (gradient psum,
# loss pmean) differs, at the fp32 rounding scale (~1e-7) per step, which
# 20 steps carry to about 5e-6 of the loss on four v5e chips. A forecast at
# the same weights has no cross-device sum at all.
DP_RTOL = 1e-4

STEPS = 20
REQUESTS = 48
SPEC = "esrnn-quarterly"


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(ok, msg: str) -> None:
    if not ok:
        fail(msg)


def rel_diff(a, b) -> float:
    """max |a - b| / max |b| over the elements."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


class Phase:
    """Times one phase: wall seconds and XLA compile seconds inside it."""

    def __init__(self, name: str, clock):
        self.name, self.clock = name, clock

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.seconds
        print(f"== {self.name}", flush=True)
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            print(f"   wall {time.perf_counter() - self.t0:.1f} s, compile "
                  f"{self.clock.seconds - self.c0:.1f} s", flush=True)


def fit(spec, data):
    from repro.forecast import ESRNNForecaster

    f = ESRNNForecaster(spec).fit(data)
    losses = np.asarray(f.history_["loss"])
    check(len(losses) == spec.n_steps and np.isfinite(losses).all(),
          f"{spec.name} fit: losses {losses}")
    print(f"   losses {np.array2string(losses, precision=5, max_line_width=200)}")
    return f, losses


# the jitted train step the trainer dispatches (per-step or fused engine)
STEP_MODULE = re.compile(r"jax_ir\d+_jit_(super)?step_compile\.mlir")
# the forward and backward halves of both kernels on the fit's main path
TRAIN_KERNELS = ("_hw_scan_kernel", "_hw_scan_bwd_kernel",
                 "_lstm_fwd_kernel", "_lstm_bwd_kernel")


def fit_with_step_kernels(spec, data):
    """Fit, and name the TPU kernels in the train step that fit compiled.

    JAX writes every module it compiles to ``jax_dump_ir_to`` while that is
    set; the train step's module is then the program the fit ran.
    """
    prev = jax.config.values.get("jax_dump_ir_to", "")
    with tempfile.TemporaryDirectory() as d:
        jax.config.update("jax_dump_ir_to", d)
        try:
            f, losses = fit(spec, data)
        finally:
            jax.config.update("jax_dump_ir_to", prev)
        steps = [n for n in os.listdir(d) if STEP_MODULE.fullmatch(n)]
        check(len(steps) == 1, f"the fit compiled train steps {steps}")
        with open(os.path.join(d, steps[0])) as fh:
            text = fh.read()
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    kernels = sorted(k for ln in calls
                     for k in re.findall(r'kernel_name = "(\w+)"', ln))
    return f, losses, len(calls), kernels


def grad_diffs(spec, kspec, data, seed: int) -> dict:
    """One batch's loss gradient at the initial weights, kernel path vs
    default path: the largest per-leaf relative difference in each group."""
    from repro.core.esrnn import esrnn_loss_and_grad, gather_series
    from repro.forecast import ESRNNForecaster

    params = ESRNNForecaster(spec).init_params(data.n_series, seed=seed)
    idx = jnp.arange(spec.batch_size)
    batch = [jnp.asarray(a)[idx] for a in (data.train, data.cats, data.mask)]
    p_b = gather_series(params, idx)
    _, g_ref = esrnn_loss_and_grad(spec.model, p_b, *batch)
    _, g_k = esrnn_loss_and_grad(kspec.model, p_b, *batch)
    return {group: max(rel_diff(a, b) for a, b in zip(
                jax.tree_util.tree_leaves(g_k[group]),
                jax.tree_util.tree_leaves(g_ref[group])))
            for group in g_ref}


def one_chip(args, clock) -> None:
    from repro.core.esrnn import esrnn_forecast
    from repro.forecast import ESRNNForecaster, get_spec
    from repro.forecast.serving import synthetic_request_stream

    spec = get_spec(SPEC, data_scale=1.0, data_seed=args.seed, seed=args.seed,
                    n_steps=STEPS)
    with Phase("data", clock):
        data = ESRNNForecaster(spec).make_data()
        print(f"   {data.n_series} series, T={data.train.shape[1]}")

    with Phase(f"fit, default path, {STEPS} steps", clock):
        f, loss_jnp = fit(spec, data)

    with Phase(f"fit, Pallas kernels, {STEPS} steps", clock):
        kspec = spec.replace(use_pallas=True)
        g, loss_k, n_calls, kernels = fit_with_step_kernels(kspec, data)
        print(f"   the fit's train step: {n_calls} tpu_custom_call, kernels "
              f"{kernels}")
        missing = sorted(set(TRAIN_KERNELS) - set(kernels))
        check(not missing, f"the use_pallas train step lacks {missing}")
        d_loss = rel_diff(loss_k, loss_jnp)
        y, cats = jnp.asarray(data.train), jnp.asarray(data.cats)
        fc_k = g.predict()
        fc_ref = np.asarray(esrnn_forecast(spec.model, g.params_, y, cats))
        d_fc = rel_diff(fc_k, fc_ref)
        print(f"   kernel vs default: loss trajectory rel diff {d_loss:.3e}, "
              f"forecast rel diff {d_fc:.3e} (tolerance {KERNEL_RTOL:g})")
        d_grad = grad_diffs(spec, kspec, data, args.seed)
        print("   kernel vs default: one batch's gradient, max per-leaf rel "
              "diff " + ", ".join(f"{k} {v:.3e}" for k, v in d_grad.items())
              + f" (tolerance {GRAD_RTOL:g})")
        check(d_loss <= KERNEL_RTOL, f"kernel loss trajectory off by {d_loss}")
        check(d_fc <= KERNEL_RTOL, f"kernel forecast off by {d_fc}")
        check(max(d_grad.values()) <= GRAD_RTOL,
              f"kernel gradient off by {d_grad}")

    with Phase("predict + evaluate(test)", clock):
        fc = f.predict()
        check(fc.shape == (data.n_series, spec.horizon)
              and np.isfinite(fc).all(), f"forecast {fc.shape} not finite")
        sc = f.evaluate(split="test")
        print(f"   forecast {fc.shape}; test sMAPE {sc['smape']:.4f} "
              f"OWA {sc['owa']:.4f} (Comb OWA {sc['owa_comb']:.4f})")
        check(np.isfinite([sc["smape"], sc["owa"]]).all(), f"scores {sc}")

    with Phase(f"serve, 2 waves of {REQUESTS} requests", clock):
        srv = f.serve()
        wave1 = synthetic_request_stream(
            spec.model, REQUESTS, n_known=f.n_series_, seed=args.seed)
        # new values at the first wave's lengths: every bucket it needs is
        # already compiled
        rng = np.random.default_rng(args.seed + 1)
        wave2 = [dataclasses.replace(
            r, y=(r.y * rng.lognormal(0.0, 0.1, r.y.shape)).astype(np.float32))
            for r in wave1]
        compiles = []
        for wave in (wave1, wave2):
            before = srv.stats.xla_compiles
            out = srv.forecast_batch(wave)
            check(len(out) == len(wave)
                  and all(np.isfinite(o).all() for o in out),
                  "a served forecast is not finite")
            compiles.append(srv.stats.xla_compiles - before)
        pct = srv.stats.latency_percentiles()
        print(f"   {srv.stats.requests} requests in {srv.stats.batches} "
              f"batches; XLA compiles per wave {compiles}; p50 "
              f"{pct['p50_ms']:.1f} ms p99 {pct['p99_ms']:.1f} ms")
        check(compiles[1] == 0, f"the second wave compiled {compiles[1]}")
        srv.check_compile_budget()


def four_chips(args, clock) -> None:
    from repro.core.esrnn import esrnn_forecast
    from repro.forecast import ESRNNForecaster, get_spec

    n_dev = len(jax.devices())
    check(n_dev == 4, f"--chips 4 needs 4 devices, JAX sees {n_dev}")
    spec = get_spec(SPEC, data_scale=1.0, data_seed=args.seed, seed=args.seed,
                    n_steps=STEPS)
    with Phase("data", clock):
        data = ESRNNForecaster(spec).make_data()
        print(f"   {data.n_series} series, T={data.train.shape[1]}")

    with Phase(f"fit, 4-way data parallel, {STEPS} steps", clock):
        dp, loss_dp = fit(spec.replace(data_parallel=4), data)
    with Phase(f"fit, one device, {STEPS} steps", clock):
        one, loss_1 = fit(spec, data)
    d_loss = rel_diff(loss_dp, loss_1)
    print(f"   4-way vs one device: loss trajectory rel diff {d_loss:.3e} "
          f"(tolerance {DP_RTOL:g})")
    check(d_loss <= DP_RTOL, f"sharded loss trajectory off by {d_loss}")

    with Phase("predict, sharded vs one device", clock):
        fc_dp = dp.predict()
        check(np.isfinite(fc_dp).all(), "sharded forecast not finite")
        # the same weights, gathered onto the first device of the mesh
        dev0 = jax.devices()[0]
        fc_1 = np.asarray(esrnn_forecast(
            spec.model, jax.device_put(dp.params_, dev0),
            jax.device_put(data.train, dev0), jax.device_put(data.cats, dev0)))
        d_fc = rel_diff(fc_dp, fc_1)
        d_fits = rel_diff(fc_dp, one.predict())
        print(f"   sharded vs one device, same weights: rel diff {d_fc:.3e} "
              f"(tolerance {DP_RTOL:g}); vs the one-device fit's forecast: "
              f"{d_fits:.3e}")
        check(d_fc <= DP_RTOL, f"sharded forecast off by {d_fc}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r})")

    from repro.analysis.recompile import CompileCounter
    from repro.launch.forecast import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    t0 = time.perf_counter()
    with CompileCounter() as clock:
        (four_chips if args.chips == 4 else one_chip)(args, clock)
    print(f"total wall {time.perf_counter() - t0:.1f} s, compile "
          f"{clock.seconds:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
