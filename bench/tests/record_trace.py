"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out_dir>

It traces a few forecasts of 64 series inside a ``bench.window`` span, with
the profiler options of ``bench.harness``, prints every plane and line of
the trace with its event count, and writes the ``.xplane.pb`` and the
reduction of it to ``<out_dir>``.
"""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import trace
    from bench.reference import esrnn as ref

    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      "esrnn-quarterly.json")))
    model = {k: cfg[k] for k in ("seasonality", "input_size", "output_size",
                                 "hidden_size", "dilations", "n_categories")}
    w = ref.init_weights(cfg, 64, 0)
    y = np.exp(np.random.default_rng(0).normal(5, 0.1, (64, 40))).astype(
        np.float32)
    cats = np.eye(6, dtype=np.float32)[np.arange(64) % 6]
    ref.forecast(model, w, y, cats, block=64)
    tmp = os.path.join(out_dir, "raw")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            ref.forecast(model, w, y, cats, block=64)
        jnp.zeros(8).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(tmp)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", repr(plane.name), lines[:20])
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp)
    s = trace.reduce(os.path.join(out_dir, "small.xplane.pb"))
    print(json.dumps({"window_s": s.window_s, "busy_s": s.busy_s,
                      "n_devices": s.n_devices, **trace.breakdown(s)}))
    print("bytes", os.path.getsize(os.path.join(out_dir, "small.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1])
