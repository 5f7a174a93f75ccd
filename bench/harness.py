"""The benchmark harness: finds a cell's files by name and runs it.

A cell (``workloads`` in BENCHMARK.json) names a configuration and a
traffic mix. Each is a data file found by name: ``bench/configs/<config>.json``
and ``bench/traffic/<traffic>.json``; the mix names the driver that runs it
(``bench/drivers/<driver>.py``), and the limits of the numbers that decide
``correct`` are in ``bench/limits/<workload>.json``. The configuration names
its model ``family``, whose plain side is ``bench/reference/<family>.py``
(``MODEL_KEYS``, ``init_weights``, ``leaves``, ``train``, ``forecast``,
``PRIMER_HW``, ``train_step_flops``, ``forecast_flops``) and whose program
side is ``bench/adapters/<family>.py`` (``make_spec``, ``program_params``,
``program_leaves``, ``program_data``); the drivers are shared. Each
per-layer metric is a reader of its own, ``bench/metrics/<metric>.py``,
with a function ``read(ctx)`` that returns a number, or None where it finds
nothing to read.

A driver gets a :class:`Cell` and returns an :class:`Outcome`. It makes its
data and weights from the seed, warms up every shape its window uses, runs
the window inside ``cell.window()``, reads the device's memory peak, frees
the program's state, and only then runs the reference.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import threading
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
# the persistent compilation cache: a fixed path inside the checkout, so that
# only the first run of a cell in a checkout compiles
CACHE_DIR = ROOT / "bench" / ".jax_cache"
TRACE_DIR = ROOT / "bench" / ".traces"
# a traced run records this long a span from the middle of its window (a
# mix may set ``trace_seconds``): the trace of a whole window is too large to
# read within a run's time
TRACE_SECONDS = 1.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class UnknownFamily(LookupError):
    """A configuration names no model family, or one without its files."""


def sub_seed(seed: int, purpose: int) -> int:
    """A 32-bit seed for one purpose, from any non-negative seed."""
    return int(np.random.SeedSequence([seed, purpose]).generate_state(1)[0])


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at ``CACHE_DIR``, every
    program in it however fast it compiled."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    mix: dict
    limits: dict
    reference: ModuleType              # bench/reference/<family>.py
    adapter: ModuleType                # bench/adapters/<family>.py
    seed: int
    seconds: float
    trace: bool
    trace_dir: Optional[Path] = None
    t_start: float = 0.0               # process start, for set-up notes
    window_start: Optional[float] = None
    window_end: Optional[float] = None
    trace_stop_s: Optional[float] = None

    @property
    def window_seconds(self) -> float:
        return self.seconds

    @property
    def model(self) -> dict:
        return {k: self.config[k] for k in self.reference.MODEL_KEYS}

    @contextlib.contextmanager
    def window(self):
        """The measured window. With ``trace``, a thread records the profiler
        trace of a span (``bench.window``, the mix's ``trace_seconds`` or
        ``TRACE_SECONDS``) from its middle; the window itself keeps its
        length and its work."""
        tracer = None
        if self.trace:
            span = self.mix.get("trace_seconds", TRACE_SECONDS)
            tracer = _Tracer(self.trace_dir, span, max(
                0.0, (self.window_seconds - span) / 2))
        self.window_start = time.perf_counter()
        if tracer is not None:
            tracer.start()
        try:
            yield
            self.window_end = time.perf_counter()
        finally:
            if tracer is not None:
                tracer.join()
                self.trace_stop_s = tracer.stop_s


class _Tracer(threading.Thread):
    def __init__(self, trace_dir: Path, span: float, skip: float):
        super().__init__(name="bench-tracer", daemon=True)
        self.trace_dir, self.span, self.skip = trace_dir, span, skip
        self.stop_s = None

    def run(self):
        import jax

        time.sleep(self.skip)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                time.sleep(self.span)
        finally:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - t0


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    numbers: Dict[str, float]          # compared with bench/limits/<cell>
    metrics: Dict[str, float]          # end-to-end, by name
    memory_peak_bytes: int
    work: Dict[str, float] = dataclasses.field(default_factory=dict)
    notes: List[str] = dataclasses.field(default_factory=list)
    detail: Dict[str, object] = dataclasses.field(default_factory=dict)


def load_cell(root: Path, workload: str, *, seed: int, seconds: float,
              trace: bool, bench: Optional[dict] = None) -> Cell:
    bench = bench or _json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}[workload]
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[wl["config"]]
    mix = _json(root / "bench" / "traffic" / f"{wl['traffic']}.json")
    limits_path = root / "bench" / "limits" / f"{workload}.json"
    limits = _json(limits_path) if limits_path.exists() else {}
    config = _json(root / cfg_file)
    if "family" not in config:
        raise UnknownFamily(f"{cfg_file} states no \"family\"")
    return Cell(workload=wl, config=config, mix=mix, limits=limits,
                reference=family_module(root, "reference", config["family"]),
                adapter=family_module(root, "adapters", config["family"]),
                seed=seed, seconds=seconds, trace=trace,
                trace_dir=root / "bench" / ".traces" / workload)


def driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def _load(name: str, path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_module(root: Path, part: str, family: str) -> ModuleType:
    """``bench/<part>/<family>.py`` of the checkout at ``root``: a model
    family's ``reference`` or its ``adapters`` module. This checkout's own
    files are imported as the package's modules, others by their path."""
    path = root / "bench" / part / f"{family}.py"
    if not path.is_file():
        raise UnknownFamily(
            f"model family {family!r} has no {path.relative_to(root)}")
    if path.resolve() == ROOT / "bench" / part / f"{family}.py":
        return importlib.import_module(f"bench.{part}.{family}")
    return _load(f"bench_{part}_{family}", path)


def reader(root: Path, metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    return _load("bench_metric_" + metric.replace(".", "_"), path).read


def metrics_of(bench: dict, section: str, workload: str) -> List[dict]:
    """The metrics of a section that a cell reports: those that list it
    under ``workloads``, and end-to-end metrics without the key (``setup_s``)
    in every cell. Every per-layer metric lists its cells."""
    return [m for m in bench[section]
            if (section == "end_to_end" and "workloads" not in m)
            or workload in m["workloads"]]


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


@contextlib.contextmanager
def frozen_heap():
    """Collect, then keep what set-up left on the Python heap out of the
    collector's later scans (``gc.freeze``), as a server process does once
    it has warmed up; thaw it again on the way out. Yields the list of
    ``(generation, seconds)`` of every collection made inside."""
    pauses, began = [], []

    def note(phase, info):
        if phase == "start":
            began.append(time.perf_counter())
        elif began:
            pauses.append((info["generation"],
                           time.perf_counter() - began.pop()))

    gc.collect()
    gc.freeze()
    gc.callbacks.append(note)
    try:
        yield pauses
    finally:
        gc.callbacks.remove(note)
        gc.unfreeze()


def free_program_state():
    """Drop what the program left on the device before the reference runs."""
    import jax

    gc.collect()
    jax.clear_caches()


def drive(cell: Cell, *, control: bool = False) -> Outcome:
    """Run the cell's driver as its configuration states, or, with
    ``control``, with the configuration's ``control`` in its place: a spec
    setting (``precision``) or a ``matmul_precision``. A matmul precision
    other than ``default`` holds for the whole run, set-up and warm-up
    included (``jax.default_matmul_precision``)."""
    import jax

    overrides = dict(cell.config["control"]) if control else {}
    matmul = overrides.pop("matmul_precision", cell.config["matmul_precision"])
    scope = (contextlib.nullcontext() if matmul == "default"
             else jax.default_matmul_precision(matmul))
    with scope:
        return driver(cell.mix["driver"]).run(cell,
                                              spec_overrides=overrides or None)


def run(root: Path, workload: str, *, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True, cell: Optional[Cell] = None,
        log=print) -> dict:
    """Run one cell and return its result line (see ``bench/run.py``)."""
    bench = _json(root / "BENCHMARK.json")
    cell = cell or load_cell(root, workload, seed=seed, seconds=seconds,
                             trace=trace, bench=bench)
    cell.t_start = t_start
    device = device_info(cell.workload["chips"], require_chip)
    kind_peaks = None
    if require_chip:
        from bench.trace import peaks

        kind_peaks = peaks(device["kind"])     # an unknown chip is an error
    out = drive(cell)
    device["memory_peak_bytes"] = out.memory_peak_bytes
    for note in out.notes:
        log(note)

    metrics = {}
    breakdown = None
    if trace:
        from bench import trace as tr

        t0 = time.perf_counter()
        path = tr.find_xplane(str(cell.trace_dir))
        size = os.path.getsize(path)
        summary = tr.reduce(path)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        log(f"trace: {size} bytes, stop_trace {cell.trace_stop_s:.3f} s, "
            f"reduced in {time.perf_counter() - t0:.3f} s")
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = tr.breakdown(summary)
        ctx = {"trace": summary, "work": out.work, "peaks": kind_peaks}
        for m in metrics_of(bench, "per_layer", workload):
            value = reader(root, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        out.metrics["setup_s"] = cell.window_start - t_start
        for m in metrics_of(bench, "end_to_end", workload):
            metrics[m["name"]] = {"value": float(out.metrics[m["name"]]),
                                  "unit": m["unit"]}

    from bench.compare import judge

    correct, checks = judge(out.numbers, cell.limits)
    line = {"correct": correct, "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line
