"""The harness finds cells, mixes, model families and metric readers by
name, holds BENCHMARK.json to its contract, and refuses to run without a
chip."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

from .conftest import ROOT, run_small, small_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_keeps_its_contract():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench"]
    assert 1 <= b["run_seconds"] <= 51
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == \
            c["reduced"]
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        stated = json.loads((ROOT / c["file"]).read_text())
        assert stated["matmul_precision"] in ("default", "highest")
        assert set(stated["control"]) <= {"precision", "matmul_precision"}
        for part in ("reference", "adapters"):
            assert (ROOT / "bench" / part /
                    f"{stated['family']}.py").is_file()
        names.add(c["name"])
    cells = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").exists()
        assert (ROOT / "bench" / "tests" / "small" /
                f"{w['name']}.json").exists()
        cells.add(w["name"])
    assert len(cells) == len(b["workloads"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in {"host_clock", "device_trace"}
    for m in b["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        for w in m["workloads"]:
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", cells)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in cells:
        reported = harness.metrics_of(b, "end_to_end", w)
        assert "setup_s" in [m["name"] for m in reported]
        assert len(reported) >= 2
        assert harness.metrics_of(b, "per_layer", w)
    # a full check of 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def _copy_bench(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces",
                                                  "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")


def _digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_mix_and_metric_are_found_by_name(tmp_path):
    _copy_bench(tmp_path)
    b = bench()
    b["workloads"].append({"name": "dummy-cell", "config": "esrnn-monthly",
                           "traffic": "dummy_mix", "chips": 1,
                           "why": "a test"})
    b["per_layer"].append({"name": "dummy_metric", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "a test", "moves": "predict_series_per_s",
                           "workloads": ["dummy-cell"]})
    b["end_to_end"][2]["workloads"].append("dummy-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    mix = json.loads((ROOT / "bench/traffic/predict_fleet.json").read_text())
    (tmp_path / "bench/traffic/dummy_mix.json").write_text(json.dumps(
        {**mix, "keep_share": 0.25}))
    (tmp_path / "bench/limits/dummy-cell.json").write_text(
        (ROOT / "bench/limits/monthly-predict.json").read_text())
    (tmp_path / "bench/metrics/dummy_metric.py").write_text(
        "def read(ctx):\n    return 1e3 * ctx['work']['window_s']\n")

    cell = harness.load_cell(tmp_path, "dummy-cell", seed=1, seconds=1.0,
                             trace=False)
    assert cell.mix["keep_share"] == 0.25
    assert cell.mix["driver"] == "predict"
    wanted = [m["name"] for m in harness.metrics_of(b, "per_layer",
                                                     "dummy-cell")]
    assert wanted == ["dummy_metric"]
    assert harness.reader(tmp_path, "dummy_metric")(
        {"work": {"window_s": 0.5}}) == 500.0
    assert [m["name"] for m in harness.metrics_of(
        b, "end_to_end", "dummy-cell")] == ["setup_s", "predict_series_per_s"]


# a family of new files only: esrnn's model under other leaf names
TOY_REFERENCE = """from bench.reference import esrnn
from bench.reference.esrnn import (MODEL_KEYS, PRIMER_HW, forecast,
                                   forecast_flops, init_weights, train,
                                   train_step_flops)


def leaves(w):
    return {"toy." + k: v for k, v in esrnn.leaves(w).items()}
"""
TOY_ADAPTER = """from bench.adapters import esrnn
from bench.adapters.esrnn import make_spec, program_data, program_params


def program_leaves(tree):
    return {"toy." + k: v for k, v in esrnn.program_leaves(tree).items()}
"""


def test_a_new_family_enters_as_new_files_only(tmp_path):
    _copy_bench(tmp_path)
    before = _digests(tmp_path / "bench")
    b = bench()
    old = {k: list(b[k]) for k in ("configs", "workloads")}
    cfg = json.loads((ROOT / "bench/configs/esrnn-quarterly-highest.json")
                     .read_text())
    (tmp_path / "bench/configs/toy.json").write_text(json.dumps(
        {**cfg, "name": "toy", "family": "toy"}))
    (tmp_path / "bench/reference/toy.py").write_text(TOY_REFERENCE)
    (tmp_path / "bench/adapters/toy.py").write_text(TOY_ADAPTER)
    b["configs"].append({"name": "toy", "source": "a test",
                         "file": "bench/configs/toy.json", "reduced": [],
                         "why": "a test"})
    for cell, traffic, like in [("toy-fit", "fit_b256", "quarterly-fit"),
                                ("toy-predict", "predict_fleet",
                                 "monthly-predict")]:
        b["workloads"].append({"name": cell, "config": "toy",
                               "traffic": traffic, "chips": 1,
                               "why": "a test"})
        for part in ("limits", "tests/small"):
            (tmp_path / "bench" / part / f"{cell}.json").write_text(
                (ROOT / "bench" / part / f"{like}.json").read_text())
    b["end_to_end"][1]["workloads"].append("toy-fit")
    b["end_to_end"][2]["workloads"].append("toy-predict")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    for cell in ("toy-fit", "toy-predict"):
        line = run_small(cell, root=tmp_path)
        assert line["correct"], line["checks"]
        assert line["failed"] == 0 and line["attempted"] > 0
    fit = small_cell("toy-fit", root=tmp_path)
    assert fit.reference.__file__ == str(tmp_path / "bench/reference/toy.py")
    assert list(fit.reference.leaves(fit.reference.init_weights(
        fit.config, 4, 1)))[0] == "toy.hw.alpha_logit"
    after = _digests(tmp_path / "bench")
    assert {p: after.get(p) for p in before} == before
    assert all(b[k][:len(v)] == v for k, v in old.items())
    # the drivers are shared: none imports a family's module by name
    for driver in (ROOT / "bench" / "drivers").glob("*.py"):
        imports = [ln for ln in driver.read_text().splitlines()
                   if ln.lstrip().startswith(("import ", "from "))]
        assert not [ln for ln in imports if re.search(
            r"reference|adapters|weights|flops", ln)], driver.name


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_a_configuration_without_its_family_fails_to_load(tmp_path, family):
    _copy_bench(tmp_path)
    path = tmp_path / "bench/configs/esrnn-monthly.json"
    cfg = json.loads(path.read_text())
    del cfg["family"]
    if family:
        cfg["family"] = family
    path.write_text(json.dumps(cfg))
    missing = ("bench/configs/esrnn-monthly.json" if family is None
               else f"bench/reference/{family}.py")
    with pytest.raises(harness.UnknownFamily, match=missing):
        harness.load_cell(tmp_path, "monthly-predict", seed=1, seconds=1.0,
                          trace=False)


def test_small_predict_run_is_correct():
    line = run_small("monthly-predict")
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"setup_s", "predict_series_per_s"}
    assert line["failed"] == 0 and line["attempted"] > 0


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "monthly-predict",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj:
            return True
    return False


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "TPU" in proc.stderr


def test_bench_files_alone_exit_nonzero_without_a_result(tmp_path):
    _copy_bench(tmp_path)
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)


def test_unknown_device_kind_raises():
    from bench.trace import UnknownDevice, peaks

    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(UnknownDevice):
        peaks("TPU v9 imaginary")


def test_sub_seeds_take_large_seeds():
    a = harness.sub_seed(2**40 + 3, 2)
    assert 0 <= a < 2**32 and a == harness.sub_seed(2**40 + 3, 2)
    assert a != harness.sub_seed(2**40 + 4, 2)
