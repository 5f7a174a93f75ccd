"""repro.launch.forecast CLI smoke: every subcommand end-to-end on CPU."""

import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.forecast import main, use_compile_cache


@pytest.fixture(scope="module", autouse=True)
def _compile_cache_in_tmp(tmp_path_factory):
    """``main`` turns on JAX's persistent compilation cache: keep it in a
    temp dir here, and hand the process back without one."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR",
                  str(tmp_path_factory.mktemp("jax_cache")))
        yield
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()


def test_main_keeps_compile_cache_where_env_says(capsys):
    assert main(["specs"]) == 0
    assert (jax.config.jax_compilation_cache_dir
            == os.environ["JAX_COMPILATION_CACHE_DIR"])


def test_compile_cache_defaults_to_a_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    assert use_compile_cache() == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        root, ".jax_cache")


@pytest.fixture(scope="module")
def saved_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("fq"))
    rc = main(["fit", "--spec", "esrnn-quarterly", "--smoke", "--steps", "3",
               "--out-dir", d])
    assert rc == 0
    return d


def test_fit_with_overrides(tmp_path, capsys):
    rc = main(["fit", "--smoke", "--steps", "2", "--set", "hidden_size=4"])
    assert rc == 0
    assert "loss" in capsys.readouterr().out


def test_fit_fused_superstep_engine(capsys):
    """--set scan_steps=K routes through the fused lax.scan engine."""
    rc = main(["fit", "--smoke", "--steps", "6", "--set", "scan_steps=4",
               "--set", "hidden_size=4"])
    assert rc == 0
    assert "6 steps" in capsys.readouterr().out


def test_fit_sparse_adam(capsys):
    rc = main(["fit", "--smoke", "--steps", "4", "--set", "sparse_adam=true",
               "--set", "scan_steps=2", "--set", "hidden_size=4"])
    assert rc == 0
    assert "4 steps" in capsys.readouterr().out


def test_set_parses_booleans():
    from repro.launch.forecast import _parse_overrides

    out = _parse_overrides(["use_pallas=false", "smoke=True", "n_steps=3",
                            "rnn_lr=0.5", "name=x"])
    assert out["use_pallas"] is False and out["smoke"] is True
    assert out["n_steps"] == 3 and out["rnn_lr"] == 0.5 and out["name"] == "x"


def test_fit_resume_from_finished_checkpoint(tmp_path, capsys):
    ck = str(tmp_path / "ck")
    assert main(["fit", "--smoke", "--steps", "2", "--ckpt-dir", ck]) == 0
    capsys.readouterr()
    assert main(["fit", "--smoke", "--steps", "2", "--ckpt-dir", ck]) == 0
    assert "resumed from a finished checkpoint" in capsys.readouterr().out


def test_predict_from_saved(saved_dir, capsys):
    assert main(["predict", "--dir", saved_dir]) == 0
    assert "forecast" in capsys.readouterr().out


def test_predict_quantiles(saved_dir, capsys):
    assert main(["predict", "--dir", saved_dir, "--quantiles", "0.1,0.9"]) == 0
    out = capsys.readouterr().out
    assert "tau=0.1" in out and "tau=0.9" in out


def test_eval_from_saved(saved_dir, capsys):
    assert main(["eval", "--dir", saved_dir, "--split", "val"]) == 0
    out = capsys.readouterr().out
    assert "esrnn" in out and "comb" in out and "naive2" in out


def test_backtest_from_saved(saved_dir, capsys):
    assert main(["backtest", "--dir", saved_dir]) == 0
    out = capsys.readouterr().out
    assert "rolling-origin backtest" in out and "overall" in out
    assert out.count("  origin ") == 2  # default: end-of-train + end-of-val


def test_backtest_explicit_origins(saved_dir, capsys):
    assert main(["backtest", "--dir", saved_dir, "--origins", "60,72,80"]) == 0
    out = capsys.readouterr().out
    assert out.count("  origin ") == 3


def test_serve_smoke(saved_dir, capsys):
    assert main(["serve", "--dir", saved_dir, "--requests", "8",
                 "--waves", "2", "--length-buckets", "32,64",
                 "--batch-buckets", "1,8"]) == 0
    out = capsys.readouterr().out
    assert "jit cache" in out and "compiles" in out


def test_specs_lists_every_head_family(capsys):
    assert main(["specs"]) == 0
    out = capsys.readouterr().out
    assert "esrnn-quarterly" in out and "esn-quarterly" in out
    assert "ssm-hourly" in out and "head" in out


def test_specs_json(capsys):
    import json

    assert main(["specs", "--json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    by_name = {r["name"]: r for r in rows}
    assert by_name["esn-yearly"]["head"] == "esn"
    assert by_name["esrnn-monthly"] == dict(
        name="esrnn-monthly", frequency="monthly", horizon=18, head="lstm")


@pytest.mark.parametrize("args", [
    ["fit", "--spec", "esn-quarterly", "--smoke", "--steps", "2"],
    ["fit", "--smoke", "--steps", "2", "--set", "head=ssm",
     "--set", "hidden_size=8"],
])
def test_fit_alternative_heads(args, capsys):
    assert main(args) == 0
    assert "2 steps" in capsys.readouterr().out


def test_eval_alternative_head(capsys):
    assert main(["eval", "--spec", "esn-quarterly", "--smoke",
                 "--steps", "2"]) == 0
    out = capsys.readouterr().out
    assert "esn-quarterly" in out and "smape" in out
