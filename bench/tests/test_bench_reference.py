"""The plain reference against the program, at the cells' widths, on CPU."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, sut, weights
from bench.fleet import generate, prepare
from bench.harness import MODEL_KEYS
from bench.reference import esrnn as ref

from .conftest import ROOT


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def few_series(cfg, n=36):
    f = prepare(generate(cfg["frequency"], scale=0.01, seed=5),
                min_length=cfg["min_length"])
    return f.train[:n], f.val_input[:n], f.cats[:n]


@pytest.mark.parametrize("name", ["esrnn-quarterly", "esrnn-monthly"])
def test_forecast_agrees_with_esrnn_forecast(name):
    from repro.core.esrnn import esrnn_forecast

    cfg = config(name)
    model = {k: cfg[k] for k in MODEL_KEYS}
    _, y, cats = few_series(cfg)
    w = weights.init_weights(cfg, len(y), 3)
    spec = sut.make_spec(cfg)
    prog = np.asarray(esrnn_forecast(spec.model, sut.program_params(cfg, w),
                                     jnp.asarray(y), jnp.asarray(cats)))
    expect = ref.forecast(model, w, y, cats, block=16)
    errs = compare.forecast_errors([prog], expect)
    assert errs["max_rel_err"] < 1e-5, errs


@pytest.mark.parametrize("name", ["esrnn-quarterly", "esrnn-monthly"])
def test_one_train_step_agrees_with_the_program(name):
    from repro.train.engine import make_step_fn
    from repro.train.optimizer import AdamConfig, adam_init

    cfg = config(name)
    model = {k: cfg[k] for k in MODEL_KEYS}
    y, _, cats = few_series(cfg)
    w = weights.init_weights(cfg, len(y), 4)
    spec = sut.make_spec(cfg)
    adam = AdamConfig(lr=cfg["rnn_lr"], clip_norm=cfg["clip_norm"],
                      group_lr={"per_series": cfg["hw_lr"] / cfg["rnn_lr"],
                                "default": 1.0})
    step = jax.jit(make_step_fn(spec.model, adam, jnp.asarray(y),
                                jnp.asarray(cats), jnp.ones_like(y)))
    idx = np.arange(0, len(y), 2)
    p0 = sut.program_params(cfg, w)
    p1, state, loss = step(p0, adam_init(p0), jnp.asarray(idx))
    losses, g1, w1 = ref.train(model, w, y, cats, idx[None])
    assert abs(float(loss) - float(losses[0])) <= 1e-6 * abs(float(loss))
    prog_g = {k: v / (1 - cfg["adam_b1"]) for k, v in compare.norms(
        sut.program_leaves(state["mu"])).items()}
    assert compare.leaf_gap(prog_g, compare.norms(weights.leaves(g1))) < 1e-5
    for k, v in sut.program_leaves(p1).items():
        np.testing.assert_allclose(np.asarray(v),
                                   np.asarray(weights.leaves(w1)[k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)


def test_reference_shapes_history_like_the_server_documents():
    y = np.arange(1, 41, dtype=np.float32)
    assert ref.length_bucket(40, (32, 64)) == 64
    assert ref.length_bucket(900, (32, 64)) == 64
    padded = ref.shape_history(y, 64)
    assert padded.shape == (64,) and np.all(padded[:24] == 1.0)
    np.testing.assert_array_equal(padded[24:], y)
    np.testing.assert_array_equal(ref.shape_history(y, 32), y[-32:])
