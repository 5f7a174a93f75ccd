"""The plain reference against the program, at the cells' widths, on CPU,
and what the four cells read from the ``esrnn`` family pinned to the values
they read before the family was a file of its own."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, harness, openloop
from bench.adapters import esrnn as adapter
from bench.fleet import generate, prepare
from bench.reference import esrnn as ref

from .conftest import ROOT, small_cell


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
    model = {k: cfg[k] for k in ref.MODEL_KEYS}
    _, y, cats = few_series(cfg)
    w = ref.init_weights(cfg, len(y), 3)
    spec = adapter.make_spec(cfg)
    prog = np.asarray(esrnn_forecast(spec.model, adapter.program_params(cfg, w),
                                     jnp.asarray(y), jnp.asarray(cats)))
    expect = ref.forecast(model, w, y, cats, block=16)
    errs = compare.forecast_errors([prog], expect)
    assert errs["max_rel_err"] < 1e-5, errs


@pytest.mark.parametrize("name", ["esrnn-quarterly", "esrnn-monthly"])
def test_one_train_step_agrees_with_the_program(name):
    from repro.train.engine import make_step_fn
    from repro.train.optimizer import AdamConfig, adam_init

    cfg = config(name)
    model = {k: cfg[k] for k in ref.MODEL_KEYS}
    y, _, cats = few_series(cfg)
    w = ref.init_weights(cfg, len(y), 4)
    spec = adapter.make_spec(cfg)
    adam = AdamConfig(lr=cfg["rnn_lr"], clip_norm=cfg["clip_norm"],
                      group_lr={"per_series": cfg["hw_lr"] / cfg["rnn_lr"],
                                "default": 1.0})
    step = jax.jit(make_step_fn(spec.model, adam, jnp.asarray(y),
                                jnp.asarray(cats), jnp.ones_like(y)))
    idx = np.arange(0, len(y), 2)
    p0 = adapter.program_params(cfg, w)
    p1, state, loss = step(p0, adam_init(p0), jnp.asarray(idx))
    losses, g1, w1 = ref.train(model, w, y, cats, idx[None])
    assert abs(float(loss) - float(losses[0])) <= 1e-6 * abs(float(loss))
    prog_g = {k: v / (1 - cfg["adam_b1"]) for k, v in compare.norms(
        adapter.program_leaves(state["mu"])).items()}
    assert compare.leaf_gap(prog_g, compare.norms(ref.leaves(g1))) < 1e-5
    for k, v in adapter.program_leaves(p1).items():
        np.testing.assert_allclose(np.asarray(v),
                                   np.asarray(ref.leaves(w1)[k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)


def test_reference_shapes_history_like_the_server_documents():
    y = np.arange(1, 41, dtype=np.float32)
    assert openloop.length_bucket(40, (32, 64)) == 64
    assert openloop.length_bucket(900, (32, 64)) == 64
    padded = openloop.shape_history(y, 64)
    assert padded.shape == (64,) and np.all(padded[:24] == 1.0)
    np.testing.assert_array_equal(padded[24:], y)
    np.testing.assert_array_equal(openloop.shape_history(y, 32), y[-32:])


# sha256 of the leaves (name, dtype, shape and bytes, in ``leaves`` order)
# of 37 series' weights at seed 2**31 + 11, on the CPU, as the weights
# module gave them before the esrnn family took it in
WEIGHT_DIGESTS = {
    "esrnn-quarterly":
        "1d68d717e02439b700a7bca2485697a9b296c5ce87f6add15385ac452fbf2b65",
    "esrnn-monthly":
        "65b5098a32c0e91b74eff7cb9965e61069927d88325434ad78a909442af5754a",
}


@pytest.mark.parametrize("name", sorted(WEIGHT_DIGESTS))
def test_the_same_seed_gives_bitwise_the_same_weights(name):
    cfg = config(name)
    fam = harness.family_module(ROOT, "reference", cfg["family"])
    h = hashlib.sha256()
    for k, v in fam.leaves(fam.init_weights(cfg, 37, 2**31 + 11)).items():
        a = np.asarray(v)
        h.update(f"{k}:{a.dtype}:{a.shape};".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == WEIGHT_DIGESTS[name]


# the keys the harness passed to the reference before each family named its
# own (``harness.MODEL_KEYS``)
PINNED_MODEL_KEYS = ("seasonality", "input_size", "output_size",
                     "hidden_size", "dilations", "n_categories", "tau",
                     "rnn_lr", "hw_lr", "clip_norm", "adam_b1", "adam_b2",
                     "adam_eps")


@pytest.mark.parametrize("workload", ["quarterly-fit", "monthly-fit",
                                      "quarterly-serve", "monthly-predict"])
def test_cell_model_is_what_the_reference_read_before(workload):
    cell = small_cell(workload)
    assert cell.model == {k: cell.config[k] for k in PINNED_MODEL_KEYS}
    assert list(cell.model) == list(PINNED_MODEL_KEYS)
