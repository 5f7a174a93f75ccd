"""Dtype-policy lint: clean programs pass, seeded f64/upcast programs fail."""

import jax
import jax.numpy as jnp

from repro.analysis.dtypes import dtype_findings


def test_clean_f32_program_passes():
    def f(x):
        return jnp.tanh(x @ x.T).sum()

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4, 3)))
    findings, metrics = dtype_findings(jaxpr, policy_dtype="float32")
    assert findings == []
    assert metrics["f64_avals"] == 0
    assert metrics["float_upcasts"] == 0
    assert metrics["eqns_scanned"] > 0


def test_f64_promotion_is_flagged():
    """Seeded violation: an x64-enabled program producing float64 values."""
    with jax.enable_x64(True):
        def f(x):
            return x.astype(jnp.float64) * 2.0

        jaxpr = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
    findings, metrics = dtype_findings(jaxpr, policy_dtype="float32")
    assert any("f64 promotion" in f.message for f in findings)
    assert metrics["f64_avals"] >= 1
    # the f32 -> f64 convert is also an above-policy upcast
    assert metrics["float_upcasts"] >= 1


def test_upcast_beyond_bf16_policy_is_flagged():
    """Under a bfloat16 policy an f32 convert is the silent-upcast failure."""
    def f(x):
        return x.astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4,), jnp.bfloat16))
    findings, metrics = dtype_findings(jaxpr, policy_dtype="bfloat16")
    assert any("silent upcast" in f.message for f in findings)
    assert metrics["float_upcasts"] >= 1


def test_downcast_within_policy_passes():
    def f(x):
        return x.astype(jnp.bfloat16).sum()

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
    findings, _ = dtype_findings(jaxpr, policy_dtype="float32")
    assert findings == []


def test_findings_dedup_by_dtype_pair():
    def f(x):
        a = x.astype(jnp.float32).sum()
        b = (x * 2).astype(jnp.float32).sum()
        return a + b

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4,), jnp.bfloat16))
    findings, metrics = dtype_findings(jaxpr, policy_dtype="bfloat16")
    assert metrics["float_upcasts"] >= 2
    assert len([f for f in findings if "silent upcast" in f.message]) == 1


# ---------------------------------------------------------------------------
# mixed-precision policy (state_dtype relaxation + accumulation checks)
# ---------------------------------------------------------------------------


def test_state_dtype_allows_declared_accumulation_upcasts():
    """Under bf16 policy + f32 state, the fp32 accumulation points pass."""
    def f(x):
        return x.astype(jnp.float32).sum()  # declared accumulation

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4,), jnp.bfloat16))
    findings, metrics = dtype_findings(
        jaxpr, policy_dtype="bfloat16", state_dtype="float32")
    assert findings == []
    assert metrics["float_upcasts"] == 0
    assert metrics["state_dtype"] == "float32"


def test_state_dtype_still_flags_f64():
    with jax.enable_x64(True):
        def f(x):
            return x.astype(jnp.float64) * 2.0

        jaxpr = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
    findings, _ = dtype_findings(
        jaxpr, policy_dtype="bfloat16", state_dtype="float32")
    assert any("f64 promotion" in f.message for f in findings)


def test_bf16_esrnn_forecast_is_policy_clean():
    """The real bf16 forecast program lints clean under (bf16, f32-state)."""
    import dataclasses as _dc

    import numpy as np

    from repro.core.esrnn import esrnn_forecast_fn, esrnn_init, make_config

    cfg = _dc.replace(make_config("quarterly"), precision="bf16")
    rng = np.random.default_rng(0)
    n, t = 8, 30
    y = jnp.asarray(np.abs(rng.lognormal(2, 0.3, (n, t))) + 0.5, jnp.float32)
    cats = jnp.eye(cfg.n_categories, dtype=jnp.float32)[
        jnp.zeros((n,), jnp.int32)]
    params = esrnn_init(jax.random.PRNGKey(0), cfg, n)
    jaxpr = jax.make_jaxpr(
        lambda p, yy, cc: esrnn_forecast_fn(cfg, p, yy, cc))(params, y, cats)
    findings, _ = dtype_findings(
        jaxpr, policy_dtype="bfloat16", state_dtype="float32")
    assert findings == []


def test_accumulation_findings_clean_on_real_trees():
    from repro.analysis.dtypes import accumulation_findings

    params = {"hw": {"alpha_logit": jnp.zeros((4,), jnp.float32)},
              "rnn": {"wx": jnp.zeros((3, 3), jnp.float32)}}
    opt = {"mu": {"rnn": jnp.zeros((3, 3), jnp.float32)},
           "nu": {"rnn": jnp.zeros((3, 3), jnp.float32)}, "t": 0}
    loss = jax.ShapeDtypeStruct((), jnp.float32)
    findings, metrics = accumulation_findings(params, opt, loss)
    assert findings == []
    assert metrics["loss_dtype"] == "float32"


def test_accumulation_findings_fire_on_seeded_violations():
    from repro.analysis.dtypes import accumulation_findings

    params = {"hw": {"alpha_logit": jnp.zeros((4,), jnp.bfloat16)}}
    opt = {"mu": {"w": jnp.zeros((3,), jnp.bfloat16)},
           "nu": {"w": jnp.zeros((3,), jnp.float32)}}
    loss = jax.ShapeDtypeStruct((), jnp.bfloat16)
    findings, metrics = accumulation_findings(params, opt, loss)
    msgs = " ".join(f.message for f in findings)
    assert "HW table" in msgs
    assert "Adam moments" in msgs
    assert "loss reduction" in msgs
    assert metrics["hw_table_dtypes_bad"] == ["bfloat16"]
