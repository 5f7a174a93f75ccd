"""Distributed tests: sharding specs + an 8-virtual-device mini dry-run.

The multi-device test runs in a subprocess because XLA locks the host device
count at first jax init (the main test process must keep seeing 1 device).
"""

import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.sharding import specs


def test_param_spec_rules():
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    specs.set_mesh(mesh)
    axes = {"dp": "data", "tp": "model"}

    class Leaf:
        def __init__(self, shape):
            self.shape = shape

    class K:
        def __init__(self, key):
            self.key = key

    # every named dim divides 1, so no divisibility fallbacks here
    assert specs.param_spec((K("embed"),), Leaf((100, 64)), axes) == P("model", "data")
    assert specs.param_spec((K("layers"), K("attn"), K("wq")), Leaf((4, 64, 128)), axes) \
        == P(None, "data", "model")
    assert specs.param_spec((K("layers"), K("attn"), K("wo")), Leaf((4, 128, 64)), axes) \
        == P(None, "model", "data")
    assert specs.param_spec((K("layers"), K("moe"), K("w_gate")), Leaf((4, 8, 64, 32)), axes) \
        == P(None, "model", "data", None)
    assert specs.param_spec((K("layers"), K("ssm"), K("w_in")), Leaf((4, 64, 200)), axes) \
        == P(None, "data", None)
    assert specs.param_spec((K("final_norm"), K("scale")), Leaf((64,)), axes) == P(None)


def test_divisibility_guard():
    from jax.sharding import PartitionSpec as P

    specs._MESH = None  # no mesh -> sizes default 1 -> everything "divides"

    class Leaf:
        def __init__(self, shape):
            self.shape = shape

    class K:
        def __init__(self, key):
            self.key = key

    spec = specs.param_spec((K("embed"),), Leaf((100, 64)),
                            {"dp": "data", "tp": "model"})
    assert spec == P("model", "data")


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import numpy as np

from repro.configs import ShapeCell, get_smoke_config
from repro.launch import steps as S
from repro.launch.mesh import make_host_mesh
from repro.models.model import build_model
from repro.roofline import analysis
from repro.roofline.jaxpr_cost import jaxpr_flops
from repro.sharding import specs
from repro.sharding.ctx import activation_sharding

mesh = make_host_mesh(model_parallel=2)
cfg = get_smoke_config("yi-6b")
cell = ShapeCell("t", "train", 32, 8, microbatch=4)
model = build_model(cfg)
specs.set_mesh(mesh)
axes = specs.axes_for(mesh)
batch_abs = S.batch_template(cfg, cell)
batch_sh = specs.batch_shardings(mesh, batch_abs, cell.global_batch)
with mesh, activation_sharding(mesh, dp=axes["dp"], tp=axes["tp"]):
    params_abs = S.abstract_params(model, master_fp32=True)
    params_sh = specs.param_shardings(mesh, params_abs)
    opt_abs = S.abstract_opt_state(params_abs)
    opt_sh = {"mu": params_sh, "nu": params_sh, "step": NamedSharding(mesh, P())}
    fn = S.make_train_step(model, cell)
    jitted = jax.jit(fn, in_shardings=(params_sh, opt_sh, batch_sh),
                     out_shardings=(params_sh, opt_sh, NamedSharding(mesh, P())))
    traced = jitted.trace(params_abs, opt_abs, batch_abs)
    flops = jaxpr_flops(traced.jaxpr)
    compiled = traced.lower().compile()
    terms = analysis.analyze(compiled, 8, flops_global=flops)

    # actually RUN the sharded step on the 8 virtual devices
    rng = np.random.default_rng(0)
    params = jax.tree_util.tree_map(
        lambda l, s: jax.device_put(jnp.zeros(l.shape, l.dtype) + 0.01, s),
        params_abs, params_sh)
    params = jax.tree_util.tree_map(
        lambda x: x if x.ndim else x, params)
    # proper init instead of zeros for stability
    p0 = model.init(jax.random.PRNGKey(0))
    p0 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x, p0)
    params = jax.tree_util.tree_map(jax.device_put, p0, params_sh)
    from repro.train.optimizer import adam_init
    opt = jax.tree_util.tree_map(jax.device_put, adam_init(params),
                                 {"mu": params_sh, "nu": params_sh,
                                  "step": NamedSharding(mesh, P())})
    batch = {
        "tokens": jax.device_put(
            jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
            batch_sh["tokens"]),
        "labels": jax.device_put(
            jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
            batch_sh["labels"]),
    }
    p2, o2, loss = jitted(params, opt, batch)
    print(json.dumps({
        "devices": len(jax.devices()),
        "loss": float(loss),
        "flops": terms.flops_global,
        "collective": terms.collective_global,
        "dominant": terms.dominant,
    }))
"""


@pytest.mark.slow
def test_mini_dryrun_and_real_step_on_8_devices():
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROC], capture_output=True, text=True,
        timeout=560, env=None, cwd=None)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    assert np.isfinite(out["loss"]) and out["loss"] > 0
    assert out["flops"] > 0
    assert out["collective"] > 0  # sharded training must communicate
