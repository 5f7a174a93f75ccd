"""Compile the main-path Pallas kernels for a described TPU v5e, no chip needed.

Interpret mode (every other kernel test) runs the kernels as Python on the
CPU and accepts layouts, slices and VMEM use that the TPU compiler refuses.
These tests lower ``hw_scan_tm`` and ``lstm_cell_padded`` -- forward and
custom_vjp backward, at the widths the forecaster runs -- with
``interpret=False`` against a ``v5e:2x2`` topology description, and assert
that the compiled program holds the Mosaic kernel (``tpu_custom_call``).
One more compiles the 4-way series-sharded loss gradient through the
kernels, with ``shard_map``'s varying-axes check on as it is on a TPU.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every xdist
worker imports this file. Keep all such compiles in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.esrnn import esrnn_init
from repro.forecast import get_spec
from repro.kernels import hw_scan as _hw
from repro.kernels import lstm_cell as _lstm
from repro.kernels import ops as _ops
from repro.sharding import series as _series


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Route ``kernels.ops`` to the compiled kernels, as on a TPU.

    The backend here is the CPU, so the wrappers would pick interpret mode;
    jit caches are cleared on both sides so no trace crosses the switch.
    """
    monkeypatch.setattr(_ops, "_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("t_len,m,y_dtype", [
    (80, 4, jnp.float32),
    (90, 12, jnp.float32),
    (72, 4, jnp.bfloat16),
])
def test_hw_scan_compiles_for_v5e(one_chip, t_len, m, y_dtype):
    n = 2 * _hw.BLOCK_N
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    args = (sds((t_len, n), y_dtype), sds((n,)), sds((n,)), sds((m, n)))

    def fwd(y, a, g, s0):
        return _hw.hw_scan_tm(y, a, g, s0, interpret=False)

    def loss(y, a, g, s0):
        lv, ss = fwd(y, a, g, s0)
        return jnp.sum(lv) + jnp.sum(jnp.square(ss))

    assert "tpu_custom_call" in _compiled_text(fwd, *args)
    grad_text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2, 3)), *args)
    # forward (residuals) and the adjoint kernel
    assert grad_text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lstm_cell_compiles_for_v5e(one_chip, dtype):
    b, i, h = 2048, 128, 128
    block_b = _lstm.block_b_for(dtype)
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (sds(i, 4 * h), sds(h, 4 * h), sds(4 * h), sds(b, i), sds(b, h),
            sds(b, h))

    def fwd(*a):
        return _lstm.lstm_cell_padded(*a, interpret=False, block_b=block_b)

    def loss(*a):
        h_new, c_new = fwd(*a)
        return (jnp.sum(h_new.astype(jnp.float32))
                + jnp.sum(jnp.square(c_new.astype(jnp.float32))))

    assert "tpu_custom_call" in _compiled_text(fwd, *args)
    grad_text = _compiled_text(jax.grad(loss, argnums=tuple(range(6))), *args)
    assert grad_text.count("tpu_custom_call") >= 2


def test_sharded_kernel_loss_grad_compiles_for_v5e_2x2(topo, mosaic):
    cfg = get_spec("esrnn-quarterly", use_pallas=True).model
    assert _series._check_vma(cfg)
    mesh = _series.make_series_mesh(4, devices=topo.devices)
    b, t_len = 256, 72
    params = jax.eval_shape(
        lambda: esrnn_init(jax.random.PRNGKey(0), cfg, b))
    params = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        params, _series.esrnn_param_shardings(mesh, params))
    rows = NamedSharding(mesh, P(_series.SERIES_AXIS))
    y = jax.ShapeDtypeStruct((b, t_len), jnp.float32, sharding=rows)
    cats = jax.ShapeDtypeStruct((b, cfg.n_categories), jnp.float32,
                                sharding=rows)

    def loss(p, y, cats, mask):
        return _series.esrnn_loss_dp(cfg, p, y, cats, mask, mesh=mesh)

    text = _compiled_text(jax.grad(loss), params, y, cats, y)
    assert text.count("tpu_custom_call") >= 4   # both kernels, both ways
    assert "all-reduce" in text
