"""Compile the forecast path for a TPU v5e that is described, not attached.

Interpret mode cannot show what Mosaic refuses (a dynamic slice of a loaded
value, a batched GEMV whose lhs has no free dimension, a squeezed block on
a vmapped bias, a kernel GSPMD cannot partition), so these tests lower and
compile at the real widths for a described ``v5e:2x2`` and assert that the
kernel is in the program.  Nothing runs: results are the parity suites'
job (``test_lstm_seq.py``, ``test_attn_seq.py``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import repro.kernels.ops as ops
from repro.core.device_plane import forecast_program
from repro.core.forecaster import (ARCH_INITS, AttnLSTMForecaster,
                                   LSTMForecaster, _lstm_fit_stacked,
                                   stacked_operands)
from repro.core.metrics import N_METRICS as M
from repro.distributed.sharding import CONTROL_AXIS
from repro.kernels.attn_lstm_seq import attn_lstm_seq, attn_lstm_seq_stacked
from repro.kernels.lstm_seq import lstm_seq, lstm_seq_stacked
from repro.training.optimizer import adamw_init

H, Z = 50, 4096
WINDOWS = {"lstm": 1, "attn": 8}          # the forecasters' defaults
FORECASTERS = {"lstm": LSTMForecaster, "attn": AttnLSTMForecaster}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the kernels for the chip: code that asks the backend still
    sees the CPU here and would pick interpret mode."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _params(arch, lead=()):
    one = jax.eval_shape(lambda: ARCH_INITS[arch](jax.random.PRNGKey(0),
                                                  M, H, M))
    return {k: jax.ShapeDtypeStruct(lead + v.shape, v.dtype)
            for k, v in one.items()}


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


KERNELS = {
    "lstm_seq": (lstm_seq, "lstm", False),
    "lstm_seq_stacked": (lstm_seq_stacked, "lstm", True),
    "attn_lstm_seq": (attn_lstm_seq, "attn", False),
    "attn_lstm_seq_stacked": (attn_lstm_seq_stacked, "attn", True),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    kernel, arch, stacked = KERNELS[name]
    params = _params(arch, (Z,) if stacked else ())
    leaves = [params[k] for k in FORECASTERS[arch].PARAM_LEAVES]
    xs = jax.ShapeDtypeStruct((Z, WINDOWS[arch], M), jnp.float32)
    text = _compiled_text(lambda *a: kernel(*a), *_shapes(leaves, one_chip),
                          *_shapes([xs], one_chip))
    assert "tpu_custom_call" in text


def test_stacked_kernel_with_recurrence_compiles_for_v5e(one_chip, mosaic):
    """Past window 1 the stacked kernel reads the leaves as they are and
    adds the recurrent term: compile that body, forward and backward."""
    params = _params("lstm", (Z,))
    leaves = [params[k] for k in LSTMForecaster.PARAM_LEAVES]
    xs = jax.ShapeDtypeStruct((Z, 2, M), jnp.float32)
    args = _shapes(leaves + [xs], one_chip)
    text = _compiled_text(lambda *a: ops.lstm_seq_stacked(*a), *args)
    assert "tpu_custom_call" in text
    _compiled_text(jax.grad(lambda *a: ops.lstm_seq_stacked(*a).sum(),
                            argnums=(0, 1)), *args)


def _plane_args(arch, Zp, mesh):
    """The forecast program's arguments as the engine installs them: the
    weight operands of ``stacked_operands`` and the scaler stats and ring,
    sharded on the target axis."""
    rows = NamedSharding(mesh, P(CONTROL_AXIS))
    params = _params(arch, (Zp,))
    operands = jax.eval_shape(lambda: stacked_operands(
        lambda k: jnp.zeros(params[k].shape, params[k].dtype),
        WINDOWS[arch], use_pallas=True, arch=arch))
    stacked = jax.tree.map(lambda v: jax.ShapeDtypeStruct(
        v.shape, v.dtype,
        sharding=NamedSharding(mesh, P(CONTROL_AXIS,
                                       *(None,) * (v.ndim - 1)))),
        operands)
    stats = jax.ShapeDtypeStruct((Zp, M), jnp.float32, sharding=rows)
    ring = jax.ShapeDtypeStruct((Zp, WINDOWS[arch], M), jnp.float32,
                                sharding=rows)
    return stacked, stats, stats, ring


@pytest.mark.parametrize("arch", ["lstm", "attn"])
def test_device_plane_forward_compiles_for_v5e(arch, topo, mosaic):
    """The device plane's jitted forward (standardise, fused stacked
    forward, residual, inverse) on one chip."""
    mesh = Mesh(np.asarray(topo.devices[:1]), (CONTROL_AXIS,))
    fwd = forecast_program(mesh, WINDOWS[arch], True, True, arch, True)
    args = _plane_args(arch, Z, mesh)
    text = fwd.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if arch == "lstm":
        # the installed form's default layout is the one the kernel reads:
        # no relayout copy of the weights inside the program
        theta = "f32[%d,%d]" % args[0].theta.shape
        assert not re.search(re.escape(theta) + r"\S* copy\(", text)


@pytest.mark.parametrize("coalesce", [True, False])
def test_device_plane_forward_compiles_on_four_chips(coalesce, topo, mosaic):
    """Both dispatch modes over a 4-chip mesh: the Mosaic kernel runs per
    device, with no collective in the program."""
    mesh = Mesh(np.asarray(topo.devices[:4]), (CONTROL_AXIS,))
    fwd = forecast_program(mesh, 1, True, True, "lstm", coalesce)
    text = fwd.lower(*_plane_args("lstm", Z, mesh)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-reduce" not in text


@pytest.mark.parametrize("arch", ["lstm", "attn"])
def test_stacked_fit_compiles_for_v5e(arch, one_chip, mosaic):
    """The batched fit vmaps the shared-weights kernel over targets."""
    m = FORECASTERS[arch](hidden=H, use_pallas=True)
    n_targets, n_windows = 64, 24
    params = _params(arch, (n_targets,))
    opt = jax.eval_shape(lambda p: adamw_init(p, m.opt_cfg),
                         _params(arch))
    opt = jax.tree.map(lambda v: jax.ShapeDtypeStruct(
        (n_targets,) + v.shape, v.dtype), opt)
    X = jax.ShapeDtypeStruct((n_targets, n_windows, m.window, M),
                             jnp.float32)
    Y = jax.ShapeDtypeStruct((n_targets, n_windows, M), jnp.float32)
    text = _compiled_text(
        lambda p, o, x, y: _lstm_fit_stacked(p, o, x, y, m.opt_cfg, 2,
                                             True, arch),
        *_shapes([params, opt, X, Y], one_chip))
    assert "tpu_custom_call" in text
