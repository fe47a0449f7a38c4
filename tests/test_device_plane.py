"""Device-mesh control plane (DESIGN.md §9): the ``DevicePlaneEngine``
behind ``ShardedControlPlane(device_mesh=...)``.

In-process tests run on the single default CPU device (a 1-device mesh is
still the device-resident path); the cross-device-count bitwise-invariance
property needs real multiple devices, so it runs in a subprocess under
``--xla_force_host_platform_device_count=8`` via the session fixture."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PPAConfig, ShardedControlPlane, Snapshot,
                        TargetSpec, ThresholdPolicy)
from repro.core.device_plane import DevicePlaneEngine, forecast_program
from repro.core.forecaster import (Z_CLIP, AttnLSTMForecaster,
                                   LSTMForecaster, Scaler,
                                   stack_scaler_stats)
from repro.core.metrics import N_METRICS
from repro.kernels import ref
from repro.kernels.lstm_seq import StackedForm, form_width

Z, W, H, S = 24, 2, 8, 4


def _fab_targets(Z=Z, window=W, hidden=H, seed=3):
    """Fabricated fitted per-target LSTMs (shared params, per-target
    scaler stats) — deterministic and fit-free, like the bench lane."""
    base = LSTMForecaster(window=window, hidden=hidden, seed=seed)
    rng = np.random.default_rng(seed + 100)
    means = rng.uniform(50.0, 300.0, (Z, N_METRICS))
    stds = 0.1 * means + 1.0
    out = []
    for i in range(Z):
        m = LSTMForecaster.__new__(LSTMForecaster)
        m.__dict__.update(base.__dict__)
        sc = Scaler()
        sc.mean, sc.std, sc.fitted = means[i], stds[i], True
        m.scaler = sc
        m._fitted, m._fit_count = True, 1
        m._valid_cache = (1, True)
        out.append(TargetSpec(f"t{i}", ThresholdPolicy(100.0, 1), model=m))
    return out


def _rows_seq(n=6, seed=11, z=Z):
    rng = np.random.default_rng(seed)
    return [rng.uniform(50.0, 300.0, (z, N_METRICS)) for _ in range(n)]


def _drive(plane, rows_seq, staged=False):
    """Fixed tick script; returns (replicas, key_metric, raw_means) per
    tick for every target in plane order."""
    out = []
    t = 0.0
    for rows in rows_seq:
        t += 15.0
        plane.observe_batch(t, rows)
        if staged:
            plane.begin_tick(t, 32, 2)
            res = plane.finish_tick()
        else:
            res = plane.control_step(t, 32, 2)
        names = list(res)
        out.append((
            np.array([res[n].replicas for n in names], np.int64),
            np.array([res[n].key_metric for n in names]),
            [res[n].raw_prediction for n in names],
        ))
    plane.shutdown()
    return out


def test_device_plane_matches_host_plane():
    """1-device mesh vs the host plane: identical decisions, predictions
    allclose (the engine computes f32 end-to-end, the host path f64)."""
    cfg = PPAConfig(threshold=100.0, stabilization_s=60.0)
    rows = _rows_seq()
    host = _drive(ShardedControlPlane(cfg, _fab_targets(), n_shards=S,
                                      coalesce_dispatch=False), rows)
    dev = _drive(ShardedControlPlane(cfg, _fab_targets(), n_shards=S,
                                     coalesce_dispatch=False,
                                     device_mesh=1), rows)
    for (hr, hk, hm), (dr, dk, dm) in zip(host, dev):
        np.testing.assert_array_equal(hr, dr)
        np.testing.assert_allclose(hk, dk, rtol=1e-4, atol=1e-3)
        for a, b in zip(hm, dm):
            assert (a is None) == (b is None)
            if a is not None:
                np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-3)


def test_device_plane_scalar_observe_matches_batch():
    """The scalar ``observe`` API (per-row device push) is bitwise equal
    to the one-shot ``observe_batch`` ring shift."""
    cfg = PPAConfig(threshold=100.0)
    rows = _rows_seq(4)

    def scalar_drive():
        plane = ShardedControlPlane(cfg, _fab_targets(), n_shards=S,
                                    coalesce_dispatch=False, device_mesh=1)
        out = []
        t = 0.0
        for r in rows:
            t += 15.0
            for i, n in enumerate(plane.target_names):
                plane.observe(n, Snapshot(t, r[i]))
            res = plane.control_step(t, 32, 2)
            out.append(np.array([res[n].replicas for n in res], np.int64))
        plane.shutdown()
        return out

    batch = _drive(ShardedControlPlane(cfg, _fab_targets(), n_shards=S,
                                       coalesce_dispatch=False,
                                       device_mesh=1), rows)
    for got, (want, _, _) in zip(scalar_drive(), batch):
        np.testing.assert_array_equal(got, want)


def test_device_plane_rejects_unstackable():
    """The device path only takes the homogeneous per-target stacked-LSTM
    shape: shared-model planes and scalar-only policies raise."""
    cfg = PPAConfig(threshold=100.0)
    shared = LSTMForecaster(window=W, hidden=H)
    with pytest.raises(ValueError, match="per-target"):
        ShardedControlPlane(
            cfg, [TargetSpec(f"t{i}", ThresholdPolicy(100.0, 1))
                  for i in range(4)],
            model=shared, n_shards=2, device_mesh=1)

    class Opaque:
        def __init__(self, inner):
            self._inner = inner

        def __call__(self, key, state=None):
            return self._inner(key, state)

    specs = _fab_targets(8)
    specs = [TargetSpec(sp.name, Opaque(sp.policy), model=sp.model)
             for sp in specs]
    with pytest.raises(ValueError, match="columnar"):
        ShardedControlPlane(cfg, specs, n_shards=2, device_mesh=1)


def test_device_plane_refit_epoch_invalidation():
    """Stacked weights re-upload iff the plane's refit epoch moves:
    mutated params are invisible until the commit bumps the epoch."""
    cfg = PPAConfig(threshold=100.0)
    rows = _rows_seq(5)
    plane = ShardedControlPlane(cfg, _fab_targets(), n_shards=S,
                                coalesce_dispatch=False, device_mesh=1)
    t = 0.0
    for r in rows[:3]:
        t += 15.0
        plane.observe_batch(t, r)
        res = plane.control_step(t, 32, 2)
    before = np.array([res[n].key_metric for n in res])

    # mutate every model's output head; same epoch -> device cache holds
    for m in plane._dev_models:
        m.params = dict(m.params)
        m.params["bo"] = m.params["bo"] + 1000.0
    t += 15.0
    plane.observe_batch(t, rows[3])
    res = plane.control_step(t, 32, 2)
    held = np.array([res[n].key_metric for n in res])
    assert np.all(np.isfinite(held))
    assert float(np.max(np.abs(held - before))) < 500.0  # no +1000 jump

    # commit: epoch bump -> refresh() restacks and the mutation lands
    plane._models_epoch += 1
    t += 15.0
    plane.observe_batch(t, rows[4])
    res = plane.control_step(t, 32, 2)
    applied = np.array([res[n].key_metric for n in res])
    assert np.all(applied > before + 100.0)
    plane.shutdown()


# ------------------------------------------------- installed kernel form --
def _engine_models(arch, window, z=Z, hidden=H, seed=5):
    """Fitted-looking per-target models on the kernel path, each with its
    own params and scaler stats."""
    cls = AttnLSTMForecaster if arch == "attn" else LSTMForecaster
    rng = np.random.default_rng(seed)
    out = []
    for i in range(z):
        m = cls(window=window, hidden=hidden, seed=seed + i, use_pallas=True)
        sc = Scaler()
        sc.mean = rng.uniform(50.0, 300.0, N_METRICS)
        sc.std = 0.1 * sc.mean + 1.0
        sc.fitted = True
        m.scaler = sc
        m._fitted, m._fit_count = True, 1
        m._valid_cache = (1, True)
        out.append(m)
    return out


def _engine(models):
    m0 = models[0]
    eng = DevicePlaneEngine(len(models), m0.window, m0.residual, True,
                            device_mesh=1, ring_rows=m0.window,
                            arch=m0.arch)
    eng.refresh(models, 0)
    return eng


def _reference_forecast(models, win):
    """The device plane's forecast from the plain jnp references: scale,
    stacked forward, residual, inverse."""
    m0 = models[0]
    mean, std = stack_scaler_stats(models)
    z = np.clip((win - mean[:, None]) / std[:, None], -Z_CLIP, Z_CLIP)
    leaves = [np.stack([np.asarray(m.params[k]) for m in models])
              for k in m0.PARAM_LEAVES]
    fwd = ref.attn_lstm_seq_stacked if m0.arch == "attn" \
        else ref.lstm_seq_stacked
    net = np.asarray(fwd(*leaves, jnp.asarray(z, jnp.float32)))
    if m0.residual:
        net = z[:, -1] + net
    return net * std + mean


FORMS = [("lstm", 1), ("lstm", 2), ("attn", 8)]


@pytest.mark.parametrize("arch,window", FORMS)
def test_engine_forecasts_match_the_plain_reference(arch, window):
    """The engine's forecast through the installed operands (the stacked
    form for the LSTM kernel) equals the plain ``ref`` forward."""
    models = _engine_models(arch, window)
    eng = _engine(models)
    rows = _rows_seq(window)
    for r in rows:
        eng.push_rows(r)
    means, cand = eng.forecast(eng.snapshot(), np.full(Z, window + 1))
    assert cand.all()
    want = _reference_forecast(models, np.stack(rows, axis=1))
    np.testing.assert_allclose(means, want, rtol=1e-4, atol=1e-3)


def _padded(a, lanes=128):
    """Rows of ``a`` zero-padded to a multiple of 128 lanes, flattened."""
    a = np.atleast_2d(a)
    n = -(-a.shape[-1] // lanes) * lanes
    return np.pad(a, ((0, 0), (0, n - a.shape[-1]))).ravel()


def test_install_holds_no_wh_at_window_one():
    """At window 1 the installed form has no recurrent weights: each
    target's row is Wx, b, Wo (as (n_out, H)) and bo, each row of each
    leaf on a 128-lane boundary.  At window 2 the install holds the five
    leaves as they are, Wh among them."""
    M = N_METRICS
    models = _engine_models("lstm", 1)
    eng = _engine(models)
    form = eng._stacked
    assert isinstance(form, StackedForm)
    width = form_width(M, H, M)
    assert form.theta.shape == (eng.Zp, width)
    assert eng.install_bytes == eng.Zp * (width + 2 * M) * 4
    p = {k: np.asarray(v) for k, v in models[3].params.items()}
    row = np.concatenate([_padded(p["Wx"]), _padded(p["b"]),
                          _padded(p["Wo"].T), _padded(p["bo"])])
    np.testing.assert_array_equal(np.asarray(form.theta)[3], row)

    models = _engine_models("lstm", 2)
    eng = _engine(models)
    leaves = LSTMForecaster.PARAM_LEAVES
    assert len(eng._stacked) == len(leaves)
    for name, got in zip(leaves, eng._stacked):
        np.testing.assert_array_equal(
            np.asarray(got)[3], np.asarray(models[3].params[name]))
    raw = sum(np.asarray(v).size for v in models[0].params.values())
    assert eng.install_bytes == eng.Zp * (raw + 2 * M) * 4


def test_epoch_bump_reinstalls_the_kernel_form():
    """A refit commit (epoch bump) builds the stacked form again from the
    new weights, and the forecast follows them; the same epoch keeps the
    installed form."""
    models = _engine_models("lstm", 1)
    eng = _engine(models)
    rows = _rows_seq(1)
    eng.push_rows(rows[0])
    counts = np.full(Z, 2)
    before, _ = eng.forecast(eng.snapshot(), counts)
    for m in models:
        m.params = dict(m.params)
        m.params["bo"] = m.params["bo"] + 1.0
    eng.refresh(models, 0)
    held, _ = eng.forecast(eng.snapshot(), counts)
    np.testing.assert_array_equal(held, before)
    eng.refresh(models, 1)
    assert eng.weight_installs == 2
    assert isinstance(eng._stacked, StackedForm)
    after, _ = eng.forecast(eng.snapshot(), counts)
    want = _reference_forecast(models, rows[0][:, None, :])
    np.testing.assert_allclose(after, want, rtol=1e-4, atol=1e-3)
    assert np.all(after - before > 5.0)      # +1 in z units, std >= 6


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _pallas_calls(sub)


@pytest.mark.parametrize("arch,window", FORMS)
def test_forecast_kernel_reads_the_window_and_writes_the_forecast(arch,
                                                                   window):
    """The benchmark finds a forecast kernel by its shapes: the kernel call
    takes the window (n, W, M) first and returns the forecast (n, M),
    whatever form its weights take."""
    eng = _engine(_engine_models(arch, window))
    fwd = forecast_program(eng.mesh, window, True, True, arch, True)
    jaxpr = jax.make_jaxpr(fwd)(eng._stacked, eng._mean, eng._std,
                                eng.snapshot())
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert len(calls) == 1
    (call,) = calls
    assert call.invars[0].aval.shape == (Z, window, N_METRICS)
    assert [v.aval.shape for v in call.outvars] == [(Z, N_METRICS)]


_CHILD = r"""
import hashlib, json
import numpy as np
from repro.core import PPAConfig, ShardedControlPlane
from repro.core.forecaster import LSTMForecaster, Scaler
from repro.core.metrics import N_METRICS

Z, W, H, S = 48, 2, 8, 4

def fab_targets():
    from repro.core import TargetSpec, ThresholdPolicy
    base = LSTMForecaster(window=W, hidden=H, seed=3)
    rng = np.random.default_rng(103)
    means = rng.uniform(50.0, 300.0, (Z, N_METRICS))
    stds = 0.1 * means + 1.0
    out = []
    for i in range(Z):
        m = LSTMForecaster.__new__(LSTMForecaster)
        m.__dict__.update(base.__dict__)
        sc = Scaler(); sc.mean, sc.std, sc.fitted = means[i], stds[i], True
        m.scaler = sc; m._fitted, m._fit_count = True, 1
        m._valid_cache = (1, True)
        out.append(TargetSpec(f"t{i}", ThresholdPolicy(100.0, 1), model=m))
    return out

rng = np.random.default_rng(11)
rows_seq = [rng.uniform(50.0, 300.0, (Z, N_METRICS)) for _ in range(6)]

def digest(D, coalesce, staged, explicit):
    assignment = ({f"t{i}": i * S // Z for i in range(Z)}
                  if explicit else None)
    plane = ShardedControlPlane(
        PPAConfig(threshold=100.0, stabilization_s=60.0), fab_targets(),
        n_shards=S, assignment=assignment, async_ticks=staged,
        coalesce_dispatch=coalesce, device_mesh=D)
    h = hashlib.sha256()
    t = 0.0
    for rows in rows_seq:
        t += 15.0
        plane.observe_batch(t, rows)
        if staged:
            plane.begin_tick(t, 32, 2)
            res = plane.finish_tick()
        else:
            res = plane.control_step(t, 32, 2)
        for n in res:
            r = res[n]
            h.update(np.int64(r.replicas).tobytes())
            h.update(np.float64(r.key_metric).tobytes())
            if r.raw_prediction is not None:
                h.update(np.asarray(r.raw_prediction).tobytes())
    plane.shutdown()
    return h.hexdigest()

cells = {}
for D in (1, 2, 8):
    cells[f"D{D}-shardmap-sync-block"] = digest(D, False, False, True)
    cells[f"D{D}-gang-sync-crc"] = digest(D, True, False, False)
    cells[f"D{D}-shardmap-async-crc"] = digest(D, False, True, False)
print("DIGESTS=" + json.dumps(cells))
"""


def test_device_count_bitwise_invariance(forced_devices_runner):
    """Tick results are bitwise identical across D in {1, 2, 8} devices,
    either dispatch mode (shard_map / gang GSPMD), sync and async staged
    ticks, any shard assignment: every per-target computation is
    row-independent, so the mesh partition cannot change numerics."""
    out = forced_devices_runner(_CHILD)
    line = next(ln for ln in out.splitlines() if ln.startswith("DIGESTS="))
    cells = json.loads(line[len("DIGESTS="):])
    assert len(cells) == 9
    vals = set(cells.values())
    assert len(vals) == 1, f"digest mismatch across cells: {cells}"
