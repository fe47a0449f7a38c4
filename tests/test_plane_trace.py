"""The control plane's stage spans and work counters (``core/obs.py``,
``ShardedControlPlane.tick_stats``), on the CPU profiler: a 1-device mesh
plane without the Pallas kernels, at a small Z."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PPAConfig, ShardedControlPlane, TargetSpec,
                        ThresholdPolicy, obs)
from repro.core.forecaster import LSTMForecaster, Scaler
from repro.core.metrics import N_METRICS as M
from repro.core.policies import GuardrailConfig, ResilienceConfig

Z, W, H, S = 12, 2, 8, 3

# every span of the device-mode tick and the span it nests in
PARENT = {
    "ppa.collect": None,
    "ppa.collect.upload": "ppa.collect",
    "ppa.forecast": None,
    "ppa.forecast.install": "ppa.forecast",
    "ppa.forecast.snapshot": "ppa.forecast",
    "ppa.forecast.device": "ppa.forecast",
    "ppa.forecast.dispatch": "ppa.forecast.device",
    "ppa.forecast.readback": "ppa.forecast.device",
    "ppa.decide": None,
    "ppa.decide.join": "ppa.decide",
    "ppa.decide.evaluate": "ppa.decide",
    "ppa.decide.stabilise": "ppa.decide",
    "ppa.decide.degrade": "ppa.decide",
    "ppa.decide.guard": "ppa.decide",
    "ppa.decide.record": "ppa.decide",
    "ppa.decide.epilogue": "ppa.decide",
    "ppa.readout": None,
    "ppa.refit.compute": None,
    "ppa.refit.install": None,
}
# the engine's forecast, which runs on a worker in async mode
WORKER = ("ppa.forecast.device",)
# a program is built where it is first launched
BUILT_IN = {"ppa_forecast": "ppa.forecast.dispatch",
            "ppa_ring_push": "ppa.collect.upload"}


def _targets():
    base = LSTMForecaster(window=W, hidden=H, seed=5)
    rng = np.random.default_rng(7)
    means = rng.uniform(50.0, 300.0, (Z, M))
    out = []
    for i in range(Z):
        m = LSTMForecaster.__new__(LSTMForecaster)
        m.__dict__.update(base.__dict__)
        sc = Scaler()
        sc.mean, sc.std, sc.fitted = means[i], 0.1 * means[i] + 1.0, True
        m.scaler = sc
        m._fitted, m._fit_count = True, 1
        m._valid_cache = (1, True)
        out.append(TargetSpec(f"t{i}", ThresholdPolicy(100.0, 1), model=m))
    return out


class _NoFit:
    """An updater whose refit does nothing: the refit spans, no fit."""

    class Pending:
        t, batched = 0.0, True

        def compute(self):
            return None

        def commit(self):
            return None

    def begin_update_batch(self, models, hists, t, targets=None):
        return self.Pending()


def _plane(**kw):
    cfg = PPAConfig(threshold=100.0, stabilization_s=60.0,
                    update_interval_s=30.0,
                    guard=GuardrailConfig(band=0.2, down_ticks=2),
                    resilience=ResilienceConfig(stale_ttl_s=3600.0))
    # contiguous blocks, as a deployment assigns them: the shards decide
    # on views of the joined forecast batch
    blocks = {f"t{i}": i * S // Z for i in range(Z)}
    return ShardedControlPlane(cfg, _targets(), n_shards=S,
                               assignment=blocks, device_mesh=1,
                               use_pallas=False, **kw)


def _rows(n, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.uniform(50.0, 300.0, (Z, M)) for _ in range(n)]


def _drive(plane, rows, refit_at=None):
    """Fill the window, then one tick per remaining row; returns each
    tick's decisions and forecasts."""
    for j in range(W):
        plane.observe_batch(15.0 * j, rows[j])
    out, cur = [], np.full(Z, 2, np.int64)
    for j in range(W, len(rows)):
        t = 15.0 * j
        plane.observe_batch(t, rows[j])
        plane.begin_tick(t, 32, cur)
        res = plane.finish_tick()
        cur = res.replicas_array()
        out.append((cur, *res.forecasts_array()))
        if j == refit_at:
            plane.maybe_update(t)
            plane.flush_updates()
    return out


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; returns its result and the ``ppa.*``
    events as ``(name, start, end, stats, line)``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("ppa."):
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   {k: v for k, v in e.stats},
                                   (plane.name, li)))
    return result, events


def _parent(ev, events):
    """The innermost other event on the same line that contains ``ev``."""
    name, s, e, _, line = ev
    best = None
    for other in events:
        if other is ev or other[4] != line:
            continue
        if other[1] <= s and e <= other[2] and (
                best is None or other[2] - other[1] < best[2] - best[1]):
            best = other
    return best


@pytest.fixture
def spans_on():
    obs.enable(True)
    yield
    obs.enable(False)


def test_spans_off_leave_no_event(tmp_path):
    assert not obs.enabled()
    plane = _plane()
    _, events = _traced(tmp_path, lambda: _drive(plane, _rows(W + 3)))
    plane.shutdown()
    assert events == []


@pytest.mark.parametrize("async_ticks", [False, True])
def test_every_span_nests_under_its_parent_with_its_tick(tmp_path,
                                                         spans_on,
                                                         async_ticks):
    plane = _plane(updater=_NoFit(), async_updates=True,
                   async_ticks=async_ticks)
    n_ticks = 4
    rows = _rows(W + n_ticks)
    _, events = _traced(tmp_path, lambda: _drive(plane, rows, refit_at=W))
    plane.shutdown()
    names = {e[0] for e in events}
    assert set(PARENT) | {"ppa.build"} == names
    for ev in events:
        name, _, _, stats, _ = ev
        par = _parent(ev, events)
        want = (BUILT_IN[stats["program"]] if name == "ppa.build"
                else PARENT[name])
        if async_ticks and name in WORKER:
            want = None              # on the worker, tagged with its tick
        assert (par and par[0]) == want, (name, par and par[0])
        if par is None:
            assert "tick" in stats, name
        top = par
        while top is not None and _parent(top, events) is not None:
            top = _parent(top, events)
        if top is not None and "tick" in stats:
            assert stats["tick"] == top[3]["tick"], name
    # the engine's forecast carries the tick it forecasts, on any thread
    for name in WORKER:
        ticks = sorted(e[3]["tick"] for e in events if e[0] == name)
        assert ticks == list(range(n_ticks)), name
    # one forecast, decide and readout span a tick, numbered from 0
    for name in ("ppa.forecast", "ppa.decide", "ppa.readout"):
        ticks = sorted(e[3]["tick"] for e in events if e[0] == name)
        assert ticks == list(range(n_ticks)), name
    # the shards' decide stages: once a tick, around every shard
    for name in ("ppa.decide.evaluate", "ppa.decide.stabilise",
                 "ppa.decide.degrade", "ppa.decide.guard",
                 "ppa.decide.record"):
        assert sum(e[0] == name for e in events) == n_ticks, name
    # the refit's compute ran on a worker, tagged with its tick
    comp, = [e for e in events if e[0] == "ppa.refit.compute"]
    assert comp[3]["tick"] == 1
    up, = [e for e in events if e[0] == "ppa.collect.upload"][:1]
    assert up[3]["bytes"] == Z * M * 4
    back = [e for e in events if e[0] == "ppa.forecast.readback"]
    assert [e[3]["bytes"] for e in back] == [Z * M * 4] * n_ticks


def test_host_plane_reuses_the_forecast_stage_names(tmp_path, spans_on):
    cfg = PPAConfig(threshold=100.0, stabilization_s=60.0)
    plane = ShardedControlPlane(cfg, _targets(), n_shards=S,
                                use_pallas=False)      # one fused dispatch
    n_ticks = 2
    _, events = _traced(tmp_path,
                        lambda: _drive(plane, _rows(W + n_ticks)))
    plane.shutdown()
    # no engine: the fused forecast launches and reads back in the tick's
    # own ppa.forecast
    parent = dict(PARENT, **{"ppa.forecast.dispatch": "ppa.forecast",
                             "ppa.forecast.readback": "ppa.forecast"})
    for name in ("ppa.forecast.install", "ppa.forecast.snapshot",
                 "ppa.forecast.dispatch", "ppa.forecast.readback",
                 "ppa.decide.join", "ppa.decide.epilogue"):
        evs = [e for e in events if e[0] == name]
        assert len(evs) == n_ticks, name
        assert all(_parent(e, events)[0] == parent[name] for e in evs)
    # the columnar shards decide one by one: each stage once per shard
    assert sum(e[0] == "ppa.decide.evaluate" for e in events) \
        == S * n_ticks
    assert not any(e[0] == "ppa.forecast.device" for e in events)
    assert not any(e[0] == "ppa.collect.upload" for e in events)
    stats = plane.tick_stats()
    assert stats["ticks"] == n_ticks
    assert stats["decision_log_ticks"] == n_ticks
    assert stats["h2d_bytes"] == stats["program_builds"] == 0


def test_decisions_and_forecasts_are_bitwise_the_same_traced(tmp_path):
    rows = _rows(W + 5)
    a = _plane()
    off = _drive(a, rows)
    a.shutdown()
    b = _plane()
    obs.enable(True)
    try:
        on, events = _traced(tmp_path, lambda: _drive(b, rows))
    finally:
        obs.enable(False)
    b.shutdown()
    assert events
    for (ra, ma, ca), (rb, mb, cb) in zip(off, on, strict=True):
        np.testing.assert_array_equal(ra, rb)
        np.testing.assert_array_equal(ma, mb)
        np.testing.assert_array_equal(ca, cb)


def test_tick_stats_count_transfers_builds_installs_and_the_log():
    plane = _plane()
    rows = _rows(W + 6)
    _drive(plane, rows[:W + 1])              # fill + one tick: warm
    s0 = plane.tick_stats()
    assert s0["ticks"] == 1
    assert s0["program_builds"] == 2         # the forecast and the shift
    assert s0["build_s"] > 0
    assert s0["weight_installs"] == 1
    assert s0["install_bytes"] > 0
    cur = np.full(Z, 2, np.int64)
    per_tick = Z * M * 4                     # Zp == Z on one device
    prev = s0
    for j in range(W + 1, W + 4):
        plane.observe_batch(15.0 * j, rows[j])
        plane.begin_tick(15.0 * j, 32, cur)
        cur = plane.finish_tick().replicas_array()
        s = plane.tick_stats()
        assert s["ticks"] == prev["ticks"] + 1
        assert s["h2d_bytes"] - prev["h2d_bytes"] == per_tick
        assert s["d2h_bytes"] - prev["d2h_bytes"] == per_tick
        assert s["program_builds"] == s0["program_builds"]
        assert s["weight_installs"] == 1
        assert s["decision_log_ticks"] == prev["decision_log_ticks"] + 1
        # the download's base once, the candidate mask's base once, and
        # per target the final, key and bound (8 B each) and two masks
        assert (s["decision_log_bytes"] - prev["decision_log_bytes"]
                == per_tick + Z + Z * (3 * 8 + 2))
        prev = s
    # a refit outside the plane: the next tick re-uploads the weights
    plane.invalidate_models()
    plane.observe_batch(15.0 * (W + 4), rows[W + 4])
    plane.begin_tick(15.0 * (W + 4), 32, cur)
    plane.finish_tick()
    s = plane.tick_stats()
    assert s["weight_installs"] == 2
    assert s["install_bytes"] == 2 * s0["install_bytes"]
    # a new shape is one more build
    push = plane._engine.programs()[1]
    push(jnp.zeros((Z, W + 1, M), jnp.float32), jnp.ones((Z, M),
                                                        jnp.float32))
    assert plane.tick_stats()["program_builds"] == s0["program_builds"] + 1
    plane.shutdown()
