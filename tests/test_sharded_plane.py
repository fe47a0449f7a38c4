"""Sharded async control plane (DESIGN.md §5, "Sharded async"):

* seeded decision equivalence — ``ShardedControlPlane`` (any shard count,
  async ticks on or off, vectorised and fallback shards) produces identical
  decisions to the single ``FleetController`` on multi-zone traces;
* double-buffer semantics — observations arriving between ``begin_tick``
  and ``finish_tick`` belong to the next window and cannot change the
  in-flight tick's decisions;
* vmapped batch refits — ``lstm_fit_batch_stacked`` / ``update_batch``
  match Z sequential ``fit`` / ``update`` calls, and the plane's async
  refit never blocks the tick loop;
* satellites — per-target ``model_path`` templates, the ensemble's
  member-stacked single dispatch, the exporter's overlap-safe read API,
  and MultiFleetSim routing through the sharded plane.
"""
import copy

import numpy as np
import pytest

from repro.core import (FleetController, LSTMForecaster, MetricsHistory,
                        PPAConfig, ShardedControlPlane, Snapshot, TargetSpec,
                        ThresholdPolicy, TargetUtilizationPolicy, Updater,
                        UpdatePolicy)
from repro.core.control_plane import shard_assignment, stage_collect
from repro.core.faults import ProgramFault, Staged
from repro.core.forecaster import EnsembleForecaster, lstm_fit_batch_stacked

from benchmarks.bench_control_plane import _traces

Z = 4
CFG = PPAConfig(threshold=100.0, stabilization_s=60.0)


@pytest.fixture(scope="module")
def base():
    """Fitted per-target LSTMs + traces, deep-copied per test config so
    every controller sees identically initialised models."""
    traces = _traces(Z)
    models = {}
    for z in traces:
        m = LSTMForecaster(window=4, epochs=12, finetune_epochs=6, seed=0)
        m.fit(traces[z][:120], from_scratch=True)
        models[z] = m
    return traces, models


def _specs(models):
    return [TargetSpec(z, ThresholdPolicy(100.0, 1),
                       model=copy.deepcopy(models[z])) for z in models]


def _drive(traces, ref, plane, k0=120, k1=150, check=True):
    cur = {z: 2 for z in traces}
    for k in range(k0, k1):
        t = 15.0 * (k - k0 + 1)
        for z in traces:
            snap = Snapshot(t, traces[z][k])
            ref.observe(z, snap)
            plane.observe(z, snap)
        a = ref.control_step(t, 16, dict(cur))
        b = plane.control_step(t, 16, dict(cur))
        if check:
            for z in traces:
                assert a[z].replicas == b[z].replicas, (t, z)
                assert a[z].predicted == b[z].predicted, (t, z)
                assert a[z].confidence_ok == b[z].confidence_ok, (t, z)
                if a[z].raw_prediction is None:
                    assert b[z].raw_prediction is None
                else:
                    np.testing.assert_allclose(
                        a[z].raw_prediction, b[z].raw_prediction,
                        rtol=1e-5, atol=1e-6)
        for z in traces:
            cur[z] = max(a[z].replicas, 1)
        ref.maybe_update(t)
        plane.maybe_update(t)
    return cur


# ------------------------------------------------ decision equivalence ----
@pytest.mark.parametrize("n_shards", [1, 2, 3])
@pytest.mark.parametrize("async_ticks,coalesce", [
    (False, True),    # sync, fused gang dispatch (the default fast path)
    (True, True),     # async double-buffered, fused
    (False, False),   # per-shard (Z/S, W, M) dispatches (multi-device shape)
    (True, False),    # per-shard dispatches on the worker pool
])
def test_sharded_equals_single_per_target(base, n_shards, async_ticks,
                                          coalesce):
    """Per-target stacked mode: any S, async on/off, fused or per-shard
    dispatch — decisions identical."""
    traces, models = base
    ref = FleetController(CFG, _specs(models))
    plane = ShardedControlPlane(CFG, _specs(models), n_shards=n_shards,
                                async_ticks=async_ticks,
                                coalesce_dispatch=coalesce)
    _drive(traces, ref, plane)
    for z in traces:
        dref, dpl = ref.decisions(z), plane.decisions(z)
        assert len(dref) == len(dpl)
        assert [d.replicas for d in dref] == [d.replicas for d in dpl]
        assert len(ref.predictions(z)) == len(plane.predictions(z))
    plane.shutdown()


@pytest.mark.parametrize("coalesce", [True, False])
def test_sharded_use_pallas_equals_single(base, coalesce):
    """Plane-level ``use_pallas=True`` routes the stacked forecast
    dispatches (fused gang and per-shard alike) through the fused Pallas
    sequence kernel (interpret mode on CPU) — decisions identical to the
    XLA path's FleetController."""
    traces, models = base
    ref = FleetController(CFG, _specs(models))
    plane = ShardedControlPlane(CFG, _specs(models), n_shards=2,
                                coalesce_dispatch=coalesce,
                                use_pallas=True)
    _drive(traces, ref, plane, check=False)
    for z in traces:
        dref, dpl = ref.decisions(z), plane.decisions(z)
        assert [d.replicas for d in dref] == [d.replicas for d in dpl]
        assert [d.predicted for d in dref] == [d.predicted for d in dpl]
        pr, pp = ref.predictions(z), plane.predictions(z)
        assert len(pr) == len(pp)
        for (ta, a), (tb, b) in zip(pr, pp):
            assert ta == tb
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    plane.shutdown()


def test_sharded_equals_single_shared_model(base):
    """Shared-model mode: one forecaster answering all targets per shard."""
    traces, _ = base
    model = LSTMForecaster(window=4, epochs=12, seed=0)
    model.fit(np.concatenate([traces[z][:100] for z in traces]),
              from_scratch=True)
    mk = lambda: copy.deepcopy(model)  # noqa: E731
    ref = FleetController(
        CFG, [TargetSpec(z, ThresholdPolicy(100.0, 1)) for z in traces],
        model=mk())
    plane = ShardedControlPlane(
        CFG, [TargetSpec(z, ThresholdPolicy(100.0, 1)) for z in traces],
        model=mk(), n_shards=2, async_ticks=True)
    _drive(traces, ref, plane)
    plane.shutdown()


def test_heterogeneous_policies_ride_columnar_and_match(base):
    """Mixed built-in policy types (Threshold + TargetUtilization) stay on
    the columnar shard via the per-policy dispatch table — and still match
    the scalar reference elementwise."""
    traces, models = base
    def specs():
        out = []
        for i, z in enumerate(models):
            pol = (TargetUtilizationPolicy(0.7, 1) if i == 0
                   else ThresholdPolicy(100.0, 1))
            out.append(TargetSpec(z, pol, model=copy.deepcopy(models[z])))
        return out
    ref = FleetController(CFG, specs())
    plane = ShardedControlPlane(CFG, specs(), n_shards=1)
    assert plane.shards[0].vectorized          # no _CtrlShard fallback
    assert len(plane.shards[0]._pol_groups) == 2
    _drive(traces, ref, plane)


class _OpaquePolicy:
    """A custom policy callable WITHOUT the stack/evaluate_batch protocol
    — the only policy shape left that forces the _CtrlShard fallback."""

    def __init__(self, threshold):
        self._inner = ThresholdPolicy(threshold, 1)

    def __call__(self, key_metric, state=None):
        return self._inner(key_metric, state)


def test_custom_policy_falls_back_and_matches(base):
    """A shard whose targets the columnar path can't take (an opaque
    custom callable) transparently falls back to an embedded
    FleetController — and still matches the reference."""
    traces, models = base
    def specs():
        out = []
        for i, z in enumerate(models):
            pol = (_OpaquePolicy(100.0) if i == 0
                   else ThresholdPolicy(100.0, 1))
            out.append(TargetSpec(z, pol, model=copy.deepcopy(models[z])))
        return out
    ref = FleetController(CFG, specs())
    plane = ShardedControlPlane(CFG, specs(), n_shards=1)
    assert not plane.shards[0].vectorized
    _drive(traces, ref, plane)


@pytest.mark.parametrize("opaque", [False, True])
def test_forecasts_array_matches_per_target_results(base, opaque):
    """``TickResult.forecasts_array`` is the columnar twin of every
    target's ``raw_prediction``, on columnar and fallback shards."""
    traces, models = base
    specs = [TargetSpec(z, (_OpaquePolicy(100.0) if opaque and i == 0
                            else ThresholdPolicy(100.0, 1)),
                        model=copy.deepcopy(models[z]))
             for i, z in enumerate(models)]
    plane = ShardedControlPlane(CFG, specs, n_shards=2)
    assert opaque != all(s.vectorized for s in plane.shards)
    for k in range(120, 128):
        t = 15.0 * (k - 119)
        for z in traces:
            plane.observe(z, Snapshot(t, traces[z][k]))
        res = plane.control_step(t, 16, 2)
        means, cand = res.forecasts_array()
        for i, n in enumerate(plane.target_names):
            raw = res[n].raw_prediction
            assert cand[i] == (raw is not None)
            if raw is None:
                assert np.isnan(means[i]).all()
            else:
                np.testing.assert_array_equal(means[i], raw)
    assert cand.all()
    plane.shutdown()


def test_async_tick_double_buffer_semantics(base):
    """Observations landing between begin_tick and finish_tick are next
    window's data: the in-flight tick decides on the snapshot."""
    traces, models = base
    ref = FleetController(CFG, _specs(models))
    plane = ShardedControlPlane(CFG, _specs(models), n_shards=2,
                                async_ticks=True)
    for k in range(120, 130):
        t = 15.0 * (k - 119)
        for z in traces:
            snap = Snapshot(t, traces[z][k])
            ref.observe(z, snap)
            plane.observe(z, snap)
    a = ref.control_step(150.0, 16, 2)
    plane.begin_tick(150.0, 16, 2)
    for z in traces:   # window-(t+1) metrics arrive while forecasting
        plane.observe(z, Snapshot(165.0, traces[z][135] * 7.0))
    b = plane.finish_tick()
    for z in traces:
        assert a[z].replicas == b[z].replicas
        np.testing.assert_allclose(a[z].raw_prediction,
                                   b[z].raw_prediction, rtol=1e-5)
    plane.shutdown()


def test_shard_assignment_deterministic_and_explicit():
    names = [f"z{i}" for i in range(12)]
    a1 = shard_assignment(names, 4)
    a2 = shard_assignment(names, 4)
    assert a1 == a2                       # crc32, not per-process hash()
    assert set(a1.values()) <= set(range(4))
    explicit = shard_assignment(names, 4, {"z0": 3, "z1": 3})
    assert explicit["z0"] == 3 and explicit["z1"] == 3
    with pytest.raises(ValueError):
        shard_assignment(names, 2, {"z0": 5})


# ------------------------------------------------- vmapped batch refits ---
def test_batch_refit_matches_sequential(base):
    """update_batch (one vmapped dispatch) == Z sequential update calls,
    for both FINETUNE and SCRATCH policies."""
    traces, models = base
    for policy in (UpdatePolicy.FINETUNE, UpdatePolicy.SCRATCH):
        seq = {z: copy.deepcopy(models[z]) for z in traces}
        bat = {z: copy.deepcopy(models[z]) for z in traces}
        hs = {z: MetricsHistory() for z in traces}
        hb = {z: MetricsHistory() for z in traces}
        for z in traces:
            for k in range(120, 150):
                hs[z].append(Snapshot(15.0 * k, traces[z][k]))
                hb[z].append(Snapshot(15.0 * k, traces[z][k]))
        us, ub = Updater(policy), Updater(policy)
        for z in traces:
            seq[z] = us.update(seq[z], hs[z], 1.0, target=z)
        ub.update_batch([bat[z] for z in traces],
                        [hb[z] for z in traces], 1.0, targets=list(traces))
        assert us.n_updates == ub.n_updates == Z
        for z in traces:
            assert len(hb[z]) == 0
            ps, _ = seq[z].predict(traces[z][150:160])
            pb, _ = bat[z].predict(traces[z][150:160])
            np.testing.assert_allclose(ps, pb, rtol=1e-5, atol=1e-6)


def test_batch_refit_ragged_pad_and_mask(base):
    """Unequal history lengths stay on the vmapped path (pad-and-mask):
    the batched refit matches Z sequential fits on the ragged histories."""
    traces, models = base
    seq = {z: copy.deepcopy(models[z]) for z in traces}
    bat = [copy.deepcopy(models[z]) for z in traces]
    hists = [MetricsHistory() for _ in bat]
    for i, z in enumerate(traces):
        for k in range(120, 140 + 4 * i):   # ragged lengths
            hists[i].append(Snapshot(15.0 * k, traces[z][k]))
    res = lstm_fit_batch_stacked(bat, [h.series() for h in hists])
    assert res is not None                  # no sequential fallback
    for i, z in enumerate(traces):
        seq[z].fit(hists[i].series())
        ps, _ = seq[z].predict(traces[z][150:160])
        pb, _ = bat[i].predict(traces[z][150:160])
        np.testing.assert_allclose(ps, pb, rtol=1e-5, atol=1e-6)
    u = Updater(UpdatePolicy.FINETUNE)
    u.update_batch(bat, hists, 1.0)
    assert u.n_updates == Z
    assert all(len(h) == 0 for h in hists)


@pytest.mark.parametrize("ragged", [False, True])
def test_batch_fit_splits_to_fit_device_memory(monkeypatch, ragged):
    """Where the device reports less free memory than one dispatch of the
    stacked fit needs, the targets are fitted in equal chunks — with the
    same result as one dispatch (targets are independent rows)."""
    import repro.core.forecaster as fc

    n = 300                                   # above the 256-target probe
    rng = np.random.default_rng(5)
    serieses = [np.abs(rng.normal(200, 40, (20 + (i % 3 if ragged else 0),
                                            5))) for i in range(n)]

    def fit(free):
        monkeypatch.setattr(fc, "_device_bytes_free", lambda: free)
        models = [LSTMForecaster(window=2, hidden=4, epochs=3, seed=i)
                  for i in range(n)]
        assert lstm_fit_batch_stacked(models, serieses, from_scratch=True)
        return models

    calls = []
    real = fc._lstm_fit_stacked_masked if ragged else fc._lstm_fit_stacked

    class Counted:                            # records each dispatch's size
        lower = real.lower

        def __call__(self, *a):
            calls.append(len(a[2]))
            return real(*a)
    monkeypatch.setattr(fc, real.__name__, Counted())
    whole = fit(None)
    assert calls == [n]
    calls.clear()
    split = fit(2**20)
    assert len(calls) > 1 and sum(calls) == n and len(set(calls[:-1])) == 1
    for a, b in zip(whole, split):
        np.testing.assert_allclose(a.last_losses, b.last_losses,
                                   rtol=1e-6, atol=1e-7)
        for k in a.params:
            np.testing.assert_allclose(a.params[k], b.params[k],
                                       rtol=1e-6, atol=1e-7)


def test_batch_refit_heterogeneous_archs_fall_back(base):
    """Architecturally heterogeneous model sets still can't stack ->
    sequential fallback with identical bookkeeping."""
    traces, models = base
    ms = [copy.deepcopy(models[z]) for z in traces]
    ms[0] = LSTMForecaster(window=4, hidden=13, epochs=12, seed=0)  # odd one
    hists = [MetricsHistory() for _ in ms]
    for i, z in enumerate(traces):
        for k in range(120, 140):
            hists[i].append(Snapshot(15.0 * k, traces[z][k]))
    assert lstm_fit_batch_stacked(ms, [h.series() for h in hists]) is None
    u = Updater(UpdatePolicy.FINETUNE)
    u.update_batch(ms, hists, 1.0)
    assert u.n_updates == Z
    assert all(len(h) == 0 for h in hists)


def test_plane_async_refit_off_critical_path(base):
    """The plane's maybe_update snapshots + submits the batch refit and
    returns without fitting; ticks keep running; poll/flush installs it."""
    traces, models = base
    cfg = PPAConfig(threshold=100.0, stabilization_s=60.0,
                    update_interval_s=120.0)
    plane = ShardedControlPlane(cfg, _specs(models), n_shards=2,
                                updater=Updater(UpdatePolicy.FINETUNE),
                                async_ticks=True)
    gen0 = [m._fit_count for m in plane._shard_of["z0"].target_models()]
    cur = 2
    for k in range(120, 145):
        t = 15.0 * (k - 119)
        for z in traces:
            plane.observe(z, Snapshot(t, traces[z][k]))
        res = plane.control_step(t, 16, cur)
        cur = max(res["z0"].replicas, 1)
        plane.maybe_update(t)
    assert plane.flush_updates() or plane.refit_log   # refit happened
    assert any(e["async"] and e["batched"] for e in plane.refit_log)
    gen1 = [m._fit_count for m in plane._shard_of["z0"].target_models()]
    assert all(g1 > g0 for g0, g1 in zip(gen0, gen1))
    # and the restacked params serve the next tick
    for z in traces:
        plane.observe(z, Snapshot(1e4, traces[z][150]))
    res = plane.control_step(1e4, 16, cur)
    assert any(res[z].predicted for z in traces)
    plane.shutdown()


def test_failed_async_refit_does_not_wedge_the_plane(base):
    """A refit whose compute raises on the worker is dropped: the plane
    keeps ticking and can refit again later (no sticky re-raise)."""
    traces, models = base
    cfg = PPAConfig(threshold=100.0, stabilization_s=60.0,
                    update_interval_s=120.0)
    plane = ShardedControlPlane(cfg, _specs(models), n_shards=2,
                                updater=Updater(UpdatePolicy.FINETUNE),
                                async_ticks=True)

    class _Boom:
        t = 0.0
        batched = False
        def compute(self):
            raise RuntimeError("corrupt history")
    plane._refit = (0.0, plane._pool.submit(_Boom().compute), _Boom())
    for k in range(120, 140):            # 20 rows: enough for min_records
        t = 15.0 * (k - 119)
        for z in traces:
            plane.observe(z, Snapshot(t, traces[z][k]))
        plane.control_step(t, 16, 2)     # must not raise, ever
    assert plane._refit is None
    assert any(e.get("failed") for e in plane.refit_log)
    # and a later healthy refit still goes through
    plane.maybe_update(1e4)
    assert plane.flush_updates()
    assert any(e.get("batched") for e in plane.refit_log)
    plane.shutdown()


def _raise_device_lost(*_):
    raise RuntimeError("device lost")


@pytest.mark.parametrize("path", ["fused", "per_shard", "device"])
def test_runtime_forecast_failure_is_reactive_and_counted(base, path):
    """A forecast dispatch that fails while it runs leaves every target on
    the reactive path and is counted in degraded_stats(), on the fused and
    per-shard dispatches of a shared model and on the device engine."""
    traces, models = base
    if path == "device":
        plane = ShardedControlPlane(CFG, _specs(models), n_shards=2,
                                    device_mesh=1)
        plane._engine._fwd = _raise_device_lost
    else:
        shared = copy.deepcopy(next(iter(models.values())))
        shared.predict_batch = _raise_device_lost
        plane = ShardedControlPlane(
            CFG, [TargetSpec(z, ThresholdPolicy(100.0, 1)) for z in traces],
            model=shared, n_shards=2, coalesce_dispatch=(path == "fused"))
    window = next(iter(models.values())).window
    n_ticks = 12
    for k in range(120, 120 + n_ticks):
        t = 15.0 * (k - 119)
        for z in traces:
            plane.observe(z, Snapshot(t, traces[z][k]))
        res = plane.control_step(t, 16, 2)
        assert not any(res[z].predicted for z in traces)
    # one failed dispatch per tick with candidates (per shard off the gang)
    per_tick = len(plane.shards) if path == "per_shard" else 1
    stats = plane.degraded_stats()
    assert stats["forecast_errors"] == per_tick * (n_ticks - window)
    assert stats["refit_failures"] == 0
    assert plane.faults.last_error == "RuntimeError: device lost"
    plane.shutdown()


def test_program_fault_propagates_instead_of_going_reactive(base):
    """A forecast program that cannot be built is a fault in the program:
    the tick raises it and nothing is counted as a reactive fallback."""
    traces, models = base
    shared = copy.deepcopy(next(iter(models.values())))

    def unbuildable(recents):
        raise ProgramFault("NotImplementedError while building forward")
    shared.predict_batch = unbuildable
    plane = ShardedControlPlane(
        CFG, [TargetSpec(z, ThresholdPolicy(100.0, 1)) for z in traces],
        model=shared, n_shards=2)
    with pytest.raises(ProgramFault):
        for k in range(120, 130):
            t = 15.0 * (k - 119)
            for z in traces:
                plane.observe(z, Snapshot(t, traces[z][k]))
            plane.control_step(t, 16, 2)
    assert plane.degraded_stats()["forecast_errors"] == 0
    plane.shutdown()


def test_staged_build_failure_is_a_program_fault():
    """``Staged`` builds once per argument signature and reports a failed
    trace/lower/compile as ProgramFault (the cause chained)."""
    import jax
    import jax.numpy as jnp

    bad = Staged(jax.jit(lambda x: x @ jnp.ones((3, 3))))
    with pytest.raises(ProgramFault) as info:
        bad(np.ones((2, 2), np.float32))
    assert isinstance(info.value.__cause__, TypeError)
    good = Staged(jax.jit(lambda x: 2 * x))
    x = np.arange(3, dtype=np.float32)
    np.testing.assert_array_equal(good(x), 2 * x)
    np.testing.assert_array_equal(good(x + 1), 2 * (x + 1))
    assert len(good.executables()) == 1
    good(np.ones(4, np.float32))
    assert len(good.executables()) == 2


def test_failed_refit_is_counted(base):
    """A refit whose compute raises on the worker is dropped and counted
    in degraded_stats(), with the failure's text kept."""
    traces, models = base
    plane = ShardedControlPlane(CFG, _specs(models), n_shards=2,
                                updater=Updater(UpdatePolicy.FINETUNE),
                                async_ticks=True)

    class _Boom:
        t = 0.0
        batched = False
        def compute(self):
            raise RuntimeError("corrupt history")
    plane._refit = (0.0, plane._pool.submit(_Boom().compute), _Boom())
    assert plane.flush_updates() is False
    stats = plane.degraded_stats()
    assert stats["refit_failures"] == 1
    assert stats["forecast_errors"] == 0
    assert plane.faults.last_error == "RuntimeError: corrupt history"
    plane.shutdown()


def test_ctrl_shard_double_buffer_candidacy(base):
    """Fallback-shard async ticks judge forecast candidacy on the
    begin_tick snapshot: a target one row short at snapshot time stays
    reactive even if observations land mid-flight."""
    traces, models = base
    def specs():
        out = []
        for i, z in enumerate(models):
            pol = (_OpaquePolicy(100.0) if i == 0
                   else ThresholdPolicy(100.0, 1))
            out.append(TargetSpec(z, pol, model=copy.deepcopy(models[z])))
        return out
    plane = ShardedControlPlane(CFG, specs(), n_shards=1, async_ticks=True)
    assert not plane.shards[0].vectorized
    names = list(traces)
    window = models[names[0]].window
    # observe exactly `window` rows: one short of predictability
    for k in range(window):
        for z in names:
            plane.observe(z, Snapshot(15.0 * (k + 1), traces[z][120 + k]))
    plane.begin_tick(15.0 * (window + 1), 16, 2)
    for z in names:   # the row that would make targets predictable
        plane.observe(z, Snapshot(15.0 * (window + 1),
                                  traces[z][120 + window]))
    res = plane.finish_tick()
    assert all(not res[z].predicted for z in names)   # snapshot ruled
    # next tick (snapshot now has window+1 rows) does predict
    res2 = plane.control_step(15.0 * (window + 2), 16, 2)
    assert all(res2[z].predicted for z in names)
    plane.shutdown()


def test_maybe_update_deferred_while_tick_in_flight(base):
    """maybe_update between begin_tick and finish_tick must not mutate
    models under a live forecast — it defers to the next between-ticks
    call without consuming the update timer."""
    traces, models = base
    cfg = PPAConfig(threshold=100.0, stabilization_s=60.0,
                    update_interval_s=60.0)
    plane = ShardedControlPlane(cfg, _specs(models), n_shards=2,
                                updater=Updater(UpdatePolicy.FINETUNE),
                                async_ticks=True)
    for k in range(120, 140):
        t = 15.0 * (k - 119)
        for z in traces:
            plane.observe(z, Snapshot(t, traces[z][k]))
    plane.begin_tick(400.0, 16, 2)
    plane.maybe_update(400.0)            # mid-tick: must defer entirely
    assert not plane.refit_inflight and not plane.refit_log
    plane.finish_tick()
    plane.maybe_update(400.0)            # between ticks: goes through now
    assert plane.refit_inflight or plane.refit_log
    plane.flush_updates()
    plane.shutdown()


# ------------------------------------------------------------ satellites --
def test_updater_per_target_path_template(base, tmp_path):
    """A '{target}' template lifts the shared-model_path restriction: Z
    targets persist to Z files (and a literal shared path still raises)."""
    traces, models = base
    tmpl = str(tmp_path / "{target}.pkl")
    with pytest.raises(ValueError):
        FleetController(CFG, _specs(models),
                        updater=Updater(UpdatePolicy.FINETUNE,
                                        model_path=str(tmp_path / "one.pkl")))
    with pytest.raises(ValueError):
        ShardedControlPlane(CFG, _specs(models),
                            updater=Updater(UpdatePolicy.FINETUNE,
                                            model_path=str(tmp_path / "x")))
    ctrl = FleetController(CFG, _specs(models),
                           updater=Updater(UpdatePolicy.FINETUNE,
                                           model_path=tmpl))
    for z in traces:
        for k in range(120, 150):
            ctrl.observe(z, Snapshot(15.0 * k, traces[z][k]))
    ctrl.maybe_update(1e6)
    for z in traces:
        assert (tmp_path / f"{z}.pkl").exists()
        loaded = LSTMForecaster(window=4).load(tmp_path / f"{z}.pkl")
        want, _ = ctrl.model_for(z).predict(traces[z][150:160])
        got, _ = loaded.predict(traces[z][150:160])
        np.testing.assert_allclose(got, want, rtol=1e-6)
    # a template without a target name must fail loudly, not save to a
    # literal 'None' file
    with pytest.raises(ValueError):
        Updater(UpdatePolicy.FINETUNE, model_path=tmpl).path_for(None)


def test_ensemble_stacked_matches_member_loop(base):
    """EnsembleForecaster.predict_batch: E members x Z targets in one
    dispatch == the per-member loop."""
    traces, _ = base
    ens = EnsembleForecaster(n_members=3, window=4, epochs=8)
    ens.fit(traces["z0"][:100], from_scratch=True)
    recents = [traces[z][100:110] for z in traces]
    mean_one, std_one = ens.predict_batch(recents)
    member_means = np.stack([m.predict_batch(recents)[0]
                             for m in ens.members])
    np.testing.assert_allclose(mean_one, member_means.mean(0),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(std_one, member_means.std(0),
                               rtol=1e-4, atol=1e-6)
    # scalar path agrees too
    m0, s0 = ens.predict(recents[0])
    np.testing.assert_allclose(m0, mean_one[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s0, std_one[0], rtol=1e-3, atol=1e-5)
    # pickle/deepcopy round-trip rebuilds members (no __init__ is run)
    import pickle
    for clone in (copy.deepcopy(ens), pickle.loads(pickle.dumps(ens))):
        mc, sc = clone.predict_batch(recents)
        np.testing.assert_allclose(mc, mean_one, rtol=1e-6)
        np.testing.assert_allclose(sc, std_one, rtol=1e-5, atol=1e-8)


def test_exporter_read_api_and_stage_collect(base):
    """WindowedExporter.latest / read_new are pure cursor reads; the
    collect stage feeds them into a controller without double-delivery."""
    from repro.sim.core import WindowedExporter
    traces, models = base
    exp = WindowedExporter(window_s=15.0, ma_windows=1)
    assert exp.latest("z0") is None
    assert exp.read_new("z0") == ([], 0)
    ctrl = FleetController(CFG, _specs(models))
    cursors = None
    seen = {z: 0 for z in traces}
    for k in range(120, 130):
        t = 15.0 * (k - 119)
        for z in traces:
            exp.push(z, t, traces[z][k])
        cursors = stage_collect(ctrl, exp, cursors=cursors)
        for z in traces:
            seen[z] += 1
            assert len(ctrl.targets[z].history) == seen[z]  # no replays
        tt, row = exp.latest("z0")
        assert tt == t
        np.testing.assert_allclose(row, traces["z0"][k])
    # an independent reader has its own cursor and sees everything
    rows, cur = exp.read_new("z0", 0)
    assert len(rows) == 10 and cur == 10


def test_multi_fleet_routes_through_sharded_plane():
    """MultiFleetSim with a ShardedControlPlane reproduces the
    FleetController allocation sequence exactly."""
    from repro.core import ARIMAD1Forecaster
    from repro.serving.fleet import FleetConfig
    from repro.serving.multi_fleet import FleetSpec, MultiFleetSim
    from repro.workloads import poisson_arrivals

    def build(ctrl_cls, **kw):
        specs = [FleetSpec(f"fleet-{i}",
                           FleetConfig(total_chips=96, chips_per_replica=16,
                                       seed=i)) for i in range(3)]
        ctrl = ctrl_cls(
            PPAConfig(threshold=560.0, stabilization_s=60.0),
            [TargetSpec(s.name, ThresholdPolicy(560.0, 1)) for s in specs],
            model=ARIMAD1Forecaster(), **kw)
        return MultiFleetSim(specs, 96, ctrl)

    rng = np.random.default_rng(0)
    requests = {}
    for i in range(3):
        arr = poisson_arrivals(2.0, 600.0, 15.0, seed=10 + i)
        ntok = rng.integers(16, 64, len(arr.times))
        requests[f"fleet-{i}"] = [(float(t), int(n))
                                  for t, n in zip(arr.times, ntok)]
    ref = build(FleetController).run(dict(requests), 600.0)
    shard = build(ShardedControlPlane, n_shards=2,
                  async_ticks=True).run(dict(requests), 600.0)
    assert ref.alloc_log == shard.alloc_log
    assert ref.peak_chips() == shard.peak_chips()
    np.testing.assert_allclose(np.sort(ref.response_times()),
                               np.sort(shard.response_times()))
