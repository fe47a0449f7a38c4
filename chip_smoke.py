#!/usr/bin/env python3
"""Smoke test of the control plane's main path on one TPU chip.

It drives ``ShardedControlPlane(..., use_pallas=True, device_mesh=1)`` — the
device-resident plane over the fused Pallas forecast kernels — through the
entry points a user calls, at the paper's forecaster width:

* phase A: Z=16384 per-target ``LSTMForecaster`` models (hidden 50,
  window 1);
* phase B: Z=4096 per-target ``AttnLSTMForecaster`` models (hidden 50,
  window 8).

Each phase fits its models with the batched fit (``lstm_fit_batch_stacked``),
runs 20 control ticks of seeded metric rows through ``observe_batch`` /
``control_step``, runs one background refit through ``maybe_update`` /
``flush_updates`` and 4 more ticks on the refit weights.  It fails unless
every target is a forecast candidate on every tick, the plane counts no
forecast error and no refit failure, the compiled forward holds a
``tpu_custom_call``, the plane's forecasts match the pure-jnp reference
(``kernels/ref.py``) run on the same chip, and every decision that differs
from a ``use_pallas=False`` plane on the same inputs is a rounding tie
(forecasts that agree within tolerance).

``--four-chips`` runs only the mesh path: Z=65536 LSTM(16) planes under
``device_mesh=4`` in both ``coalesce_dispatch`` modes, whose decisions must
equal the ``device_mesh=1`` plane's bitwise.  It needs a host with four chips.

The run uses f32 matmuls throughout (``jax_default_matmul_precision`` =
highest): the kernels are f32 by construction, and the references they are
held to must be too.  Ticks run with ``stabilization_s=0`` and the same
current replica counts on both planes, so each tick's decision is a function
of that tick's forecast alone.

The last line of standard output is one JSON object naming the device.
Without a TPU the script exits non-zero and prints no such line.

Run from the repository root: ``python chip_smoke.py [--four-chips]``.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

# forecast tolerance: the parity suites' plane-level bound (metric units)
RTOL, ATOL = 1e-4, 1e-5
THRESHOLD = 100.0          # ThresholdPolicy: replicas = ceil(cpu / 100)
MAX_R = 64
INTERVAL_S = 15.0          # the paper's control interval
N_TICKS, POST_TICKS = 20, 4
N_SHARDS = 4


def metric_rows(Z: int, T: int, seed: int) -> np.ndarray:
    """(T, Z, M) seeded metric rows: per-target CPU level and diurnal-like
    swing, the other metrics proportional to it, 5% log-normal noise."""
    from repro.core.metrics import N_METRICS
    rng = np.random.default_rng(seed)
    level = rng.uniform(100.0, 800.0, Z)
    amp = rng.uniform(0.1, 0.5, Z)
    phase = rng.uniform(0.0, 2 * np.pi, Z)
    k = np.arange(T)[:, None]
    cpu = level * (1.0 + amp * np.sin(2 * np.pi * k / 96.0 + phase))
    scale = np.array([1.0, 2.0, 0.5, 0.4, 0.2])[:N_METRICS]
    return (cpu[:, :, None] * scale
            * rng.lognormal(0.0, 0.05, (T, Z, N_METRICS)))


class CompileClock:
    """Seconds JAX spent in backend compiles, from its monitoring events."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


class Checks:
    def __init__(self, phase: str):
        self.phase = phase
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = ""):
        print(f"[{self.phase}] check {name}: {'ok' if ok else 'FAIL'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)


class Reference:
    """The kernel-free forecast of every target on the chip: the models'
    weights stacked apart from the plane, the ``kernels/ref.py`` oracle,
    and the residual and inverse transform as the forecasters define
    them.  Re-read the models with ``load`` after a refit."""

    def __init__(self, models):
        import jax
        from repro.kernels import ref
        self.models = models
        m0 = models[0]
        self.fn = jax.jit(ref.attn_lstm_seq_stacked if m0.arch == "attn"
                          else ref.lstm_seq_stacked)
        self.load()

    def load(self):
        from repro.core.forecaster import stack_params, stack_scaler_stats
        stacked = stack_params(self.models)
        self.leaves = [stacked[k] for k in self.models[0].PARAM_LEAVES]
        self.mean, self.std = stack_scaler_stats(self.models)

    def __call__(self, win: np.ndarray) -> np.ndarray:
        from repro.core.forecaster import Z_CLIP
        z = np.clip((win - self.mean[:, None]) / self.std[:, None],
                    -Z_CLIP, Z_CLIP)
        net = np.asarray(self.fn(*self.leaves, z.astype(np.float32)),
                         np.float64)
        if self.models[0].residual:
            net = z[:, -1] + net
        return net * self.std + self.mean


def device_memory() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return " ".join(f"{k}={stats.get(k)}" for k in
                    ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"))


def forward_has_kernel(plane) -> bool:
    return any("tpu_custom_call" in exe.as_text()
               for exe in plane._engine._fwd.executables())


def run_phase(name: str, cls, Z: int, hidden: int, window: int,
              hist_rows: int, seed: int, clock: CompileClock) -> list[str]:
    """One phase of the smoke run; returns the names of failed checks."""
    from repro.core import (PPAConfig, ShardedControlPlane, TargetSpec,
                            ThresholdPolicy, Updater, UpdatePolicy)
    from repro.core.forecaster import lstm_fit_batch_stacked
    check = Checks(name)
    c0 = clock.total
    rows = metric_rows(Z, hist_rows + window + N_TICKS + POST_TICKS, seed)
    t0 = time.perf_counter()
    models = [cls(window=window, hidden=hidden, seed=i, use_pallas=True)
              for i in range(Z)]
    build_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    fit = lstm_fit_batch_stacked(
        models, list(np.swapaxes(rows[:hist_rows], 0, 1)), from_scratch=True)
    losses = np.stack([m.last_losses for m in models])
    fit_s = time.perf_counter() - t0
    check("batched fit", fit is not None and np.isfinite(losses).all(),
          f"final loss median {np.median(losses[:, -1]):.4f}")

    cfg = PPAConfig(threshold=THRESHOLD, stabilization_s=0.0,
                    update_interval_s=N_TICKS * INTERVAL_S)
    specs = [TargetSpec(f"t{i}", ThresholdPolicy(THRESHOLD), model=m)
             for i, m in enumerate(models)]
    t0 = time.perf_counter()
    plane = ShardedControlPlane(
        cfg, specs, updater=Updater(UpdatePolicy.FINETUNE, min_records=16),
        n_shards=N_SHARDS, async_updates=True, use_pallas=True,
        device_mesh=1)
    ref_plane = ShardedControlPlane(cfg, specs, n_shards=N_SHARDS,
                                    use_pallas=False, device_mesh=1)
    plane_s = time.perf_counter() - t0
    reference = Reference(models)

    r = hist_rows
    t = 0.0
    for _ in range(window):          # fill the forecast window
        t += INTERVAL_S
        plane.observe_batch(t, rows[r])
        ref_plane.observe_batch(t, rows[r])
        r += 1

    cur = np.ones(Z, np.int64)
    tick_s, max_ref_diff, max_plane_diff = [], 0.0, 0.0
    n_cand_short = n_off = n_differ = n_untied = 0
    k = cfg.key_metric_idx
    for tick in range(N_TICKS + POST_TICKS):
        t += INTERVAL_S
        if tick == N_TICKS:
            # the comparison plane is done: free its device state first
            ref_plane.shutdown()
            ref_plane = None
            gc.collect()
            # one background refit of every target, installed between ticks
            t0 = time.perf_counter()
            plane.maybe_update(t - INTERVAL_S)
            submitted = plane.refit_inflight
            applied = plane.flush_updates()
            refit_s = time.perf_counter() - t0
            log = plane.refit_log[-1] if plane.refit_log else {}
            check("refit", submitted and applied and bool(log.get("batched"))
                  and not log.get("failed"),
                  f"{len(plane.refit_log)} refit(s), {refit_s:.2f} s")
            reference.load()
        plane.observe_batch(t, rows[r])
        t0 = time.perf_counter()
        res = plane.control_step(t, MAX_R, cur)
        dec = res.replicas_array()
        tick_s.append(time.perf_counter() - t0)
        means, cand = res.forecasts_array()
        n_cand_short += int((~cand).sum())

        want = reference(np.swapaxes(rows[r - window + 1:r + 1], 0, 1))
        n_off += int((~np.isclose(means, want, rtol=RTOL, atol=ATOL)
                      ).any(1).sum())
        max_ref_diff = max(max_ref_diff,
                           float(np.nanmax(np.abs(means - want))))
        if tick < N_TICKS:
            ref_plane.observe_batch(t, rows[r])
            rres = ref_plane.control_step(t, MAX_R, cur)
            rdec = rres.replicas_array()
            rmeans, _ = rres.forecasts_array()
            max_plane_diff = max(max_plane_diff,
                                 float(np.nanmax(np.abs(means - rmeans))))
            differ = dec != rdec
            n_differ += int(differ.sum())
            n_untied += int((~np.isclose(means[differ, k], rmeans[differ, k],
                                         rtol=RTOL, atol=ATOL)).sum())
        cur = np.maximum(dec, 1)
        r += 1

    stats = plane.degraded_stats()
    check("every target a candidate on every tick", n_cand_short == 0,
          f"{n_cand_short} target-ticks short")
    check("no forecast errors", stats["forecast_errors"] == 0,
          f"forecast_errors={stats['forecast_errors']}, "
          f"last={plane.faults.last_error}")
    check("no refit failures", stats["refit_failures"] == 0,
          f"refit_failures={stats['refit_failures']}")
    check("tpu_custom_call in the compiled forward", forward_has_kernel(plane))
    check("forecasts within tolerance of the on-chip jnp reference",
          n_off == 0, f"{n_off} target-ticks off, max abs diff "
          f"{max_ref_diff!r}")
    check("decisions vs use_pallas=False plane: only rounding ties",
          n_untied == 0,
          f"{n_differ} of {Z * N_TICKS} target-ticks differ, "
          f"{n_untied} without agreeing forecasts; max abs forecast diff "
          f"{max_plane_diff!r}")

    steady = np.asarray(tick_s[1:N_TICKS]) * 1e3
    print(f"[{name}] arch={models[0].arch} Z={Z} hidden={hidden} "
          f"window={window}")
    print(f"[{name}] compile_s={clock.total - c0!r} build_s={build_s!r} "
          f"fit_s={fit_s!r} plane_setup_s={plane_s!r} refit_s={refit_s!r}")
    print(f"[{name}] first_tick_ms={tick_s[0] * 1e3!r} steady_tick_ms "
          f"median={float(np.median(steady))!r} "
          f"p90={float(np.percentile(steady, 90))!r} n={steady.size}")
    print(f"[{name}] max_abs_forecast_diff_vs_reference={max_ref_diff!r} "
          f"differing_decisions={n_differ}")
    print(f"[{name}] device memory: {device_memory()}")
    plane.shutdown()
    return check.failed


def run_four_chips(clock: CompileClock, Z: int = 65536) -> list[str]:
    """LSTM(16) planes over Z targets: device_mesh=4 in both dispatch
    modes against device_mesh=1, decisions and forecasts bitwise."""
    import jax
    from repro.core import (PPAConfig, ShardedControlPlane, TargetSpec,
                            ThresholdPolicy)
    from repro.core.forecaster import (ARCH_INITS, BatchFitResult,
                                       LSTMForecaster, Scaler)
    from repro.core.metrics import N_METRICS
    check = Checks("mesh")
    hidden, hist_rows = 16, 32
    rows = metric_rows(Z, hist_rows + 1 + N_TICKS, 7)
    # seeded random weights installed through the batched fit's result
    # path (host arrays, so the planes' weight stacking stays on the host)
    keys = jax.random.split(jax.random.PRNGKey(7), Z)
    stacked = jax.tree.map(np.asarray, jax.vmap(
        lambda key: ARCH_INITS["lstm"](key, N_METRICS, hidden, N_METRICS)
    )(keys))
    base = LSTMForecaster(window=1, hidden=hidden, use_pallas=True)
    models = [copy.copy(base) for _ in range(Z)]
    scalers = []
    for i in range(Z):
        sc = Scaler()
        sc.fit(rows[:hist_rows, i])
        scalers.append(sc)
    fit = BatchFitResult()
    fit.add(models, scalers, stacked, np.zeros((Z, 1)))
    fit.apply()
    cfg = PPAConfig(threshold=THRESHOLD, stabilization_s=0.0)

    def drive(D: int, coalesce: bool):
        specs = [TargetSpec(f"t{i}", ThresholdPolicy(THRESHOLD), model=m)
                 for i, m in enumerate(models)]
        plane = ShardedControlPlane(cfg, specs, n_shards=N_SHARDS,
                                    coalesce_dispatch=coalesce,
                                    use_pallas=True, device_mesh=D)
        t = INTERVAL_S
        plane.observe_batch(t, rows[hist_rows])
        cur = np.ones(Z, np.int64)
        decs, fcs, tick_s = [], [], []
        for j in range(N_TICKS):
            t += INTERVAL_S
            plane.observe_batch(t, rows[hist_rows + 1 + j])
            t0 = time.perf_counter()
            res = plane.control_step(t, MAX_R, cur)
            dec = res.replicas_array()
            tick_s.append(time.perf_counter() - t0)
            decs.append(dec)
            fcs.append(res.forecasts_array())
            cur = np.maximum(dec, 1)
        kernel = forward_has_kernel(plane)
        stats = plane.degraded_stats()
        plane.shutdown()
        steady = np.asarray(tick_s[1:]) * 1e3
        print(f"[mesh] device_mesh={D} coalesce_dispatch={coalesce} "
              f"steady_tick_ms median={float(np.median(steady))!r} "
              f"n={steady.size} tpu_custom_call={kernel} "
              f"forecast_errors={stats['forecast_errors']}")
        return decs, fcs, kernel, stats["forecast_errors"]

    c0 = clock.total
    base_run = drive(1, True)
    for coalesce in (True, False):
        decs, fcs, kernel, errs = drive(4, coalesce)
        same_dec = all(np.array_equal(a, b)
                       for a, b in zip(decs, base_run[0]))
        same_fc = all(np.array_equal(a[0], b[0], equal_nan=True)
                      and np.array_equal(a[1], b[1])
                      for a, b in zip(fcs, base_run[1]))
        check(f"device_mesh=4 coalesce_dispatch={coalesce} bitwise equal "
              "to device_mesh=1", same_dec and same_fc,
              f"decisions equal={same_dec}, forecasts equal={same_fc}")
        check(f"device_mesh=4 coalesce_dispatch={coalesce} healthy",
              kernel and errs == 0 and all(f[1].all() for f in fcs),
              f"tpu_custom_call={kernel}, forecast_errors={errs}")
    check("device_mesh=1 healthy", base_run[2] and base_run[3] == 0)
    print(f"[mesh] Z={Z} hidden={hidden} compile_s={clock.total - c0!r}")
    return check.failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the device_mesh=4 comparison")
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    jax.config.update("jax_default_matmul_precision", "highest")
    from repro.core.forecaster import AttnLSTMForecaster, LSTMForecaster
    print(f"device_kind={dev.device_kind!r} devices={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        failed = run_four_chips(clock)
    else:
        failed = run_phase("A", LSTMForecaster, Z=16384, hidden=50,
                           window=1, hist_rows=48, seed=1, clock=clock)
        gc.collect()
        print(f"between phases, device memory: {device_memory()}")
        # 28 history rows: 20 training windows of 8 steps, as many as the
        # refit sees (8 + 20 rows)
        failed += run_phase("B", AttnLSTMForecaster, Z=4096, hidden=50,
                            window=8, hist_rows=28, seed=2, clock=clock)
    print(f"wall_s={time.perf_counter() - t0!r}")
    if failed:
        print(f"chip_smoke: FAILED checks: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
