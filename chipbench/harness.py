"""One run of one cell: build the control plane from the seed, warm it up,
drive ticks back to back for the window, check what the window produced
against the plain reference, and report the cell's metrics.

A tick is what a user of the plane waits for: from the moment its metric
batch is ready to the moment its decisions array is on the host.  It
drives the plane's public calls in order -- ``observe_batch`` (collect),
``begin_tick`` (forecast dispatch; the plane runs synchronously, so this
includes the device forward and the download), ``finish_tick`` and
``replicas_array`` (decide) -- each inside a host span named after its
layer.  Decisions are fed back as the next tick's current replicas.
Traced and untraced runs drive the same path; a traced run profiles the
whole window.
"""
from __future__ import annotations

import gc
import glob
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from chipbench import reference, traffic, weights
from chipbench import tracing
from chipbench.layout import Layout

WARMUP_TICKS = 4        # ticks before the window: compile, weight upload
CHECK_TICKS = 6         # window ticks whose forecasts meet the reference
CHECK_BLOCK = 64        # targets per block of the reference forward


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class BadCell(ValueError):
    """A cell whose configuration does not run on the chips it asks for."""


def find_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX found platform "
                     f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} TPU chips, found {len(devices)}")
    return devices


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import os

    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Backend compiles (count and seconds) seen by this process."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


class Run:
    """State of one run, and the context the metric readers get."""

    def __init__(self, layout: Layout, cell: dict, seed: int):
        self.layout = layout
        self.cell = cell
        self.config = layout.config(cell["config"])
        self.mix = layout.traffic(cell["traffic"])
        self.arch = layout.model(self.config["arch"])
        self.seed = int(seed)
        self.Z = int(self.mix["targets"])
        # the plane's mesh is the first ``device_mesh`` devices; the cell's
        # device metrics count its ``chips``: the two have to be one number
        self.n_chips = int(cell["chips"])
        if int(self.config["device_mesh"]) != self.n_chips:
            raise BadCell(
                f"cell {cell['name']!r} asks for {self.n_chips} chip(s) but "
                f"its config {cell['config']!r} runs on device_mesh="
                f"{self.config['device_mesh']}")
        self.tick_s: list[float] = []
        self.window_s = float("nan")
        self.setup_s = float("nan")
        self.trace: tracing.Summary | None = None
        self.peaks: dict = {}

    # -- what readers use ---------------------------------------------------
    def kernel_calls(self, targets_of):
        return tracing.kernel_calls(self.trace, targets_of)

    # -- inputs -------------------------------------------------------------
    def make_inputs(self):
        cfg = self.config
        self.history, self.pool = traffic.generate(self.mix, self.seed)
        self.leaves = weights.make(
            self.arch.leaf_shapes(cfg["hidden"], cfg["n_metrics"]),
            self.Z, cfg["hidden"], self.seed)

    def row_at(self, j: int) -> np.ndarray:
        """The (Z, M) metric rows observed at tick ``j``: the history's tail
        fills the window before tick 0, then the pool cycles."""
        if j < 0:
            return self.history[len(self.history) + j]
        return self.pool[j % len(self.pool)]

    def t_at(self, j: int) -> float:
        return (len(self.history) + j + 1) * float(self.mix["interval_s"])

    # -- the system under test ----------------------------------------------
    def build_plane(self, leaves: dict):
        from repro.core import (PPAConfig, ShardedControlPlane, TargetSpec,
                                ThresholdPolicy)
        from repro.core import forecaster as fc
        cfg, a = self.config, self.config["assumed"]
        cls = getattr(fc, self.arch.PROGRAM_CLASS)
        base = cls(window=cfg["window"], hidden=cfg["hidden"],
                   residual=cfg["residual"], use_pallas=cfg["use_pallas"])
        # shallow copies of one model; ``copy.copy`` would go through the
        # pickle hooks, which put every leaf on the device again
        models = []
        for _ in range(self.Z):
            m = cls.__new__(cls)
            m.__dict__.update(base.__dict__)
            models.append(m)
        scalers = []
        for i in range(self.Z):
            sc = fc.Scaler()
            sc.fit(self.history[:, i])
            scalers.append(sc)
        fit = fc.BatchFitResult()
        fit.add(models, scalers, leaves, np.zeros((self.Z, 1)))
        fit.apply()
        policy = ThresholdPolicy(a["threshold"], a["min_replicas"],
                                 cfg["tolerance"])
        specs = [TargetSpec(f"t{i}", policy, min_replicas=a["min_replicas"],
                            model=m) for i, m in enumerate(models)]
        ppa = PPAConfig(control_interval_s=cfg["control_interval_s"],
                        threshold=a["threshold"],
                        min_replicas=a["min_replicas"],
                        stabilization_s=cfg["stabilization_s"],
                        key_metric_idx=cfg["key_metric_idx"])
        self.plane = ShardedControlPlane(
            ppa, specs, n_shards=cfg["n_shards"],
            coalesce_dispatch=cfg["coalesce_dispatch"],
            use_pallas=cfg["use_pallas"], device_mesh=cfg["device_mesh"])

    # -- driving it -----------------------------------------------------------
    def drive(self, seconds: float, trace_dir: str | None, t_proc0: float,
              clock: CompileClock):
        import jax
        from jax.profiler import TraceAnnotation
        plane = self.plane
        max_r = int(self.config["assumed"]["max_replicas"])
        k = int(self.config["key_metric_idx"])
        W = int(self.config["window"])
        for j in range(-W, 0):                 # fill the forecast window
            plane.observe_batch(self.t_at(j), self.row_at(j))
        # per tick only what the check reads: the key forecast each target
        # decided on and the decisions; the full forecasts of CHECK_TICKS
        # window ticks, drawn from the seed as the window runs (a reservoir
        # sample), and of the last tick
        self.key_fc, self.decisions = [], []
        self.sampled: dict[int, np.ndarray] = {}
        self.failed = 0
        pick = np.random.default_rng([self.seed, 3])
        last = {}
        cur = np.full(self.Z, self.config["assumed"]["min_replicas"],
                      np.int64)
        perf = time.perf_counter

        def tick(j, cur):
            rows, t = self.row_at(j), self.t_at(j)
            t0 = perf()
            with TraceAnnotation("chipbench.collect"):
                plane.observe_batch(t, rows)
            with TraceAnnotation("chipbench.forecast"):
                plane.begin_tick(t, max_r, cur)
            with TraceAnnotation("chipbench.decide"):
                res = plane.finish_tick()
                dec = res.replicas_array()
            dt = perf() - t0
            means, cand = res.forecasts_array()
            self.key_fc.append(np.where(cand, means[:, k], np.nan))
            self.decisions.append(dec)
            return dt, dec, means, cand

        for j in range(WARMUP_TICKS):
            _, cur, _, _ = tick(j, cur)
        gc.collect()                           # the window starts clean
        gc_pauses = []
        gc_t0 = [0.0]

        def on_gc(phase, info):
            if phase == "start":
                gc_t0[0] = perf()
            else:
                gc_pauses.append((info["generation"], perf() - gc_t0[0]))
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        compiles0 = clock.n
        gc.callbacks.append(on_gc)
        j = WARMUP_TICKS
        w0 = perf()
        self.setup_s = w0 - t_proc0
        while True:
            dt, cur, means, cand = tick(j, cur)
            self.tick_s.append(dt)
            self.failed += int((~cand).sum())
            n = j - WARMUP_TICKS
            if n < CHECK_TICKS - 1:
                self.sampled[j] = means
            else:
                r = int(pick.integers(0, n + 1))
                if r < CHECK_TICKS - 1:
                    del self.sampled[sorted(self.sampled)[r]]
                    self.sampled[j] = means
            last = {j: means}
            j += 1
            if perf() - w0 >= seconds:
                break
        self.window_s = perf() - w0
        gc.callbacks.remove(on_gc)
        self.compiles_in_window = clock.n - compiles0
        self.gc_pauses = gc_pauses
        if trace_dir is not None:
            jax.profiler.stop_trace()
        self.sampled.update(last)
        self.check_ticks = sorted(self.sampled)
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()[:self.n_chips])

    def release(self):
        """Free the program's state once the window's readings are taken."""
        self.forecast_errors = self.plane.degraded_stats()["forecast_errors"]
        self.plane.shutdown()
        self.plane = None
        gc.collect()

    # -- the check --------------------------------------------------------------
    def check(self, control: bool = False) -> dict:
        """The numbers that decide ``correct``, each beside its limit.  With
        ``control`` the plain reference computed in bfloat16 takes the
        program's place at the sampled ticks: its forecasts are held to the
        same limit as the program's.

        Every target is checked.  The decisions of every tick come first;
        then the forecasts of the sampled ticks, one reference forward a
        block of targets over all of them, the blocks on one thread per CPU
        this process may use, with BLAS held to one thread in each.  The
        seconds of each phase and the thread count are kept in
        ``check_s`` and ``check_threads``."""
        from threadpoolctl import threadpool_limits
        cfg, a = self.config, self.config["assumed"]
        mean, std = reference.scaler_stats(self.history)
        perf = time.perf_counter
        # decisions: every tick, from the key forecast each tick decided on
        t0 = perf()
        k = int(cfg["key_metric_idx"])
        decide = reference.Decider(a["threshold"], a["min_replicas"],
                                   cfg["tolerance"], cfg["stabilization_s"])
        cur = np.full(self.Z, a["min_replicas"], np.int64)
        mismatches = 0
        for j, dec in enumerate(self.decisions):
            want = decide(self.t_at(j), self.row_at(j)[:, k], self.key_fc[j],
                          cur, int(a["max_replicas"]))
            mismatches += int((want != dec).sum())
            cur = dec
        t1 = perf()
        # forecasts: sampled window ticks, every target, in z units; a
        # (tick, target) with no forecast there is missing, not compared
        W = int(cfg["window"])
        ticks = self.check_ticks

        def forecasts(sl):
            leaves = {n: v[sl].astype(np.float64)
                      for n, v in self.leaves.items()}
            win = np.stack([np.stack([self.row_at(i)[sl]
                                      for i in range(j - W + 1, j + 1)],
                                     axis=1) for j in ticks], axis=1)
            want = reference.forecast(self.arch, leaves, mean[sl], std[sl],
                                      win, cfg["residual"])
            got = reference.forecast(
                self.arch, leaves, mean[sl], std[sl], win, cfg["residual"],
                rnd=to_bf16) if control else np.stack(
                    [self.sampled[j][sl] for j in ticks], axis=1)
            diff = np.abs(got - want) / std[sl][:, None, :]
            seen = np.isfinite(diff).all(axis=-1)
            return (float(np.max(diff[seen])) if seen.any() else 0.0,
                    int((~seen).sum()))

        self.check_threads = len(os.sched_getaffinity(0))
        with threadpool_limits(1, user_api="blas"), \
                ThreadPoolExecutor(self.check_threads) as pool:
            gaps = list(pool.map(forecasts, [
                slice(lo, lo + CHECK_BLOCK)
                for lo in range(0, self.Z, CHECK_BLOCK)]))
        self.check_s = {"decisions": t1 - t0, "forecasts": perf() - t1}
        lim = cfg["check"]
        return {"forecast_gap_z": {"value": max(g[0] for g in gaps),
                                   "limit": lim["forecast_gap_z"]},
                "missing_forecasts": {"value": sum(g[1] for g in gaps),
                                      "limit": 0},
                "decision_mismatches": {"value": mismatches,
                                        "limit": lim["decision_mismatches"]}}


def judge(checks: dict) -> bool:
    """A run is correct when every number is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def to_bf16(x):
    """Round to the nearest bfloat16 (the control's precision)."""
    import ml_dtypes
    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


def start(layout: Layout, workload: str, seed: int, *,
          require_tpu: bool = True,
          log=sys.stderr) -> tuple[Run, CompileClock]:
    """The run of one cell before any work is done.  Raises ``BadCell``
    when the cell's config runs on another number of chips than the cell
    asks for, and ``NoChip`` when ``require_tpu`` and JAX finds no TPU or
    too few chips."""
    run = Run(layout, layout.cell(workload), seed)
    if require_tpu:
        find_chips(run.n_chips)
    import jax
    src = layout.root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    use_compile_cache(layout.root)
    clock = CompileClock()
    run.device = jax.devices()[0]
    if require_tpu:
        run.peaks = layout.peaks(run.device.device_kind)

    def say(msg):
        print(f"[{workload} seed={seed}] {msg}", file=log, flush=True)
    run.say = say
    return run, clock


def drive_cell(layout: Layout, workload: str, seed: int, seconds: float,
               trace: bool, t_proc0: float, *, require_tpu: bool = True,
               log=sys.stderr) -> Run:
    """Set up one cell, drive its window and free the program's state;
    returns the run with what the window produced.  Refuses a cell before
    any work as ``start`` does."""
    run, clock = start(layout, workload, seed, require_tpu=require_tpu,
                       log=log)
    say = run.say
    run.make_inputs()
    say(f"inputs made at {time.perf_counter() - t_proc0:.3f} s")
    run.build_plane(run.leaves)
    say(f"plane built at {time.perf_counter() - t_proc0:.3f} s")
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        run.drive(seconds, trace_dir, t_proc0, clock)
        say(f"setup_s={run.setup_s!r} (compiles {clock.n}, "
            f"{clock.seconds:.3f} s), window {len(run.tick_s)} ticks in "
            f"{run.window_s!r} s, compiles in window "
            f"{run.compiles_in_window}")
        ms = np.asarray(run.tick_s) * 1e3
        med = float(np.median(ms))
        gc_ms = [p * 1e3 for _, p in run.gc_pauses]
        say("tick ms p50/p90/p99/max "
            f"{med:.4f}/{np.percentile(ms, 90):.4f}/"
            f"{np.percentile(ms, 99):.4f}/{ms.max():.4f}; ms in ticks over "
            f"3x the median {float(np.sum(ms[ms > 3 * med])):.1f}; ms "
            f"between ticks {run.window_s * 1e3 - float(ms.sum()):.1f}; "
            f"gc collections {len(gc_ms)} (gen 2: "
            f"{sum(g == 2 for g, _ in run.gc_pauses)}), ms in gc "
            f"{sum(gc_ms):.1f}, longest {max(gc_ms, default=0.0):.1f}")
        if trace_dir is not None:
            files = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
            run.trace = tracing.reduce(tracing.read(files[0]),
                                       run.n_chips) if files else None
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run.release()
    return run


def run_cell(layout: Layout, workload: str, seed: int, seconds: float,
             trace: bool, t_proc0: float, *, require_tpu: bool = True,
             log=sys.stderr) -> dict:
    """Run one cell once and return its result object (the line the
    benchmark prints)."""
    run = drive_cell(layout, workload, seed, seconds, trace, t_proc0,
                     require_tpu=require_tpu, log=log)
    t0 = time.perf_counter()
    checks = run.check()
    run.say(f"check took {time.perf_counter() - t0:.3f} s (decisions "
            f"{run.check_s['decisions']:.3f} s, forecasts "
            f"{run.check_s['forecasts']:.3f} s, {run.check_threads} "
            f"threads) over {len(run.decisions)} ticks and "
            f"{len(run.check_ticks)} sampled; "
            f"forecast_errors={run.forecast_errors}")

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in layout.metrics_for(workload, kind):
        value = layout.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": run.device.platform,
              "kind": run.device.device_kind, "count": run.n_chips,
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": judge(checks), "attempted": run.Z * len(run.tick_s),
           "failed": run.failed, "metrics": metrics, "device": device}
    if trace:
        t = run.trace
        device["busy_s"] = (t.busy_ns * 1e-9) if t else 0.0
        device["window_s"] = (t.window_ns * 1e-9) if t else 0.0
        if t is not None:
            out["breakdown"] = {
                "device_ops": tracing.top_ops(t),
                "idle_gaps": [[k, v * 1e-9] for k, v in sorted(
                    t.idle_by_span.items(), key=lambda kv: -kv[1])][:10]}
            run.say(f"trace: {t.ticks} ticks on {t.n_devices} device(s)")
    out["checks"] = checks
    return out
