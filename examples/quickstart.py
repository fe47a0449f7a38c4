"""Quickstart: the four layers of the framework in a few minutes on CPU.

1. The hybrid proactive+reactive control plane (DESIGN.md §§5-10,
   docs/architecture.md): a guardrail-enabled ``ShardedControlPlane``
   scaling a continuous-batching serving fleet through a flash crowd,
   with the ``SLAPolicy`` p95 objective and the staged tick
   collect -> formulate -> forecast -> evaluate -> guard -> actuate.
2. The paper's PPA autoscaling the simulated edge cluster (vs HPA).
3. A reduced LM training run with checkpoint-restart.
4. A continuous-batching decode engine serving requests.

    PYTHONPATH=src python examples/quickstart.py [--quick]

``--quick`` (the CI smoke lane) shrinks the closed loops and skips the
PPA-vs-HPA scenario so the walkthrough exits in well under a minute;
the guardrail demo always runs.  docs/quickstart.md walks through the
output line by line.
"""
import argparse
import shutil

import numpy as np


def guardrail_demo(quick: bool = False, forecaster: str = "lstm"):
    """Collect -> fit -> proact -> guard, end to end on one service:

    * collect: a statically provisioned fleet serves a steady Poisson
      load while the metric exporter records per-window samples (slot 1
      is the window p95 of booked response times — the latency feed);
    * fit: a per-target forecaster (``--forecaster``: the plain LSTM or
      the Attention-Double-LSTM "attn" zoo entry) learns the series;
    * proact + guard: a ``ShardedControlPlane`` with ``SLAPolicy`` (p95
      objective, ``key_metric_idx=1``) and the reactive guardrail scales
      the fleet through a flash crowd the forecaster has never seen.
    """
    from repro.core import (GuardrailConfig, PPAConfig,
                            ShardedControlPlane, SLAPolicy, TargetSpec)
    from repro.serving.fleet import FleetConfig, ServingFleet
    from repro.workloads import poisson_arrivals

    print("== 1. Guardrail-enabled sharded control plane "
          "(SLA p95 objective, flash crowd) ==")
    w = 15.0
    t_end = 600.0 if quick else 1200.0
    spike = (t_end / 2, t_end / 2 + 120.0)
    base_rate, spike_rate, target_p95 = 6.0, 30.0, 6.0
    fcfg = FleetConfig(total_chips=1024, chips_per_replica=16, seed=0,
                      deadline_factor=1e9)
    rng = np.random.default_rng(0)

    def arrivals(rates, seed):
        arr = poisson_arrivals(rates, t_end, w, seed=seed)
        ntok = rng.integers(32, 64, len(arr.times)).astype(np.float64)
        return arr.times, ntok

    def closed_loop(fleet, times, ntok, step):
        lo = 0
        for tick in np.arange(w, t_end + w / 2, w):
            fleet._apply_events(tick)
            hi = int(np.searchsorted(times, tick, side="right"))
            fleet.dispatch_window(times[lo:hi], ntok[lo:hi])
            fleet.completed_log.seal_window()
            lo = hi
            step(tick, fleet.sample(tick))
        return fleet

    # -- collect: static provisioning, steady load ------------------------
    fleet = ServingFleet(fcfg, batch=True)
    fleet.scale_to(4, 0.0)
    fleet.make_ready_now(0.0)
    times, ntok = arrivals(base_rate, seed=99)
    closed_loop(fleet, times, ntok, lambda t, s: None)
    series = np.stack([v for _, v in fleet.samples])
    print(f"  collected {len(series)} control windows "
          f"(steady p95 ~{np.median(series[:, 1]):.2f}s)")

    # -- fit + build the guarded plane ------------------------------------
    fkw = dict(window=4)
    if forecaster not in ("arma", "arima", "arima_d1"):
        fkw["epochs"] = 20 if quick else 40
        if forecaster != "ensemble":     # members seed themselves (0..E-1)
            fkw["seed"] = 0
    cfg = PPAConfig(key_metric_idx=1,          # scale on the p95 feed
                    stabilization_s=60.0,
                    guard=GuardrailConfig(band=0.3, headroom=1.15,
                                          down_ticks=3),
                    forecaster=forecaster, forecaster_kw=fkw)
    model = cfg.build_forecaster()
    model.fit(series, from_scratch=True)
    plane = ShardedControlPlane(
        cfg, [TargetSpec("svc", SLAPolicy(target_p95, min_replicas=2),
                         model=model)],
        n_shards=1)

    # -- proact + guard through the flash crowd ---------------------------
    n_win = int(np.ceil(t_end / w))
    edges = np.arange(n_win) * w
    rates = np.where((edges >= spike[0]) & (edges < spike[1]),
                     spike_rate, base_rate)
    times, ntok = arrivals(rates, seed=1)
    fleet = ServingFleet(fcfg, batch=True)
    fleet.scale_to(2, 0.0)
    fleet.make_ready_now(0.0)
    stats = {"violation_s": 0.0, "pod_s": 0.0}

    def step(tick, snap):
        cur = len(fleet.live_replicas(tick))
        stats["pod_s"] += cur * w
        if snap.values[1] > target_p95:
            stats["violation_s"] += w
        plane.observe_batch(tick, snap.values[None, :])
        res = plane.control_step(tick, 64, cur)
        fleet.scale_to(max(res["svc"].replicas, 2), tick)

    closed_loop(fleet, times, ntok, step)
    g = plane.guard_stats()
    plane.shutdown()
    print(f"  flash crowd {spike_rate:.0f} req/s for "
          f"{spike[1] - spike[0]:.0f}s: SLA violation "
          f"{stats['violation_s']:.0f}s of {t_end:.0f}s, "
          f"{stats['pod_s'] / 3600:.2f} pod-hours, guard overrides "
          f"up={g['up_overrides']} down={g['down_overrides']}")


def ppa_demo():
    from repro.core.experiments import collect_series, run_scenario
    from repro.workloads import random_access

    print("== 2. PPA vs HPA on the simulated edge cluster (20 min sim) ==")
    pre = collect_series(random_access(600 * 15, seed=99), 600 * 15)
    T = 20 * 60
    tasks = random_access(T, seed=3)
    for kind in ("hpa", "ppa"):
        kw = dict(pretrain=pre) if kind == "ppa" else {}
        r = run_scenario(tasks, T, scaler=kind, min_replicas=2, **kw)
        print(f"  {kind}: sort {r.sort_mean:.3f}s eigen {r.eigen_mean:.2f}s "
              f"idle_edge {r.rir_edge[0]:.3f}")


def train_demo(quick: bool = False):
    from repro.configs import smoke_config
    from repro.training.train_loop import TrainConfig, train

    print("== 3. LM training with checkpoint-restart (injected failure) ==")
    cfg = smoke_config("h2o-danube-1.8b")
    steps = 12 if quick else 20
    ckpt_dir = "/tmp/quickstart_ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)   # stale runs confuse restart
    tc = TrainConfig(steps=steps, global_batch=4, seq_len=64, ckpt_every=8,
                     ckpt_dir=ckpt_dir, log_every=10)
    train(cfg, tc, fail_at={steps - 3})


def serve_demo():
    import jax
    import jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.models.registry import build_model
    from repro.serving import ContinuousBatcher, DecodeEngine, Request

    print("== 4. Continuous-batching decode engine ==")
    cfg = smoke_config("mamba2-780m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    engine = DecodeEngine(cfg, params, slots=4, max_len=64)
    batcher = ContinuousBatcher(engine)
    rng = np.random.default_rng(0)
    for i in range(6):
        batcher.submit(Request(i, rng.integers(0, cfg.vocab, 16), 8))
    done = batcher.drain()
    print(f"  served {len(done)} requests "
          f"({sum(len(r.output) for r in done)} tokens, "
          f"{engine.steps} decode steps)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke lane: shrink the closed loops, skip "
                         "the PPA-vs-HPA scenario")
    ap.add_argument("--forecaster", default="lstm",
                    choices=["lstm", "attn", "arma", "arima_d1", "ensemble"],
                    help="forecaster zoo entry for the guardrail demo "
                         "(make_forecaster kind; 'attn' = the fused "
                         "Attention-Double-LSTM)")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    guardrail_demo(quick=args.quick, forecaster=args.forecaster)
    if not args.quick:
        ppa_demo()
    else:
        print("== 2. PPA vs HPA scenario skipped (--quick; run without "
              "the flag for the full comparison) ==")
    train_demo(quick=args.quick)
    serve_demo()
