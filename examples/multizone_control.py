"""Multi-zone batched autoscaling: one control plane drives every edge
zone + the cloud with one forecast dispatch per control tick.

The paper's deployment runs one PPA per scaling target; here 6 edge zones
and the cloud (7 targets) share one batched control plane (DESIGN.md §5):
per-zone LSTMs are pretrained on a static-provisioning collection run,
stacked, and vmapped — each 15 s tick costs one device dispatch instead
of 7.

``--shards S`` routes the zones through the ``ShardedControlPlane``
(staged collect -> formulate -> batched forecast -> evaluate -> actuate
tick, S controller shards); ``--async`` adds double-buffered ticks (the
window-t forecast overlaps window-(t+1) metric collection) and runs the
hourly vmapped batch refit off the tick critical path.  The workload is
the NASA + Random Access mixed trace: the bursty Random Access foreground
(paper Alg. 2) rides on the NASA-KSC diurnal background (paper §5.2.2).

Run: PYTHONPATH=src python examples/multizone_control.py
         [--shards 4] [--async] [--minutes 30]
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.cluster import ClusterSim, SimConfig, paper_topology
from repro.core import (FleetController, PPAConfig, ShardedControlPlane,
                        TargetSpec, ThresholdPolicy, Updater, UpdatePolicy,
                        LSTMForecaster)
from repro.workloads import nasa_requests, nasa_trace, random_access

N_EDGE_ZONES = 6
ZONES = tuple(f"edge-{i}" for i in range(N_EDGE_ZONES)) + ("cloud",)
THRESHOLD = 350.0


def mixed_trace(t_end: float, seed: int = 7) -> list[tuple[float, str, str]]:
    """NASA diurnal background + Random Access bursty foreground, merged
    and sorted — the heterogeneous-zone mix the federated-zone work
    evaluates on (ROADMAP)."""
    edge = list(ZONES[:-1])
    ra = random_access(t_end, zones=edge, seed=seed)
    minutes = int(np.ceil(t_end / 60.0))
    counts = nasa_trace(days=max(1, minutes // 1440 + 1),
                        scale=0.4, seed=seed)[:minutes]
    nasa = [(t, kind, zone) for t, kind, zone in
            nasa_requests(counts, zones=edge, seed=seed + 1) if t < t_end]
    tasks = ra + nasa
    tasks.sort(key=lambda x: x[0])
    return tasks


def collect_pretrain(t_end: float = 1800.0) -> dict[str, np.ndarray]:
    """Static-provisioning collection run (paper §5.3.1, scaled to Z zones)."""
    sim = ClusterSim(paper_topology(n_edge_zones=N_EDGE_ZONES),
                     SimConfig(seed=42))
    for z in ZONES:
        sim.scale_to(z, 4, 0.0)
    sim.make_ready_now()
    tasks = mixed_trace(t_end, seed=99)
    w = sim.cfg.control_interval_s
    ti = 0
    for tick in np.arange(w, t_end, w):
        while ti < len(tasks) and tasks[ti][0] <= tick:
            at, kind, zone = tasks[ti]
            from repro.cluster.simulator import Task
            sim.dispatch(Task(at, kind, zone, 0.0), at)
            ti += 1
        for z in ZONES:
            sim.sample_zone(z, tick)
    return {z: np.stack([v for _, v in sim.samples[z]]) for z in ZONES}


def main():
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--minutes", type=int, default=30)
    ap.add_argument("--shards", type=int, default=0,
                    help="route through ShardedControlPlane with S shards")
    ap.add_argument("--async", dest="async_ticks", action="store_true",
                    help="double-buffered ticks + off-critical-path refits")
    args = ap.parse_args()

    print(f"collecting pretraining series for {len(ZONES)} zones ...")
    pre = collect_pretrain()
    specs = []
    for z in ZONES:
        model = LSTMForecaster(window=4, epochs=60, seed=0)
        model.fit(pre[z], from_scratch=True)
        specs.append(TargetSpec(z, ThresholdPolicy(THRESHOLD, 1),
                                min_replicas=1, model=model))
    cfg = PPAConfig(threshold=THRESHOLD, stabilization_s=120.0)
    updater = Updater(UpdatePolicy.FINETUNE)
    if args.shards > 0 or args.async_ticks:
        ctrl = ShardedControlPlane(cfg, specs, updater=updater,
                                   n_shards=max(args.shards, 1),
                                   async_ticks=args.async_ticks)
        kind = (f"ShardedControlPlane (S={ctrl.n_shards}, "
                f"async={'on' if args.async_ticks else 'off'})")
    else:
        ctrl = FleetController(cfg, specs, updater=updater)
        kind = "FleetController"

    T = args.minutes * 60
    tasks = mixed_trace(T, seed=7)
    sim = ClusterSim(paper_topology(n_edge_zones=N_EDGE_ZONES),
                     SimConfig(seed=1, startup_s=25.0))
    print(f"running {args.minutes} min NASA+RandomAccess mix, "
          f"{len(tasks)} tasks, {kind}, one batched dispatch per "
          f"{sim.cfg.control_interval_s:.0f}s tick")
    sim.run(tasks, ctrl, T, initial_replicas=2)
    if hasattr(ctrl, "flush_updates"):
        ctrl.flush_updates()
        if ctrl.refit_log:
            e = ctrl.refit_log[-1]
            print(f"batch refit: {'async' if e['async'] else 'inline'}, "
                  f"{(e['applied'] - e['submitted']) * 1e3:.0f} ms "
                  f"{'off' if e['async'] else 'on'} the tick path")

    rs, re_ = sim.response_times("sort"), sim.response_times("eigen")
    print(f"\nsort  p50={np.percentile(rs, 50):.3f}s "
          f"p95={np.percentile(rs, 95):.3f}s  (n={len(rs)})")
    if len(re_):
        print(f"eigen p50={np.percentile(re_, 50):.3f}s "
              f"p95={np.percentile(re_, 95):.3f}s  (n={len(re_)})")
    edge = [z for z in ZONES if z != "cloud"]
    print(f"RIR edge={sim.rir_stats(edge)[0]:.3f} "
          f"cloud={sim.rir_stats(['cloud'])[0]:.3f}")
    for z in ZONES:
        reps = [n for _, n in sim.replica_log[z]]
        pred = sum(1 for d in ctrl.decisions(z) if d.predicted)
        print(f"  {z:8s} replicas min/mean/max = "
              f"{min(reps)}/{np.mean(reps):.1f}/{max(reps)}  "
              f"proactive_ticks={pred}/{len(reps)}")
    if hasattr(ctrl, "shutdown"):
        ctrl.shutdown()


if __name__ == "__main__":
    main()
