"""End-to-end serving driver (the paper's kind of system, on the TPU fleet):
a real continuous-batching decode engine serves batched requests while the
PPA — fed by the batcher's own metric exporter — makes the replica-count
decisions for the surrounding fleet.

    PYTHONPATH=src python examples/autoscale_serving.py [--requests 40]
"""
import argparse
import time

import numpy as np


def main():
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.core import (PPA, PPAConfig, LSTMForecaster, MetricsHistory,
                            ThresholdPolicy, Updater, UpdatePolicy)
    from repro.models.registry import build_model
    from repro.serving import ContinuousBatcher, DecodeEngine, Request

    cfg = smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    engine = DecodeEngine(cfg, params, slots=8, max_len=96)
    batcher = ContinuousBatcher(engine)

    ppa = PPA(PPAConfig(threshold=60.0, control_interval_s=5.0,
                        stabilization_s=30.0),
              LSTMForecaster(window=4, epochs=40),
              ThresholdPolicy(60.0, 1),
              Updater(UpdatePolicy.FINETUNE), MetricsHistory())

    rng = np.random.default_rng(0)
    t0 = time.time()
    submitted = 0
    decisions = []
    step = 0
    while len(batcher.done) < args.requests:
        now = time.time() - t0
        # bursty arrivals
        if submitted < args.requests and rng.random() < 0.4:
            n = int(rng.integers(1, 4))
            for _ in range(min(n, args.requests - submitted)):
                batcher.submit(Request(submitted,
                                       rng.integers(0, cfg.vocab, 24), 12,
                                       arrival=now))
                submitted += 1
        batcher.step(now)
        step += 1
        if step % 10 == 0:
            snap = batcher.snapshot(now, 5.0)
            ppa.observe(snap)
            res = ppa.control_step(now, max_replicas=16, current_replicas=1)
            decisions.append(res.replicas)
    dt = time.time() - t0
    toks = sum(len(r.output) for r in batcher.done)
    print(f"served {len(batcher.done)} requests / {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s)")
    print(f"PPA replica decisions over the run: min={min(decisions)} "
          f"max={max(decisions)} (proactive on the queue/rate metrics)")


if __name__ == "__main__":
    main()
