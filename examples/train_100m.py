"""Train a ~100M-parameter LM with the full substrate (data pipeline, AdamW,
checkpointing, failure recovery).

Default runs a reduced ~20M config for 60 steps (CPU-feasible, ~10 min);
``--full`` selects the real ~100M config x 300 steps (hours on CPU — sized
for a TPU host).

    PYTHONPATH=src python examples/train_100m.py [--full] [--fail-at 40]
"""
import argparse


def main():
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--fail-at", type=int, action="append", default=[])
    ap.add_argument("--ckpt-dir", default="/tmp/train100m_ckpt")
    args = ap.parse_args()

    from repro.configs.base import ModelConfig
    from repro.training.train_loop import TrainConfig, train

    if args.full:
        # ~100M params: 12L, d=768, llama-style
        cfg = ModelConfig(name="lm-100m", n_layers=12, d_model=768,
                          n_heads=12, n_kv_heads=12, head_dim=64, d_ff=2048,
                          vocab=32000, attn_impl="blocked", remat="full")
        tc = TrainConfig(steps=args.steps or 300, global_batch=32,
                         seq_len=512, ckpt_every=50, ckpt_dir=args.ckpt_dir)
    else:
        cfg = ModelConfig(name="lm-20m", n_layers=6, d_model=384, n_heads=6,
                          n_kv_heads=6, head_dim=64, d_ff=1024, vocab=8192,
                          attn_impl="naive", remat="none")
        tc = TrainConfig(steps=args.steps or 60, global_batch=8, seq_len=256,
                         ckpt_every=20, ckpt_dir=args.ckpt_dir)

    from repro.models.registry import build_model
    from repro.models.params import param_count
    n = param_count(build_model(cfg).specs())
    print(f"model: {cfg.name} ({n/1e6:.1f}M params), steps={tc.steps}")
    _, hist = train(cfg, tc, fail_at=set(args.fail_at))
    print(f"loss: {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
