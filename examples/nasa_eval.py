"""Reproduce the paper's §6.4 evaluation (Figs. 11-14): 48 h NASA trace,
optimal PPA (LSTM + finetune updates + CPU key metric) vs stock HPA.

    PYTHONPATH=src:. python examples/nasa_eval.py [--days 2]

Takes ~3 minutes for the full 2-day simulation.
"""
import argparse
import json


def main():
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--days", type=int, default=2)
    args = ap.parse_args()

    from benchmarks import bench_evaluation
    out = bench_evaluation.run(days=args.days)
    print(json.dumps({"hpa": out["hpa"], "ppa": out["ppa"],
                      "claims": out["claims"]}, indent=2, default=float))
    ok = all(out["claims"].values())
    print("ALL PAPER §6.4 CLAIMS REPRODUCED" if ok
          else f"claims: {out['claims']}")


if __name__ == "__main__":
    main()
