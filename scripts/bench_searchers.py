"""Searcher-zoo benchmark: best-metric-at-budget across the method zoo.

Runs the trial-free simulation harness (``determined_tpu/searcher/
simulate.py``) over a seeded lr-sensitive curve model for random, ASHA,
Hyperband, and PBT at EQUAL total budget, averaged over several seeds —
the number that matters for method choice is "how good is the best config
after N training units", not wall-clock (simulation costs milliseconds).

Prints ONE JSON line:

    python scripts/bench_searchers.py
    python scripts/bench_searchers.py --trials 16 --max-time 64 --seeds 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

METHODS = ("random", "asha", "hyperband", "pbt")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=16)
    ap.add_argument("--max-time", type=int, default=64)
    ap.add_argument("--seeds", type=int, default=8)
    args = ap.parse_args()

    from determined_tpu.config import ExperimentConfig
    from determined_tpu.searcher import (
        SyntheticCurveModel,
        compare_methods,
        format_comparison,
    )

    cfg = ExperimentConfig.parse(
        {
            "name": "bench-searchers",
            "hyperparameters": {
                "lr": {"type": "log", "minval": -4, "maxval": -1}
            },
            "searcher": {
                "name": "random",
                "metric": "validation_loss",
                "max_trials": args.trials,
                "max_time": args.max_time,
                "num_rungs": 3,
                "divisor": 4,
            },
        }
    )

    t0 = time.monotonic()
    sums = {m: {"best": 0.0, "units": 0, "trials": 0, "wins": 0} for m in METHODS}
    last_reports = None
    for seed in range(args.seeds):
        reports = compare_methods(cfg, METHODS, SyntheticCurveModel(seed), seed=seed)
        last_reports = reports
        best_of_round = min(r.best_metric for r in reports)
        for r in reports:
            s = sums[r.method]
            s["best"] += r.best_metric
            s["units"] += r.total_units
            s["trials"] += r.trials_created
            if r.best_metric == best_of_round:
                s["wins"] += 1
    elapsed = time.monotonic() - t0

    print(format_comparison(last_reports), file=sys.stderr)
    line = {
        "bench": "searchers",
        "seeds": args.seeds,
        "budget_units": max(r.total_units for r in last_reports),
        "sim_seconds": round(elapsed, 3),
    }
    for m in METHODS:
        s = sums[m]
        line[m] = {
            "mean_best": round(s["best"] / args.seeds, 5),
            "mean_units": s["units"] // args.seeds,
            "mean_trials": s["trials"] // args.seeds,
            "wins": s["wins"],
        }
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
