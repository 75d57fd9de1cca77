"""Find the knee of an open-loop cell: the highest rate it sustains.

    python3 benchmark/sweep.py --workload <serve-open cell> --rates 1.5,2,2.5,3 [--seconds 25]

One process, one engine, one rate after another (the engine drains between
them).  For each rate: time to first token over the window's halves (a queue
that grows shows as a second half far above the first), its percentiles, the
gap between tokens, and what was still queued when the window closed.  The
knee is read off by a person and written, times four fifths, into the
traffic file as ``rate_per_s``; a later ``benchmark`` PR that moves it runs
this again.  Needs a TPU, like ``run.py``.
"""

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=2_147_483_000)
    args = ap.parse_args()
    from benchlib import harness, model, serve_run, spec as spec_mod, stats

    cell = spec_mod.Spec().cell(args.workload)
    harness._setup_jax(True, cell.chips)
    from determined_tpu.serve.scheduler import AdmissionRejected

    engine, _, model_cfg = serve_run.build_engine(cell, model.adapter(cell), args.seed)
    temperature = float(cell.traffic["temperature"])
    engine.start()
    try:
        serve_run._warm(engine, cell.traffic, model_cfg.vocab_size, temperature)
        for rate in [float(r) for r in args.rates.split(",")]:
            traffic = dict(cell.traffic, rate_per_s=rate)
            res = serve_run._open_loop(
                engine, traffic, args.seed, model_cfg.vocab_size, temperature, args.seconds,
                lambda: None, None, AdmissionRejected,
            )
            queued = engine.stats()["queue_depth"]
            ttft = res["ttft_ms"]
            half = len(ttft) // 2
            t_open, t_close = res["window"]
            tpot = serve_run._tpot_ms(res["records"], t_open, time.monotonic(), 0.0)
            print(json.dumps({
                "rate_per_s": rate, "requests": len(ttft), "failed": res["failed"],
                "ttft_ms_p50": stats.percentile(ttft, 50), "ttft_ms_p90": stats.percentile(ttft, 90),
                "ttft_ms_max": max(ttft),
                "ttft_ms_p50_first_half": stats.percentile(ttft[:half], 50),
                "ttft_ms_p50_second_half": stats.percentile(ttft[half:], 50),
                "tpot_ms_p50": statistics.median(tpot) if tpot else None,
                "queue_depth_at_end": queued, "late_ms_p50_max": res["late_ms"],
            }), flush=True)
            while True:  # drain before the next rate
                st = engine.stats()
                if st["queue_depth"] == 0 and st["lanes"]["active"] == 0:
                    break
                time.sleep(0.2)
    finally:
        engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
