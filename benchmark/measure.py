"""Run a cell several times in new processes and show how widely it spreads.

    python3 benchmark/measure.py --workload <cell> [--sets 2] [--runs 6]
        [--seconds <run_seconds>] [--seed0 1000] [--traced] [--describe]

This is how the bounds in ``BENCHMARK.json`` were set (PERF.md section 6):
for each set, ``--runs`` runs with seeds ``seed0 .. seed0 + runs - 1`` (the
same seeds in every set), and for each end-to-end metric the distance
between its quartiles as a share of its median.  ``--traced`` adds one
``--trace 1`` run; ``--describe`` prints the planes, lines and commonest
operations of its trace.  This process never touches jax: each run is a
child that has the chip to itself.  Everything is also appended to
``chiprun_out/measure/<cell>.jsonl``.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from benchlib.stats import spread  # noqa: E402  (no jax in there)


def one_run(workload: str, seed: int, seconds: float, trace: int, log) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.monotonic()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.monotonic() - t0
    lines = [x for x in p.stdout.splitlines() if x.startswith("{")]
    for x in lines:
        log.write(x + "\n")
    last = json.loads(lines[-1]) if lines and p.returncode == 0 else None
    if last is None or "correct" not in last:
        tail = "\n".join(p.stderr.splitlines()[-25:])
        print(f"RUN FAILED rc={p.returncode} seed={seed}\n{tail}", flush=True)
        return {}
    last["_wall_s"] = wall
    last["_events"] = [json.loads(x) for x in lines[:-1]]
    return last


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed0", type=int, default=2_147_483_000)
    ap.add_argument("--traced", action="store_true")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--grep", default="", help="with --describe: list trace events matching this")
    ap.add_argument("--show", default="", help="comma-separated events to print from each run")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "measure")
    os.makedirs(out_dir, exist_ok=True)
    show = [s for s in args.show.split(",") if s]
    with open(os.path.join(out_dir, args.workload + ".jsonl"), "a") as log:
        per_set = []
        for s in range(args.sets):
            rows = []
            for r in range(args.runs):
                line = one_run(args.workload, args.seed0 + r, seconds, 0, log)
                if not line:
                    return 1  # a fault repeats: do not spend the chip on it
                vals = {k: v["value"] for k, v in line["metrics"].items()}
                rows.append(vals)
                print(json.dumps({"set": s, "seed": args.seed0 + r, "correct": line["correct"],
                                  "attempted": line["attempted"], "failed": line["failed"],
                                  "wall_s": round(line["_wall_s"], 1),
                                  "memory_peak_GB": round(line["device"]["memory_peak_bytes"] / 1e9, 2),
                                  **vals}), flush=True)
                for ev in line["_events"]:
                    if ev["event"] in show:
                        print("   ", json.dumps(ev)[:3000], flush=True)
            per_set.append(rows)
        for s, rows in enumerate(per_set):
            if len(rows) >= 2:
                for k in rows[0]:
                    vals = [row[k] for row in rows if k in row]
                    print(json.dumps({"set": s, "metric": k, "median": statistics.median(vals),
                                      "spread": round(spread(vals), 5), "min": min(vals), "max": max(vals),
                                      "first": vals[0]}), flush=True)
        if args.traced:
            line = one_run(args.workload, args.seed0, seconds, 1, log)
            if line:
                for ev in line.pop("_events"):
                    if ev["event"] in show or ev["event"] == "end_to_end_of_traced_run":
                        print("   ", json.dumps(ev)[:3000], flush=True)
                print("TRACED", json.dumps(line), flush=True)
            if args.describe:
                code = (
                    "import sys; sys.path.insert(0, %r); from benchlib import trace, harness; import os; "
                    "print(trace.describe(trace.newest_xplane(os.path.join(harness.SCRATCH, 'bench', %r, 'trace')), 40, %r))"
                ) % (HERE, args.workload, args.grep)
                env = dict(os.environ, JAX_PLATFORMS="cpu")
                d = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, capture_output=True, text=True)
                with open(os.path.join(out_dir, args.workload + ".trace.txt"), "w") as f:
                    f.write(d.stdout + d.stderr[-3000:])
                print("\n".join(x for x in d.stdout.splitlines() if "MATCH" in x)[:8000], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
