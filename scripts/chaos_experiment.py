"""Chaos: SIGKILL the experiment DRIVER mid-ASHA-search and prove the
search survives through journal-backed resume.

PR 1's ``chaos_trial.py`` killed individual trials; this kills the whole
``LocalExperiment`` process — the scenario where, before the experiment
journal, every scheduling decision was lost.  The loop:

1. run an oracle search (no faults) and record its completed trial set;
2. start the same search in a child process, SIGKILL it at a random
   moment inside the training window;
3. resume the directory in a fresh child; repeat the kill/resume cycle up
   to ``--kills`` times, then let the final resume run to completion;
4. assert the resumed search completed the SAME request-id set as the
   oracle, that no request id was ever created twice across the crash
   boundaries, and that every resumed in-flight trial with a verified
   checkpoint restarted from it (never from step 0).

Usage:
    python scripts/chaos_experiment.py                 # default chaos
    python scripts/chaos_experiment.py --kills 3 --seed 7
    python scripts/chaos_experiment.py --child --checkpoint-dir D [--resume]

Exit code 0 = survived; the printed JSON records the schedule for
BENCH-style tracking.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

EXP_RAW = {
    "name": "chaos-experiment",
    "hyperparameters": {
        "lr": {"type": "log", "minval": -3, "maxval": -1},
        "hidden": 8,
        "global_batch_size": 16,
        "dataset_size": 64,
    },
    "searcher": {
        "name": "asha",
        "metric": "validation_accuracy",
        "smaller_is_better": False,
        "max_trials": 4,
        "max_length": {"batches": 8},
        "num_rungs": 2,
        "divisor": 4,
        "max_concurrent_trials": 2,
    },
    "resources": {"mesh": {"data": 1}},
    "min_validation_period": {"batches": 2},
    "min_checkpoint_period": {"batches": 2},
    "optimizations": {"async_checkpointing": False},
}


def child_main(args) -> int:
    """One driver attempt: fresh run or journal resume; exits 0 when the
    search completes, 75 when preempted-resumable."""
    from determined_tpu.config import ExperimentConfig
    from determined_tpu.experiment import PREEMPTED_EXIT_CODE, LocalExperiment
    from determined_tpu.models.mnist import MnistTrial

    cfg = ExperimentConfig.parse(dict(EXP_RAW))
    exp = LocalExperiment(cfg, MnistTrial, checkpoint_dir=args.checkpoint_dir)
    summary = exp.run(serial=True, resume=args.resume)
    print(json.dumps(summary, default=str))
    return PREEMPTED_EXIT_CODE if summary.get("status") == "preempted" else 0


def _spawn_child(checkpoint_dir: str, resume: bool) -> subprocess.Popen:
    argv = [sys.executable, os.path.abspath(__file__), "--child",
            "--checkpoint-dir", checkpoint_dir]
    if resume:
        argv.append("--resume")
    return subprocess.Popen(argv, env=dict(os.environ, JAX_PLATFORMS="cpu"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--kills", type=int, default=2, help="SIGKILL cycles before the final resume")
    ap.add_argument("--seed", type=int, default=None, help="kill-schedule seed (default: time)")
    ap.add_argument("--sigterm", action="store_true",
                    help="use SIGTERM (graceful drain) instead of SIGKILL")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--resume", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--checkpoint-dir", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        if not args.checkpoint_dir:
            print("--child requires --checkpoint-dir", file=sys.stderr)
            return 2
        return child_main(args)

    import shutil
    import tempfile

    from determined_tpu.experiment import journal_path, read_journal

    seed = args.seed if args.seed is not None else int(time.time())
    rng = random.Random(seed)
    workdir = tempfile.mkdtemp(prefix="dtpu-chaos-exp-")

    # -- oracle: the same search, never killed ------------------------------
    oracle_dir = os.path.join(workdir, "oracle")
    t0 = time.monotonic()
    rc = _spawn_child(oracle_dir, resume=False).wait()
    if rc != 0:
        print("oracle run failed", file=sys.stderr)
        return 1
    oracle = read_journal(journal_path(oracle_dir))
    oracle_done = sorted(oracle.results)

    # -- chaos: kill/resume cycles ------------------------------------------
    chaos_dir = os.path.join(workdir, "chaos")
    kills = []
    attempt = 0
    resume = False
    while True:
        proc = _spawn_child(chaos_dir, resume=resume)
        if attempt < args.kills:
            # kill at a random moment inside the training window, but only
            # after the journal exists so every cycle tests real replay
            delay = rng.uniform(0.5, 4.0)
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and proc.poll() is None:
                if os.path.exists(journal_path(chaos_dir)):
                    time.sleep(delay)
                    break
                time.sleep(0.1)
            if proc.poll() is None:
                sig = signal.SIGTERM if args.sigterm else signal.SIGKILL
                proc.send_signal(sig)
                proc.wait()
                kills.append({"attempt": attempt, "delay_s": round(delay, 2),
                              "signal": sig.name})
                attempt += 1
                resume = True
                continue
            # finished before we could kill it: count it as the final run
        rc = proc.wait()
        break

    elapsed = time.monotonic() - t0
    ok = rc == 0
    report = {"ok": ok, "seed": seed, "kills": kills, "exit_code": rc}
    if ok:
        replay = read_journal(journal_path(chaos_dir))
        created = [r["rid"] for r in replay.records if r.get("type") == "trial_created"]
        resumed_from_ckpt = sorted(
            {
                r["rid"]
                for r in replay.records
                if r.get("type") == "trial_running" and r.get("resume_checkpoint")
            }
        )
        report.update(
            {
                "status": replay.status,
                "completed": sorted(replay.results),
                "oracle_completed": oracle_done,
                "same_trial_set": sorted(replay.results) == oracle_done,
                "duplicate_request_ids": len(created) != len(set(created)),
                "trials_resumed_from_checkpoint": resumed_from_ckpt,
                "elapsed_seconds": round(elapsed, 2),
            }
        )
        ok = (
            replay.status == "completed"
            and report["same_trial_set"]
            and not report["duplicate_request_ids"]
        )
        report["ok"] = ok
    print(json.dumps(report, indent=2))
    shutil.rmtree(workdir, ignore_errors=True)
    if not ok:
        print("chaos experiment FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
