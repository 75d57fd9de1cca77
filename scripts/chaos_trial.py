"""Chaos smoke: run a local trial under RANDOM injected faults and prove
it still finishes with the right step count.

The local analog of killing pods on a live cluster: every run draws a
random schedule of step-crashes and storage-put failures from a seeded
RNG, drives MnistTrial through the same ``TrialSupervisor`` the trial
entrypoint uses (``exec/run_trial.py``), and asserts the supervised run
reaches exactly ``--steps`` optimizer steps — resuming from verified
checkpoints across every injected failure.

Usage:
    python scripts/chaos_trial.py                      # default chaos
    python scripts/chaos_trial.py --steps 24 --crashes 3 --seed 7
    python scripts/chaos_trial.py --storage-failures 2

Exit code 0 = survived; the printed JSON records the fault schedule and
restart count for BENCH-style tracking.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"  # a chaos drill, never a chip run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=16, help="optimizer steps to reach")
    ap.add_argument("--checkpoint-period", type=int, default=4)
    ap.add_argument("--crashes", type=int, default=2, help="random step-crashes to inject")
    ap.add_argument("--storage-failures", type=int, default=1, help="random upload failures")
    ap.add_argument("--max-restarts", type=int, default=10)
    ap.add_argument("--seed", type=int, default=None, help="fault-schedule seed (default: time)")
    args = ap.parse_args()

    from determined_tpu import core, train
    from determined_tpu.config import ExperimentConfig, Length
    from determined_tpu.exec.run_trial import TrialSupervisor
    from determined_tpu.models.mnist import MnistTrial
    from determined_tpu.parallel.mesh import MeshConfig
    from determined_tpu.train._restart import RestartPolicy
    from tests.faults import FaultInjector, SimulatedCrash

    seed = args.seed if args.seed is not None else int(time.time())
    rng = random.Random(seed)
    # sync saves: every checkpoint boundary leaves a durable resume point,
    # so each crash costs at most checkpoint_period steps of rework
    exp = ExperimentConfig.parse({"optimizations": {"async_checkpointing": False}})

    crash_steps = sorted(rng.sample(range(1, args.steps), min(args.crashes, args.steps - 1)))
    inj = FaultInjector(seed=seed)
    for step in crash_steps:
        inj.kill_at_step(step)
    if args.storage_failures:
        # delay the upload failures into the run so they hit real saves
        inj.raise_at(
            "storage.upload",
            lambda: OSError("chaos: injected storage put failure"),
            times=args.storage_failures,
            when=lambda info: rng.random() < 0.5,
        )

    workdir = tempfile.mkdtemp(prefix="dtpu-chaos-")
    hparams = {"lr": 1e-2, "hidden": 16, "global_batch_size": 16, "dataset_size": 64}

    def make_trainer():
        core_ctx = core._dummy_init(checkpoint_dir=os.path.join(workdir, "ckpts"))
        ctx = train.init(
            hparams=dict(hparams),
            mesh_config=MeshConfig(data=1),
            core_context=core_ctx,
            exp_config=exp,
            seed=seed,
        )
        return train.Trainer(MnistTrial(ctx))

    supervisor = TrialSupervisor(
        make_trainer,
        policy=RestartPolicy(max_restarts=args.max_restarts, backoff_base=0.0, jitter=0.0),
        sleep=lambda s: None,
    )
    t0 = time.monotonic()
    with inj.installed():
        summary = supervisor.run(
            Length.batches(args.steps),
            checkpoint_period=Length.batches(args.checkpoint_period),
            report_period=Length.batches(args.steps),
        )
    elapsed = time.monotonic() - t0

    ok = summary["steps_completed"] == args.steps
    print(
        json.dumps(
            {
                "ok": ok,
                "seed": seed,
                "steps": summary["steps_completed"],
                "target_steps": args.steps,
                "restarts": summary.get("restarts", 0),
                "injected_crash_steps": crash_steps,
                "injected_storage_failures": args.storage_failures,
                "train_step_fires": inj.count("train.step"),
                "elapsed_seconds": round(elapsed, 2),
            },
            indent=2,
        )
    )
    if not ok:
        print("chaos trial FAILED to reach target steps", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
