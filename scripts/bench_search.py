"""Search-throughput benchmark: serial vs mesh-packed hyperparameter search.

Runs the SAME fixed-architecture 4-trial search twice through
``LocalExperiment`` on a virtual 8-device CPU mesh (2 slots per trial) —
once with the sequential reference loop (``run(serial=True)``), once with
the gang scheduler packing trials onto disjoint submeshes — and reports the
wall-clock speedup.  Each arm runs in its own subprocess so neither inherits
the other's warm jit caches.

The trial is an MLP over a map-style dataset whose per-item latency models
disk/decode cost: on real TPU hardware
the step executes on the device, so a packed host overlaps its trials'
input/dispatch stalls the same way this CPU proxy overlaps the fetch
latency.  The trial routes its learning rate through
``optax.inject_hyperparams`` and declares it runtime
(``compile_cache_runtime_hparams``), so same-gang trials share ONE
compiled train/eval step via the cross-trial jit-reuse cache: the serial
arm compiles once for all four trials (3 hits via LIFO slot affinity); the
packed arm's four gangs compile once each, concurrently.  The line reports
both arms' cache counters so the reuse is visible.

Prints ONE JSON line:

    JAX_PLATFORMS=cpu python scripts/bench_search.py
    python scripts/bench_search.py --trials 4 --steps 32 --item-ms 0.5
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Dict

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


class SlowMlpDataset:
    """Map-style dataset with a fixed per-item fetch latency (models the
    disk/decode cost a real input pipeline pays off-device)."""

    def __init__(self, size: int, item_ms: float, seed: int = 0) -> None:
        self._delay = item_ms / 1000.0
        rng = np.random.default_rng(seed)
        self._x = rng.standard_normal((size, 16)).astype(np.float32)
        self._y = rng.integers(0, 4, size=(size,)).astype(np.int32)

    def __len__(self) -> int:
        return len(self._x)

    def __getitem__(self, idx: int) -> Dict[str, np.ndarray]:
        time.sleep(self._delay)
        return {"image": self._x[idx], "label": self._y[idx]}


def _make_trial_cls(item_ms: float):
    """Built lazily so the parent process never imports jax."""
    import optax

    from determined_tpu.data import DataLoader
    from determined_tpu.models.mnist import MnistTrial

    class SearchBenchTrial(MnistTrial):
        def build_optimizer(self):
            # lr lives in opt_state (runtime), not the trace: every trial of
            # this architecture shares one compiled step
            return optax.inject_hyperparams(optax.adam)(
                learning_rate=float(self.context.get_hparam("lr", 1e-3))
            )

        def compile_cache_runtime_hparams(self):
            return ("lr",)

        def _dataset(self, train: bool):
            size = int(self.context.get_hparam("dataset_size", 128))
            return SlowMlpDataset(size, item_ms, seed=0 if train else 1)

        def build_training_data_loader(self):
            return DataLoader(
                self._dataset(train=True),
                self.context.get_global_batch_size(),
                shuffle=True,
                seed=self.context.seed,
            )

        def build_validation_data_loader(self):
            return DataLoader(
                self._dataset(train=False),
                self.context.get_global_batch_size(),
                shuffle=False,
                seed=self.context.seed,
            )

    return SearchBenchTrial


def run_arm(args: argparse.Namespace) -> None:
    """One arm, in-process: prints its own JSON line on stdout's last line."""
    from determined_tpu import train
    from determined_tpu.config import ExperimentConfig
    from determined_tpu.experiment import LocalExperiment

    lrs = [round(3e-3 * (1 + i), 6) for i in range(args.trials)]
    cfg = ExperimentConfig.parse(
        {
            "name": f"bench-search-{args.arm}",
            "hyperparameters": {
                "lr": {"type": "categorical", "vals": lrs},
                "hidden": args.hidden,
                "global_batch_size": args.batch_size,
                "dataset_size": args.batch_size * 2,
            },
            "searcher": {
                "name": "grid",
                "metric": "validation_accuracy",
                "smaller_is_better": False,
                "max_length": {"batches": args.steps},
                "max_concurrent_trials": args.trials,
            },
            "resources": {"mesh": {"data": args.slots_per_trial}},
            "checkpoint_policy": "none",
        }
    )
    import tempfile

    exp = LocalExperiment(
        cfg,
        _make_trial_cls(args.item_ms),
        checkpoint_dir=tempfile.mkdtemp(prefix=f"dtpu-bench-search-{args.arm}-"),
        seed=0,
    )
    t0 = time.perf_counter()
    summary = exp.run(serial=(args.arm == "serial"))
    wall = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "arm": args.arm,
                "wall_s": round(wall, 4),
                "trials": summary["trials"],
                "total_steps": summary["total_steps"],
                "jit_cache": train.step_cache_stats(),
                "scheduler": summary.get("scheduler"),
            }
        )
    )


def _spawn_arm(arm: str, args: argparse.Namespace) -> Dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append(f"--xla_force_host_platform_device_count={args.devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    cmd = [
        sys.executable,
        os.path.abspath(__file__),
        "--arm",
        arm,
        "--trials",
        str(args.trials),
        "--slots-per-trial",
        str(args.slots_per_trial),
        "--steps",
        str(args.steps),
        "--batch-size",
        str(args.batch_size),
        "--hidden",
        str(args.hidden),
        "--item-ms",
        str(args.item_ms),
        "--devices",
        str(args.devices),
    ]
    out = subprocess.run(
        cmd, env=env, cwd=REPO_ROOT, capture_output=True, text=True, check=False
    )
    if out.returncode != 0:
        sys.stderr.write(out.stdout + out.stderr)
        raise SystemExit(f"{arm} arm failed with exit code {out.returncode}")
    last = [l for l in out.stdout.splitlines() if l.strip().startswith("{")][-1]
    return json.loads(last)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arm", choices=["serial", "packed"], default=None)
    p.add_argument("--trials", type=int, default=4)
    p.add_argument("--slots-per-trial", type=int, default=2)
    p.add_argument("--steps", type=int, default=48, help="max_length batches per trial")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--item-ms", type=float, default=0.8, help="per-item fetch latency")
    p.add_argument("--devices", type=int, default=8, help="virtual CPU device count")
    args = p.parse_args()

    if args.arm:
        run_arm(args)
        return

    serial = _spawn_arm("serial", args)
    packed = _spawn_arm("packed", args)
    speedup = serial["wall_s"] / packed["wall_s"] if packed["wall_s"] else None
    print(
        json.dumps(
            {
                "metric": "search_wall_clock_speedup",
                "value": round(speedup, 3) if speedup else None,
                "unit": "x",
                # serial execution IS the baseline for this metric
                "vs_baseline": round(speedup, 3) if speedup else None,
                "serial_s": serial["wall_s"],
                "packed_s": packed["wall_s"],
                "trials": args.trials,
                "slots_per_trial": args.slots_per_trial,
                "devices": args.devices,
                "steps_per_trial": args.steps,
                "item_ms": args.item_ms,
                "packed_peak_concurrency": (packed.get("scheduler") or {}).get(
                    "peak_concurrency"
                ),
                "jit_cache_hits_packed": (packed.get("jit_cache") or {}).get("hits"),
                "jit_cache_hits_serial": (serial.get("jit_cache") or {}).get("hits"),
            }
        )
    )


if __name__ == "__main__":
    main()
