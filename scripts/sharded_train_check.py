"""Sharded training against one chip: same model, seed and batches.

    python scripts/sharded_train_check.py <experiment.yaml>

Needs four devices (one four-chip host; ``--xla_force_host_platform_device_
count=4`` for a CPU rehearsal).  One process drives all four: it trains the
config's trial for ``STEPS`` steps on ONE of the chips, then on ``fsdp: 4`` and on
``fsdp: 2, tensor: 2`` — through ``train.init`` / ``Trainer.fit``, what
``LocalExperiment`` and ``run_trial`` call — and compares the per-step loss
curves.  For each run it prints the Mosaic kernels and collectives found in
the compiled step (``RUN``) and every device's ``memory_stats`` (``MEM``),
so "everything sits on the first chip" is visible; then one ``RESULT {json}``
line.  Exits 1 when a curve leaves the tolerance, a loss is not finite, a
sharded run's memory sits unevenly on the chips, or a step has no kernel.

This is the path ``chip_smoke.py --chips 4`` runs: the two Pallas kernels
(flash attention, fused AdamW) run per device inside ``shard_map`` there,
which no virtual CPU mesh can show (the interpreter partitions; Mosaic
does not).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STEPS = 4
#: largest per-step |loss - one-chip loss| accepted: same seed and batches,
#: bf16 matmuls and gradient sums reduced in another order across chips.
#: Measured on four v5e chips at the const.yaml widths (loss ~10.88):
#: 5.0e-4 on fsdp4, 3.8e-4 on fsdp2 x tensor2.
TOLERANCE = 0.005
#: a sharded run's least-loaded chip must hold at least this share of what
#: its most-loaded chip holds (both layouts are even: measured 1.00)
MIN_MEMORY_BALANCE = 0.5


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    args = ap.parse_args()

    import importlib
    import logging
    import math

    import jax
    import yaml

    from determined_tpu import core, train
    from determined_tpu.config import ExperimentConfig, Length
    from determined_tpu.data import to_global
    from determined_tpu.parallel.mesh import MeshConfig
    from determined_tpu.train import Callback
    from determined_tpu.utils.chip import device_facts
    from determined_tpu.utils.compilation_cache import program_facts

    logging.basicConfig(level=logging.INFO)
    device = device_facts()
    if device["count"] < 4:
        print("RESULT " + json.dumps({"ok": False, "error": "needs 4 devices", "device": device}))
        return 1
    devices = jax.devices()[:4]
    with open(args.config) as f:
        cfg = ExperimentConfig.parse(yaml.safe_load(f))
    module_name, _, class_name = cfg.entrypoint.partition(":")
    sys.path.insert(0, os.getcwd())
    trial_cls = getattr(importlib.import_module(module_name), class_name)

    class Losses(Callback):
        def __init__(self) -> None:
            self.by_step = []

        def on_training_workload_end(self, steps, metrics) -> None:
            self.by_step.append(float(metrics["loss"]))

    def mem() -> list:
        return [
            {k: (d.memory_stats() or {}).get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}
            for d in devices
        ]

    runs = {}
    for name, mesh_cfg, devs in (
        ("one_chip", MeshConfig(data=1), devices[:1]),
        ("fsdp4", MeshConfig(fsdp=4), devices),
        ("fsdp2_tensor2", MeshConfig(fsdp=2, tensor=2), devices),
    ):
        ctx = train.init(
            exp_config=cfg, mesh_config=mesh_cfg, devices=devs,
            core_context=core._dummy_init(), seed=0,
        )
        trial = trial_cls(ctx)
        losses = Losses()
        trial.build_callbacks = lambda losses=losses: {"losses": losses}
        trainer = train.Trainer(trial)
        trainer.fit(
            Length.batches(STEPS), checkpoint_policy="none",
            report_period=Length.batches(1),
        )
        # what the step program holds: the lowering the fit just ran, so this
        # finds jax's executable instead of compiling another
        batch = to_global(trainer._sample_host_batch, trainer.mesh)
        with trainer.mesh:
            program = program_facts(
                trainer._train_step_jit.lower(trainer.state, batch).compile().as_text()
            )
        in_use = mem()
        runs[name] = {"losses": losses.by_step, "program": program}
        print(f"RUN {name}: losses {losses.by_step} program {program}", flush=True)
        print(f"MEM {name}: {json.dumps(in_use)}", flush=True)
        held = [m["bytes_in_use"] for m in in_use]
        # None where the backend reports no memory statistics (the CPU)
        runs[name]["memory_balance"] = (
            round(min(held) / max(held), 3) if all(held) else None
        )
        # the next layout needs the memory back
        del trainer, trial, ctx, batch
        train.clear_step_cache()
        gc.collect()

    base = runs["one_chip"]["losses"]
    problems = []
    result = {"steps": STEPS, "tolerance": TOLERANCE, "device": device, "runs": runs}
    for name, run in runs.items():
        if len(run["losses"]) != STEPS or not all(math.isfinite(l) for l in run["losses"]):
            problems.append(f"{name}: losses {run['losses']}")
            continue
        run["max_abs_diff_vs_one_chip"] = max(abs(a - b) for a, b in zip(run["losses"], base))
        if run["max_abs_diff_vs_one_chip"] > TOLERANCE:
            problems.append(f"{name}: loss curve off by {run['max_abs_diff_vs_one_chip']:.4g}")
        if name != "one_chip":
            balance = run["memory_balance"]
            if balance is not None and balance < MIN_MEMORY_BALANCE:
                problems.append(f"{name}: memory sits unevenly on the chips (min/max {balance})")
            if not any(k in run["program"] for k in ("all-reduce", "reduce-scatter", "all-gather")):
                problems.append(f"{name}: no collective in the step: {run['program']}")
        if device["platform"] == "tpu" and not run["program"].get("tpu_custom_call"):
            problems.append(f"{name}: no Mosaic kernel in the step: {run['program']}")
    result["ok"] = not problems
    result["problems"] = problems
    print("RESULT " + json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
