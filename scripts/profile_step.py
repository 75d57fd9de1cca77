"""xplane profile of the headline train step: per-op device-time table.

Usage: DTPU_BENCH_OPT=fused python scripts/profile_step.py [steps]
Prints the top device ops and an optimizer-attributed total, the tool
behind BASELINE.md's roofline accounting.
"""

from __future__ import annotations

import os
import sys
import tempfile
from collections import defaultdict


def parse_xplane(trace_dir):
    """Op table via the shared analyzer (determined_tpu/utils/xplane.py)."""
    from determined_tpu.utils.xplane import hlo_op_table

    ops = defaultdict(float)
    for op in hlo_op_table(trace_dir):
        ops[(op["name"], op["category"], op["expression"][:120])] += op["time_us"]
    return ops


def main():
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from determined_tpu import core, train
    from determined_tpu.data import to_global
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig
    from determined_tpu.utils.chip import require_tpu

    print("device:", require_tpu("scripts/profile_step.py"))
    fused = os.environ.get("DTPU_BENCH_OPT", "auto")
    hp = {
        "lr": 3e-4, "global_batch_size": 8, "seq_len": 1024,
        "vocab_size": 32768, "d_model": 2048, "n_layers": 8, "n_heads": 16,
        "dataset_size": 64, "bf16": True,
        "attention": "flash",
        "warmup_steps": 10,
        "fused_adamw": {"auto": "auto", "fused": True, "ref": False}[fused],
        "adam_mu_bf16": os.environ.get("DTPU_BENCH_MU_BF16", "0") == "1",
    }
    ctx = train.init(hparams=hp, mesh_config=MeshConfig(data=1),
                     core_context=core._dummy_init(), seed=0)
    trainer = train.Trainer(LMTrial(ctx))
    trainer._setup()
    it = iter(trainer.train_loader)
    step = trainer._train_step
    for _ in range(3):  # compile + warm
        trainer.state = step(trainer.state, to_global(next(it), trainer.mesh))
    jax.device_get(trainer.state.metric_count)

    trace_dir = tempfile.mkdtemp(prefix="dtpu-prof-")
    with jax.profiler.trace(trace_dir):
        for _ in range(steps):
            trainer.state = step(trainer.state, to_global(next(it), trainer.mesh))
        jax.device_get(trainer.state.metric_count)

    ops = parse_xplane(trace_dir)
    total = sum(ops.values())
    print(f"\ndevice total: {total/1000:.2f} ms over {steps} steps "
          f"({total/1000/steps:.2f} ms/step)")
    groups = defaultdict(float)
    for (name, cat, _expr), us in ops.items():
        groups[cat or name.split(".")[0]] += us
    print(f"{'category':<32} {'ms/step':>9} {'%':>6}")
    for name, us in sorted(groups.items(), key=lambda kv: -kv[1])[:20]:
        print(f"{name:<32} {us/1000/steps:9.3f} {100*us/total:5.1f}%")
    print(f"\ntop ops:")
    print(f"{'op':<52} {'ms/step':>9} {'%':>6}")
    for (name, cat, expr), us in sorted(ops.items(), key=lambda kv: -kv[1])[:25]:
        print(f"{name[:52]:<52} {us/1000/steps:9.3f} {100*us/total:5.1f}%  {expr[:60]}")
    print(f"\n[raw] trace dir: {trace_dir}")


if __name__ == "__main__":
    main()
