"""Measure the train-loop stall caused by a checkpoint save, sync vs
async, at the flagship-bench model size (judge order r4#5: BASELINE.md
records save-stall before/after).

The stall metric is the wall time the TRAIN LOOP is blocked:
 - sync: the whole `_save_checkpoint(asynchronous=False)` call;
 - async: the `_save_checkpoint()` call (device snapshot + store-path
   enter; serialization runs on the writer thread) plus the later
   `_drain_pending_save` — measured at the next boundary, after the
   overlapped steps have already run.

Also times the steps executed while the save is in flight vs the
baseline step time, so the overlap's interference (device copies vs
training compute) is visible rather than assumed.

Usage: python scripts/ckpt_stall.py  (runs on the local chip)
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax

    from determined_tpu import core, train
    from determined_tpu.data import to_global
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig
    from determined_tpu.utils.chip import require_tpu

    device = require_tpu("scripts/ckpt_stall.py")
    n = device["count"]
    hp = {
        "lr": 3e-4,
        "global_batch_size": 8 * n,
        "seq_len": 1024,
        "vocab_size": 32768,
        "d_model": 2048,
        "n_layers": 8,
        "n_heads": 16,
        "dataset_size": 64 * n,
        "bf16": True,
        "attention": "flash",
        "warmup_steps": 10,
    }
    ckpt_dir = tempfile.mkdtemp(prefix="dtpu-stall-")
    ctx = train.init(
        hparams=hp,
        mesh_config=MeshConfig(data=n),
        core_context=core._dummy_init(checkpoint_dir=ckpt_dir),
        seed=0,
    )
    trainer = train.Trainer(LMTrial(ctx))
    trainer._setup()

    it = iter(trainer.train_loader)
    step = trainer._train_step

    def run_steps(k):
        t0 = time.perf_counter()
        for _ in range(k):
            trainer.state = step(trainer.state, to_global(next(it), trainer.mesh))
        jax.device_get(trainer.state.metric_count)  # a value fetch is the sync
        return (time.perf_counter() - t0) / k

    for _ in range(5):  # warmup/compile
        trainer.state = step(trainer.state, to_global(next(it), trainer.mesh))
    jax.device_get(trainer.state.metric_count)
    base_step_s = run_steps(10)

    state_bytes = sum(
        a.size * a.dtype.itemsize
        for a in jax.tree.leaves((trainer.state.params, trainer.state.opt_state))
    )

    # --- sync save stall ---
    t0 = time.perf_counter()
    trainer._save_checkpoint(asynchronous=False)
    sync_stall_s = time.perf_counter() - t0

    # --- async: start stall + overlapped steps + drain stall ---
    t0 = time.perf_counter()
    trainer._save_checkpoint()
    start_stall_s = time.perf_counter() - t0
    overlap_step_s = run_steps(10)   # steps advance while the writer runs
    t0 = time.perf_counter()
    trainer._drain_pending_save()
    drain_stall_s = time.perf_counter() - t0

    print(json.dumps({
        "metric": "checkpoint_save_stall",
        "device": device,
        "state_gb": round(state_bytes / 1e9, 2),
        "base_step_ms": round(base_step_s * 1e3, 1),
        "sync_stall_ms": round(sync_stall_s * 1e3, 1),
        "async_start_stall_ms": round(start_stall_s * 1e3, 1),
        "async_drain_stall_ms": round(drain_stall_s * 1e3, 1),
        "overlap_step_ms": round(overlap_step_s * 1e3, 1),
        "stall_reduction": round(
            1 - (start_stall_s + drain_stall_s) / max(sync_stall_s, 1e-9), 3),
    }))


if __name__ == "__main__":
    main()
