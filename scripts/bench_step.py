"""Step-program optimization microbench: overlap / quantized matmul /
pipeline-schedule A/Bs.

The structural step-time knobs from the 0.70-MFU plateau attack
(docs/performance.md) each get a like-for-like A/B on the same machine,
emitting ONE ``bench.py``-shaped JSON row per requested mode:

- ``DTPU_BENCH_OVERLAP=1`` — baseline end-of-backward gradient reduction
  vs ``overlap_grad_sync`` (bucketed reduce-scatter / sharded optimizer /
  all-gather params).  The row carries tokens/s for both arms, the
  goodput ledger's exposed-vs-hidden comm split for both arms (the
  ``step.comm`` rows fed by the bucket-schedule model), and the measured
  max param deviation after N identical steps — the overlap restructure
  must be numerically a no-op.
- ``DTPU_BENCH_QUANT=1`` — bf16/f32 oracle vs ``quantized_matmul: int8``
  (and fp8 where supported/emulated): same seed, same data, N steps; the
  row carries both loss curves' max relative deviation against the
  stated tolerance plus tokens/s for both arms.
- ``DTPU_BENCH_PIPE=1`` — gpipe vs 1f1b vs interleaved (V=2) at fixed
  global batch on the pipe4 x data2 virtual mesh: per schedule the row
  carries the analytic tick count, the modeled bubble %, the measured
  wall-clock step time, the compiled program's max live-activation
  (temp) bytes, and the loss deviation vs the gpipe arm.
- ``DTPU_BENCH_MULTISLICE=1`` — flat all-reduce vs hierarchical
  ICI/DCN collectives on the 2-slice x 4-chip virtual mesh (slices=2):
  the row carries tokens/s for both arms, the modeled per-hop bytes
  (the hierarchical arm must put exactly 1/N_ici of the flat arm's
  payload on ``dcn``), the goodput ledger's per-hop exposed/hidden
  split, and the measured param deviation — the two-level sync must be
  numerically a no-op vs the flat collective.

On CPU the A/Bs run on the virtual 8-device mesh and prove STRUCTURE +
NUMERICS (collective layout, sharded opt state, loss parity, the 1f1b
memory cap, the interleaved tick model); the TPU MFU row is marked
"next chip round" — wall-clock wins need real async collectives and an
MXU.

    DTPU_BENCH_OVERLAP=1    python bench.py
    DTPU_BENCH_QUANT=1      python bench.py
    DTPU_BENCH_PIPE=1       python bench.py
    DTPU_BENCH_MULTISLICE=1 python bench.py
    JAX_PLATFORMS=cpu python scripts/bench_step.py overlap quant pipe multislice
"""

from __future__ import annotations

import json
import os
import statistics
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

def _cpu_virtual_devices() -> None:
    """These A/Bs need 8 devices.  Where the run is held to the CPU
    (``JAX_PLATFORMS=cpu``), ask for the virtual 8-device platform — decided
    from the environment, BEFORE jax is imported: a process that asks jax
    what it has already holds the chip, and no child could then use it."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


HP = {
    "lr": 1e-3,
    "global_batch_size": 16,
    "seq_len": int(os.environ.get("DTPU_BENCH_STEP_SEQ", 64)),
    "vocab_size": 512,
    "d_model": int(os.environ.get("DTPU_BENCH_STEP_D", 128)),
    "n_layers": 2,
    "n_heads": 4,
    "dataset_size": 256,
    "bf16": False,  # f32 keeps the numerics comparison meaningful on CPU
    "attention": "reference",
    "warmup_steps": 1,
}
STEPS = int(os.environ.get("DTPU_BENCH_STEP_STEPS", 12))


def _run_arm(opts: dict, tag: str, hp: dict, steps: int = STEPS, mesh=None):
    """One trainer run; returns (trainer, losses, tokens_per_s, ledger)."""
    import jax

    from determined_tpu import core, train
    from determined_tpu.config import ExperimentConfig, Length
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.observability import compute_ledger, get_tracer
    from determined_tpu.parallel.mesh import MeshConfig
    from determined_tpu.train import _jit_cache

    _jit_cache.clear_step_cache()
    if mesh is None:
        if jax.default_backend() == "cpu":
            mesh = MeshConfig(data=2, fsdp=4)
        else:
            mesh = MeshConfig(data=-1)
    exp = ExperimentConfig.parse({"optimizations": opts})
    ctx = train.init(
        hparams=dict(hp),
        mesh_config=mesh,
        core_context=core._dummy_init(),
        exp_config=exp,
        seed=7,
    )
    trainer = train.Trainer(LMTrial(ctx))
    losses = []
    sps = []
    orig = ctx.core.train.report_training_metrics
    ctx.core.train.report_training_metrics = lambda s, m: (
        losses.append(float(m["loss"])),
        sps.append(float(m["samples_per_second"])),
        orig(s, m),
    )
    tracer = get_tracer()
    tracer.reset()
    tracer.configure(enabled=True)
    tracer.start()
    try:
        with tracer.span("trial.run", cat="trial", trial=tag):
            trainer.fit(
                Length.batches(steps),
                report_period=Length.batches(1),
                checkpoint_policy="none",
            )
    finally:
        tracer.stop()
    ledger = compute_ledger(tracer.chrome_events(), dropped=tracer.dropped())
    # per-report samples/s; the first reports pay compile, so take the
    # median of the tail as the steady-state number
    tail = sps[len(sps) // 2:] or sps
    tokens_per_s = statistics.median(tail) * hp["seq_len"]
    return trainer, losses, tokens_per_s, ledger


def _param_maxdiff(a, b) -> float:
    import jax
    import numpy as np

    return max(
        float(
            np.abs(
                np.asarray(x, dtype=np.float64) - np.asarray(y, dtype=np.float64)
            ).max()
        )
        for x, y in zip(
            jax.tree.leaves(jax.device_get(a)), jax.tree.leaves(jax.device_get(b))
        )
    )


def _chip() -> str:
    import jax

    return getattr(jax.devices()[0], "device_kind", "unknown")


def bench_overlap() -> dict:
    import jax

    t_off, _, tps_off, led_off = _run_arm({}, "overlap-off", HP)
    t_on, _, tps_on, led_on = _run_arm(
        {"overlap_grad_sync": True, "overlap_bucket_mb": 1}, "overlap-on", HP
    )
    comm_off = led_off["experiment"].get("step.comm", {})
    comm_on = led_on["experiment"].get("step.comm", {})
    maxdiff = _param_maxdiff(t_off.state.params, t_on.state.params)
    plan = t_on._overlap_plan
    row = {
        "metric": "transformer_lm_overlap_grad_sync_tokens_per_sec",
        "value": round(tps_on, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tps_on / max(tps_off, 1e-9), 3),
        "baseline_tokens_per_s": round(tps_off, 1),
        "exposed_comm_s_baseline": comm_off.get("exposed_s"),
        "exposed_comm_s_overlap": comm_on.get("exposed_s"),
        "hidden_comm_s_overlap": comm_on.get("hidden_s"),
        "comm_model": comm_on.get("model"),
        "buckets": len(plan.buckets) if plan else 0,
        "synced_leaves": plan.synced_leaves if plan else 0,
        "numerics_param_maxdiff": maxdiff,
        "numerically_identical": maxdiff < 1e-5,
        "chip": _chip(),
        "steps": STEPS,
    }
    if jax.default_backend() != "tpu":
        row["note"] = (
            "CPU virtual mesh: structure+numerics A/B; TPU MFU row next chip round"
        )
    return row


def bench_quant() -> dict:
    import jax

    from determined_tpu.train import _quant

    _, l_ref, tps_ref, _ = _run_arm({}, "quant-ref", HP)
    _, l_int8, tps_int8, _ = _run_arm({"quantized_matmul": "int8"}, "quant-int8", HP)
    rel_dev = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(l_ref, l_int8))
    tol = float(os.environ.get("DTPU_BENCH_QUANT_TOL", 0.02))
    row = {
        "metric": "transformer_lm_quantized_matmul_tokens_per_sec",
        "value": round(tps_int8, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tps_int8 / max(tps_ref, 1e-9), 3),
        "mode": "int8",
        "baseline_tokens_per_s": round(tps_ref, 1),
        "loss_final_ref": round(l_ref[-1], 5),
        "loss_final_int8": round(l_int8[-1], 5),
        "loss_curve_max_rel_dev": round(rel_dev, 5),
        "loss_tolerance": tol,
        "within_tolerance": rel_dev <= tol,
        "fp8_supported_here": _quant.fp8_supported(),
        "chip": _chip(),
        "steps": STEPS,
    }
    if jax.default_backend() != "tpu":
        row["note"] = (
            "CPU: int8 arithmetic is emulated (no MXU) — numerics-only A/B; "
            "TPU MFU row next chip round"
        )
    return row


def bench_pipe() -> dict:
    """A/B the three microbatch schedules at fixed global batch on the
    pipe4 x data2 virtual mesh (M=8): gpipe is the baseline arm; each
    schedule reports its analytic ticks + modeled bubble, measured step
    time, compiled max live-activation (temp) bytes, and loss parity."""
    import jax

    from determined_tpu.data import to_global

    hp = dict(
        HP,
        n_layers=8,  # divides into pipe4 stages AND pipe4 x V=2 chunks
        d_model=64,
        vocab_size=256,
        pipe_microbatches=8,
    )
    steps = int(os.environ.get("DTPU_BENCH_PIPE_STEPS", 6))
    arms = {
        "gpipe": {},
        "1f1b": {"pipeline_schedule": "1f1b"},
        "interleaved": {"pipeline_schedule": "interleaved", "virtual_stages": 2},
    }
    results = {}
    losses = {}
    from determined_tpu.parallel.mesh import MeshConfig

    for name, opts in arms.items():
        trainer, arm_losses, tps, _ = _run_arm(
            opts, f"pipe-{name}", hp, steps=steps,
            mesh=MeshConfig(pipe=4, data=2),
        )
        losses[name] = arm_losses
        bm = trainer._bubble_model
        sched = bm.schedule
        # max live-activation bytes: the compiled step's temp allocation
        # (XLA's buffer assignment), measured — the 1f1b stash-vs-residual
        # claim in bytes rather than HLO shapes
        host = next(trainer.train_loader.iter_epoch(0))
        batch = to_global(host, trainer.mesh)
        with trainer.mesh:
            mem = (
                trainer._train_step_jit.lower(trainer.state, batch)
                .compile()
                .memory_analysis()
            )
        temp_bytes = getattr(mem, "temp_size_in_bytes", None)
        gbs = hp["global_batch_size"]
        step_s = gbs * hp["seq_len"] / max(tps, 1e-9)
        results[name] = {
            "ticks": sched.total_ticks,
            "bubble_ticks": sched.bubble_ticks,
            "modeled_bubble_pct": round(100.0 * bm.fraction, 2),
            "step_time_s": round(step_s, 4),
            "tokens_per_s": round(tps, 1),
            "max_live_activation_bytes": temp_bytes,
            "loss_final": round(arm_losses[-1], 6),
        }
    for name in ("1f1b", "interleaved"):
        results[name]["loss_max_dev_vs_gpipe"] = max(
            abs(a - b) for a, b in zip(losses["gpipe"], losses[name])
        )
    row = {
        "metric": "transformer_lm_pipeline_schedule_tokens_per_sec",
        "value": results["interleaved"]["tokens_per_s"],
        "unit": "tokens/s",
        "vs_baseline": round(
            results["interleaved"]["tokens_per_s"]
            / max(results["gpipe"]["tokens_per_s"], 1e-9),
            3,
        ),
        "mesh": "pipe4xdata2",
        "microbatches": 8,
        "schedules": results,
        "parity_ok": (
            results["1f1b"]["loss_max_dev_vs_gpipe"] < 1e-5
            and results["interleaved"]["loss_max_dev_vs_gpipe"] < 1e-5
        ),
        # None (not False) when the backend's memory_analysis lacks temp
        # accounting: the exit gate must not fail on an unavailable metric
        "memory_win_1f1b": (
            results["1f1b"]["max_live_activation_bytes"]
            < results["gpipe"]["max_live_activation_bytes"]
            if results["1f1b"]["max_live_activation_bytes"] is not None
            and results["gpipe"]["max_live_activation_bytes"] is not None
            else None
        ),
        "chip": _chip(),
        "steps": steps,
    }
    if jax.default_backend() != "tpu":
        row["note"] = (
            "CPU virtual mesh: schedule structure + numerics A/B (tick "
            "model, 1f1b memory cap, parity); TPU MFU row next chip round"
        )
    return row


def bench_multislice() -> dict:
    """A/B flat all-reduce vs hierarchical ICI/DCN collectives on the
    2-slice x 4-chip virtual mesh: flat shards the gradient sync over
    every mesh axis including ``dcn``; hierarchical reduce-scatters
    within each slice first so only the 1/N_ici fragment crosses the
    slow inter-slice hop.  The row carries both arms' tokens/s, the
    modeled per-hop bytes (hier dcn must be exactly flat dcn / N_ici),
    the ledger's per-hop exposed/hidden split, and param parity."""
    import jax

    from determined_tpu.parallel.mesh import MeshConfig

    mesh = MeshConfig(num_slices=2, data=2, fsdp=2)
    base = {"overlap_grad_sync": True, "overlap_bucket_mb": 1}
    t_flat, _, tps_flat, led_flat = _run_arm(
        dict(base), "ms-flat", HP, mesh=mesh
    )
    t_hier, _, tps_hier, led_hier = _run_arm(
        dict(base, hierarchical_collectives=True), "ms-hier", HP, mesh=mesh
    )
    maxdiff = _param_maxdiff(t_flat.state.params, t_hier.state.params)
    flat_comm = t_flat._overlap_plan.comm
    hier_comm = t_hier._overlap_plan.comm
    assert t_hier._overlap_plan.hierarchical_dcn == 2
    n_ici = mesh.data * mesh.fsdp  # chips per slice
    hops_flat = led_flat["experiment"].get("step.comm", {}).get("hops", {})
    hops_hier = led_hier["experiment"].get("step.comm", {}).get("hops", {})
    row = {
        "metric": "transformer_lm_hierarchical_collectives_tokens_per_sec",
        "value": round(tps_hier, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tps_hier / max(tps_flat, 1e-9), 3),
        "baseline_tokens_per_s": round(tps_flat, 1),
        "mesh": "dcn2x(data2xfsdp2)",
        "slices": 2,
        "modeled_dcn_bytes_flat": flat_comm.dcn_bytes_per_step,
        "modeled_dcn_bytes_hier": hier_comm.dcn_bytes_per_step,
        "dcn_fragment_ok": (
            hier_comm.dcn_bytes_per_step
            == flat_comm.dcn_bytes_per_step // n_ici
        ),
        "hops_flat": hops_flat,
        "hops_hier": hops_hier,
        "numerics_param_maxdiff": maxdiff,
        "numerically_identical": maxdiff < 1e-5,
        "chip": _chip(),
        "steps": STEPS,
    }
    if jax.default_backend() != "tpu":
        row["note"] = (
            "CPU virtual slices (contiguous device blocks): structure + "
            "numerics A/B; the DCN wall-clock win needs real inter-slice "
            "links — TPU MULTICHIP row next chip round"
        )
    return row


_MODES = ("overlap", "quant", "pipe", "multislice")


def main() -> None:
    modes = [m for m in sys.argv[1:] if m in _MODES]
    if not modes:
        env_by_mode = {
            "overlap": "DTPU_BENCH_OVERLAP",
            "quant": "DTPU_BENCH_QUANT",
            "pipe": "DTPU_BENCH_PIPE",
            "multislice": "DTPU_BENCH_MULTISLICE",
        }
        for mode, var in env_by_mode.items():
            if os.environ.get(var, "0") not in ("0", ""):
                modes.append(mode)
    if not modes:
        modes = list(_MODES)
    _cpu_virtual_devices()
    ok = True
    for mode in modes:
        if mode == "overlap":
            row = bench_overlap()
            ok = ok and row["numerically_identical"]
        elif mode == "quant":
            row = bench_quant()
            ok = ok and row["within_tolerance"]
        elif mode == "multislice":
            row = bench_multislice()
            ok = ok and row["numerically_identical"] and row["dcn_fragment_ok"]
        else:
            row = bench_pipe()
            ok = ok and row["parity_ok"] and row["memory_win_1f1b"] is not False
        print(json.dumps(row))
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
