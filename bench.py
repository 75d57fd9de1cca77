"""Benchmark: prints ONE JSON line {"metric", "value", "unit", "vs_baseline"}.

Flagship workload: decoder-only transformer LM training step (the class of
model the reference platform's hf_trainer/deepspeed examples train), sized
to fill one chip: ~600M params (d=2048, L=8, heads=16 -> head_dim=128 on
the MXU's 128 lanes), bf16 compute, f32 Adam state.

Honest reporting: alongside tokens/s the line carries ``mfu`` and
``tflops`` against the *detected chip's* bf16 peak — not a self-chosen
anchor.  ``vs_baseline`` keeps the driver-set GPU-parity north star
(BASELINE.md): an A100-class GPT training efficiency of 50 TFLOP/s/chip,
so vs_baseline > 1.0 beats GPU parity.
"""

from __future__ import annotations

import json
import time


def chip_peak_flops(device) -> float:
    # bf16 peak FLOP/s by TPU generation: the table lives with the goodput
    # ledger (observability/_goodput.py), which needs the same roofline.
    # A kind it does not know raises: no MFU against a guessed peak.
    from determined_tpu.observability import chip_peak_flops as peak_by_kind

    return peak_by_kind(getattr(device, "device_kind", ""))


def _bench_hook(env_var: str, script: str) -> None:
    """Env-gated dispatch to a scripts/bench_*.py with the same one-line
    JSON contract; exits with the script's status when the var is set."""
    import os

    if os.environ.get(env_var, "0") in ("0", ""):
        return
    import subprocess
    import sys

    raise SystemExit(
        subprocess.call(
            [
                sys.executable,
                os.path.join(
                    os.path.dirname(os.path.abspath(__file__)), "scripts", script
                ),
            ]
        )
    )


def main() -> None:
    # A/B hook for the search scheduler (docs/search-scheduler.md): serial
    # vs mesh-packed hyperparameter search, serial as the baseline
    _bench_hook("DTPU_BENCH_SEARCH", "bench_search.py")
    # searcher zoo (docs/searchers.md): trial-free simulator comparison of
    # random/ASHA/Hyperband/PBT at equal budget; milliseconds, no devices
    _bench_hook("DTPU_BENCH_SEARCHERS", "bench_searchers.py")
    # sentinel cost (docs/lint.md "SPMD correctness"): the collective-
    # sequence sentinel's digest+envelope overhead vs a bare 2-rank star,
    # so hang-to-named-error conversion stays a tracked number
    _bench_hook("DTPU_BENCH_SENTINEL", "bench_sentinel.py")
    # serving tier (docs/serving.md): continuous batching vs the naive
    # static batch over one shared kernel set, static as the baseline
    _bench_hook("DTPU_BENCH_SERVE", "bench_serve.py")
    # step-program optimizations (docs/performance.md): overlapped
    # gradient sync, quantized matmul, and pipeline-schedule A/Bs —
    # baseline reduction / bf16 arithmetic / gpipe as the respective
    # baselines; on CPU these prove structure + numerics, the TPU MFU
    # rows land next chip round
    _bench_hook("DTPU_BENCH_OVERLAP", "bench_step.py")
    _bench_hook("DTPU_BENCH_QUANT", "bench_step.py")
    # pipeline bubble: gpipe vs 1f1b vs circular-interleaved on the
    # pipe4 x data2 virtual mesh (tick model, 1f1b live-activation cap,
    # loss parity) — docs/performance.md "Pipeline schedules"
    _bench_hook("DTPU_BENCH_PIPE", "bench_step.py")
    # multi-slice: flat all-reduce vs hierarchical ICI/DCN collectives
    # on the 2-slice x 4-chip virtual mesh (fragment-only dcn payload,
    # per-hop ledger, parity) — docs/performance.md "Multi-slice"
    _bench_hook("DTPU_BENCH_MULTISLICE", "bench_step.py")

    import os

    import jax

    from determined_tpu import core, train
    from determined_tpu.data import to_global
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig
    from determined_tpu.utils.chip import require_tpu

    device = require_tpu("bench.py")
    n = device["count"]
    # env overrides for tuning sweeps (defaults are the tuned config)
    bs = int(os.environ.get("DTPU_BENCH_BS", 8)) * n
    seq = int(os.environ.get("DTPU_BENCH_SEQ", 1024))
    fused = os.environ.get("DTPU_BENCH_FUSED", "auto")
    if fused not in ("auto", "1", "0"):
        raise SystemExit("DTPU_BENCH_FUSED must be one of: auto, 1, 0")
    hp = {
        "lr": 3e-4,
        "global_batch_size": bs,
        "seq_len": seq,
        "vocab_size": 32768,
        "d_model": 2048,
        "n_layers": 8,
        "n_heads": 16,
        "dataset_size": 8 * bs,
        "bf16": True,
        "attention": "flash",
        "warmup_steps": 10,
        "fused_ce": {"auto": "auto", "1": True, "0": False}[fused],
        "ce_chunk": int(os.environ["DTPU_BENCH_CHUNK"])
        if "DTPU_BENCH_CHUNK" in os.environ
        else None,
        # per-block remat: required for very long context on one chip
        # (seq 32k activations exceed HBM without it)
        "remat": os.environ.get("DTPU_BENCH_REMAT", "0") == "1",
        # optimizer: fused single-sweep pallas adamw (auto = on-TPU) vs
        # the optax chain; DTPU_BENCH_OPT=ref for A/B sweeps
        "fused_adamw": {"auto": "auto", "fused": True, "ref": False}[
            os.environ.get("DTPU_BENCH_OPT", "auto")
        ],
        # bf16 first moment is free inside the fused kernel (conversion
        # rides the same pass) and halves mu traffic: part of the tuned
        # config.  DTPU_BENCH_MU_BF16=0 for the f32 A/B.
        "adam_mu_bf16": os.environ.get("DTPU_BENCH_MU_BF16", "1") == "1",
    }
    ctx = train.init(
        hparams=hp,
        mesh_config=MeshConfig(data=n),
        core_context=core._dummy_init(),
        seed=0,
    )
    trainer = train.Trainer(LMTrial(ctx))
    trainer._setup()

    gbs = hp["global_batch_size"]
    d, L, V = hp["d_model"], hp["n_layers"], hp["vocab_size"]
    # matmul params: attn (4 d^2) + swiglu (3 * 4 d^2) per layer + lm head;
    # fwd+bwd flops/token ~ 6 * params + attention O(seq) term
    n_params = L * (4 * d * d + 12 * d * d) + V * d
    flops_per_token = 6 * n_params + 12 * L * seq * d
    baseline_tps = 5e13 / flops_per_token * n

    def sync():
        # a value fetch: the host has the number only when every
        # dispatched step has run
        jax.device_get(trainer.state.metric_count)

    # A/B switch for the overlapped input pipeline (docs/input-pipeline.md):
    # DTPU_BENCH_PREFETCH=1 (default) feeds through the background-fetch +
    # double-buffered pipeline; =0 is the synchronous fetch->transfer->step
    # loop for like-for-like comparison on the same machine
    prefetch = os.environ.get("DTPU_BENCH_PREFETCH", "1")
    if prefetch not in ("0", "1"):
        raise SystemExit("DTPU_BENCH_PREFETCH must be 0 or 1")
    if prefetch == "1":
        from determined_tpu.data import InputPipeline

        pipeline = InputPipeline(
            trainer.train_loader, trainer.mesh, prefetch_depth=2, device_buffer=2
        )
        next_batch = lambda: next(pipeline)  # noqa: E731
    else:
        it = iter(trainer.train_loader)
        next_batch = lambda: to_global(next(it), trainer.mesh)  # noqa: E731

    step = trainer._train_step
    for _ in range(5):  # warmup/compile
        trainer.state = step(trainer.state, next_batch())
    sync()

    measured = 30
    t0 = time.perf_counter()
    for _ in range(measured):
        trainer.state = step(trainer.state, next_batch())
    sync()
    dt = time.perf_counter() - t0

    # A/B hook for the observability layer (docs/observability.md):
    # DTPU_BENCH_TRACE=1 re-runs the measured loop with the tracer's
    # per-step instrumentation (the exact data.wait/step.dispatch records
    # Trainer._fit_loop emits, plus a live shipper draining the rings) and
    # reports the overhead — the <2% contract for spans-on training
    trace = os.environ.get("DTPU_BENCH_TRACE", "0")
    if trace not in ("0", "1"):
        raise SystemExit("DTPU_BENCH_TRACE must be 0 or 1")
    trace_fields = {}
    if trace == "1":
        from determined_tpu.observability import get_tracer

        tracer = get_tracer()
        tracer.configure(enabled=True)
        tracer.start()
        mono = time.monotonic
        t0 = time.perf_counter()
        for _ in range(measured):
            w0 = mono()
            batch = next_batch()
            w1 = mono()
            trainer.state = step(trainer.state, batch)
            w2 = mono()
            tracer.record_span("data.wait", "data", w0, w1)
            tracer.record_span("step.dispatch", "step", w1, w2)
        sync()
        dt_traced = time.perf_counter() - t0
        tracer.stop()
        trace_fields = {
            "trace_overhead_pct": round(100.0 * (dt_traced / dt - 1.0), 2),
            "trace_spans": 2 * measured,
            "trace_dropped": tracer.dropped(),
        }
    if prefetch == "1":
        pipeline.close()

    tps = measured * gbs * seq / dt
    achieved = tps * flops_per_token
    peak = chip_peak_flops(jax.devices()[0]) * n
    print(
        json.dumps(
            {
                "metric": "transformer_lm_train_tokens_per_sec",
                "value": round(tps, 1),
                "unit": "tokens/s",
                "vs_baseline": round(tps / baseline_tps, 3),
                "tflops": round(achieved / 1e12, 1),
                "mfu": round(achieved / peak, 3),
                "chip": device["kind"],
                "device": device,
                "model": f"d{d}-L{L}-V{V}-seq{seq}-bs{gbs}",
                "prefetch": int(prefetch),
                **trace_fields,
            }
        )
    )


if __name__ == "__main__":
    main()
