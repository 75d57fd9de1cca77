"""Serving benchmark: continuous batching vs naive static batching.

Drives the SAME synthetic request trace through both engines
(``determined_tpu/serve/engine.py``) over one shared set of compiled
prefill/decode kernels — identical model, cache, sampling, and admission
machinery; the ONLY difference is the scheduling policy:

- **continuous**: requests join the running decode batch between any two
  steps and retire immediately (the production ``ServeEngine``);
- **static**: a batch decodes until EVERY member finishes before the next
  batch forms (``StaticBatchEngine``) — short requests idle their lane
  behind the longest member.

Workload: open-loop arrivals (Poisson at ``--rate``, or an instantaneous
burst at the default ``--rate 0`` — the capacity measurement) with a
bimodal output-length mix (mostly short completions, a long tail), which
is exactly the mix static batching handles worst and production traffic
actually looks like.

Two more A/B sections ride the same JSON line (ISSUE 17 fast path):

- **prefix**: the continuous engine with the prefix cache on vs OFF over a
  workload where ``--shared-frac`` of requests open with one shared system
  prompt — warm admissions map the cached blocks and prefill only the
  unique tail, so TTFT is the number to watch;
- **lazy_decode**: per-step decode latency, paged decode attention
  (``decode_chunk_blocks`` > 0) vs the full-table gather, at a live
  context a fraction of the table width (where laziness pays) and at full
  context (where it must not lose).

Reports requests/s, p50/p95 end-to-end latency, and time-to-first-token
per arm, plus the requests/s ratio as the headline metric — ONE JSON line,
the ``bench.py`` schema family (DTPU_BENCH_SERVE=1 hooks it there).

    JAX_PLATFORMS=cpu python scripts/bench_serve.py
    JAX_PLATFORMS=cpu python scripts/bench_serve.py --rate 30 --requests 60
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Any, Dict, List

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def make_trace(args: argparse.Namespace) -> List[Dict[str, Any]]:
    """The request trace both arms replay: arrival offsets + prompts +
    output lengths.  Bimodal outputs: ``long_frac`` of requests generate
    ``long_tokens``, the rest ``short_tokens``."""
    rng = np.random.default_rng(args.seed)
    trace = []
    t = 0.0
    for i in range(args.requests):
        if args.rate > 0:
            t += float(rng.exponential(1.0 / args.rate))
        prompt_len = int(rng.integers(4, args.max_prompt_len - 1))
        long = rng.random() < args.long_frac
        trace.append(
            {
                "arrival": t,
                "prompt": [int(x) for x in rng.integers(0, 64, size=prompt_len)],
                "max_new_tokens": args.long_tokens if long else args.short_tokens,
                "temperature": 0.0 if i % 2 else 0.7,
                "seed": i,
            }
        )
    return trace


def percentile(xs: List[float], p: float) -> float:
    return float(np.percentile(np.asarray(xs), p)) if xs else float("nan")


def run_arm(
    engine: Any,
    trace: List[Dict[str, Any]],
    warmup: List[List[int]] | None = None,
) -> Dict[str, Any]:
    from determined_tpu.serve import AdmissionRejected

    engine.start()
    # warm every kernel outside the measurement (shared across arms
    # anyway); the prefix arms pass a repeated prompt so the warm-path
    # suffix kernel compiles here too, not under the first measured hit
    for prompt in warmup if warmup is not None else [trace[0]["prompt"]]:
        engine.generate(prompt, max_new_tokens=2)
    rejected = 0
    reqs = []
    t0 = time.monotonic()
    for item in trace:
        now = time.monotonic() - t0
        if item["arrival"] > now:
            time.sleep(item["arrival"] - now)
        try:
            reqs.append(
                engine.submit(
                    item["prompt"],
                    max_new_tokens=item["max_new_tokens"],
                    temperature=item["temperature"],
                    seed=item["seed"],
                )
            )
        except AdmissionRejected:
            rejected += 1
    for r in reqs:
        assert r.done.wait(600), "request starved"
        assert r.error is None, r.error
    makespan = max(r.finished_at for r in reqs) - t0
    engine.stop()
    lat = [r.latency_s for r in reqs]
    ttft = [r.ttft_s for r in reqs]
    return {
        "requests": len(reqs),
        "rejected": rejected,
        "makespan_s": round(makespan, 4),
        "requests_per_s": round(len(reqs) / makespan, 3),
        "tokens_generated": sum(len(r.output) for r in reqs),
        "p50_latency_s": round(percentile(lat, 50), 4),
        "p95_latency_s": round(percentile(lat, 95), 4),
        "mean_ttft_s": round(float(np.mean(ttft)), 4),
        "p95_ttft_s": round(percentile(ttft, 95), 4),
    }


def _shared_prefix(args: argparse.Namespace) -> List[int]:
    rng = np.random.default_rng(args.seed + 1)
    return [int(x) for x in rng.integers(0, 64, size=args.shared_prefix_len)]


def make_prefix_trace(args: argparse.Namespace) -> List[Dict[str, Any]]:
    """``--shared-frac`` of requests open with ONE shared system prompt of
    ``--shared-prefix-len`` tokens followed by a short unique tail; the
    rest are fully random prompts of the same total length."""
    rng = np.random.default_rng(args.seed + 1)
    shared = _shared_prefix(args)
    trace = []
    for i in range(args.prefix_requests):
        tail = [int(x) for x in rng.integers(0, 64, size=8)]
        if rng.random() < args.shared_frac:
            prompt = shared + tail
        else:
            prompt = [
                int(x)
                for x in rng.integers(0, 64, size=args.shared_prefix_len + 8)
            ]
        trace.append(
            {
                "arrival": 0.0,  # burst: queue pressure makes TTFT honest
                "prompt": prompt,
                "max_new_tokens": 4,
                "temperature": 0.0,
                "seed": i,
            }
        )
    return trace


def run_prefix_ab(args) -> Dict[str, Any]:
    """ServeEngine with the prefix cache on vs off, same trace.  Uses a
    bigger model than the capacity arms (d256/L4): prefill must be
    compute-bound for the suffix-only path to show its real shape — at toy
    sizes dispatch overhead drowns the tokens saved."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta as flax_meta

    from determined_tpu.models.transformer import TransformerConfig, TransformerLM
    from determined_tpu.serve import DecodeKernels, ServeConfig, ServeEngine

    model_cfg = TransformerConfig(
        vocab_size=64, d_model=256, n_layers=4, n_heads=8, n_kv_heads=4,
        max_seq_len=512, dtype=jnp.float32, attention_impl="reference",
    )
    variables = flax_meta.unbox(
        TransformerLM(model_cfg).init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))
    )
    trace = make_prefix_trace(args)
    arms = {}
    for on in (True, False):
        serve_cfg = ServeConfig(
            block_size=32,
            num_blocks=128,
            max_batch=args.max_batch,
            max_prompt_len=args.shared_prefix_len + 8,
            max_new_tokens=4,
            queue_depth=max(args.prefix_requests, 4),
            prefix_cache=on,
        )
        eng = ServeEngine(DecodeKernels(model_cfg, variables, serve_cfg))
        # two identical warmup prompts: the repeat compiles the warm-path
        # suffix kernel (a cold miss compiles the wide prefill)
        shared = _shared_prefix(args)
        res = run_arm(eng, trace, warmup=[shared + [0], shared + [0]])
        st = eng.stats()
        res["prefix_hit_rate"] = st["prefix_hit_rate"]
        res["prefix_tokens_saved"] = st["prefix_tokens_saved"]
        arms["on" if on else "off"] = res
    speedup = (
        arms["off"]["mean_ttft_s"] / arms["on"]["mean_ttft_s"]
        if arms["on"]["mean_ttft_s"]
        else None
    )
    return {
        "shared_frac": args.shared_frac,
        "shared_prefix_len": args.shared_prefix_len,
        "requests": args.prefix_requests,
        "model": "d256-L4-h8kv4-v64 (CPU test config)",
        "on": arms["on"],
        "off": arms["off"],
        "ttft_speedup": round(speedup, 3) if speedup else None,
    }


def run_decode_ab(model_cfg, variables, args) -> Dict[str, Any]:
    """Per-step decode latency, chunked vs full-table gather, at a live
    context 1/8 of the table width and again at full context.  Times the
    compiled kernel directly: block-table contents do not change the work,
    so no prefill is needed."""
    from determined_tpu.serve import DecodeKernels, ServeConfig

    table_tokens = args.decode_table_tokens
    serve = {}
    for chunk in (args.decode_chunk_blocks, 0):
        serve_cfg = ServeConfig(
            block_size=4,
            num_blocks=512,
            max_batch=args.max_batch,
            max_prompt_len=table_tokens - 8,
            max_new_tokens=8,
            queue_depth=4,
            decode_chunk_blocks=chunk,
        )
        serve[chunk] = DecodeKernels(model_cfg, variables, serve_cfg)
    t_blocks = serve[0].serve_cfg.blocks_per_seq

    def step_ms(kernels, live_tokens: int) -> float:
        b = args.max_batch
        tokens = np.ones(b, np.int32)
        positions = np.full(b, live_tokens - 1, np.int32)
        tables = np.tile(
            (1 + np.arange(t_blocks, dtype=np.int32)) % kernels.serve_cfg.num_blocks,
            (b, 1),
        )
        for _ in range(3):  # compile + warm
            kernels.decode(tokens, positions, tables)
        t0 = time.monotonic()
        iters = 20
        for _ in range(iters):
            kernels.decode(tokens, positions, tables)
        return (time.monotonic() - t0) / iters * 1e3

    out: Dict[str, Any] = {
        "table_tokens": table_tokens,
        "table_blocks": t_blocks,
        "chunk_blocks": args.decode_chunk_blocks,
    }
    for label, live in (("short_ctx", table_tokens // 8),
                        ("full_ctx", table_tokens)):
        lazy = step_ms(serve[args.decode_chunk_blocks], live)
        full = step_ms(serve[0], live)
        out[label] = {
            "live_tokens": live,
            "lazy_ms": round(lazy, 3),
            "full_ms": round(full, 3),
            "speedup": round(full / lazy, 3) if lazy else None,
        }
    return out


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--requests", type=int, default=120)
    p.add_argument("--rate", type=float, default=0.0,
                   help="Poisson arrivals/s; 0 = instantaneous burst "
                        "(capacity measurement)")
    p.add_argument("--long-frac", type=float, default=0.2)
    p.add_argument("--short-tokens", type=int, default=2)
    p.add_argument("--long-tokens", type=int, default=96)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-prompt-len", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--shared-frac", type=float, default=0.7,
                   help="fraction of prefix-A/B requests opening with the "
                        "shared system prompt")
    p.add_argument("--shared-prefix-len", type=int, default=232)
    p.add_argument("--prefix-requests", type=int, default=24)
    p.add_argument("--decode-table-tokens", type=int, default=512,
                   help="block-table span (tokens) for the lazy-decode A/B")
    p.add_argument("--decode-chunk-blocks", type=int, default=8)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    from flax.core import meta as flax_meta

    from determined_tpu.models.transformer import TransformerConfig, TransformerLM
    from determined_tpu.serve import (
        DecodeKernels,
        ServeConfig,
        ServeEngine,
        StaticBatchEngine,
    )

    model_cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        max_seq_len=128, dtype=jnp.float32, attention_impl="reference",
    )
    variables = flax_meta.unbox(
        TransformerLM(model_cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )
    serve_cfg = ServeConfig(
        block_size=4,
        num_blocks=256,
        max_batch=args.max_batch,
        max_prompt_len=args.max_prompt_len,
        max_new_tokens=args.long_tokens,
        queue_depth=max(args.requests, 4),  # open loop: absorb the burst
    )
    kernels = DecodeKernels(model_cfg, variables, serve_cfg)
    trace = make_trace(args)

    static = run_arm(StaticBatchEngine(kernels), trace)
    continuous = run_arm(ServeEngine(kernels), trace)
    ratio = (
        continuous["requests_per_s"] / static["requests_per_s"]
        if static["requests_per_s"]
        else None
    )

    prefix = run_prefix_ab(args)
    # the decode A/B spans a longer context than the capacity arms need;
    # params are max_seq_len-independent (RoPE is computed on the fly)
    long_cfg = dataclasses.replace(
        model_cfg, max_seq_len=max(args.decode_table_tokens, model_cfg.max_seq_len)
    )
    lazy_decode = run_decode_ab(long_cfg, variables, args)

    print(
        json.dumps(
            {
                "metric": "serve_continuous_vs_static_requests_per_sec",
                "value": round(ratio, 3) if ratio else None,
                "unit": "x",
                # the naive static batch IS the baseline for this metric
                "vs_baseline": round(ratio, 3) if ratio else None,
                "continuous": continuous,
                "static": static,
                "prefix": prefix,
                "lazy_decode": lazy_decode,
                "requests": args.requests,
                "rate_per_s": args.rate,
                "long_frac": args.long_frac,
                "short_tokens": args.short_tokens,
                "long_tokens": args.long_tokens,
                "max_batch": args.max_batch,
                "model": "d32-L2-h4kv2-v64 (CPU test config)",
            }
        )
    )


if __name__ == "__main__":
    main()
