"""A serving cell: the program's ``ServeEngine`` under an open or closed loop.

The system under test is the engine exactly as ``dtpu serve`` builds it —
``ServeEngine(DecodeKernels(model_cfg, params, serve_cfg))``, its own thread
running its own loop — fed through ``engine.submit`` by this file's load
generator.  The benchmark's readings come from the requests' own stamps
(``first_token_at``, ``finished_at``), from thin wrappers this file puts
round the engine's three device calls (step boundaries for the slice rates)
and from the program's own spans (``serve.step``, ``serve.sample``,
``serve.decode`` and its children).  Nothing here knows where the program
samples: on the host from ``decode``'s logits, or inside the decode program.
"""

from __future__ import annotations

import itertools
import statistics
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import stats, traffic as traffic_mod
from .observe import Observations, Profiler, tracer_epoch
from .spec import SpecError

mono = time.monotonic


class StepRecorder:
    """Wrappers round ``kernels.decode`` / ``prefill`` / ``prefill_suffix``.

    Every run keeps one tuple a call, with the engine's own count of the
    tokens it has emitted so far (the earlier line's slice rates are cut at
    these step boundaries).  What ``decode`` returns (logits for a host
    sampler, or token ids) passes through untouched.
    """

    def __init__(self, engine: Any) -> None:
        self.engine = engine
        #: (t0, t1, active lanes, live kv tokens, engine's tokens_generated at t0)
        self.decodes: List[Tuple[float, float, int, int, int]] = []
        #: (t0, t1)
        self.prefills: List[Tuple[float, float]] = []
        self._restore: List[Callable[[], None]] = []

    def install(self) -> None:
        k = self.engine.kernels
        decode, prefill, suffix = k.decode, k.prefill, k.prefill_suffix

        def timed_decode(tokens: np.ndarray, positions: np.ndarray, tables: np.ndarray) -> np.ndarray:
            # a decode call starts when the last step's sampling and every
            # admission since are done: a whole-step boundary of the counter
            emitted = int(self.engine.stats()["tokens_generated"])
            t0 = mono()
            out = decode(tokens, positions, tables)
            live = positions >= 0
            self.decodes.append(
                (t0, mono(), int(live.sum()), int(positions[live].sum() + live.sum()), emitted)
            )
            return out

        def timed(fn: Callable[..., np.ndarray]) -> Callable[..., np.ndarray]:
            def call(*args: Any) -> np.ndarray:
                t0 = mono()
                out = fn(*args)
                self.prefills.append((t0, mono()))
                return out

            return call

        k.decode, k.prefill, k.prefill_suffix = timed_decode, timed(prefill), timed(suffix)
        self._restore.append(lambda: (setattr(k, "decode", decode), setattr(k, "prefill", prefill), setattr(k, "prefill_suffix", suffix)))

    def remove(self) -> None:
        for undo in self._restore:
            undo()
        self._restore = []

    def marks(self) -> List[Tuple[float, float]]:
        """(a decode call's start, the engine's ``tokens_generated`` then)."""
        return [(d[0], float(d[4])) for d in self.decodes]


def _submit(engine: Any, req: traffic_mod.Request, temperature: float) -> Any:
    return engine.submit(
        req.prompt,
        max_new_tokens=req.max_new_tokens,
        temperature=temperature,
        seed=req.seed,
    )


def _warm(engine: Any, traffic: Dict[str, Any], vocab: int, temperature: float) -> None:
    """One request down each path the cell's traffic takes: the wide
    prefill and the decode step, and the suffix prefill where prompts share
    a prefix.  Nothing may compile once the window is open."""
    block = int(traffic["engine"]["block_size"])
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, vocab, size=3 * block, dtype=np.int64).tolist()
    engine.generate(prompt, max_new_tokens=3, temperature=temperature, seed=1, timeout=1100.0)
    if traffic.get("shared_prefix"):
        again = prompt[: 2 * block] + rng.integers(1, vocab, size=block, dtype=np.int64).tolist()
        engine.generate(again, max_new_tokens=3, temperature=temperature, seed=2, timeout=1100.0)


def _tpot_ms(records: List[Dict[str, Any]], t_open: float, t_close: float, not_before: float) -> List[float]:
    out = []
    for r in records:
        if r["error"] is None and r["finished_at"] is not None and r["tokens"] >= 2:
            if t_open <= r["finished_at"] <= t_close:
                start = max(r["first_token_at"], not_before)
                out.append(1000.0 * (r["finished_at"] - start) / (r["tokens"] - 1))
    return out


def _record(req: Any, due: Optional[float] = None) -> Dict[str, Any]:
    return {
        "due": due,
        "first_token_at": req.first_token_at,
        "finished_at": req.finished_at,
        "tokens": len(req.output),
        "error": req.error,
    }


def check(cell: Any) -> None:
    """What can be refused before a device is touched."""
    if cell.chips != 1:
        raise SpecError(
            f"cell {cell.name}: a serving cell runs one engine on one chip; {cell.chips} chips "
            "(replicas behind a router, a sharded model) need a runner that benchlib/serve_run.py is not"
        )


def build_engine(cell: Any, arch: Any, seed: int) -> Tuple[Any, Any, Any]:
    """The engine as ``dtpu serve`` builds it, on weights made on the device
    from the seed (no checkpoint, so no Trainer is built to reach them)."""
    from determined_tpu.serve.config import ServeConfig
    from determined_tpu.serve.engine import DecodeKernels, ServeEngine

    serve_cfg = ServeConfig(**cell.traffic["engine"])
    model_cfg = arch.model_config(cell.config, serve_cfg.max_seq_len)
    params = arch.init_params(model_cfg, seed)
    return ServeEngine(DecodeKernels(model_cfg, params, serve_cfg)), params, model_cfg


def run(
    cell: Any, arch: Any, seed: int, seconds: float, traced: bool,
    t_start: float, say: Callable[..., None], trace_dir: str,
) -> Dict[str, Any]:
    import jax

    from determined_tpu.observability import get_tracer
    from determined_tpu.serve import engine as _  # noqa: F401  (so that the next line times the imports)
    from determined_tpu.serve.scheduler import AdmissionRejected

    say("setup", stage="program_imported", seconds_since_start=mono() - t_start)
    config, traffic = cell.config, cell.traffic
    engine, params, model_cfg = build_engine(cell, arch, seed)
    jax.block_until_ready(params)
    say("setup", stage="weights_on_device", seconds_since_start=mono() - t_start)
    serve_cfg = engine.cfg
    vocab, temperature = model_cfg.vocab_size, float(traffic["temperature"])
    tracer = get_tracer()
    tracer.configure(enabled=traced)
    epoch = tracer_epoch(tracer) if traced else 0.0
    recorder = StepRecorder(engine)
    recorder.install()
    engine.start()
    kv_samples: List[float] = []
    profiler = Profiler(trace_dir) if traced else None
    closed = traffic["kind"] == "serve-closed"
    try:
        _warm(engine, traffic, vocab, temperature)
        say("setup", stage="programs_warm", seconds_since_start=mono() - t_start)

        def sample_pool() -> None:
            kv = engine.allocator.stats()
            kv_samples.append(kv["used"] / max(1, kv["capacity"]))

        if closed:
            result = _closed_loop(
                engine, traffic, seed, vocab, temperature, seconds, sample_pool, profiler
            )
        else:
            result = _open_loop(
                engine, traffic, seed, vocab, temperature, seconds, sample_pool,
                profiler, AdmissionRejected,
            )
    finally:
        engine.stop()
        recorder.remove()
        if profiler is not None:
            profiler.close()
    t_open, t_close = result["window"]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices()[: cell.chips])

    marks = recorder.marks()
    inside = [d for d in recorder.decodes if t_open <= d[1] <= t_close]
    slices = int(traffic.get("slices", 10))
    tpots = _tpot_ms(result["records"], t_open, t_close, result.get("not_before", 0.0))
    values: Dict[str, float] = {"setup_s": t_open - t_start}
    if "tokens" in result:
        # all the tokens the engine counted between the window's two edges
        # over all of its length
        n_open, n_close = result["tokens"]
        values["serve_tokens_per_s"] = (n_close - n_open) / (t_close - t_open)
    if tpots:
        values["tpot_p50_ms"] = statistics.median(tpots)
    ttfts = result.get("ttft_ms")
    if ttfts:
        values["ttft_p90_ms"] = stats.percentile(ttfts, 90)
        values["ttft_p50_ms"] = stats.percentile(ttfts, 50)
    refills = [0] * slices
    for t0, _ in recorder.prefills:
        if t_open <= t0 < t_close:
            refills[min(slices - 1, int((t0 - t_open) / (t_close - t_open) * slices))] += 1
    # beside the metric, for a reader: the same rate between the window's
    # first and last step boundary, and slice by slice (a stall shows as a slice)
    whole = stats.slice_rates(marks, t_open, t_close, 1)
    say(
        "serve.window",
        window_s=t_close - t_open,
        decode_steps=len(inside),
        tokens_in_window=result.get("tokens"),
        step_aligned_mean_tokens_per_s=whole[0] if whole else None,
        slice_tokens_per_s=stats.slice_rates(marks, t_open, t_close, slices),
        prefills_per_slice=refills,
        tpot_samples=len(tpots),
        tpot_ms_p10_p50_p90=[stats.percentile(tpots, q) for q in (10, 50, 90)] if tpots else None,
        ttft_samples=len(ttfts) if ttfts else 0,
        ttft_ms_p50_p90_p99=[stats.percentile(ttfts, q) for q in (50, 90, 99)] if ttfts else None,
        generator_late_ms_p50_max=result.get("late_ms"),
        engine=engine.stats(),
    )

    counters = {
        "serve.decode_steps": float(len(inside)),
        "serve.lanes_active": float(sum(d[2] for d in inside)),
        "serve.lanes_total": float(len(inside) * serve_cfg.max_batch),
        "serve.live_kv_tokens": float(sum(d[3] for d in inside)),
        "serve.kv_pool_used_share": float(sum(kv_samples)),
        "serve.kv_pool_reads": float(len(kv_samples)),
    }
    for name in ("ttft_p50_ms", "ttft_p90_ms"):
        if name in values:
            counters["serve." + name] = values[name]
    spans: List[Tuple[str, float, float]] = []
    if traced:
        spans += [("bench.serve.decode_call", t0, t1 - t0) for t0, t1, *_ in recorder.decodes]
        spans += [("bench.serve.prefill_call", t0, t1 - t0) for t0, t1 in recorder.prefills]
        spans += result.get("spans", [])
    obs = Observations(
        window=(t_open, t_close), spans=spans, counters=counters,
        program_events=tracer.chrome_events() if traced else [],
        profiler=profiler, config=config, traffic=traffic, chips=cell.chips,
        program_epoch=epoch, arch=arch, data_dir=cell.data_dir,
    )
    correct, detail = _check(engine, params, config, arch, vocab, seed)
    say("serve.check", **detail)
    return {
        "values": values,
        "attempted": result["attempted"],
        "failed": result["failed"],
        "correct": bool(correct and result["failed"] == 0),
        "check": detail,
        "memory_peak_bytes": peak,
        "observations": obs,
    }


# ---------------------------------------------------------------------------
# the two loops
# ---------------------------------------------------------------------------


def _closed_loop(
    engine: Any, traffic: Dict[str, Any], seed: int, vocab: int, temperature: float,
    seconds: float, sample_pool: Callable[[], None], profiler: Optional[Any],
) -> Dict[str, Any]:
    plan = traffic_mod.closed_plan(traffic, seed, vocab)
    stop = threading.Event()
    sent: List[List[Any]] = [[] for _ in plan.first]
    firsts = [_submit(engine, r, temperature) for r in plan.first]

    def client(c: int) -> None:
        firsts[c].done.wait()
        for lap in itertools.count():
            for i, nxt in enumerate(plan.later[c]):
                if stop.is_set():
                    return
                if lap:
                    # the same sizes again with new contents: a repeated
                    # prompt would be served from the prefix cache
                    nxt = traffic_mod.with_new_contents(nxt, [int(seed), c, lap, i], vocab)
                req = _submit(engine, nxt, temperature)
                sent[c].append(req)
                req.done.wait()

    threads = [
        threading.Thread(target=client, args=(c,), name=f"bench-client-{c}", daemon=True)
        for c in range(len(firsts))
    ]
    for t in threads:
        t.start()
    # the window opens once every lane holds its part-done first request:
    # that is the loop's steady state (see traffic.closed_plan)
    deadline = mono() + 300.0
    while any(r.first_token_at is None and r.error is None for r in firsts):
        if mono() > deadline:
            raise RuntimeError("the first wave was not admitted in 300 s")
        time.sleep(0.005)
    t_open = mono()
    n_open = int(engine.stats()["tokens_generated"])
    if profiler is not None:
        profiler.run_in_background(t_open + 0.4 * seconds, min(3.0, seconds / 4))
    while mono() < t_open + seconds:
        time.sleep(min(0.5, max(0.0, t_open + seconds - mono())))
        sample_pool()
    t_close = mono()
    n_close = int(engine.stats()["tokens_generated"])
    reqs = firsts + [r for per in sent for r in list(per)]
    records = [_record(r) for r in reqs]
    stop.set()
    engine.stop()  # fails what is in flight, which wakes every client
    for t in threads:
        t.join(timeout=30.0)
    done = [r for r in records if r["finished_at"] is not None and t_open <= r["finished_at"] <= t_close]
    failed = sum(1 for r in done if r["error"] is not None)
    return {
        "window": (t_open, t_close),
        "tokens": (n_open, n_close),
        "records": records,
        "not_before": t_open,
        "attempted": len(done),
        "failed": failed,
    }


def _open_loop(
    engine: Any, traffic: Dict[str, Any], seed: int, vocab: int, temperature: float,
    seconds: float, sample_pool: Callable[[], None], profiler: Optional[Any],
    rejected: type,
) -> Dict[str, Any]:
    sched = traffic_mod.open_schedule(traffic, seed, vocab, seconds)
    t0 = mono() + 0.05
    t_open, t_close = t0 + sched.window_start, t0 + sched.window_end
    if profiler is not None:
        profiler.run_in_background(t_open + 0.4 * seconds, min(3.0, seconds / 4))
    window: List[Tuple[float, Optional[Any]]] = []
    late: List[float] = []
    spans: List[Tuple[str, float, float]] = []
    last_pool = 0.0
    in_window = set(map(id, sched.window))

    def first_tokens_in() -> bool:
        return all(g is None or g.first_token_at is not None or g.error is not None for _, g in window)

    for req in sched.ramp + sched.window + sched.tail:
        due = t0 + req.due
        while True:
            now = mono()
            if now >= due:
                break
            if now - last_pool > 1.0 and t_open <= now <= t_close:
                sample_pool()
                last_pool = now
            time.sleep(min(due - now, 0.25))
        if due > t_close and first_tokens_in():
            break
        s0 = mono()
        try:
            got = _submit(engine, req, temperature)
        except rejected:
            got = None
        s1 = mono()
        if id(req) in in_window:
            window.append((due, got))
            late.append(1000.0 * (s0 - due))
            spans.append(("bench.load.submit", s0, s1 - s0))
    grace = mono() + 5.0
    while not first_tokens_in() and mono() < grace:
        time.sleep(0.01)
    records = [
        _record(g, due) if g is not None
        else {"due": due, "first_token_at": None, "finished_at": None, "tokens": 0, "error": "refused"}
        for due, g in window
    ]
    worst = 1000.0 * (mono() - t_open)
    ttft, failed, still = [], 0, 0
    for r in records:
        if r["error"] is not None or r["first_token_at"] is None:
            failed += 1
            ttft.append(worst)  # a failed or refused request counts as the largest
        else:
            ttft.append(1000.0 * (r["first_token_at"] - r["due"]))
            if r["finished_at"] is None:
                still += 1
    return {
        "window": (t_open, t_close),
        "records": records,
        "ttft_ms": ttft,
        "late_ms": [stats.percentile(late, 50), max(late)] if late else None,
        "attempted": len(records) - still,
        "failed": failed,
        "spans": spans,
    }


# ---------------------------------------------------------------------------
# correctness, outside the window
# ---------------------------------------------------------------------------


def _check(engine: Any, params: Any, config: Dict[str, Any], arch: Any, vocab: int, seed: int) -> Tuple[bool, Dict[str, Any]]:
    """One seeded sequence: prefill its first half, decode the second half
    token by token through the paged cache (teacher-forced), and hold the
    logits to the full forward of the architecture's reference at the
    published widths."""
    import jax
    import jax.numpy as jnp

    tol = config["tolerance"]["serve_logits"]
    n, half = int(tol["sequence_tokens"]), int(tol["sequence_tokens"]) // 2
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    seq = rng.integers(1, vocab, size=n, dtype=np.int64)
    cfg = engine.cfg
    blocks = engine.allocator.alloc(engine.allocator.blocks_for(n))
    table = blocks + [0] * (cfg.blocks_per_seq - len(blocks))
    k = engine.kernels
    rows = [k.prefill(seq[:half].tolist(), table)]
    tokens = np.zeros(cfg.max_batch, np.int32)
    positions = np.full(cfg.max_batch, -1, np.int32)
    tables = np.zeros((cfg.max_batch, cfg.blocks_per_seq), np.int32)
    tables[0] = table
    for t in range(half, n):
        tokens[0], positions[0] = seq[t], t
        rows.append(k.decode(tokens, positions, tables)[0])
    got = np.stack(rows)  # predictions after positions half-1 .. n-1
    ref_fn = jax.jit(lambda weights, tokens: arch.reference_forward(weights, tokens, config))
    want = np.asarray(ref_fn(arch.reference_weights(params, config), jnp.asarray(seq, jnp.int32)))[half - 1:]
    diff = got.astype(np.float64) - want.astype(np.float64)
    rel_rms = float(np.sqrt(np.mean(diff**2)) / np.sqrt(np.mean(want.astype(np.float64) ** 2)))
    max_abs = float(np.max(np.abs(diff)))
    ok = bool(np.isfinite(got).all() and rel_rms <= tol["rel_rms"] and max_abs <= tol["max_abs"])
    return ok, {
        "rel_rms": rel_rms, "max_abs": max_abs, "rows": int(got.shape[0]),
        "tolerance": {k_: tol[k_] for k_ in ("rel_rms", "max_abs")}, "ok": ok,
        "top1_agree": float(np.mean(got.argmax(-1) == want.argmax(-1))),
    }
