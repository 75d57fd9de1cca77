"""One cell, one run, one last line.

``run_cell`` is what ``benchmark/run.py`` calls; the tests call it too, on
the CPU at a tiny size with ``require_tpu=False`` (an argument of this
function and of nothing a user can reach: the command always measures a
TPU or fails).
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

from . import model, readers, spec as spec_mod, trace as trace_mod

#: compile cache and traces: inside the checkout, listed in .gitignore, fixed
SCRATCH = os.path.join(spec_mod.CHECKOUT, ".dtpu_cache")
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoDevice(RuntimeError):
    """jax found no TPU, or fewer chips than the cell asks for."""


def _say(event: str, **fields: Any) -> None:
    """An earlier line of the output: everything that is not the result."""
    print(json.dumps({"event": event, **fields}, default=str), flush=True)


def _setup_jax(require_tpu: bool, chips: int) -> Dict[str, Any]:
    import jax

    from determined_tpu.utils.compilation_cache import setup_compilation_cache

    # the program's own rule: where the machine sets the variable jax caches
    # there, otherwise at its fixed path inside the checkout
    # (<checkout>/.dtpu_cache/xla).  Small programs are cached too, so that a
    # second run compiles nothing at all.
    setup_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    facts = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": chips,
    }
    if require_tpu and facts["platform"] != "tpu":
        raise NoDevice(f"jax reports platform {facts['platform']!r}: the benchmark measures a TPU")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips and jax finds {len(devices)}")
    return facts


class CompileCounter:
    """Counts XLA compilations by the time they end, so that one inside the
    measured window shows."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.ends: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw: Any) -> None:
        if event == self.EVENT:
            self.ends.append(time.monotonic())

    def inside(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.ends if lo <= t <= hi)


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    traced: bool,
    *,
    root: str = spec_mod.CHECKOUT,
    require_tpu: bool = True,
    t_start: Optional[float] = None,
) -> Dict[str, Any]:
    """Run one cell and return the object of the last line."""
    t_start = time.monotonic() if t_start is None else t_start
    spec = spec_mod.Spec(root)
    cell = spec.cell(workload)
    kind = cell.traffic["kind"]
    if kind == "train":
        from . import train_run as runner
    elif kind in ("serve-open", "serve-closed"):
        from . import serve_run as runner
    else:
        raise spec_mod.SpecError(f"traffic {cell.traffic_name}: unknown kind {kind!r}")
    # everything the cell names is found, and fits together, before a device is touched
    arch = model.adapter(cell)
    for m in cell.per_layer:
        readers.check(m, cell.data_dir)
    runner.check(cell)
    facts = _setup_jax(require_tpu, cell.chips)
    peak = spec.peak(facts["kind"]) if require_tpu else next(iter(spec.peaks["device_kinds"].values()))
    compiles = CompileCounter()
    _say(
        "start", workload=workload, seed=seed, seconds=seconds, trace=int(traced), device=facts,
        seconds_since_start=time.monotonic() - t_start,
    )
    trace_dir = os.path.join(SCRATCH, "bench", workload, "trace")
    out = runner.run(cell, arch, seed, float(seconds), traced, t_start, _say, trace_dir)
    obs = out["observations"]
    lo, hi = obs.window
    compiled = compiles.inside(lo, hi)
    _say("compiles", total=len(compiles.ends), inside_window=compiled)
    device = {**facts, "memory_peak_bytes": out["memory_peak_bytes"]}
    line: Dict[str, Any] = {
        "correct": bool(out["correct"] and compiled == 0),
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
    }
    if not traced:
        line["metrics"] = {
            m["name"]: {"value": out["values"][m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
            if m["name"] in out["values"]
        }
    else:
        metrics = {}
        for m in cell.per_layer:
            value = readers.read(m, obs, peak)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        data = obs.trace()
        if data is not None and data.devices:
            w = trace_mod.window_of(data)
            device["busy_s"] = trace_mod.busy_seconds(data)
            device["window_s"] = (w[1] - w[0]) / 1e9
            line["breakdown"] = {
                "device_ops": [[n, s] for n, s in trace_mod.top_ops(data)],
                "idle_gaps": [
                    [n, s] for n, s in trace_mod.idle_gaps_by_host_span(
                        data, obs.host_spans_on_trace_clock()
                    )
                ],
            }
        _say("end_to_end_of_traced_run", values=out["values"])
    line["device"] = device
    # each number the verdict rests on beside its limit (the runner's check
    # names both), last in the line; one that is not a number as a word: the
    # line stays JSON
    check = out["check"]
    compared = {**{q: (check[q], limit) for q, limit in check["tolerance"].items()}, "compiles_in_window": (compiled, 0)}
    line["compared"] = {
        name: [value if math.isfinite(value) else str(value), limit] for name, (value, limit) in compared.items()
    }
    return line


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None, **cell_kwargs: Any) -> int:
    """``cell_kwargs`` (``root``, ``require_tpu``) are the tests': the
    command passes none."""
    import argparse

    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json on the TPU.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_start=t_start, **cell_kwargs)
    except (NoDevice, spec_mod.SpecError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    return 0
