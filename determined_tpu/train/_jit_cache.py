"""Cross-trial jit-reuse cache: share compiled train/eval steps between trials.

A hyperparameter search runs many trials of the SAME architecture; each one
builds a fresh ``Trainer`` whose jitted step closures are new Python
objects, so jax's in-process jit cache misses and every trial re-traces and
re-compiles an identical program.  The reference platform never pays this
because its trials are separate processes that each pay the compile anyway;
here trials share one process (``experiment/scheduler.py`` packs them onto
submeshes), so the compile is shareable work.

This cache closes the gap: jitted ``train_step``/``eval_step`` callables are
keyed on everything that shapes the traced computation —

- the trial class (its ``loss``/``evaluate_batch``/optimizer construction),
- the trial-static hyperparameters (a closure bakes python scalars like a
  learning rate into the HLO as constants, so by default EVERY hparam is
  part of the key; a trial that routes an hparam through runtime state —
  e.g. ``optax.inject_hyperparams`` — may exclude it via
  ``JaxTrial.compile_cache_runtime_hparams``),
- the mesh — axis names, sizes, AND device ids.  Device identity must be
  part of the key because a trial's model may bake its concrete mesh into
  the trace (``with_sharding_constraint``/``shard_map`` over
  ``context.mesh``, as the transformer LM does): a callable compiled
  against gang A's devices cannot serve a trial on gang B.  The scheduler's
  LIFO slot reuse (``SlotPool``) makes this cheap in practice — a stopped
  trial's block is preferentially handed to the next same-architecture
  create, which then hits: same callable, same devices, zero retrace AND
  zero recompile.  Different-gang trials of one architecture each compile
  once; the persistent XLA compilation cache
  (``utils/compilation_cache.py``) covers the cross-process half,
- the host batch structure (shapes/dtypes) and the gradient-accumulation
  settings that change the stacked batch layout.

Two trials that hash to the same key therefore trace to byte-identical HLO
on the same device set, and sharing the callable is sound for any trial,
including ones that close over their concrete mesh.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import threading
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

from determined_tpu.observability import get_tracer

logger = logging.getLogger("determined_tpu.train.jit_cache")


@dataclasses.dataclass
class CachedSteps:
    """One cache entry: the shared jitted callables for a step signature."""

    train_step: Any
    eval_step: Any
    trial_class: str
    hits: int = 0
    # the UNwrapped jax.jit object for train_step: tests and benches use
    # it to lower/inspect the compiled HLO (collective structure) without
    # tripping the first-call compile-span wrapper
    train_jit: Any = None


class StepCache:
    """Bounded, thread-safe LRU of jitted step callables.

    Entries keep their defining trial's closure alive (model/optimizer
    objects), so the cache is bounded: ``maxsize`` distinct step signatures,
    oldest evicted first.  All methods are safe to call from concurrent
    trial threads.
    """

    def __init__(self, maxsize: int = 32) -> None:
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, CachedSteps]" = OrderedDict()
        self._maxsize = maxsize
        self.hits = 0
        self.misses = 0

    def lookup(self, key: str) -> Optional[CachedSteps]:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
            else:
                self._entries.move_to_end(key)
                self.hits += 1
                entry.hits += 1
        # lock-free tracer push, outside the cache lock on principle
        get_tracer().counter("jit_cache.miss" if entry is None else "jit_cache.hit")
        return entry

    def insert(self, key: str, entry: CachedSteps) -> CachedSteps:
        """Insert, returning the winning entry.  Under a concurrent race the
        first writer wins so every racer converges on ONE callable (later
        same-key trials then share its jax-side trace/executable caches)."""
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                return existing
            self._entries[key] = entry
            while len(self._entries) > self._maxsize:
                evicted_key, _ = self._entries.popitem(last=False)
                logger.debug("jit-reuse cache evicted %s", evicted_key[:12])
            return entry

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = 0
            self.misses = 0

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "hits": self.hits,
                "misses": self.misses,
            }


# Process-global instance: trials in one process (the concurrent scheduler,
# sequential searches, tests) all share it.
_cache = StepCache()


def get_step_cache() -> StepCache:
    return _cache


def step_cache_stats() -> Dict[str, int]:
    """Snapshot of the process-global cache counters (bench/observability)."""
    return _cache.stats()


def clear_step_cache() -> None:
    _cache.clear()


def _canonical(value: Any) -> Any:
    """JSON-stable view of an hparam value (Const wrappers collapse)."""
    value = getattr(value, "val", value)
    if isinstance(value, dict):
        # sort on str(k): mixed-type keys (legal YAML) must hash, not raise
        return {
            str(k): _canonical(v)
            for k, v in sorted(value.items(), key=lambda kv: str(kv[0]))
        }
    if isinstance(value, (list, tuple)):
        return [_canonical(v) for v in value]
    return value if isinstance(value, (int, float, str, bool, type(None))) else repr(value)


def step_cache_key(
    *,
    trial: Any,
    hparams: Dict[str, Any],
    mesh: Any,
    agg: int,
    average_grads: bool,
    sample_batch: Dict[str, Any],
    metric_keys: Tuple[str, ...],
    rules: Optional[Dict[str, Any]] = None,
    overlap: str = "overlap:none",
    quant: str = "none",
    pipeline: str = "pipe:none",
) -> str:
    """Hash of everything that shapes the traced train/eval step.

    ``sample_batch`` is the HOST batch (pre-sharding, pre-agg-stacking);
    together with ``agg`` and the mesh axis sizes it determines the traced
    batch avals.  The mesh's device ids are included (see module doc:
    models may bake the concrete mesh into the trace).
    """
    runtime = frozenset(getattr(trial, "compile_cache_runtime_hparams", tuple)() or ())
    static_hp = {k: _canonical(v) for k, v in hparams.items() if k not in runtime}
    payload = {
        "trial": f"{type(trial).__module__}:{type(trial).__qualname__}",
        "hparams": static_hp,
        "mesh": [[name, int(size)] for name, size in mesh.shape.items()],
        "devices": [int(getattr(d, "id", -1)) for d in mesh.devices.flat],
        # logical-axis sharding rules enter the trace (models pass
        # context.rules into sharding constraints), so they key the cache
        "rules": {str(k): _canonical(v) for k, v in (rules or {}).items()},
        "agg": int(agg),
        "average_grads": bool(average_grads),
        # step-program knobs (ISSUE 12/14): the overlapped-grad-sync
        # bucket structure, the quantized-matmul mode, and the pipeline
        # microbatch schedule (name/P/M/virtual stages) all change the
        # traced program without touching hparams or batch avals
        "overlap": str(overlap),
        "quant": str(quant),
        "pipeline": str(pipeline),
        "batch": sorted(
            (k, tuple(int(d) for d in v.shape), str(v.dtype))
            for k, v in sample_batch.items()
        ),
        "metric_keys": list(metric_keys),
    }
    blob = json.dumps(payload, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()
