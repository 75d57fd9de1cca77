"""Persistent XLA compilation cache wiring, and what a first call compiled.

Every program that jits the main path — the trainer (``train.init``),
``exec/run_trial.py`` and the server (``serve/engine.py``) — calls
:func:`setup_compilation_cache` once, so a new process (a supervised
restart, the next trial of a search, a relaunched replica, the next chip
call) loads its step programs from disk instead of compiling them again.
The compiled executable is keyed on the HLO and the cache's own path, so
the directory must not move between runs:

1. ``JAX_COMPILATION_CACHE_DIR`` set: jax itself reads it at import and
   caches there.  The program sets no directory in code; an experiment's
   ``optimizations.compilation_cache_dir`` is then only logged as
   overridden.
2. else the experiment's ``optimizations.compilation_cache_dir``;
3. else :data:`DEFAULT_CACHE_DIR`, one fixed git-ignored path inside the
   checkout — never a temporary name, a pid or the time.

In-process, the cross-trial jit-reuse cache (``train/_jit_cache.py``) sits
a tier above this one: a fresh Trainer in the SAME process shares the
jitted callable itself — no retrace, no disk read.

:func:`timed_first_call` is the other half: the one call that pays trace +
compile is timed, and what the compiler produced — Mosaic kernels
(``tpu_custom_call``) and collectives in the optimized program — is logged
from the process that ran it, so "the kernel really is in the step" is
something a run shows rather than something a config implies.
"""

from __future__ import annotations

import logging
import os
import re
import time
from typing import Any, Dict, List, Optional

logger = logging.getLogger("determined_tpu.utils.compilation_cache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout default (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".dtpu_cache",
    "xla",
)

# path already applied this process (repeat calls must not re-log)
_configured: Optional[str] = None


def resolve_cache_dir(config_dir: Optional[str] = None) -> str:
    """The directory this process caches in, by the order above."""
    return os.path.abspath(
        os.environ.get(ENV_VAR) or config_dir or DEFAULT_CACHE_DIR
    )


def setup_compilation_cache(config_dir: Optional[str] = None) -> str:
    """Apply the cache directory for this process and return it.

    Idempotent: a later call that declares nothing (``config_dir=None``)
    keeps what an earlier one applied.  Logs one warm/cold line so the task
    log says whether this process's compiles can be disk reads.
    """
    global _configured
    if _configured is not None and config_dir is None:
        return _configured
    path = resolve_cache_dir(config_dir)
    if _configured == path:
        return path
    import jax

    # A program's cache key must not depend on who called it.  Mosaic
    # serializes a Pallas kernel's MLIR with its debug locations, and by
    # default those carry ten frames of Python call stack; XLA's key hashes
    # that payload.  So one and the same train step got a different key
    # from `dtpu experiment run`, from `run_trial`, and from every restart
    # of a cluster trial (its code is unpacked to a fresh temp directory) —
    # measured on the chip: five 30 MiB `jit_train_step` entries for one
    # program, 28 s compiled again each time, and no restart ever hit.
    # With this off a location is the op's own frame (a file of the
    # checkout), and the same program loads in 2 s.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    from_env = bool(os.environ.get(ENV_VAR))
    if from_env:
        source = ENV_VAR
        if config_dir and os.path.abspath(config_dir) != path:
            logger.info(
                "optimizations.compilation_cache_dir=%s is overridden by %s",
                config_dir, ENV_VAR,
            )
    else:
        source = (
            "optimizations.compilation_cache_dir" if config_dir else "default"
        )
        jax.config.update("jax_compilation_cache_dir", path)
    try:
        os.makedirs(path, exist_ok=True)
        entries = sum(1 for e in os.scandir(path) if e.is_file())
    except OSError as e:
        # a cache is an optimization: a read-only install still trains
        logger.warning("compilation cache %s (%s) is unusable: %s", path, source, e)
        _configured = path
        return path
    logger.info(
        "compilation cache %s (%s) is %s",
        path,
        source,
        f"warm ({entries} entries): compiles can load from disk"
        if entries
        else "cold: compiles will populate it",
    )
    _configured = path
    return path


# ---------------------------------------------------------------------------
# what the first call compiled
# ---------------------------------------------------------------------------

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def program_facts(hlo_text: str) -> Dict[str, int]:
    """Counts read from an optimized HLO module's text: Mosaic (Pallas TPU)
    kernels and each collective.  Async pairs count once (their -start)."""
    facts = {"tpu_custom_call": hlo_text.count('custom_call_target="tpu_custom_call"')}
    for op in _COLLECTIVES:
        n = len(re.findall(rf" {op}(?:-start)?\(", hlo_text))
        if n:
            facts[op] = n
    return facts


#: a `jax.named_scope` of this repo: dotted, as its spans are (`moe.route`, `attn.window`)
_SCOPE_RE = re.compile(r"^[a-z_][a-z0-9_]*(\.[a-z0-9_]+)+$")
_INSTRUCTION_RE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*metadata=\{[^}]*op_name=\"([^\"]*)\"", re.M)


def program_scopes(hlo_text: str) -> Dict[str, List[str]]:
    """``jax.named_scope`` name -> the optimized module's instructions whose
    ``op_name`` passes through it.  A device trace names an operation by its
    instruction and keeps no scope, so this table is what lets a reader of a
    trace say which part of a jitted step an operation belongs to (a fusion
    belongs to the scope of the instruction it is named after)."""
    scopes: Dict[str, List[str]] = {}
    for name, op_name in _INSTRUCTION_RE.findall(hlo_text):
        for part in set(op_name.split("/")):
            if _SCOPE_RE.match(part):
                scopes.setdefault(part, []).append(name)
    return scopes


def timed_first_call(fn: Any, label: str) -> Any:
    """Wrap a jitted callable so its FIRST invocation — the one that pays
    trace + compile — is recorded as a ``compile`` span and a
    ``jit_cache.compile_s`` counter, and logs what was compiled.  Every
    later call pays one list index.  A cache-hit trial shares the wrapper,
    so its first step is correctly NOT marked as compile time.

    The program's facts come from ``fn.lower(...).compile()`` made just
    before the call: jax keeps one executable for a lowering, so the call
    that follows runs that same executable instead of compiling again.
    The wrapper's ``temp_bytes`` is then the scratch memory the program
    reserves on each device while it runs (0 until the first call) — what
    the allocator's own statistics do not count.  While the tracer is on,
    the program's named scopes go to it as one ``jit.scopes`` instant
    (``program_scopes``: scope -> instruction names).
    """
    done = [False]

    def wrapped(*args: Any, **kwargs: Any) -> Any:
        if done[0]:
            return fn(*args, **kwargs)
        done[0] = True  # benign race: two concurrent first-callers both record
        from determined_tpu.observability import get_tracer

        t0 = time.monotonic()
        facts: Dict[str, int] = {}
        try:
            if hasattr(fn, "lower"):
                compiled = fn.lower(*args, **kwargs).compile()
                text = compiled.as_text()
                facts = program_facts(text)
                if get_tracer().enabled:
                    scopes = program_scopes(text)
                    if scopes:
                        get_tracer().instant("jit.scopes", cat="compile", program=label, scopes=scopes)
                wrapped.temp_bytes = int(
                    getattr(compiled.memory_analysis(), "temp_size_in_bytes", 0)
                )
            return fn(*args, **kwargs)
        finally:
            t1 = time.monotonic()
            tracer = get_tracer()
            tracer.record_span(label, "compile", t0, t1)
            tracer.counter("jit_cache.compile_s", t1 - t0)
            logger.info(
                "%s: first call (trace + compile or cache load) took %.2fs; "
                "program: %s",
                label,
                t1 - t0,
                " ".join(f"{k}={v}" for k, v in facts.items()) or "not inspected",
            )

    wrapped.temp_bytes = 0
    return wrapped
