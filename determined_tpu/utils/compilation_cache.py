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
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

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

#: jax's own account of a compile (``jax.monitoring``), as spans of the
#: program's tracer: a duration event becomes a span that ends when the
#: listener is called and starts its duration earlier.  They fire at compiles
#: only, for every program of the process (also the ones no
#: ``timed_first_call`` wraps: an initialiser, a pool's zeros, eager
#: operations), and carry the function's name where jax hands one.  On a hit of
#: the persistent cache ``xla.cache_load`` lies inside ``xla.compile``: jax
#: times the retrieval inside the same ``backend_compile_duration``.  A trace
#: shorter than ``_TRACE_FLOOR_S`` is left out: every inner ``jit`` of a traced
#: function fires the event (one call of ``jax.numpy``'s is one), a model's
#: ``init`` under ``eval_shape`` eleven thousand of them, and a thread's ring
#: holds 8,192; the outer trace that holds them is long and is kept (measured
#: on a tiny ZAYA1 trainer: 274 of 10,976 events cover 97 % of their union).
_XLA_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "xla.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "xla.lower",
    "/jax/core/compile/backend_compile_duration": "xla.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "xla.cache_load",
}
_XLA_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "xla.cache_hits",
    "/jax/compilation_cache/cache_misses": "xla.cache_misses",
}
_TRACE_FLOOR_S = 1e-3
_listening = False


def _listen_to_xla() -> None:
    """Register the two listeners, once a process."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax.monitoring

    from determined_tpu.observability import get_tracer

    tracer = get_tracer()

    def on_duration(event: str, duration: float, **kw: Any) -> None:
        if not tracer.enabled:
            return
        name = _XLA_SPANS.get(event)
        if name is not None and (duration >= _TRACE_FLOOR_S or name != "xla.trace"):
            t1 = time.monotonic()
            fun_name = kw.get("fun_name")
            tracer.record_span(name, "compile", t1 - duration, t1, {"fun_name": fun_name} if fun_name else None)

    def on_event(event: str, **kw: Any) -> None:
        if tracer.enabled and event in _XLA_COUNTERS:
            tracer.counter(_XLA_COUNTERS[event])

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


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
    _keep_name_stacks()
    _listen_to_xla()
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
    first = _configured is None
    try:
        os.makedirs(path, exist_ok=True)
        entries = sum(1 for e in os.scandir(path) if e.is_file())
    except OSError as e:
        # a cache is an optimization: a read-only install still trains
        logger.warning("compilation cache %s (%s) is unusable: %s", path, source, e)
        _configured = path
        return path
    if first:
        # the next thing an entry point does is start the device's runtime
        # (``jax.devices()``), which no event of jax's announces: this mark
        # and the first ``import.*`` span bracket it
        from determined_tpu.observability import get_tracer

        get_tracer().instant("setup.cache_configured", cat="setup", path=path, entries=entries)
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


#: Hashed into every cache key (jax's ``cache_key.custom_hook``).  The key
#: leaves an instruction's metadata out, so an executable compiled before a
#: ``jax.named_scope`` existed would be loaded, names as they were, by the
#: code that has the scope, and ``program_scopes`` would list nothing under
#: it.  Raise it in a change that renames or adds scopes to a program whose
#: lowering it leaves as it is.
SCOPES_EPOCH = "dtpu-scopes-2"


def _keep_name_stacks() -> None:
    """Let an operation's ``op_name`` keep its name stack (its
    ``jax.named_scope``s) with tracebacks out of the locations.

    jax lowers most primitives once into a private function and inlines it at
    each use; the inliner joins the use's name stack and the primitive's name
    into one name (``jit(step)/jvp(loss.ce)/dot_general``) only where the
    use's location is more than a bare file position, which is what it is with
    ``jax_include_full_tracebacks_in_locations`` off.  XLA then reads the
    primitive's name alone, and every scope entered directly in a jitted
    function is lost (measured: all but the operations inside a nested ``jit``;
    it is also why a bare Mosaic call reaches the optimized step program
    without its metadata).  So the file position of a use is wrapped in an
    empty name.  A ``pallas_call`` keeps the location it had: XLA names a
    custom call after its ``op_name``, and the training kernels are found in a
    trace by the name they have without one (``%tpu_custom_call.N``).
    Locations are metadata: the lowered text without them, a Mosaic kernel's
    payload and the cache key's hash of the program are what they were."""
    try:
        from jax._src import cache_key
        from jax._src.interpreters import mlir
        from jax._src.lib.mlir import ir

        inner = mlir._cached_lowering
        cache_key.custom_hook  # noqa: B018 - both or neither
    except (ImportError, AttributeError) as e:  # another jax than the one this repo is written for
        logger.warning("op names will lack their scopes: %s", e)
        return
    if getattr(inner, "keeps_name_stacks", False):
        return

    def cached_lowering(ctx: Any, eqn: Any, *args: Any, **params: Any) -> Any:
        loc = ir.Location.current
        if eqn.primitive.name == "pallas_call" or not (loc.is_a_name() and loc.child_loc.is_a_file()):
            return inner(ctx, eqn, *args, **params)
        with ir.Location.name(loc.name_str, childLoc=ir.Location.name("", childLoc=loc.child_loc)):
            return inner(ctx, eqn, *args, **params)

    cached_lowering.keeps_name_stacks = True
    mlir._cached_lowering = cached_lowering
    cache_key.custom_hook = lambda: SCOPES_EPOCH


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
#: what a transformation wraps the first part of the name stack under it in
_WRAPPER_RE = re.compile(r"^[a-z_0-9]+\((.*)\)$")
_COMPUTATION_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTRUCTION_RE = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_OPCODE_RE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_OP_NAME_RE = re.compile(r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
_CALLED_RE = re.compile(r"\b(?:body|condition|to_apply|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_FUSED_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
#: the instructions that run a computation of their own, and the ones of a
#: body whose operands are the bytes and the FLOPs of the fusion round them
_CALLERS = ("while", "call", "conditional")
_PRODUCTS = ("dot", "convolution")
#: what stands between a prefetch and its user; what the compiler's namelessness means nothing for; what does no work
_PASS_THROUGH = ("tuple", "get-tuple-element", "bitcast")
_NEVER_NAMED = ("parameter", "constant") + _PASS_THROUGH
_NO_WORK = _NEVER_NAMED + ("copy", "copy-start", "copy-done")


class ProgramScopes(NamedTuple):
    """``scopes``: ``jax.named_scope`` name -> the instructions listed under
    it; ``mixed``: fusion -> the innermost scopes its body passes through,
    for the fusions whose body passes through more than one; ``unnamed``: the
    instructions that run under no scope, of the ``listable`` that do work
    (all but parameters, constants, tuples and their elements, copies and
    bitcasts)."""

    scopes: Dict[str, List[str]]
    mixed: Dict[str, List[str]]
    unnamed: List[str]
    listable: int


def scopes_of(op_name: str) -> List[str]:
    """The scopes an ``op_name`` passes through, outermost first.  A part of
    the name is a scope after its wrappers are peeled: a transformation wraps
    the first part under it (``jvp(loss.ce)``, ``transpose(jvp(loss.ce))``,
    ``vmap(transpose(jvp(loss.ce)))``; ``jvp()`` wraps nothing), so backward
    instructions are listed under the scope of the code they differentiate."""
    found = []
    parts = op_name.split("/")
    for part in parts if len(parts) > 1 else ():  # alone it is an argument's own name (``state.step``)
        m = _WRAPPER_RE.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPER_RE.match(part)
        if _SCOPE_RE.match(part):
            found.append(part)
    return found


def read_program_scopes(hlo_text: str) -> ProgramScopes:
    """What :func:`program_scopes` lists, and the fusions that mix scopes.

    Listed are the instructions that run: those of the entry computation and
    of the computations it reaches by ``while`` / ``call`` / ``conditional``
    (lines outside any computation count as the entry's).  An instruction
    inside a fused computation is read, never listed: a fusion is listed by
    the ``op_name`` of its body's ``dot`` / ``convolution`` when the body's
    products all share one innermost scope (a product's operands are the
    bytes and the FLOPs; what rides behind it is elementwise), else by its
    own, which is its root's.  An instruction that carries no ``op_name`` is
    the compiler's own (an operand's prefetch: ``slice-start`` / ``-done``,
    ``copy-start`` / ``-done``, a ``ConcatBitcast``) and is listed where the
    first of its users that has a name is (a fusion without a name anywhere
    in its body likewise: the CPU compiler's ``wrapped_*``), or, with no such
    user, where the ``while`` / ``call`` / ``conditional`` that runs its
    computation is."""
    # computation -> [(instruction, opcode, op_name, rest of the line)]
    computations: Dict[str, List[Tuple[str, str, str, str]]] = {"": []}
    entry, current = "", ""
    for line in hlo_text.splitlines():
        if not line[:1].isspace():
            m = _COMPUTATION_RE.match(line)
            current = m.group(2) if m else ""
            if m:
                computations[current] = []
                entry = current if m.group(1) else entry
            continue
        m = _INSTRUCTION_RE.match(line)
        if not m:
            continue
        rest = line[m.end():]
        opcode, op_name = _OPCODE_RE.search(rest), _OP_NAME_RE.search(rest)
        computations[current].append(
            (m.group(2), opcode.group(1) if opcode else "", op_name.group(1) if op_name else "", rest)
        )

    def body_scopes(computation: str, seen: Tuple[str, ...] = ()) -> Tuple[List[List[str]], List[List[str]]]:
        """(scopes of each instruction of a fused body, of each product in it)."""
        every, products = [], []
        for _, opcode, op_name, rest in computations.get(computation, ()):
            inner = _FUSED_RE.search(rest) if opcode == "fusion" else None
            if inner and inner.group(1) not in seen:
                e, p = body_scopes(inner.group(1), seen + (computation,))
                every += e
                products += p
                continue
            found = scopes_of(op_name)
            every.append(found)
            if opcode in _PRODUCTS:
                products.append(found)
        return every, products

    scopes: Dict[str, List[str]] = {}
    mixed: Dict[str, List[str]] = {}
    unnamed: List[str] = []
    listable = 0
    # computation -> the scopes of the instruction that runs it: what its nameless instructions are listed under
    reached: Dict[str, List[str]] = {entry: [], "": []}
    queue = sorted(reached)
    while queue:
        computation = queue.pop()
        instructions = computations.get(computation, ())
        chosen: Dict[str, List[str]] = {}
        nameless: set = set()
        calls: List[Tuple[str, str]] = []
        for name, opcode, op_name, rest in instructions:
            found = scopes_of(op_name)
            if opcode == "fusion":
                called = _FUSED_RE.search(rest)
                every, products = body_scopes(called.group(1)) if called else ([], [])
                innermost = sorted({s[-1] for s in every if s})
                if len(innermost) > 1:
                    mixed[name] = innermost
                if products and all(p and p[-1] == products[0][-1] for p in products):
                    found = products[0]
                elif not op_name:  # a fusion of several results has no name: its last named instruction's
                    found = next((s for s in reversed(every) if s), [])
                    if not found:
                        nameless.add(name)
            elif opcode in _CALLERS:
                called = _CALLED_RE.findall(rest)
                for group in _BRANCHES_RE.findall(rest):
                    called += [c.strip().lstrip("%") for c in group.split(",")]
                calls += [(name, c) for c in called]
            if not op_name and opcode not in _NEVER_NAMED and opcode != "fusion":
                nameless.add(name)
            chosen[name] = found
        if nameless:
            users: Dict[str, List[str]] = {}
            for name, opcode, _, rest in instructions:
                operands = rest[rest.find(opcode + "(") + len(opcode) + 1:]
                for operand in _OPERAND_RE.findall(operands[: operands.find(")")]):
                    users.setdefault(operand, []).append(name)
            # a tuple, its element or a bitcast stands between a prefetch and what uses it
            through = {name for name, opcode, _, _ in instructions if opcode in _PASS_THROUGH}

            def of_users(name: str, hops: int = 0) -> List[str]:
                for user in users.get(name, ()) if hops < 8 else ():
                    found = chosen[user] or (of_users(user, hops + 1) if user in nameless or user in through else [])
                    if found:
                        return found
                return []

            for name in nameless:
                chosen[name] = of_users(name) or reached[computation]
        for name, callee in calls:
            if callee not in reached:
                reached[callee] = chosen[name]
                queue.append(callee)
        for name, found in chosen.items():
            for scope in set(found):
                scopes.setdefault(scope, []).append(name)
        for name, opcode, _, _ in instructions:
            if opcode not in _NO_WORK:
                listable += 1
                if not chosen[name]:
                    unnamed.append(name)
    return ProgramScopes(scopes, mixed, unnamed, listable)


def program_scopes(hlo_text: str) -> Dict[str, List[str]]:
    """``jax.named_scope`` name -> the optimized module's instructions whose
    ``op_name`` passes through it.  A device trace names an operation by its
    instruction and keeps no scope, so this table is what lets a reader of a
    trace say which part of a jitted step an operation belongs to
    (:func:`read_program_scopes` says which instructions, and by which name
    a fusion)."""
    return read_program_scopes(hlo_text).scopes


def timed_first_call(fn: Any, label: str) -> Any:
    """Wrap a jitted callable so its FIRST invocation — the one that pays
    trace + compile — is recorded as a ``compile`` span, and logs what was
    compiled.  Every later call pays one list index.  A cache-hit trial
    shares the wrapper, so its first step is correctly NOT marked as compile
    time.  Two children say what the span holds beside jax's own ``xla.*``
    spans (the lowering, the compile or the cache's load): ``<label>.inspect``,
    this function's reading of the optimized text (argument ``text_bytes``),
    and ``<label>.first_run``, the call after the lowering to its return.

    The program's facts come from ``fn.lower(...).compile()`` made just
    before the call: jax keeps one executable for a lowering, so the call
    that follows runs that same executable instead of compiling again.
    The wrapper's ``temp_bytes`` is then the scratch memory the program
    reserves on each device while it runs (0 until the first call) — what
    the allocator's own statistics do not count.  While the tracer is on,
    the program's named scopes go to it as one ``jit.scopes`` instant
    (``read_program_scopes``: ``scopes``, scope -> instruction names, and
    ``mixed``, fusion -> the scopes its body mixes).
    """
    done = [False]

    def wrapped(*args: Any, **kwargs: Any) -> Any:
        if done[0]:
            return fn(*args, **kwargs)
        done[0] = True  # benign race: two concurrent first-callers both record
        from determined_tpu.observability import get_tracer

        t0 = time.monotonic()
        facts: Dict[str, int] = {}
        inspect: Optional[Tuple[float, float, int]] = None
        t_run = t0
        try:
            if hasattr(fn, "lower"):
                compiled = fn.lower(*args, **kwargs).compile()
                t_inspect = time.monotonic()
                text = compiled.as_text()
                facts = program_facts(text)
                if get_tracer().enabled:
                    t_parse = time.monotonic()
                    found = read_program_scopes(text)
                    if found.scopes:
                        get_tracer().instant(
                            "jit.scopes", cat="compile", program=label, scopes=found.scopes, mixed=found.mixed
                        )
                    logger.info(
                        "%s: %d scopes, %d fusions of mixed scopes, %d of %d instructions under none, "
                        "read off %d bytes of program text in %.3fs",
                        label, len(found.scopes), len(found.mixed), len(found.unnamed), found.listable,
                        len(text), time.monotonic() - t_parse,
                    )
                wrapped.temp_bytes = int(
                    getattr(compiled.memory_analysis(), "temp_size_in_bytes", 0)
                )
                t_run = time.monotonic()
                inspect = (t_inspect, t_run, len(text))
            return fn(*args, **kwargs)
        finally:
            t1 = time.monotonic()
            tracer = get_tracer()
            tracer.record_span(label, "compile", t0, t1)
            if inspect is not None:
                tracer.record_span(label + ".inspect", "compile", inspect[0], inspect[1], {"text_bytes": inspect[2]})
            tracer.record_span(label + ".first_run", "compile", t_run, t1)
            logger.info(
                "%s: first call (trace + compile or cache load) took %.2fs; "
                "program: %s",
                label,
                t1 - t0,
                " ".join(f"{k}={v}" for k, v in facts.items()) or "not inspected",
            )

    wrapped.temp_bytes = 0
    return wrapped
