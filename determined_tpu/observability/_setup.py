"""A start, second by second: the set-up timeline and the line an operator reads.

The timeline runs from ``process.start`` (the instant the tracer leaves when
it is made: the OS's own record of the process's creation) to ``until``: a
replica's readiness, a trial's first report, a benchmark window's opening.
The spans that END before ``until`` are laid over it in the fixed order of
``GROUPS``; a moment counts to the FIRST group that covers it, so the parts
and ``rest`` (what no group covers) add up to ``whole`` exactly.

``benchmark/readers/setup_parts_s.py`` does the same arithmetic on its own
(the benchmark measures the program; it does not ask it), and
``tests/benchmark/test_bench_setup_parts.py`` holds the two to each other.
See ``docs/observability.md`` "Reading a start".
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from determined_tpu.observability._tracer import get_tracer

Interval = Tuple[float, float]

ORIGIN = "process.start"
#: the timeline's first part ends where the first of these starts
FIRST_IMPORT = "import.determined_tpu."


def _named(*names: str) -> Callable[[str], bool]:
    return lambda n: n in names


#: (part, does a span of this name belong to it), in the order a moment is
#: given away; ``before_program`` is no span but the stretch from the origin
#: to the first ``import.determined_tpu.*`` span
GROUPS: Tuple[Tuple[str, Callable[[str], bool]], ...] = (
    ("import", lambda n: n.startswith("import.")),
    ("program_inspect", lambda n: n.startswith("jit.compile.") and n.endswith(".inspect")),
    ("xla_trace_lower", _named("xla.trace", "xla.lower")),
    ("xla_load", _named("xla.cache_load", "xla.compile")),
    ("program_first_run", lambda n: n.startswith("jit.compile.") and n.endswith(".first_run")),
    (
        "program_self",
        lambda n: n in ("serve.setup", "trainer.setup", "serve.engine.start") or n.startswith("jit.compile."),
    ),
    # an admission beside its step: a closed loop's first wave is admitted inside ONE step
    # that is still decoding when work is flowing, and only its admissions have ended
    ("first_work", _named("serve.step", "serve.admission", "data.wait", "step.dispatch", "step.boundary_block")),
)


def _union(intervals: List[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _total(intervals: List[Interval]) -> float:
    return sum((b - a for a, b in intervals), 0.0)


def setup_parts(events: List[Dict[str, Any]], until_s: float) -> Optional[Dict[str, float]]:
    """Seconds of ``before_program``, of each of ``GROUPS``, of ``rest`` and
    of ``whole``, from the tracer's
    events (``Tracer.chrome_events()``: ``ts`` and ``dur`` in microseconds
    from its epoch) up to ``until_s`` (seconds from the same epoch).  None
    where the events hold no ``process.start`` (a tracer that was off when it
    was made, or ``reset()`` since).  A part nothing fell into reads 0.0."""
    origin = next((e["ts"] / 1e6 for e in events if e.get("name") == ORIGIN and e.get("ph") == "i"), None)
    if origin is None or until_s <= origin:
        return None
    whole = until_s - origin
    # seconds from the origin: small numbers add up exactly enough
    spans = [
        (e["name"], e["ts"] / 1e6 - origin, (e["ts"] + e["dur"]) / 1e6 - origin)
        for e in events
        if e.get("ph") == "X" and (e["ts"] + e["dur"]) / 1e6 <= until_s
    ]
    first = min((a for n, a, _ in spans if n.startswith(FIRST_IMPORT)), default=None)
    laid = [("before_program", [(0.0, first)] if first is not None else [])]
    laid += [(part, [(a, b) for n, a, b in spans if belongs(n)]) for part, belongs in GROUPS]
    parts: Dict[str, float] = {}
    covered: List[Interval] = []
    for part, intervals in laid:
        # what the part adds to what the parts before it cover
        both = _union(covered + [(max(a, 0.0), min(b, whole)) for a, b in intervals])
        parts[part] = _total(both) - _total(covered)
        covered = both
    parts["rest"] = whole - sum(parts.values())
    parts["whole"] = whole
    return parts


def format_setup_line(what: str, parts: Dict[str, float]) -> str:
    """``"<what> in 41.2 s: before the program 12.9, imports 2.8, ..."``."""
    programs = sum(parts[p] for p in ("xla_trace_lower", "xla_load", "program_inspect", "program_first_run", "program_self"))
    return (
        f"{what} in {parts['whole']:.1f} s: before the program {parts['before_program']:.1f}, "
        f"imports {parts['import']:.1f}, programs {programs:.1f} "
        f"(trace and lower {parts['xla_trace_lower']:.1f}, load or compile {parts['xla_load']:.1f}, "
        f"inspect {parts['program_inspect']:.1f}, first run {parts['program_first_run']:.1f}, "
        f"the rest of set-up and first calls {parts['program_self']:.1f}), "
        f"first work {parts['first_work']:.1f}, under no span {parts['rest']:.1f}"
    )


_logged = False


def log_setup_line(logger: logging.Logger, what: str) -> Optional[str]:
    """Log, once a process, how its start went by those parts up to now
    (``what``: "replica ready", "first report").  Nothing where the tracer
    is off or holds no ``process.start``.  Returns the line it logged."""
    global _logged
    tracer = get_tracer()
    if _logged or not tracer.enabled:
        return None
    _logged = True
    parts = setup_parts(tracer.chrome_events(), time.monotonic() - tracer.epoch_monotonic)
    if parts is None:
        return None
    line = format_setup_line(what, parts)
    logger.info("%s", line)
    return line
