"""Seconds of one part of a start: the set-up timeline from the process's
creation to the window's opening, tiled by the program's own spans.

``args``: ``group``, one of ``GROUPS``' names, ``"rest"`` or ``"whole"``.
The timeline starts at the program tracer's ``process.start`` instant (the
OS's record of the process's creation, on the tracer's monotonic clock) and
ends where the window opens.  The program's spans that END before the window
are laid over it in the fixed order of ``GROUPS``; a moment counts to the
FIRST group that covers it, so the groups and ``rest`` (what no group covers)
add up to ``whole`` exactly, as the four ``tpot_*`` parts add up to a
request's ``tpot``.  ``before_program`` is no span but the stretch from the
origin to the start of the first ``import.determined_tpu.*`` span: the
interpreter, the harness's own checks, ``import jax``, the compile cache's
configuration (the ``setup.cache_configured`` instant lies in it) and the
start of the TPU's runtime.

No ``process.start`` among the events (a program older than the span) gives
nothing; a group that is empty while the timeline exists reads 0.0.  Only the
program's tracer writes these spans, so only ``obs.program_events`` is read.
"""

from benchlib import trace

ORIGIN = "process.start"
FIRST_IMPORT = "import.determined_tpu."


def _compile_child(suffix):
    return lambda n: n.startswith("jit.compile.") and n.endswith(suffix)


#: (group, does a span of this name belong to it), in the order a moment is given away
GROUPS = (
    ("import", lambda n: n.startswith("import.")),
    ("program_inspect", _compile_child(".inspect")),
    ("xla_trace_lower", lambda n: n in ("xla.trace", "xla.lower")),
    ("xla_load", lambda n: n in ("xla.cache_load", "xla.compile")),
    ("program_first_run", _compile_child(".first_run")),
    ("program_self", lambda n: n in ("serve.setup", "trainer.setup", "serve.engine.start") or n.startswith("jit.compile.")),
    ("first_work", lambda n: n in ("serve.step", "serve.admission", "data.wait", "step.dispatch", "step.boundary_block")),
)


def parts(obs):
    """``{group: seconds}`` with ``rest`` and ``whole``, or None without an origin."""
    t_open = obs.window[0]
    origin = next(
        (obs.program_epoch + ev["ts"] / 1e6 for ev in obs.program_events
         if ev.get("name") == ORIGIN and ev.get("ph") == "i"),
        None,
    )
    if origin is None or t_open <= origin:
        return None
    whole = t_open - origin
    # seconds from the origin: small numbers, so the parts add up to the whole
    spans = []
    for ev in obs.program_events:
        if ev.get("ph") == "X":
            start = obs.program_epoch + ev["ts"] / 1e6
            if start + ev["dur"] / 1e6 <= t_open:
                spans.append((ev["name"], start - origin, start + ev["dur"] / 1e6 - origin))
    first = min((a for n, a, _ in spans if n.startswith(FIRST_IMPORT)), default=None)
    laid = [("before_program", [(0.0, first)] if first is not None else [])]
    laid += [(group, [(a, b) for n, a, b in spans if belongs(n)]) for group, belongs in GROUPS]
    out, covered = {}, []
    for group, intervals in laid:
        # what the group adds to what the groups before it cover
        both = trace.union(covered + trace.clip(intervals, 0.0, whole))
        out[group] = float(trace.total(both) - trace.total(covered))
        covered = both
    out["rest"] = whole - sum(out.values())
    out["whole"] = whole
    return out


def read(obs, args, peak):
    found = parts(obs)
    return None if found is None else found[args["group"]]
