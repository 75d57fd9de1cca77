"""Percent of the traced device time spent in operations of some of the
program's ``jax.named_scope``s.

A jitted step takes no host span, so the program names its device work by
scope.  A device trace names an operation by its HLO instruction and keeps
no scope, so the program says which instructions each scope holds: at a
jitted program's first call its tracer gets one ``jit.scopes`` instant
(``utils/compilation_cache.py``: scope -> instruction names, read off the
optimized module).  An operation counts if its instruction is listed under
one of ``args["scopes"]``.  Self times (a ``while`` does not count its body
twice), averaged over the devices, over all operation time.  A program that
sends no such instant (the parent of the PR that brought it), or none of the
scopes, gives nothing.
"""

import re

from benchlib import trace

_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")


def instructions(obs, scopes):
    """The instruction names the program listed under any of ``scopes``."""
    found = set()
    for ev in obs.program_events:
        if ev.get("name") == "jit.scopes":
            for scope, names in (ev.get("args") or {}).get("scopes", {}).items():
                if scope in scopes:
                    found.update(names)
    return found


def read(obs, args, peak):
    data = obs.trace()
    wanted = instructions(obs, set(args["scopes"]))
    if data is None or not data.devices or not wanted:
        return None
    scoped = every = 0.0
    for device, events in data.devices.items():
        for name, _, own in trace.self_times(events, data.nested(device)):
            every += own
            m = _INSTRUCTION.match(name)
            scoped += own if m and m.group(1) in wanted else 0.0
    return 100.0 * scoped / every if scoped > 0.0 and every > 0.0 else None
