"""Percent of device time in operations the program can put a name to: how
much of a step its scopes cover, and how far a scope's share can be wrong.

Over the operations the two scope readers count: the whole trace
(``scope_device_share``, a training cell), or with ``args["span"]`` and
``args["program"]`` the operations that START inside a span of that name the
trace holds whole (``decode_step_ops``, whose ``steps_in_trace`` this reads
them by), against that one program's ``jit.scopes`` instant.  Self times,
summed over the devices, over all of those operations' time.

- default: an operation counts if its instruction is listed under ANY scope of
  the instant, or its name matches ``args["also"]`` (a pattern: the handle of
  the kernels the program calls bare, ``^%tpu_custom_call``).  100 minus the
  reading is the step nobody can put a source line to.
- ``args["count"] == "mixed"``: an operation counts if the instant lists it
  under ``mixed``: a fusion whose body passes through more than one innermost
  scope, so whichever scope it is listed under owns only part of its time.

A trace without such operations, or a program that sends no such instant or
an instant without the table asked for (``mixed`` under the parent of the PR
that brought it), gives nothing.
"""

import bisect
import re

from benchlib import model, trace

_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")


def listed(obs, program, table):
    """The instruction names under ``table`` (``scopes`` or ``mixed``) of the
    program's instants (every program's where ``program`` is None), or None
    where no instant has that table."""
    found = None
    for ev in obs.program_events:
        a = ev.get("args") or {}
        if ev.get("name") != "jit.scopes" or program not in (None, a.get("program")) or table not in a:
            continue
        found = set() if found is None else found
        if table == "scopes":
            found.update(n for names in a[table].values() for n in names)
        else:
            found.update(a[table])
    return found


def read(obs, args, peak):
    data = obs.trace()
    if data is None or not data.devices:
        return None
    steps = None
    if "span" in args:
        steps = model.beside(__file__, "readers", "decode_step_ops").steps_in_trace(obs, args["span"])
        if not steps:
            return None
        starts = [s for s, _, _ in steps]
    mixed = args.get("count") == "mixed"
    wanted = listed(obs, args.get("program"), "mixed" if mixed else "scopes")
    if wanted is None:
        return None
    also = re.compile(args["also"]) if "also" in args and not mixed else None
    counted = every = 0.0
    for device, events in data.devices.items():
        for name, start, own in trace.self_times(events, data.nested(device)):
            if steps is not None:
                i = bisect.bisect_right(starts, start) - 1
                if i < 0 or start >= steps[i][1]:
                    continue
            every += own
            m = _INSTRUCTION.match(name)
            if (m and m.group(1) in wanted) or (also is not None and also.search(name)):
                counted += own
    return 100.0 * counted / every if every > 0.0 else None
