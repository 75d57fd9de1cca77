"""Device time of the decode steps a trace holds whole, by the program's
scopes: a share of the steps' device time, or a roofline share of what a cost
function counts for ONE such step.

A serving process runs three jitted programs whose instructions share names
(``fusion.12`` of the prefill is not ``fusion.12`` of the decode step), and a
step's work follows its lanes' tokens.  So this reader takes the program's
spans of one name (``args["span"]``: ``serve.decode``, one a step, from
before the call until the logits are on the host) that lie wholly inside the
device trace, and of the trace only the operations that START inside one of
them: self times (a ``while`` does not count its body twice), averaged over
the devices.

- ``scopes`` (optional): count the operations whose instruction the program
  listed under one of these ``jax.named_scope``s in the ``jit.scopes``
  instant of ``args["program"]`` (``utils/compilation_cache.py``); absent,
  every operation of the steps.
- ``cost`` (optional): 100 x the least time the chip could take for one step
  of what the cost function counts / the counted time a step.  The function
  is handed, beside the run's counters, the mean over THESE steps of every
  numeric argument of their spans as ``traced.<argument>`` (``traced.active``,
  ``traced.live_kv_tokens``, ``traced.serve.moe.experts_hit``, ...).  Absent:
  100 x the counted time / all operation time of the steps.

A trace without such spans, a program that sends no such instant or none of
the scopes (the parent of the PR that brought it), or a cost function whose
counter the spans lack, gives nothing.
"""

import bisect
import re

from benchlib import costs, trace

_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")


def steps_in_trace(obs, name):
    """[(start_ns, end_ns, args)] of the program's spans of ``name`` that lie
    wholly inside the device trace, on the trace's clock, by start."""
    data = obs.trace()
    if data is None or not data.devices or obs.profiler is None:
        return []
    lo, hi = trace.window_of(data)
    off = trace.clock_offset_ns(data, obs.profiler.sync_marks_ns)
    found = []
    for ev in obs.program_events:
        if ev.get("ph") == "X" and ev.get("name") == name:
            start = (obs.program_epoch + ev["ts"] / 1e6) * 1e9 + off
            end = start + ev["dur"] * 1e3
            if start >= lo and end <= hi:
                found.append((start, end, ev.get("args") or {}))
    return sorted(found, key=lambda s: s[0])


def instructions(obs, program, scopes):
    """The instruction names ``program`` listed under any of ``scopes``."""
    found = set()
    for ev in obs.program_events:
        a = ev.get("args") or {}
        if ev.get("name") == "jit.scopes" and a.get("program") == program:
            for scope, names in a.get("scopes", {}).items():
                if scope in scopes:
                    found.update(names)
    return found


def read(obs, args, peak):
    steps = steps_in_trace(obs, args["span"])
    if not steps:
        return None
    wanted = None
    if "scopes" in args:
        wanted = instructions(obs, args["program"], set(args["scopes"]))
        if not wanted:
            return None
    data = obs.trace()
    starts = [s for s, _, _ in steps]
    counted = every = 0.0
    for device, events in data.devices.items():
        for name, start, own in trace.self_times(events, data.nested(device)):
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start >= steps[i][1]:
                continue
            every += own
            m = _INSTRUCTION.match(name)
            if wanted is None or (m and m.group(1) in wanted):
                counted += own
    if counted <= 0.0 or every <= 0.0:
        return None
    if "cost" not in args:
        return 100.0 * counted / every
    traced = {}
    for key in set().union(*(a.keys() for _, _, a in steps)):
        values = [a[key] for _, _, a in steps if isinstance(a.get(key), (int, float))]
        if len(values) == len(steps):
            traced["traced." + key] = sum(values) / len(values)
    try:
        need = costs.find(args["cost"], obs.data_dir)(obs.config, obs.traffic, obs.chips, {**obs.counters, **traced}, obs.arch)
    except KeyError:
        return None
    least = max(need["flops"] / peak["bf16_flops_per_s"], need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / (counted / len(data.devices) / len(steps) / 1e9)
