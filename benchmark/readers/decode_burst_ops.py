"""Device time of the decode steps a trace holds whole, by the program's
scopes, a step's operations taken by the device's own burst: what
``decode_step_ops`` reads, without trusting the two clocks of a trace to
agree within a millisecond.

``decode_step_ops`` counts the operations that START inside a ``serve.decode``
span laid on the trace's clock.  The device's line and the host's annotations
of ONE trace do not always agree that closely: in the Command A+ cell's traces
a step's first operations begin 0.3-1.5 ms BEFORE the host entered the call
that dispatched them (my chip runs, PR 38: every one of 115 bursts holds all
twelve grouped products, the spans hold ten or eleven), so a share of a
roofline read that way loses the step's head from its time and passes 100 %.

Here a step is the BURST the span overlaps most: a run of operations with no
gap longer than ``gap_us`` (100: inside a step the device hands over within
microseconds; between two steps the host samples for milliseconds).  The
burst is counted whole, wherever the span's edges fall in it.  Left out are a
span whose burst starts or ends more than ``slack_us`` (2,000) outside it (a
prefill that ran into the step: its ``fusion.N`` are not the decode
program's), and a span whose burst is the first or the last of the device's
line (the trace may have cut it).

``args`` and the result are ``decode_step_ops``'s: ``span``, ``program``,
``scopes`` (optional), ``cost`` (optional; handed ``traced.<argument>``, the
mean over the steps counted HERE).  Nothing to read gives nothing.
"""

import bisect

from benchlib import costs, model

_steps = model.beside(__file__, "readers", "decode_step_ops")


def bursts(nested, gap_ns):
    """[(start, end, first, past_last)] over one line's events in start order
    (``TraceData.nested``: [name, start, self time, duration, children])."""
    out = []
    for i, (_, start, _, dur, _) in enumerate(nested):
        if out and start - out[-1][1] <= gap_ns:
            out[-1][1] = max(out[-1][1], start + dur)
            out[-1][3] = i + 1
        else:
            out.append([start, start + dur, i, i + 1])
    return out


def burst_of(span, found, starts, slack_ns):
    """The index of the burst ``span`` overlaps most, if it is whole and the
    span's own: not the line's first or last, and within ``slack_ns`` of the span."""
    lo, hi = span
    best, most = None, 0.0
    for i in range(max(0, bisect.bisect_right(starts, lo) - 1), len(found)):
        if found[i][0] >= hi:
            break
        over = min(hi, found[i][1]) - max(lo, found[i][0])
        if over > most:
            best, most = i, over
    if best is None or best in (0, len(found) - 1):
        return None
    if found[best][0] < lo - slack_ns or found[best][1] > hi + slack_ns:
        return None
    return best


def read(obs, args, peak):
    steps = _steps.steps_in_trace(obs, args["span"])
    if not steps:
        return None
    wanted = None
    if "scopes" in args:
        wanted = _steps.instructions(obs, args["program"], set(args["scopes"]))
        if not wanted:
            return None
    data = obs.trace()
    gap_ns, slack_ns = 1e3 * float(args.get("gap_us", 100.0)), 1e3 * float(args.get("slack_us", 2000.0))
    kept = set(range(len(steps)))
    per_device = []
    for device in data.devices:
        nested = data.nested(device)
        found = bursts(nested, gap_ns)
        starts = [b[0] for b in found]
        mine = {i: burst_of((s, e), found, starts, slack_ns) for i, (s, e, _) in enumerate(steps)}
        taken = [b for b in mine.values() if b is not None]
        kept &= {i for i, b in mine.items() if b is not None and taken.count(b) == 1}
        per_device.append((nested, found, mine))
    if not kept:
        return None
    counted = every = 0.0
    for nested, found, mine in per_device:
        for i in kept:
            for name, _, own, _, _ in nested[found[mine[i]][2]:found[mine[i]][3]]:
                own = max(0.0, own)
                every += own
                m = _steps._INSTRUCTION.match(name)
                if wanted is None or (m and m.group(1) in wanted):
                    counted += own
    if counted <= 0.0 or every <= 0.0:
        return None
    if "cost" not in args:
        return 100.0 * counted / every
    spans = [steps[i][2] for i in sorted(kept)]
    traced = {}
    for key in set().union(*(a.keys() for a in spans)):
        values = [a[key] for a in spans if isinstance(a.get(key), (int, float))]
        if len(values) == len(spans):
            traced["traced." + key] = sum(values) / len(values)
    try:
        need = costs.find(args["cost"], obs.data_dir)(obs.config, obs.traffic, obs.chips, {**obs.counters, **traced}, obs.arch)
    except KeyError:
        return None
    least = max(need["flops"] / peak["bf16_flops_per_s"], need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / (counted / len(data.devices) / len(spans) / 1e9)
