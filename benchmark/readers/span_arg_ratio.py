"""The ratio of two arguments of the program's spans of one name, each summed
over the window: what ``counter_ratio`` is to two counters, for counts the
program leaves on its spans and not among the run's counters.

``args``: ``span`` (the name), ``num`` and ``den`` (the keys under the event's
``args``), ``scale`` (optional).  Counted are the spans that END inside the
window and carry a number under BOTH keys (``span_arg_percentile`` counts the
same spans).  Only the program's tracer writes arguments, so only
``obs.program_events`` is read.  No such span, or a denominator of nothing,
gives nothing.
"""


def read(obs, args, peak):
    lo, hi = obs.window
    num = den = 0.0
    for ev in obs.program_events:
        if ev.get("ph") != "X" or ev.get("name") != args["span"]:
            continue
        end = obs.program_epoch + (ev["ts"] + ev["dur"]) / 1e6
        said = ev.get("args") or {}
        up, down = said.get(args["num"]), said.get(args["den"])
        if lo <= end <= hi and isinstance(up, (int, float)) and isinstance(down, (int, float)):
            num, den = num + up, den + down
    if not den:
        return None
    return float(args.get("scale", 1.0)) * num / den
