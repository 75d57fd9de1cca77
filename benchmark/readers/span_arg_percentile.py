"""A percentile of one argument of the program's spans of one name.

``args``: ``span`` (the name), ``arg`` (the key under the event's ``args``),
``q`` (0..100), ``scale`` (optional).  Counted are the spans that END inside
the window — as ``tpot_p50_ms`` counts the requests that finished in it: a
request's span starts at its arrival, which may lie before the window — and
carry a number under ``arg``.  Only the program's tracer writes arguments,
so only ``obs.program_events`` is read.
"""

from benchlib import stats


def read(obs, args, peak):
    lo, hi = obs.window
    values = []
    for ev in obs.program_events:
        if ev.get("ph") != "X" or ev.get("name") != args["span"]:
            continue
        end = obs.program_epoch + (ev["ts"] + ev["dur"]) / 1e6
        value = (ev.get("args") or {}).get(args["arg"])
        if lo <= end <= hi and isinstance(value, (int, float)):
            values.append(float(value))
    if not values:
        return None
    return float(args.get("scale", 1.0)) * stats.percentile(values, float(args["q"]))
