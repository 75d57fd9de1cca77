"""Sums of the program's counter events inside the window, and a ratio of two.

The Trainer pushes its step metrics as tracer counters at each report
boundary (``"ph": "C"`` events in ``obs.program_events``: value = the
period's mean x its steps).  ``args``: ``num`` (a counter's name), ``den``
(another; absent: the sum alone) and ``scale``.  An event counts if its time
lies inside the window: the boundary that closes the window counts, the one
that opens it (its steps ran before) does not.  A program without the
counter gives nothing.
"""


def total(obs, name):
    lo, hi = obs.window
    found = [
        float(ev["args"]["value"]) for ev in obs.program_events
        if ev.get("ph") == "C" and ev.get("name") == name and lo < obs.program_epoch + ev["ts"] / 1e6 <= hi
    ]
    return sum(found) if found else None


def per_step(obs, name):
    """A counter's mean a step over the window's report periods."""
    value, steps = total(obs, name), total(obs, "train.steps")
    return value / steps if value is not None and steps else None


def read(obs, args, peak):
    num = total(obs, args["num"])
    if num is None:
        return None
    if "den" not in args:
        return float(args.get("scale", 1.0)) * num
    den = total(obs, args["den"])
    return float(args.get("scale", 1.0)) * num / den if den else None
