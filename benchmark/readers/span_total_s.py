"""Seconds of set-up spent inside some of the program's spans.

``args``: ``spans`` (a list of names) and/or ``prefix`` (a name's start).
Counted is the union of the matching spans that END before the window
opens — set-up is everything before it — unclipped, in seconds; spans that
nest or overlap count once.  Nothing there, nothing returned.
"""

from benchlib import trace


def read(obs, args, peak):
    names = set(args.get("spans", ()))
    prefix = args.get("prefix")
    lo, _ = obs.window
    found = [
        (s, s + d) for n, s, d in obs.all_spans()
        if (n in names or (prefix is not None and n.startswith(prefix))) and s + d <= lo
    ]
    if not found:
        return None
    return trace.total(trace.union(found))
