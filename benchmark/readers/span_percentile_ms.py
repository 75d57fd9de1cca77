"""A percentile of the durations of the spans of one name, in milliseconds.

``args``: ``span`` (the name), ``q`` (0..100).  Spans are those that lie
wholly inside the window, as for ``span_median_ms``; nothing there, nothing
returned.
"""

from benchlib import stats


def read(obs, args, peak):
    spans = obs.spans_named(args["span"])
    if not spans:
        return None
    return 1000.0 * stats.percentile([d for _, _, d in spans], float(args["q"]))
