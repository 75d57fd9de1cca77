"""A request's milliseconds a token inside some of the program's spans: a
percentile over the requests that ``tpot_p50_ms`` counts, in its own unit.

``args``: ``spans`` (names of the program's spans) OR ``rest_of`` (names: what
of a request's time lies in NONE of them), and ``q`` (0..100).  Counted are
the ``serve.request`` spans that END inside the window, carry no ``error``
and two ``output_tokens`` or more, as the end-to-end metric counts requests.
A request's time runs from its first token (the span's start + ``ttft_ms``),
or from the window's opening where that is later, to its end: the same
interval the runner divides by ``output_tokens - 1``.  A closed loop's
first-wave requests got their first token before the window, while every
lane was being filled, and the runner counts them from the window's opening;
so the program's own split on the span (``tpot_*_ms``, from the first token)
cannot be read as it is.  Inside the interval the reader adds up the overlap
with the named spans of the engine's thread (or the interval less it) and
divides by ``output_tokens - 1``.  Metrics whose spans tile a step
(``serve.decode.wait``, ``serve.sample``, ``serve.admission``, and the rest)
add up, request by request, to that request's ``tpot``.  A request's own
admission ends at its first token and is never inside.

0.0 where none of the spans met the interval; nothing only where no request
qualified.  Only the program's tracer writes the arguments, so only
``obs.program_events`` is read.
"""

from benchlib import stats, trace

REQUEST = "serve.request"


def per_request(obs, args):
    """``[(request, milliseconds a token)]`` of the requests counted."""
    lo, hi = obs.window
    names = set(args.get("spans") or args["rest_of"])
    found, requests = [], []
    for ev in obs.program_events:
        if ev.get("ph") != "X":
            continue
        start = obs.program_epoch + ev["ts"] / 1e6
        if ev.get("name") in names:
            found.append((start, start + ev["dur"] / 1e6))
        elif ev.get("name") == REQUEST:
            requests.append((start, start + ev["dur"] / 1e6, ev.get("args") or {}))
    covered = trace.clip(trace.union(found), lo, hi)
    out = []
    for start, end, a in requests:
        tokens, ttft = a.get("output_tokens"), a.get("ttft_ms")
        if not lo <= end <= hi or a.get("error") is not None:
            continue
        if not isinstance(tokens, int) or tokens < 2 or not isinstance(ttft, (int, float)):
            continue
        first = max(start + ttft / 1e3, lo)
        inside = trace.total(trace.clip(covered, first, end))
        if "rest_of" in args:
            inside = (end - first) - inside
        out.append((a.get("request"), 1000.0 * inside / (tokens - 1)))
    return out


def read(obs, args, peak):
    values = [v for _, v in per_request(obs, args)]
    return stats.percentile(values, float(args["q"])) if values else None
