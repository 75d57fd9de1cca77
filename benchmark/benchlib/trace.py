"""From a profiler trace to numbers: the reduction every PR is measured by.

A trace is read with nothing but jax (``jax.profiler.ProfileData``).  What
comes out is plain data — per device a list of operation events, and the
host's spans on the same clock — and every function below works on that
plain data, so the tests check them on a small synthetic trace.

The idea of the per-operation table is ``determined_tpu/utils/xplane.py``'s
(which needs the ``xprof`` package and gives totals only); intervals are
needed here for the busy union, the idle gaps and the exposed collectives.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: (name, start_ns, duration_ns)
Event = Tuple[str, float, float]
Interval = Tuple[float, float]

#: the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
#: gaps shorter than this are the device's own hand-over between operations
SHORT_GAP_NS = 20_000.0
SYNC_NAME = "bench.clock_sync"


@dataclasses.dataclass
class TraceData:
    """``devices``: plane name -> that device's operation events.
    ``host``: annotation events of the host planes (name, start, duration).
    All times in nanoseconds on the trace's clock."""

    devices: Dict[str, List[Event]]
    host: List[Event]
    _busy: Dict[str, List[Interval]] = dataclasses.field(default_factory=dict, repr=False)
    _nested: Dict[str, List[List]] = dataclasses.field(default_factory=dict, repr=False)

    def busy(self, device: str) -> List[Interval]:
        """The union of one device's operation intervals, computed once: a
        serving trace holds hundreds of thousands of events."""
        if device not in self._busy:
            self._busy[device] = union(_intervals(self.devices[device]))
        return self._busy[device]

    def nested(self, device: str) -> List[List]:
        if device not in self._nested:
            self._nested[device] = _nest(self.devices[device])
        return self._nested[device]


def newest_xplane(trace_dir: str) -> str:
    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, host_prefixes: Sequence[str] = ("bench.",)) -> TraceData:
    """Read an ``.xplane.pb``.  Device planes are those named
    ``/device:<KIND>:<n>`` with an ``XLA Ops`` line; of the host planes only
    events whose name starts with one of ``host_prefixes`` are kept."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(tuple(host_prefixes)):
                        host.append((e.name, float(e.start_ns), float(e.duration_ns)))
    return TraceData(devices=devices, host=host)


def describe(path: str, top: int = 12, grep: str = "") -> str:
    """Planes, lines and the commonest event names of a trace: what a
    builder looks at by hand before trusting the reduction.  ``grep`` also
    lists, for each line, the events whose name matches it, with their stats."""
    from jax.profiler import ProfileData

    rx = re.compile(grep) if grep else None
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            total: Dict[str, float] = defaultdict(float)
            for e in events:
                total[e.name] += float(e.duration_ns)
            names = sorted(total.items(), key=lambda kv: -kv[1])[:top]
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            out.extend(f"    {ns / 1e6:10.3f} ms  {name[:140]}" for name, ns in names)
            if rx is not None:
                seen = set()
                for e in events:
                    if rx.search(e.name) and e.name not in seen:
                        seen.add(e.name)
                        stats = {k: str(v)[:120] for k, v in list(e.stats)[:10]}
                        out.append(f"    MATCH {total[e.name] / 1e6:9.3f} ms  {e.name[:300]}  {stats}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of ``a`` (a union) that no interval of ``b`` (a union) covers."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _intervals(events: Iterable[Event]) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


def window_of(trace: TraceData) -> Interval:
    """From the first to the last operation on any device."""
    starts = [s for ev in trace.devices.values() for _, s, _ in ev]
    ends = [s + d for ev in trace.devices.values() for _, s, d in ev]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_seconds(trace: TraceData, window: Optional[Interval] = None) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = window or window_of(trace)
    per = [total(clip(trace.busy(d), lo, hi)) for d in trace.devices]
    return sum(per) / len(per) / 1e9


def idle_share(trace: TraceData, window: Optional[Interval] = None) -> float:
    lo, hi = window or window_of(trace)
    return 1.0 - busy_seconds(trace, (lo, hi)) * 1e9 / (hi - lo)


# ---------------------------------------------------------------------------
# operations
# ---------------------------------------------------------------------------


def _nest(events: Sequence[Event]) -> List[List]:
    """[name, start, self duration, duration, children] for every event of
    one line, where events nest properly (a ``while`` encloses its body)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[List] = []
    stack: List[int] = []
    for name, start, dur in order:
        while stack and start >= out[stack[-1]][1] + out[stack[-1]][3]:
            stack.pop()
        if stack:
            out[stack[-1]][2] -= dur
            out[stack[-1]][4] += 1
        out.append([name, start, dur, dur, 0])
        stack.append(len(out) - 1)
    return out


def self_times(events: Sequence[Event], nested: Optional[List[List]] = None) -> List[Event]:
    """Each event with the time of the events nested inside it taken out."""
    return [(n, s, max(0.0, own)) for n, s, own, _, _ in (nested or _nest(events))]


def overlap_ns(busy: Sequence[Interval], spans: Sequence[Interval]) -> float:
    """Time of ``busy`` (a union) that falls inside ``spans``, by bisection
    over running totals: one pass, however many spans there are."""
    import bisect

    starts = [a for a, _ in busy]
    ends = [b for _, b in busy]
    run = [0.0]
    for a, b in busy:
        run.append(run[-1] + (b - a))

    def upto(t: float) -> float:  # busy time before t
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0.0
        return run[i - 1] + min(t, ends[i - 1]) - starts[i - 1]

    return sum(upto(hi) - upto(lo) for lo, hi in spans)


def op_seconds(trace: TraceData, pattern: str) -> float:
    """Self time of the operations whose name matches ``pattern``, averaged
    over the devices, in seconds."""
    rx = re.compile(pattern)
    per = [
        sum(d for n, _, d in self_times(ev, trace.nested(dev)) if rx.search(n))
        for dev, ev in trace.devices.items()
    ]
    return sum(per) / len(per) / 1e9


_HLO_RE = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")


def short_name(name: str) -> str:
    """An operation's name for a list a person reads.  On a TPU an event's
    name is the whole HLO instruction; keep its name and its (first) result
    shape, as in ``fusion_f32[4096]``; fold what JSON readers dislike."""
    m = _HLO_RE.match(name)
    if m:
        # without the instruction's number: the same operation of every layer
        # (fusion.5, fusion.13, ... of one shape) then adds up to one row
        name = re.sub(r"\.\d+$", "", m.group(1)) + ("_" + m.group(2) if m.group(2) else "")
    return re.sub(r"[^A-Za-z0-9_.\-\[\],]+", "_", name)[:110].strip("_")


def top_ops(trace: TraceData, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` operations with most self time, as shares of all device
    operation time, summed over devices."""
    tally: Dict[str, float] = defaultdict(float)
    for dev, ev in trace.devices.items():
        for name, _, d in self_times(ev, trace.nested(dev)):
            tally[short_name(name)] += d
    all_time = sum(tally.values()) or 1.0
    return [(k, v / all_time) for k, v in sorted(tally.items(), key=lambda kv: -kv[1])[:n]]


# ---------------------------------------------------------------------------
# idle gaps, by what the host was doing
# ---------------------------------------------------------------------------


def idle_gaps_by_host_span(
    trace: TraceData, host_spans: Sequence[Event], n: int = 10
) -> List[Tuple[str, float]]:
    """The device's idle time inside the window, split by the host span that
    covers each stretch of it (the innermost, i.e. shortest, span wins), in
    seconds, averaged over devices.  Gaps under 20 us are the device's own;
    idle time under no span is the host outside any span."""
    lo, hi = window_of(trace)
    spans = sorted(host_spans, key=lambda e: e[2])  # shortest first
    tally: Dict[str, float] = defaultdict(float)
    for dev in trace.devices:
        gaps = subtract([(lo, hi)], trace.busy(dev))
        for a, b in gaps:
            if b - a < SHORT_GAP_NS:
                tally["between ops (<20 us)"] += b - a
                continue
            left = [(a, b)]
            for name, s, d in spans:
                if not left:
                    break
                if s + d <= a or s >= b:
                    continue
                covered = clip(left, s, s + d)
                if covered:
                    tally[name] += total(covered)
                    left = subtract(left, union(covered))
            tally["host: outside any span"] += total(left)
    k = len(trace.devices) or 1
    ranked = sorted(tally.items(), key=lambda kv: -kv[1])[:n]
    return [(short_name(name), ns / k / 1e9) for name, ns in ranked]


def clock_offset_ns(trace: TraceData, sync_marks_ns: Sequence[float]) -> float:
    """Trace clock minus the host's monotonic clock.  The harness wraps
    ``time.monotonic_ns()`` readings in ``bench.clock_sync`` annotations; the
    k-th annotation in the trace belongs to the k-th reading."""
    found = sorted(s + d / 2 for n, s, d in trace.host if n == SYNC_NAME)
    pairs = list(zip(found, sorted(sync_marks_ns)))
    if not pairs:
        raise ValueError("no bench.clock_sync annotation in the trace")
    diffs = sorted(t - m for t, m in pairs)
    return diffs[len(diffs) // 2]
