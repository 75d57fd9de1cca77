"""What a run hands to the per-layer readers, and the device profiler.

Host spans are kept on the host's monotonic clock (seconds); the profiler's
trace has a clock of its own (nanoseconds from its start).  The profiler
wraps a few ``time.monotonic_ns()`` readings in ``bench.clock_sync``
annotations, from which ``trace.clock_offset_ns`` recovers the offset, so
that idle gaps on the device can be laid against what the host was doing.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from . import trace as trace_mod

Span = Tuple[str, float, float]  # name, start (monotonic s), duration (s)


class Profiler:
    """One short device trace into ``trace_dir`` (emptied first)."""

    def __init__(self, trace_dir: str) -> None:
        self.trace_dir = trace_dir
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        self.sync_marks_ns: List[float] = []
        self.started_at: Optional[float] = None
        self.stopped_at: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._data: Optional[trace_mod.TraceData] = None

    def _sync(self) -> None:
        import jax

        for _ in range(3):
            with jax.profiler.TraceAnnotation(trace_mod.SYNC_NAME):
                self.sync_marks_ns.append(float(time.monotonic_ns()))

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no event per Python call: the host stays fast
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.started_at = time.monotonic()
        self._sync()

    def stop(self) -> None:
        import jax

        if self.started_at is None or self.stopped_at is not None:
            return
        self._sync()
        self.stopped_at = time.monotonic()
        jax.profiler.stop_trace()

    def run_in_background(self, at: float, seconds: float) -> None:
        """Trace from monotonic time ``at`` for ``seconds``, off the thread
        that offers the load."""

        def work() -> None:
            time.sleep(max(0.0, at - time.monotonic()))
            self.start()
            time.sleep(seconds)
            self.stop()

        self._thread = threading.Thread(target=work, name="bench-profiler", daemon=True)
        self._thread.start()

    def close(self) -> None:
        if self._thread is not None:
            self._thread.join(timeout=120.0)
        self.stop()

    def data(self) -> Optional[trace_mod.TraceData]:
        """The reduced trace, or None where nothing was traced."""
        if self._data is None and self.stopped_at is not None:
            self._data = trace_mod.load(trace_mod.newest_xplane(self.trace_dir))
        return self._data


def tracer_epoch(tracer: Any) -> float:
    """Monotonic time of the program tracer's ``ts`` 0, read off an instant
    of our own rather than off the tracer's private fields."""
    now = time.monotonic()
    tracer.instant("bench.epoch_probe", cat="bench")
    for ev in reversed(tracer.chrome_events()):
        if ev.get("name") == "bench.epoch_probe":
            return now - ev["ts"] / 1e6
    return now


@dataclasses.dataclass
class Observations:
    window: Tuple[float, float]                 # monotonic seconds
    spans: List[Span]                           # the benchmark's own spans
    counters: Dict[str, float]
    program_events: List[Dict[str, Any]]        # the program tracer's events
    profiler: Optional[Profiler]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    program_epoch: float = 0.0                  # monotonic time of tracer ts 0
    arch: Any = None                            # the configuration's adapter (benchmark/archs/)
    data_dir: str = ""                          # the benchmark directory: readers/ and costs/ are looked up there

    def all_spans(self) -> List[Span]:
        """The benchmark's spans and the program's (``ph == "X"`` events of
        the repo's tracer, whose ``ts`` is microseconds from its epoch)."""
        out = list(self.spans)
        for ev in self.program_events:
            if ev.get("ph") == "X":
                out.append((ev["name"], self.program_epoch + ev["ts"] / 1e6, ev["dur"] / 1e6))
        return out

    def spans_named(self, name: str, clip: bool = True) -> List[Span]:
        lo, hi = self.window
        return [
            s for s in self.all_spans()
            if s[0] == name and (not clip or (s[1] >= lo and s[1] + s[2] <= hi))
        ]

    def trace(self) -> Optional[trace_mod.TraceData]:
        return self.profiler.data() if self.profiler is not None else None

    def host_spans_on_trace_clock(self) -> List[trace_mod.Event]:
        """Every span as (name, start_ns, duration_ns) on the trace's clock."""
        data = self.trace()
        if data is None or self.profiler is None:
            return []
        off = trace_mod.clock_offset_ns(data, self.profiler.sync_marks_ns)
        return [(n, s * 1e9 + off, d * 1e9) for n, s, d in self.all_spans()]
