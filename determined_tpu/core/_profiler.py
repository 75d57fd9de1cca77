"""ProfilerContext: system-metrics sampler (reference ``core/_profiler.py``).

Collectors sample host CPU/memory/network/disk plus **TPU device metrics**
(HBM in use / device memory stats via jax, replacing the reference's
pynvml GPU collector) on a daemon thread, reporting into the metrics
shipper under per-resource groups.

Framework-level (XLA) tracing is separate: ``on(trace=True)`` also starts
``jax.profiler`` writing an xplane trace viewable in TensorBoard/XProf —
the analog of the reference's torch.profiler wrapper
(``_pytorch_context.py:426-462``).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Dict, Optional

from determined_tpu.core._distributed import DistributedContext
from determined_tpu.core._metrics import MetricsContext
from determined_tpu.observability import get_tracer

logger = logging.getLogger("determined_tpu.core.profiler")


def _read_proc_stat() -> Optional[Dict[str, float]]:
    try:
        with open("/proc/stat") as f:
            line = f.readline().split()
        vals = [float(v) for v in line[1:8]]
        idle = vals[3] + vals[4]
        total = sum(vals)
        return {"idle": idle, "total": total}
    except (OSError, ValueError, IndexError):
        return None


def _read_meminfo() -> Dict[str, float]:
    out: Dict[str, float] = {}
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                k, _, rest = line.partition(":")
                out[k.strip()] = float(rest.split()[0]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return out


def _read_net_bytes() -> Dict[str, float]:
    rx = tx = 0.0
    try:
        with open("/proc/net/dev") as f:
            for line in f.readlines()[2:]:
                name, _, rest = line.partition(":")
                if name.strip() == "lo":
                    continue
                cols = rest.split()
                rx += float(cols[0])
                tx += float(cols[8])
    except (OSError, ValueError, IndexError):
        pass
    return {"rx": rx, "tx": tx}


def _read_disk_bytes() -> Dict[str, float]:
    rd = wr = 0.0
    try:
        with open("/proc/diskstats") as f:
            for line in f:
                cols = line.split()
                if len(cols) < 10:
                    continue
                rd += float(cols[5]) * 512
                wr += float(cols[9]) * 512
    except (OSError, ValueError, IndexError):
        pass
    return {"read": rd, "write": wr}


def _tpu_memory_stats() -> Dict[str, float]:
    out: Dict[str, float] = {}
    try:
        import jax

        for i, d in enumerate(jax.local_devices()):
            stats = getattr(d, "memory_stats", lambda: None)()
            if not stats:
                continue
            out[f"device{i}_bytes_in_use"] = float(stats.get("bytes_in_use", 0))
            limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
            if limit:
                out[f"device{i}_bytes_limit"] = float(limit)
                out[f"device{i}_hbm_util_pct"] = (
                    100.0 * float(stats.get("bytes_in_use", 0)) / float(limit)
                )
    except Exception:  # noqa: BLE001
        pass
    return out


#: the annotation an xplane window carries at both ends, and the instant
#: the process tracer gets with it
CLOCK_SYNC = "dtpu.clock_sync"


def _clock_sync() -> None:
    """Stamp the host's monotonic clock into the running device trace and
    into the process tracer: three ``dtpu.clock_sync`` annotations, each with
    the ``time.monotonic_ns()`` read just before it as ``monotonic_ns``, and
    an instant of the same name and argument beside each.  The annotation's
    start on the trace's clock less its ``monotonic_ns`` is the offset
    between the two clocks (take the median of the six), so whoever has the
    xplane and ``events.jsonl`` lays one on the other with no side file
    (``docs/observability.md`` "A device trace on the tracer's clock")."""
    import jax

    tracer = get_tracer()
    for _ in range(3):
        ns = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(CLOCK_SYNC, monotonic_ns=ns):
            pass
        tracer.instant(CLOCK_SYNC, cat="profile", monotonic_ns=ns)


class ProfilerContext:
    SAMPLE_INTERVAL = 10.0

    def __init__(
        self,
        dist: DistributedContext,
        metrics: MetricsContext,
        trace_dir: Optional[str] = None,
    ) -> None:
        self._dist = dist
        self._metrics = metrics
        self._trace_dir = trace_dir
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._tracing = False
        self._steps_fn = lambda: None  # trainer installs a steps provider

    def set_steps_fn(self, fn) -> None:
        # rebinding a callable attr the sampler reads: a reference store is
        # GIL-atomic; the sampler uses either the old or new provider
        # dtpu: lint-ok[unlocked-shared-state]
        self._steps_fn = fn

    def on(self, sampling: bool = True, trace: bool = False) -> None:
        if sampling and self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._sample_loop, daemon=True, name="profiler-sampler"
            )
            self._thread.start()
        if trace and not self._tracing:
            import jax

            trace_dir = self._trace_dir or os.path.join(os.getcwd(), "xplane_traces")
            os.makedirs(trace_dir, exist_ok=True)
            jax.profiler.start_trace(trace_dir)
            self._tracing = True
            _clock_sync()

    @property
    def tracing(self) -> bool:
        return self._tracing

    def stop_trace(self) -> None:
        """End the xplane capture window (the trainer calls this after
        ``profiling.end_after_batch`` steps — whole-run traces grow
        unboundedly)."""
        if self._tracing:
            import jax

            _clock_sync()
            jax.profiler.stop_trace()
            self._tracing = False
            self._report_trace_summary()

    def _report_trace_summary(self) -> None:
        """Parse the just-captured xplane into an op table + category
        totals and report them as a ``profile`` metrics row, so the WebUI
        experiment page renders the profiler surface without launching the
        viewer task (reference: profiler charts on the experiment detail
        page, ``webui/react/src/pages/``).  Chief-only; best-effort — a
        missing xprof toolchain must never fail the trial."""
        if getattr(self._dist, "rank", 0) != 0:
            return
        trace_dir = self._trace_dir or os.path.join(os.getcwd(), "xplane_traces")
        try:
            from determined_tpu.utils import xplane

            ops = xplane.hlo_op_table(trace_dir)
            if not ops:
                return
            totals = xplane.category_totals(ops)
            self._metrics.report(
                "profile",
                self._steps_fn(),
                {
                    # top ops only: the row is a UI artifact, not an archive
                    "op_table": ops[:25],
                    "category_totals": totals,
                },
            )
        except Exception as e:  # noqa: BLE001
            logging.getLogger("determined_tpu.profiler").warning(
                "trace summary not reported: %s", e
            )

    def off(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join(timeout=5)
            self._thread = None
        self.stop_trace()

    def _sample_loop(self) -> None:
        prev_cpu = _read_proc_stat()
        prev_net = _read_net_bytes()
        prev_disk = _read_disk_bytes()
        prev_t = time.time()
        while not self._stop.wait(self.SAMPLE_INTERVAL):
            now = time.time()
            dt = max(now - prev_t, 1e-6)
            sample: Dict[str, Any] = {}
            cpu = _read_proc_stat()
            if cpu and prev_cpu:
                didle = cpu["idle"] - prev_cpu["idle"]
                dtotal = cpu["total"] - prev_cpu["total"]
                if dtotal > 0:
                    sample["cpu_util_pct"] = 100.0 * (1.0 - didle / dtotal)
            prev_cpu = cpu
            mem = _read_meminfo()
            if mem.get("MemTotal"):
                sample["memory_used_bytes"] = mem["MemTotal"] - mem.get("MemAvailable", 0.0)
                sample["memory_util_pct"] = 100.0 * sample["memory_used_bytes"] / mem["MemTotal"]
            net = _read_net_bytes()
            sample["net_rx_Bps"] = (net["rx"] - prev_net["rx"]) / dt
            sample["net_tx_Bps"] = (net["tx"] - prev_net["tx"]) / dt
            prev_net = net
            disk = _read_disk_bytes()
            sample["disk_read_Bps"] = (disk["read"] - prev_disk["read"]) / dt
            sample["disk_write_Bps"] = (disk["write"] - prev_disk["write"]) / dt
            prev_disk = disk
            sample.update(_tpu_memory_stats())
            prev_t = now
            try:
                self._metrics.report("system_metrics", self._steps_fn(), sample)
            except RuntimeError:
                return
