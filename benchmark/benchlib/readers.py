"""The generic readers a per-layer metric file names.

A metric is a small file of its own, ``benchmark/metrics/<name>.json``:
``{"reader": <name>, "args": {...}}``.  A reader takes the run's
:class:`~benchlib.observe.Observations` and the arguments, and returns the
number, or ``None`` where it finds nothing to read (the harness then leaves
the metric out of the line).  No reader knows a cell or a model: what a
reader needs of the architecture it asks the cell's adapter (``obs.arch``).
A name that is not one of ``READERS`` is a file a later PR added,
``benchmark/readers/<name>.py``, whose ``read(obs, args, peak)`` is called;
a ``cost`` is found the same way (``costs.find``).
"""

from __future__ import annotations

import os
import statistics
from typing import Any, Callable, Dict, Optional

from . import costs, model, trace as trace_mod
from .observe import Observations

Reader = Callable[[Observations, Dict[str, Any], Dict[str, Any]], Optional[float]]


def span_share(obs: Observations, args: Dict[str, Any], peak: Dict[str, Any]) -> Optional[float]:
    """Percent of the window spent inside spans of one name."""
    spans = obs.spans_named(args["span"])
    if not spans:
        return None
    lo, hi = obs.window
    covered = trace_mod.total(trace_mod.union((s, s + d) for _, s, d in spans))
    return 100.0 * covered / (hi - lo)


def span_median_ms(obs: Observations, args: Dict[str, Any], peak: Dict[str, Any]) -> Optional[float]:
    spans = obs.spans_named(args["span"])
    return 1000.0 * statistics.median(d for _, _, d in spans) if spans else None


def counter_ratio(obs: Observations, args: Dict[str, Any], peak: Dict[str, Any]) -> Optional[float]:
    num, den = obs.counters.get(args["num"]), obs.counters.get(args["den"])
    if num is None or not den:
        return None
    return float(args.get("scale", 1.0)) * num / den


def counter_value(obs: Observations, args: Dict[str, Any], peak: Dict[str, Any]) -> Optional[float]:
    """A reading the runner took itself and left among the counters."""
    value = obs.counters.get(args["counter"])
    return None if value is None else float(args.get("scale", 1.0)) * value


def _spans_in_trace(obs: Observations, name: str):
    """Spans of one name that lie wholly inside the device trace, on its clock."""
    data = obs.trace()
    if data is None or not data.devices:
        return None, []
    lo, hi = trace_mod.window_of(data)
    spans = [
        (s, s + d) for n, s, d in obs.host_spans_on_trace_clock()
        if n == name and s >= lo and s + d <= hi
    ]
    return data, spans


def device_ms_in_span(obs: Observations, args: Dict[str, Any], peak: Dict[str, Any]) -> Optional[float]:
    """Device busy time inside the host spans of one name, per span, ms:
    the device time of the one program those spans wait for."""
    data, spans = _spans_in_trace(obs, args["span"])
    if not spans:
        return None
    busy = sum(trace_mod.overlap_ns(data.busy(d), spans) for d in data.devices)
    return busy / len(data.devices) / len(spans) / 1e6


def device_busy_ms_per(obs: Observations, args: Dict[str, Any], peak: Dict[str, Any]) -> Optional[float]:
    """Device busy time of the whole trace over a count (steps traced), ms."""
    data, n = obs.trace(), obs.counters.get(args["per"])
    if data is None or not data.devices or not n:
        return None
    return 1000.0 * trace_mod.busy_seconds(data) / n


def device_idle_share(obs: Observations, args: Dict[str, Any], peak: Dict[str, Any]) -> Optional[float]:
    data = obs.trace()
    if data is None or not data.devices:
        return None
    return 100.0 * trace_mod.idle_share(data)


def _least_seconds(obs: Observations, cost: str, peak: Dict[str, Any]) -> float:
    """The least time the chip could take for what the cost function counts."""
    need = costs.find(cost, obs.data_dir)(obs.config, obs.traffic, obs.chips, obs.counters, obs.arch)
    return max(need["flops"] / peak["bf16_flops_per_s"], need["bytes"] / peak["hbm_bytes_per_s"])


def op_roofline(obs: Observations, args: Dict[str, Any], peak: Dict[str, Any]) -> Optional[float]:
    """Percent of its roofline bound that a kernel reaches: the least time
    the chip could take for one call's operations and bytes (``cost``, a
    function of ``costs.py`` or a file of ``costs/``) over the trace time of the operations whose
    name matches ``pattern``, per ``per`` (a counter: calls traced)."""
    data, n = obs.trace(), obs.counters.get(args["per"])
    if data is None or not data.devices or not n:
        return None
    measured = trace_mod.op_seconds(data, args["pattern"]) / n
    if measured <= 0.0:
        return None
    return 100.0 * _least_seconds(obs, args["cost"], peak) / measured


def span_roofline(obs: Observations, args: Dict[str, Any], peak: Dict[str, Any]) -> Optional[float]:
    """As :func:`op_roofline`, for a whole program: the measured time is the
    device busy time inside the host spans that wait for it."""
    measured_ms = device_ms_in_span(obs, args, peak)
    if not measured_ms:
        return None
    return 100.0 * _least_seconds(obs, args["cost"], peak) / (measured_ms / 1e3)


def mfu(obs: Observations, args: Dict[str, Any], peak: Dict[str, Any]) -> Optional[float]:
    """Model FLOP/s utilisation: operations the forward and backward passes
    need for a token (no recomputation) x tokens a second, over chips x peak."""
    rate = obs.counters.get(args["rate"])
    if not rate:
        return None
    per_token = costs.train_flops_per_token(obs.config, int(obs.traffic["seq_len"]), obs.arch)
    return 100.0 * per_token * rate / (obs.chips * peak["bf16_flops_per_s"])


READERS: Dict[str, Reader] = {
    f.__name__: f
    for f in (
        span_share, span_median_ms, counter_ratio, counter_value, device_ms_in_span,
        device_busy_ms_per, device_idle_share, op_roofline, span_roofline, mfu,
    )
}


def find(metric: Dict[str, Any], data_dir: str) -> Reader:
    """The reader a metric file names: one of ``READERS``, or the ``read``
    of ``<data_dir>/readers/<name>.py``."""
    name = str(metric["reader"].get("reader"))
    return model.named(READERS, name, os.path.join(data_dir, "readers", name + ".py"), "read", f"metric {metric['name']}: reader")


def check(metric: Dict[str, Any], data_dir: str) -> None:
    """Before a run: the metric's reader and its cost function exist."""
    find(metric, data_dir)
    cost = metric["reader"].get("args", {}).get("cost")
    if cost is not None:
        costs.find(cost, data_dir)


def read(metric: Dict[str, Any], obs: Observations, peak: Dict[str, Any]) -> Optional[float]:
    return find(metric, obs.data_dir)(obs, metric["reader"].get("args", {}), peak)
