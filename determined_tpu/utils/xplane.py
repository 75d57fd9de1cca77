"""xplane (profiler trace) analysis: per-op device-time tables.

One parser serves three consumers: ``dtpu experiment profile --xplane``
(``cli/main.py``), the trial profiler (``core/_profiler.py``) and the
tensorboard viewer task (``exec/tensorboard.py`` renders op tables per
trial — the reference wires torch.profiler traces into TensorBoard's plugin,
``_pytorch_context.py:426-462``; here the platform parses its own traces).

Parsing rides the ``xprof`` package's hlo_stats tool (baked into this
image next to jax.profiler); there is no proto-schema copy in-repo.
"""

from __future__ import annotations

import glob
import json
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

# HLO categories that are cross-device communication
COLLECTIVE_CATEGORIES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective",
)


def xplane_files(trace_dir: str) -> List[str]:
    return sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )


def hlo_op_table(trace_source) -> List[Dict[str, object]]:
    """[{name, category, expression, time_us}] from a trace dir or file list.

    Raises RuntimeError when the xprof tooling is unavailable or the trace
    holds no xplane files.
    """
    try:
        from xprof.convert import raw_to_tool_data
    except Exception as e:  # pragma: no cover - environment-dependent
        raise RuntimeError(f"xprof tooling unavailable: {e}") from e

    files = (
        trace_source
        if isinstance(trace_source, (list, tuple))
        else xplane_files(trace_source)
    )
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_source}")
    data, _ = raw_to_tool_data.xspace_to_tool_data(list(files), "hlo_stats", {})
    if isinstance(data, bytes):
        data = data.decode()
    table = json.loads(data)
    if isinstance(table, dict):  # gviz DataTable
        cols = [c.get("label") or c.get("id") or "" for c in table["cols"]]
        rows = [[(c or {}).get("v") for c in r["c"]] for r in table["rows"]]
    else:
        cols = [c["label"] if isinstance(c, dict) else c for c in table[0]]
        rows = table[1:]
    low = [str(c).lower() for c in cols]
    name_i = next(i for i, c in enumerate(low) if "hlo op name" in c or c == "name")
    expr_i = next((i for i, c in enumerate(low) if "expression" in c), name_i)
    time_i = next(i for i, c in enumerate(low) if "total time" in c and "us" in c)
    cat_i = next((i for i, c in enumerate(low) if "category" in c), None)
    merged: Dict[Tuple[str, str, str], float] = defaultdict(float)
    for row in rows:
        key = (
            str(row[name_i]),
            str(row[cat_i]) if cat_i is not None else "",
            str(row[expr_i])[:160],
        )
        merged[key] += float(row[time_i] or 0)
    if merged:
        return [
            {"name": n, "category": c, "expression": e, "time_us": us}
            for (n, c, e), us in sorted(merged.items(), key=lambda kv: -kv[1])
        ]
    # CPU traces carry no per-HLO device rows (hlo_stats is empty); fall
    # back to aggregating the host plane's TraceMe events so the viewer
    # still renders something meaningful off-TPU.  Nested events mean
    # parents include children — a host-activity table, not a roofline.
    return _host_trace_table(files)


def _host_trace_table(files: List[str]) -> List[Dict[str, object]]:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2  # type: ignore

    merged: Dict[str, float] = defaultdict(float)
    for f in files:
        xs = xplane_pb2.XSpace()
        with open(f, "rb") as fh:
            xs.ParseFromString(fh.read())
        for plane in xs.planes:
            if not plane.name.endswith(":CPU"):
                continue
            ev_meta = {m.id: m.name for m in plane.event_metadata.values()}
            for line in plane.lines:
                for ev in line.events:
                    name = ev_meta.get(ev.metadata_id, "?")
                    merged[name] += ev.duration_ps / 1e6  # ps -> us
    return [
        {"name": n, "category": "host", "expression": n, "time_us": us}
        for n, us in sorted(merged.items(), key=lambda kv: -kv[1])
    ]


def split_collectives(ops: List[Dict[str, object]]) -> Tuple[float, float]:
    """(collective_us, other_us) for an op table."""
    coll = other = 0.0
    for op in ops:
        hay = (str(op["category"]) + " " + str(op["name"])).lower()
        if any(c in hay for c in COLLECTIVE_CATEGORIES):
            coll += float(op["time_us"])
        else:
            other += float(op["time_us"])
    return coll, other


def category_totals(ops: List[Dict[str, object]]) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for op in ops:
        out[str(op["category"]) or str(op["name"]).split(".")[0]] += float(
            op["time_us"]
        )
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
