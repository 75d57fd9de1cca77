"""Where the benchmark's data lives and how a cell is put together from it.

``BENCHMARK.json`` (at the root given, the checkout's by default) names the
cells; everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under ``benchmark/``, found by the
name ``BENCHMARK.json`` gives it.  A later PR adds a cell by adding files
and entries; nothing here knows a cell, a model or a metric by name.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

#: what a metric's file may say under ``"cells"``: the cells that report it, by
#: the ``kind`` of their traffic.  ``{"of": <one of these>, "scope": <name>}``
#: narrows it to the cells whose program has that scope.  Absent, the metric's
#: list is its builder's to say (a cost function counts one architecture's work)
CELLS = {
    "every": ("train", "serve-open", "serve-closed"),
    "training": ("train",),
    "serving": ("serve-open", "serve-closed"),
    "open-loop": ("serve-open",),
}

#: the checkout: ``benchmark/benchlib/spec.py`` -> two levels up
CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


class SpecError(ValueError):
    """A benchmark data file is missing, malformed or inconsistent."""


def _load(path: str) -> Dict[str, Any]:
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file: {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path} is not JSON: {e}") from None
    if not isinstance(data, dict):
        raise SpecError(f"{path} must hold a JSON object")
    return data


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    name: str
    chips: int
    data_dir: str  # holds archs/, costs/ and readers/ beside the data files
    config_name: str
    config_file: str  # as BENCHMARK.json gives it, for messages
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]  # each: the BENCHMARK.json entry + "reader" (its metric file)


class Spec:
    """``BENCHMARK.json`` plus the directory its data files sit in."""

    def __init__(self, root: str = CHECKOUT) -> None:
        self.root = os.path.abspath(root)
        self.doc = _load(os.path.join(self.root, "BENCHMARK.json"))
        #: data files sit beside the harness unless the root brings its own
        #: ``benchmark/`` (a test's throw-away root does)
        own = os.path.join(self.root, "benchmark")
        self.data_dir = own if os.path.isdir(own) else os.path.join(CHECKOUT, "benchmark")
        self.peaks = _load(os.path.join(self.data_dir, "peaks.json"))

    def _applies(self, metric: Dict[str, Any], cell_name: str) -> bool:
        return "workloads" not in metric or cell_name in metric["workloads"]

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.doc["workloads"] if w["name"] == name), None)
        if entry is None:
            known = ", ".join(w["name"] for w in self.doc["workloads"])
            raise SpecError(f"no workload {name!r} in BENCHMARK.json (has: {known})")
        cfg_entry = next(
            (c for c in self.doc["configs"] if c["name"] == entry["config"]), None
        )
        if cfg_entry is None:
            raise SpecError(f"workload {name} names unknown config {entry['config']!r}")
        config = _load(os.path.join(self.root, cfg_entry["file"]))
        traffic = _load(
            os.path.join(self.data_dir, "traffic", entry["traffic"] + ".json")
        )
        per_layer = []
        for m in self.doc["per_layer"]:
            if self._applies(m, name):
                reader = _load(os.path.join(self.data_dir, "metrics", m["name"] + ".json"))
                per_layer.append({**m, "reader": reader})
        return Cell(
            name=name,
            chips=int(entry["chips"]),
            data_dir=self.data_dir,
            config_name=entry["config"],
            config_file=cfg_entry["file"],
            config=config,
            traffic_name=entry["traffic"],
            traffic=traffic,
            end_to_end=[m for m in self.doc["end_to_end"] if self._applies(m, name)],
            per_layer=per_layer,
        )

    def belongs(self, metric: str, cell: Cell, has_scope: Callable[[Cell, str], bool]) -> Optional[bool]:
        """Does the metric's file (``"cells"``: see ``CELLS``) put this cell
        on the metric's list?  None where the file states no rule.
        ``has_scope(cell, scope)`` says whether the cell's program has a
        scope: the tests ask a CPU lowering of the architecture's tiny form."""
        path = os.path.join(self.data_dir, "metrics", metric + ".json")
        rule = _load(path).get("cells")
        if rule is None:
            return None
        of, scope = (rule.get("of"), rule.get("scope")) if isinstance(rule, dict) else (rule, None)
        if not isinstance(of, str) or of not in CELLS or (isinstance(rule, dict) and (set(rule) != {"of", "scope"} or not isinstance(scope, str))):
            raise SpecError(f'{path}: "cells" is one of {", ".join(CELLS)}, or {{"of": one of them, "scope": a scope\'s name}}')
        return cell.traffic["kind"] in CELLS[of] and (scope is None or bool(has_scope(cell, scope)))

    def list_faults(self, has_scope: Callable[[Cell, str], bool]) -> List[str]:
        """Where a per-layer metric's ``workloads`` differs from what its
        file's ``"cells"`` says, for whatever cells the document has."""
        bad = []
        cells = [self.cell(w["name"]) for w in self.doc["workloads"]]
        for m in self.doc["per_layer"]:
            for cell in cells:
                want = self.belongs(m["name"], cell, has_scope)
                if want is not None and want != self._applies(m, cell.name):
                    bad.append(f"{m['name']}: {cell.name} is {'not ' if want else ''}on its list, against its file's \"cells\"")
        return bad

    def peak(self, device_kind: str) -> Dict[str, Any]:
        """Peaks of one chip; a kind the table does not hold is an error."""
        try:
            return self.peaks["device_kinds"][device_kind]
        except KeyError:
            raise SpecError(
                f"device kind {device_kind!r} is not in benchmark/peaks.json: "
                "add it with its source; no roofline against a guessed peak"
            ) from None


def check_document(doc: Dict[str, Any]) -> List[str]:
    """The contract's rules on names, units and references that a test can
    hold ``BENCHMARK.json`` to without the driver.  Returns the faults."""
    bad: List[str] = []

    def name_ok(what: str, value: Any) -> None:
        if not isinstance(value, str) or not NAME_RE.match(value):
            bad.append(f"{what}: {value!r} is not a valid name")

    def line_ok(what: str, value: Any) -> None:
        if not isinstance(value, str) or not 1 <= len(value) <= 200 or re.search(r"[\n\t]", value):
            bad.append(f"{what}: must be 1..200 characters on one line")

    keys = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    if set(doc) != keys:
        bad.append(f"top-level keys {sorted(doc)} != {sorted(keys)}")
        return bad
    if not 1 <= int(doc["run_seconds"]) <= 51:
        bad.append("run_seconds outside 1..51")
    seen: Dict[str, set] = {"config": set(), "workload": set(), "metric": set()}
    for c in doc["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config keys: {sorted(c)}")
            continue
        name_ok("config", c["name"])
        line_ok(f"config {c['name']} source", c["source"])
        line_ok(f"config {c['name']} why", c["why"])
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in doc["paths"]):
            bad.append(f"config file {c['file']} is outside paths")
        for k in c["reduced"]:
            name_ok("reduced key", k)
        if c["name"] in seen["config"]:
            bad.append(f"duplicate config {c['name']}")
        seen["config"].add(c["name"])
    pairs = set()
    for w in doc["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload keys: {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            name_ok(f"workload {k}", w[k])
        line_ok(f"workload {w['name']} why", w["why"])
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips must be 1 or 4")
        if w["config"] not in seen["config"]:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
        if w["name"] in seen["workload"] or (w["config"], w["traffic"]) in pairs:
            bad.append(f"duplicate workload or (config, traffic): {w['name']}")
        seen["workload"].add(w["name"])
        pairs.add((w["config"], w["traffic"]))
    for c in doc["configs"]:
        if not any(w.get("config") == c.get("name") for w in doc["workloads"]):
            bad.append(f"config {c.get('name')} is used by no cell")
    four = sum(1 for w in doc["workloads"] if w.get("chips") == 4)
    if four > max(1, len(doc["workloads"]) // 4):
        bad.append(f"{four} four-chip cells of {len(doc['workloads'])}")
    e2e = {m.get("name") for m in doc["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s among end_to_end")
    for kind, extra in (("end_to_end", {"bound"}), ("per_layer", {"layer", "moves"})):
        for m in doc[kind]:
            need = {"name", "unit", "better", "source"} | extra
            if not need <= set(m) or not set(m) <= need | {"workloads"}:
                bad.append(f"{kind} metric keys: {sorted(m)}")
                continue
            name_ok("metric", m["name"])
            if not UNIT_RE.match(str(m["unit"])):
                bad.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"metric {m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"metric {m['name']}: source {m['source']!r}")
            if m["name"] in seen["metric"]:
                bad.append(f"duplicate metric {m['name']}")
            seen["metric"].add(m["name"])
            for w in m.get("workloads", []):
                if w not in seen["workload"]:
                    bad.append(f"metric {m['name']}: unknown workload {w}")
            if kind == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"end-to-end {m['name']}: source {m['source']}")
                if not 0 < float(m["bound"]) <= 0.1:
                    bad.append(f"end-to-end {m['name']}: bound {m['bound']}")
            else:
                line_ok(f"metric {m['name']} layer", m["layer"])
                if m["moves"] not in e2e:
                    bad.append(f"metric {m['name']} moves unknown {m['moves']}")
    # every cell reports setup_s, one more end-to-end metric, one per-layer
    # metric; and a per-layer metric's `moves` is reported wherever it is
    for w in doc["workloads"]:
        def there(m: Dict[str, Any]) -> bool:
            return "workloads" not in m or w["name"] in m["workloads"]

        mine = {m["name"] for m in doc["end_to_end"] if there(m)}
        if "setup_s" not in mine or len(mine) < 2:
            bad.append(f"cell {w['name']} lacks setup_s or a second end-to-end metric")
        layer = [m for m in doc["per_layer"] if there(m)]
        if not layer:
            bad.append(f"cell {w['name']} has no per-layer metric")
        for m in layer:
            if m.get("moves") not in mine:
                bad.append(f"{m['name']} moves {m.get('moves')}, not reported in {w['name']}")
    return bad
