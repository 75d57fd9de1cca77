"""The reader PR 68 brought (``readers/setup_parts_s.py``) and its ten metric
files: on hand-made events the groups add up to the whole, the order of the
groups decides a moment two spans cover, a span that ends inside the window is
left out, an empty group reads 0.0 and events without ``process.start`` read
nothing; the program's own arithmetic (``observability.setup_parts``, what the
operator's line is made from) is held to the reader's; and the tiny cells
through the harness, where the timeline stands beside the run's ``setup_s``."""

import importlib.util
import json
import os

import pytest

import bench_rules as R
import bench_testlib as B
from benchlib import harness, readers, spec as S
from benchlib.observe import Observations

from determined_tpu.observability import format_setup_line, setup_parts

EPOCH = 1000.0          # monotonic time of the program tracer's ts 0
WINDOW = (1040.0, 1090.0)
METRICS = {
    "setup_timeline_s": "whole", "setup_before_program_s": "before_program", "setup_import_s": "import",
    "setup_program_inspect_s": "program_inspect", "setup_xla_trace_lower_s": "xla_trace_lower",
    "setup_xla_load_s": "xla_load", "setup_program_first_run_s": "program_first_run",
    "setup_program_self_s": "program_self", "setup_first_work_s": "first_work", "setup_rest_s": "rest",
}
PARTS = [m for m, g in METRICS.items() if g != "whole"]


def ev(name, start, dur, cat="setup", **args):
    """A span as the program's tracer exports it (microseconds from its epoch)."""
    out = {"ph": "X", "name": name, "cat": cat, "ts": (start - EPOCH) * 1e6, "dur": dur * 1e6}
    if args:
        out["args"] = args
    return out


def origin(at):
    return {"ph": "i", "s": "t", "name": "process.start", "cat": "setup", "ts": (at - EPOCH) * 1e6, "args": {"source": "proc_stat"}}


def obs(events=(), window=WINDOW):
    return Observations(
        window=window, spans=[], counters={}, program_events=list(events),
        profiler=None, config={}, traffic={}, chips=1, program_epoch=EPOCH, data_dir=B.BENCH,
    )


def metric(name):
    with open(os.path.join(B.BENCH, "metrics", name + ".json")) as f:
        return {"name": name, "reader": json.load(f)}


def read(name, o):
    return readers.read(metric(name), o, {})


def reader_module():
    spec = importlib.util.spec_from_file_location("setup_parts_s", os.path.join(B.BENCH, "readers", "setup_parts_s.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def a_start():
    """A serving start of 40.5 s: the process at 999.5 (half a second before
    the tracer), imports 1012-1015, weights unnamed to 1022, ``serve.setup``
    1022-1024, a first call 1024-1034 (trace 2, lower 1, load 3 with the
    retrieval inside, inspect 1.5, first run 2, 0.5 of its own), the engine's
    start, warm-up steps 1035-1037, and the first wave: ONE step from 1038
    across the window's opening with two admissions of 0.75 s inside it."""
    return [
        origin(999.5),
        {"ph": "i", "s": "t", "name": "setup.cache_configured", "cat": "setup", "ts": 3.0e6, "args": {"path": "/c", "entries": 3}},
        ev("import.determined_tpu.serve", 1012.0, 3.0),
        ev("import.determined_tpu.models", 1013.0, 1.5),                       # nested: counted once
        ev("serve.setup", 1022.0, 2.0, cat="serve"),
        ev("xla.trace", 1022.5, 0.25, cat="compile", fun_name="zeros"),         # inside serve.setup: the trace's, not set-up's
        ev("jit.compile.serve.prefill", 1024.0, 10.0, cat="compile"),
        ev("xla.trace", 1024.0, 2.0, cat="compile", fun_name="serve_prefill"),
        ev("xla.lower", 1026.0, 1.0, cat="compile", fun_name="jit(serve_prefill)"),
        ev("xla.compile", 1027.0, 3.0, cat="compile", fun_name="jit(serve_prefill)"),
        ev("xla.cache_load", 1027.5, 2.25, cat="compile"),
        ev("jit.compile.serve.prefill.inspect", 1030.5, 1.5, cat="compile", text_bytes=10),
        ev("jit.compile.serve.prefill.first_run", 1032.0, 2.0, cat="compile"),
        ev("xla.trace", 1032.0, 0.5, cat="compile", fun_name="serve_prefill"),  # inside the first run: the trace's
        ev("serve.engine.start", 1034.0, 0.25, cat="serve"),
        ev("serve.step", 1035.0, 0.75, cat="serve", step=1),
        ev("serve.step", 1036.0, 1.0, cat="serve", step=2),
        ev("serve.step", 1038.0, 2.5, cat="serve", step=3),                     # the first wave's step ends inside the window: left out,
        ev("serve.admission", 1038.0, 0.75, cat="serve", request=1),             # its admissions end before it: in
        ev("serve.admission", 1038.75, 0.75, cat="serve", request=2),
        ev("serve.step", 1050.0, 1.0, cat="serve", step=4),
    ]


EXPECT = {
    "setup_timeline_s": 40.5, "setup_before_program_s": 12.5, "setup_import_s": 3.0,
    "setup_program_inspect_s": 1.5, "setup_xla_trace_lower_s": 3.75, "setup_xla_load_s": 3.0,
    "setup_program_first_run_s": 1.5, "setup_program_self_s": 2.5, "setup_first_work_s": 3.25,
    "setup_rest_s": 9.5,
}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_new_metric_is_on_the_list_of_every_cell(name):
    spec = S.Spec()
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    assert entry["moves"] == "setup_s" and entry["unit"] == "s" and entry["source"] == "program_span" and entry["better"] == "lower"
    # a layer the accepted set-up metrics already name, letter for letter
    assert entry["layer"] in {m["layer"] for m in spec.doc["per_layer"] if m["name"] in ("setup_program_build_s", "setup_program_load_s")}
    for w in spec.doc["workloads"]:
        assert w["name"] in entry["workloads"] and spec.belongs(name, spec.cell(w["name"]), R.has_scope) is True
    m = metric(name)
    assert m["reader"]["reader"] == "setup_parts_s" and m["reader"]["args"] == {"group": METRICS[name]}
    readers.check(m, B.BENCH)


def test_the_ten_metrics_fault_no_cells_list():
    spec = S.Spec()
    assert S.check_document(spec.doc) == []
    # the ten alone (the rest of the document is ``test_bench_rules.py``'s, and lowers every architecture)
    spec.doc = dict(spec.doc, per_layer=[m for m in spec.doc["per_layer"] if m["name"] in METRICS])
    assert len(spec.doc["per_layer"]) == 10 and len(spec.doc["workloads"]) == 14
    assert spec.list_faults(R.has_scope) == []


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_groups_of_a_start(name):
    assert read(name, obs(a_start())) == pytest.approx(EXPECT[name], abs=1e-9)


def test_the_groups_and_the_rest_add_up_to_the_whole():
    o = obs(a_start())
    assert sum(read(name, o) for name in PARTS) == pytest.approx(read("setup_timeline_s", o), abs=1e-9)
    # the origin far from the spans, as a machine's uptime is: still to 1e-9
    far = [dict(e, ts=e["ts"] + 3.0e11) for e in a_start()]
    o = Observations(
        window=(WINDOW[0] + 3.0e5, WINDOW[1] + 3.0e5), spans=[], counters={}, program_events=far,
        profiler=None, config={}, traffic={}, chips=1, program_epoch=EPOCH, data_dir=B.BENCH,
    )
    assert sum(read(name, o) for name in PARTS) == pytest.approx(read("setup_timeline_s", o), abs=1e-9)
    assert read("setup_timeline_s", o) == pytest.approx(40.5, abs=1e-4)


def test_the_order_of_the_groups_decides_a_moment_two_spans_cover():
    # one second that an import, a trace, a first call and a step all cover: the import's
    events = [origin(1000.0), ev("import.determined_tpu.train", 1010.0, 1.0), ev("xla.trace", 1010.0, 1.0),
              ev("jit.compile.train", 1010.0, 1.0), ev("step.dispatch", 1010.0, 1.0)]
    o = obs(events)
    assert [read(name, o) for name in ("setup_import_s", "setup_xla_trace_lower_s", "setup_program_self_s", "setup_first_work_s")] == [1.0, 0.0, 0.0, 0.0]
    # without the import the trace takes it, then the first call, then the step
    for gone, name in ((1, "setup_xla_trace_lower_s"), (2, "setup_program_self_s"), (3, "setup_first_work_s")):
        o = obs([origin(1000.0), ev("import.determined_tpu.models", 1001.0, 0.5)] + events[1 + gone:])
        assert read(name, o) == 1.0
    # a training cell's first work: the three spans of a step before the window
    o = obs([origin(1000.0), ev("import.determined_tpu.train", 1001.0, 1.0), ev("data.wait", 1030.0, 0.5, cat="data"),
             ev("step.dispatch", 1030.5, 0.25, cat="step"), ev("step.boundary_block", 1031.0, 2.0, cat="step")])
    assert read("setup_first_work_s", o) == 2.75


def test_a_span_that_ends_inside_the_window_is_left_out():
    events = [origin(1000.0), ev("import.determined_tpu.serve", 1001.0, 1.0), ev("trainer.setup", 1030.0, 10.5)]
    assert read("setup_program_self_s", obs(events)) == 0.0
    assert read("setup_rest_s", obs(events)) == 38.0
    events[-1] = ev("trainer.setup", 1030.0, 10.0)  # ends as the window opens: in
    assert read("setup_program_self_s", obs(events)) == 10.0


def test_an_empty_group_reads_zero_and_no_origin_reads_nothing():
    only = [origin(999.0)]
    assert [read(name, obs(only)) for name in sorted(METRICS)] == [41.0 if METRICS[n] in ("whole", "rest") else 0.0 for n in sorted(METRICS)]
    # the parent's program: every span and no process.start
    assert all(read(name, obs(a_start()[1:])) is None for name in METRICS)
    assert all(read(name, obs()) is None for name in METRICS)
    # a window that opened before the process began is no timeline
    assert read("setup_timeline_s", obs(only, window=(990.0, 995.0))) is None


def test_the_programs_own_arithmetic_is_the_readers():
    """What the operator's line is made from (``observability.setup_parts``)
    against what the benchmark reads, on the same events."""
    module = reader_module()
    assert [g for g, _ in module.GROUPS] == [g for g in METRICS.values() if g not in ("whole", "before_program", "rest")]
    for events in (a_start(), [origin(999.0)], a_start()[:3], a_start()[1:]):
        ours = module.parts(obs(events))
        theirs = setup_parts(events, WINDOW[0] - EPOCH)
        assert (ours is None) == (theirs is None)
        if ours is not None:
            assert set(ours) == set(theirs) == set(METRICS.values())
            assert all(ours[g] == pytest.approx(theirs[g], abs=1e-9) for g in ours)
    line = format_setup_line("replica ready", setup_parts(a_start(), WINDOW[0] - EPOCH))
    assert line.startswith("replica ready in 40.5 s: before the program 12.5, imports 3.0, programs 12.2 (trace and lower 3.8, load or compile 3.0, inspect 1.5, first run 1.5,")
    assert line.endswith("first work 3.2, under no span 9.5")


# ---------------------------------------------------------------------------
# through the harness, on the CPU: the timeline beside the run's setup_s
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The throw-away root with the tiny cells on the ten lists, and on the two set-up metrics' that were there."""
    root = B.throwaway_root(str(tmp_path_factory.mktemp("bench_root")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    for m in doc["per_layer"]:
        if m["name"] in METRICS or m["name"] in ("setup_program_build_s", "setup_program_load_s"):
            m["workloads"] += ["tiny.closed", "tiny.open", "tiny.train"]
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


#: a process of its own a cell, as the command is: the timeline starts where the process does
RUN = """
import json, sys, time
t_start = time.monotonic()
sys.path[:0] = [{bench!r}, {repo!r}]
from benchlib import harness
rc = harness.main(["--workload", {workload!r}, "--seed", str(2**31 + 68), "--seconds", "1.5", "--trace", "1"],
                  t_start=t_start, root={root!r}, require_tpu=False)
from determined_tpu.observability import get_tracer
print(json.dumps({{"event": "tracer", "rc": rc, "dropped": get_tracer().dropped(), "events": get_tracer().stats()["events"]}}))
"""


@pytest.mark.parametrize("workload", ["tiny.closed", "tiny.open", "tiny.train"])
def test_a_traced_run_prints_the_ten_and_they_stand_beside_its_setup_s(tiny_root, workload):
    import subprocess
    import sys

    script = RUN.format(bench=B.BENCH, repo=B.REPO, workload=workload, root=tiny_root)
    done = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, timeout=170, cwd=tiny_root)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.strip().splitlines() if x.startswith("{")]
    line, tracer = lines[-2], lines[-1]
    assert tracer["rc"] == 0 and tracer["dropped"] == 0 and line["correct"] is True
    assert set(METRICS) <= set(line["metrics"])
    got = {name: line["metrics"][name]["value"] for name in METRICS}
    assert all(v >= 0.0 for v in got.values()) and got["setup_before_program_s"] > 0
    assert sum(got[name] for name in PARTS) == pytest.approx(got["setup_timeline_s"], abs=1e-6)
    # the timeline is the run's own setup_s and the interpreter's start before the command's first stamp
    setup_s = next(x for x in lines if x.get("event") == "end_to_end_of_traced_run")["values"]["setup_s"]
    assert 0.0 < got["setup_timeline_s"] - setup_s < 0.5
    # what the cell built lies under a name: imports, jax's own events, the program's set-up, the first work
    for name in ("setup_import_s", "setup_xla_trace_lower_s", "setup_xla_load_s", "setup_program_self_s", "setup_first_work_s"):
        assert got[name] > 0, (name, got)
    # the two accepted readings of set-up read on
    assert line["metrics"]["setup_program_build_s"]["value"] > 0 and line["metrics"]["setup_program_load_s"]["value"] > 0
