"""The three reader files PR 24 brought, on hand-made observations, and the
ten metric files that name them: found by name, nothing read where nothing
is, the window held to."""

import json
import os

import pytest

import bench_rules as R
import bench_testlib as B
from benchlib import readers, spec as S
from benchlib.observe import Observations

EPOCH = 1000.0          # monotonic time of the program tracer's ts 0
WINDOW = (1100.0, 1150.0)

#: the ten; which cells list each is its own file's to say ("cells": benchlib/spec.py), not this file's
NEW = [
    "serve_step_ms", "serve_step_sample_ms", "serve_decode_wait_ms", "serve_logits_d2h_ms", "serve_queue_wait_ms",
    "serve_queue_wait_p90_ms", "serve_itl_worst_p50_ms", "prefill_device_ms", "setup_program_build_s", "setup_program_load_s",
]


def ev(name, start, dur, **args):
    """A span as the program's tracer exports it (microseconds from its epoch)."""
    out = {"ph": "X", "name": name, "cat": "serve", "ts": (start - EPOCH) * 1e6, "dur": dur * 1e6}
    if args:
        out["args"] = args
    return out


def obs(events=(), spans=()):
    return Observations(
        window=WINDOW, spans=list(spans), counters={}, program_events=list(events),
        profiler=None, config={}, traffic={}, chips=1, program_epoch=EPOCH, data_dir=B.BENCH,
    )


def metric(name):
    with open(os.path.join(B.BENCH, "metrics", name + ".json")) as f:
        return {"name": name, "reader": json.load(f)}


def read(name, o):
    return readers.read(metric(name), o, {})


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_reads_nothing_from_a_program_without_its_spans(name):
    # the parent commit's program: no serve.* span of PR 24, no trace
    assert read(name, obs()) is None
    assert read(name, obs([ev("serve.other", 1110.0, 1.0)])) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_an_entry_of_the_cells_its_rule_names_and_of_no_other(name):
    spec = S.Spec()
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    for w in spec.doc["workloads"]:
        cell = spec.cell(w["name"])
        has = name in {m["name"] for m in cell.per_layer}
        assert has == (w["name"] in entry["workloads"]) == spec.belongs(name, cell, R.has_scope)
    reader = metric(name)["reader"]["reader"]
    assert reader in readers.READERS or os.path.isfile(os.path.join(B.BENCH, "readers", reader + ".py"))


def test_every_entry_has_its_metric_file_and_every_file_its_entry():
    with open(os.path.join(B.REPO, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    files = sorted(f[:-5] for f in os.listdir(os.path.join(B.BENCH, "metrics")) if f.endswith(".json"))
    assert sorted(names) == files and set(NEW) <= set(names)


@pytest.mark.parametrize("name,span", [
    ("serve_step_ms", "serve.step"), ("serve_step_sample_ms", "serve.sample"),
    ("serve_decode_wait_ms", "serve.decode.wait"), ("serve_logits_d2h_ms", "serve.decode.d2h"),
])
def test_step_anatomy_metrics_take_the_median_inside_the_window(name, span):
    events = [ev(span, 1090.0, 9.0)]                            # before the window
    events += [ev(span, 1099.99, 0.5)]                          # straddles its opening
    events += [ev(span, 1101.0 + k, 0.010 * (k + 1)) for k in range(5)]   # 10..50 ms
    events += [ev(span, 1149.9, 0.5), ev(span + ".x", 1120.0, 3.0)]       # straddles the close; another name
    assert read(name, obs(events)) == pytest.approx(30.0)


def test_queue_wait_percentiles_clip_to_the_window():
    waits = [ev("serve.queue_wait", 1101.0 + k, 0.001 * (k + 1), request=k) for k in range(11)]  # 1..11 ms
    outside = [ev("serve.queue_wait", 1099.0, 2.0, request=98), ev("serve.queue_wait", 1151.0, 0.5, request=99)]
    assert read("serve_queue_wait_ms", obs(waits + outside)) == pytest.approx(6.0)
    assert read("serve_queue_wait_p90_ms", obs(waits + outside)) == pytest.approx(10.0)
    # only spans outside the window: nothing to read
    assert read("serve_queue_wait_ms", obs(outside)) is None
    # the benchmark's own spans are read the same way
    assert read("serve_queue_wait_ms", obs(spans=[("serve.queue_wait", 1120.0, 0.004)])) == pytest.approx(4.0)


def test_worst_gap_counts_requests_that_end_in_the_window():
    def req(start, dur, itl, **more):
        return ev("serve.request", start, dur, request=1, itl_max_ms=itl, **more)

    events = [
        req(1080.0, 25.0, 40.0),           # arrived before the window, finished in it: counted
        req(1110.0, 5.0, 60.0),
        req(1120.0, 5.0, 80.0),
        req(1080.0, 10.0, 999.0),          # finished before the window
        req(1140.0, 15.0, 999.0),          # finished after it
        req(1130.0, 1.0, None, error="engine stopped"),   # failed: no gap to count
        ev("serve.request", 1131.0, 1.0, request=2),       # one token: no argument
        ev("serve.step", 1132.0, 1.0, itl_max_ms=5000.0),  # another span's argument
    ]
    assert read("serve_itl_worst_p50_ms", obs(events)) == pytest.approx(60.0)
    assert read("serve_itl_worst_p50_ms", obs(events[3:])) is None


def test_setup_totals_count_what_ended_before_the_window_once():
    events = [
        ev("serve.setup", 1010.0, 20.0),
        ev("serve.setup.kv_pool", 1015.0, 5.0),     # a child: another name, not counted
        ev("trainer.setup", 1025.0, 10.0),          # overlaps serve.setup by 5 s: union 25 s
        ev("serve.setup", 1095.0, 10.0),            # ends inside the window: not set-up
        ev("jit.compile.serve.prefill", 1040.0, 6.0),
        ev("jit.compile.serve.decode", 1050.0, 4.5),
        ev("jit.compile.train", 1120.0, 3.0),       # a compile INSIDE the window is not set-up
        ev("jit_cache.compile_s", 1041.0, 1.0),     # not under the prefix
    ]
    assert read("setup_program_build_s", obs(events)) == pytest.approx(25.0)
    assert read("setup_program_load_s", obs(events)) == pytest.approx(10.5)
    assert read("setup_program_load_s", obs(events[:4])) is None
    assert read("setup_program_build_s", obs(events[3:])) is None


def test_prefill_device_ms_needs_a_trace():
    # device time inside a span exists only in a traced run: without a
    # profiler the generic reader returns nothing and the line leaves it out
    assert read("prefill_device_ms", obs([ev("serve.prefill", 1110.0, 0.045, request=1)])) is None
