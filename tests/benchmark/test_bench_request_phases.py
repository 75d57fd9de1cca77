"""The reader PR 53 brought (``readers/request_phase_ms.py``) and the six
metric files that came with it: on hand-made observations (a request that
started before the window is counted from its opening, its own admission is
never inside, 0.0 where no span met it, nothing where no request has two
tokens), and on the tiny closed and open cells through the harness, where
the reader's split of a request equals the program's own arguments."""

import importlib.util
import json
import os

import pytest

import bench_rules as R
import bench_testlib as B
from benchlib import harness, readers, spec as S
from benchlib.observe import Observations

EPOCH = 1000.0          # monotonic time of the program tracer's ts 0
WINDOW = (1100.0, 1150.0)
TPOT = {
    "tpot_decode_wait_ms": "decode_wait", "tpot_sample_ms": "sample",
    "tpot_prefill_stall_ms": "prefill_stall", "tpot_host_ms": "host",
}
STEP = {"serve_sample_wait_ms": "serve.sample.wait", "serve_lanes_ms": "serve.lanes"}
NEW = sorted(TPOT) + sorted(STEP)


def ev(name, start, dur, **args):
    """A span as the program's tracer exports it (microseconds from its epoch)."""
    out = {"ph": "X", "name": name, "cat": "serve", "ts": (start - EPOCH) * 1e6, "dur": dur * 1e6}
    if args:
        out["args"] = args
    return out


def request(start, ttft_s, end, tokens, rid, **more):
    return ev("serve.request", start, end - start, request=rid, output_tokens=tokens, ttft_ms=1000.0 * ttft_s, error=None, **more)


def obs(events=()):
    return Observations(
        window=WINDOW, spans=[], counters={}, program_events=list(events),
        profiler=None, config={}, traffic={}, chips=1, program_epoch=EPOCH, data_dir=B.BENCH,
    )


def metric(name):
    with open(os.path.join(B.BENCH, "metrics", name + ".json")) as f:
        return {"name": name, "reader": json.load(f)}


def read(name, o):
    return readers.read(metric(name), o, {})


def reader_module():
    spec = importlib.util.spec_from_file_location("request_phase_ms", os.path.join(B.BENCH, "readers", "request_phase_ms.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def steps(first, n, every=0.020, wait=0.012, sample=0.003):
    """``n`` steps of 20 ms from ``first``: 12 ms of wait, then 3 ms of sampling, then 5 ms of neither."""
    out = []
    for k in range(n):
        t = first + k * every
        out += [ev("serve.decode.wait", t, wait, step=k), ev("serve.sample", t + wait, sample, step=k)]
    return out


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_on_the_list_of_every_serving_cell_and_of_no_other(name):
    spec = S.Spec()
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    assert entry["moves"] == "tpot_p50_ms" and entry["unit"] == "ms" and entry["source"] == "program_span"
    for w in spec.doc["workloads"]:
        cell = spec.cell(w["name"])
        serving = cell.traffic["kind"].startswith("serve-")
        assert (w["name"] in entry["workloads"]) == spec.belongs(name, cell, R.has_scope) == serving
    reader = metric(name)["reader"]["reader"]
    assert reader in readers.READERS or os.path.isfile(os.path.join(B.BENCH, "readers", reader + ".py"))


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_reads_nothing_where_nothing_is(name):
    # no span at all; spans of a step and no request; a request of one token
    assert read(name, obs()) is None
    assert read(name, obs([ev("serve.step", 1110.0, 0.02, step=1)])) is None
    one = [request(1110.0, 0.5, 1111.0, 1, rid=1)] + (steps(1110.5, 10) if name in TPOT else [])
    assert read(name, obs(one)) is None


@pytest.mark.parametrize("name,span", sorted(STEP.items()))
def test_the_two_medians_of_a_steps_spans_hold_to_the_window(name, span):
    events = [ev(span, 1099.9995, 0.001), ev(span, 1149.9995, 0.001)]            # across the window's edges
    events += [ev(span, 1101.0 + k, 0.001 * (k + 1), step=k) for k in range(5)]  # 1..5 ms
    assert read(name, obs(events)) == pytest.approx(3.0)


def test_a_request_is_split_by_the_spans_its_time_lies_in():
    # first token at 1110.0 (arrived at 1109.0), 101 tokens, finished at 1112.0:
    # 100 steps of 20 ms, each 12 ms of wait and 3 ms of sampling; its own
    # admission (1109.4 - 1110.0) ends with its first token; two other
    # requests are admitted meanwhile, 40 ms and 60 ms, and stretch two steps
    events = [request(1109.0, 1.0, 1112.1, 101, rid=7)]
    events += [ev("serve.admission", 1109.4, 0.6, request=7)]
    events += steps(1110.0, 50) + [ev("serve.admission", 1111.0, 0.04, request=8)]
    events += steps(1111.04, 25) + [ev("serve.admission", 1111.54, 0.06, request=9)]
    events += steps(1111.6, 25)
    o = obs(events)
    assert read("tpot_decode_wait_ms", o) == pytest.approx(12.0)
    assert read("tpot_sample_ms", o) == pytest.approx(3.0)
    assert read("tpot_prefill_stall_ms", o) == pytest.approx(1.0)      # 100 ms of others' admissions over 100 gaps
    assert read("tpot_host_ms", o) == pytest.approx(5.0)
    # the four are the request's time a token: (1112.1 - 1110.0) / 100
    assert sum(read(name, o) for name in TPOT) == pytest.approx(21.0)


def test_a_request_that_started_before_the_window_is_counted_from_its_opening():
    # first token at 1090.0 while every lane was filled (10 s of admissions
    # before the window), finished at 1101.0 with 51 tokens: the runner
    # counts 1.0 s / 50, and so does the reader
    events = [request(1080.0, 10.0, 1101.0, 51, rid=1)]
    events += [ev("serve.admission", 1090.0 + k, 0.9, request=100 + k) for k in range(10)]   # all before 1100
    events += [ev("serve.admission", 1099.95, 0.1, request=200)]                             # across the opening: 50 ms inside
    events += steps(1100.05, 47)
    o = obs(events)
    assert read("tpot_prefill_stall_ms", o) == pytest.approx(1.0)
    assert read("tpot_decode_wait_ms", o) == pytest.approx(47 * 12.0 / 50)
    assert sum(read(name, o) for name in TPOT) == pytest.approx(20.0)


def test_zero_where_no_span_met_the_request_and_the_median_is_over_requests():
    quiet = [request(1105.0, 0.1, 1106.0, 11, rid=1)]
    assert read("tpot_prefill_stall_ms", obs(quiet)) == 0.0
    assert read("tpot_decode_wait_ms", obs(quiet)) == 0.0
    assert read("tpot_host_ms", obs(quiet)) == pytest.approx(90.0)      # all of 0.9 s over 10 gaps
    # three requests of 1, 2 and 4 ms of stall a token: the median is the second's
    events = []
    for k, stall in enumerate((0.010, 0.020, 0.040)):
        t = 1110.0 + 2 * k
        events += [request(t - 0.5, 0.5, t + 1.0, 11, rid=k), ev("serve.admission", t + 0.2, stall, request=50 + k)]
    assert read("tpot_prefill_stall_ms", obs(events)) == pytest.approx(2.0)


def test_requests_the_end_to_end_metric_does_not_count_are_not_counted():
    stalled = [ev("serve.admission", 1120.2, 0.05, request=9)]
    counted = request(1119.0, 1.0, 1121.0, 11, rid=1)
    others = [
        request(1090.0, 1.0, 1099.0, 11, rid=2),                              # finished before the window
        request(1140.0, 1.0, 1151.0, 11, rid=3),                              # finished after it
        dict(request(1119.0, 1.0, 1121.0, 11, rid=4), args={"request": 4, "output_tokens": 11, "ttft_ms": 1000.0, "error": "engine stopped"}),
        ev("serve.request", 1119.0, 2.0, request=5, output_tokens=0, ttft_ms=None, error="refused"),
    ]
    assert read("tpot_prefill_stall_ms", obs(others + stalled)) is None
    assert read("tpot_prefill_stall_ms", obs([counted] + others + stalled)) == pytest.approx(5.0)
    assert [rid for rid, _ in reader_module().per_request(obs([counted] + others + stalled), {"spans": ["serve.admission"], "q": 50})] == [1]


# ---------------------------------------------------------------------------
# through the harness, on the CPU: the reader against the program's own split
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The throw-away root with the tiny serving cells on the six lists."""
    root = B.throwaway_root(str(tmp_path_factory.mktemp("bench_root")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] += ["tiny.closed", "tiny.open"]
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


@pytest.mark.parametrize("workload", ["tiny.closed", "tiny.open"])
def test_the_readers_split_of_a_request_is_the_programs_own(tiny_root, monkeypatch, workload):
    seen = []
    real = readers.read
    monkeypatch.setattr(readers, "read", lambda m, o, peak: (seen.append(o), real(m, o, peak))[1])
    line = harness.run_cell(workload, seed=2**31 + 53, seconds=1.5, traced=True, root=tiny_root, require_tpu=False)
    assert line["correct"] is True and line["failed"] == 0
    # all six are in the line; a part may read 0.0 (no admission met the median request), the whole cannot
    assert set(NEW) <= set(line["metrics"])
    parts = {name: line["metrics"][name]["value"] for name in TPOT}
    assert all(v >= 0.0 for v in parts.values()) and parts["tpot_decode_wait_ms"] > 0 and parts["tpot_host_ms"] > 0
    assert all(line["metrics"][name]["value"] > 0 for name in STEP)
    o = seen[0]
    lo, hi = o.window
    spans = {}
    for e in o.program_events:
        if e.get("ph") == "X" and e["name"] == "serve.request":
            spans[e["args"]["request"]] = e
    per_request = reader_module().per_request
    compared = 0
    totals = {}
    for name, part in TPOT.items():
        for rid, value in per_request(o, metric(name)["reader"]["args"]):
            e = spans[rid]
            totals[rid] = totals.get(rid, 0.0) + value
            first = o.program_epoch + e["ts"] / 1e6 + e["args"]["ttft_ms"] / 1e3
            if first >= lo:
                # the same stamps on both sides: events round to 0.1 us, the arguments to 0.1 us a token
                assert value == pytest.approx(e["args"][f"tpot_{part}_ms"], abs=0.003), (name, e)
                compared += 1
    assert compared >= 4 * 3
    # whichever the interval, a request's four parts are its time a token as the runner counts it
    for rid, total in totals.items():
        e = spans[rid]
        start, end = o.program_epoch + e["ts"] / 1e6, o.program_epoch + (e["ts"] + e["dur"]) / 1e6
        first = max(start + e["args"]["ttft_ms"] / 1e3, lo)
        assert total == pytest.approx(1000.0 * (end - first) / (e["args"]["output_tokens"] - 1), abs=0.003)
