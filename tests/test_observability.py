"""Observability layer: tracer thread-safety, ring overflow, Chrome trace
well-formedness, the goodput ledger, chaos-restart attribution, and the
overhead A/B (docs/observability.md).
"""

import json
import os
import threading
import time

import pytest

from determined_tpu.observability import (
    Tracer,
    compute_ledger,
    format_ledger_text,
    get_tracer,
    load_trace_events,
)

# lock_order: the runtime half of the lint concurrency pass — every
# test in this suite runs with threading.Lock/RLock patched so an
# acquisition-order inversion fails the test that exhibited it
pytestmark = [pytest.mark.no_thread_leaks, pytest.mark.lock_order]


@pytest.fixture(autouse=True)
def _clean_global_tracer():
    """The process-global tracer must not leak shipper threads, export
    handles, or events between tests."""
    yield
    tracer = get_tracer()
    tracer.close()
    tracer.configure(enabled=True)
    tracer.reset()


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_spans_thread_safe_under_concurrent_trial_threads():
    """Many threads recording concurrently (the scheduler's per-trial
    threads) lose nothing when the rings are sized for the load."""
    tracer = Tracer(ring_capacity=8192, flush_interval=0.05)
    tracer.start()
    n_threads, per_thread = 8, 1000
    # all threads alive at once: the OS may recycle a finished thread's
    # ident, which would merge trace tracks (and hide real races)
    barrier = threading.Barrier(n_threads)

    def worker(i):
        barrier.wait(timeout=30)
        for k in range(per_thread):
            t0 = time.monotonic()
            tracer.record_span("work", "step", t0, t0 + 1e-6, {"k": k})
            if k % 100 == 0:
                tracer.counter("work.count", 1.0)

    threads = [
        threading.Thread(target=worker, args=(i,), name=f"dtpu-trial-{i}")
        for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tracer.stop()
    events = tracer.chrome_events()
    spans = [e for e in events if e.get("ph") == "X"]
    assert len(spans) == n_threads * per_thread
    assert tracer.dropped() == 0
    assert tracer.counters()["work.count"] == n_threads * (per_thread // 100)
    # per-thread attribution survives: 8 distinct trace tracks
    assert len({e["tid"] for e in spans}) == n_threads


def test_ring_overflow_drops_counted_never_blocks():
    tracer = Tracer(ring_capacity=16)  # no shipper: the ring must overflow
    t0 = time.monotonic()
    for i in range(100):
        tracer.record_span("s", "step", t0, t0 + 1e-6)
    elapsed = time.monotonic() - t0
    assert elapsed < 1.0  # a full ring drops; it never blocks the producer
    assert tracer.dropped() == 84
    assert len([e for e in tracer.chrome_events() if e.get("ph") == "X"]) == 16
    stats = tracer.stats()
    assert stats["dropped"] == 84


def test_disabled_tracer_records_nothing():
    tracer = Tracer()
    tracer.configure(enabled=False)
    tracer.record_span("s", "step", 0.0, 1.0)
    tracer.counter("c", 1)
    with tracer.span("x", cat="step"):
        pass
    assert tracer.chrome_events() == []


def test_chrome_trace_json_well_formed(tmp_path):
    out_dir = str(tmp_path / "traces")
    tracer = Tracer()
    tracer.configure(out_dir=out_dir)
    tracer.start()

    def worker():
        with tracer.span("child", cat="data"):
            time.sleep(0.002)
        tracer.gauge("depth", 3.0)

    with tracer.span("parent", cat="trial", trial=7):
        t = threading.Thread(target=worker, name="dtpu-obs-test-w")
        t.start()
        t.join()
    tracer.instant("marker", "checkpoint")
    tracer.stop()
    path = tracer.export_chrome_trace(os.path.join(out_dir, "trace.json"))
    tracer.close()

    with open(path) as f:
        payload = json.load(f)
    events = payload["traceEvents"]
    assert isinstance(events, list) and events
    for ev in events:
        assert {"ph", "name", "ts", "pid", "tid"} <= set(ev)
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
    phs = {e["ph"] for e in events}
    assert {"X", "i", "C", "M"} <= phs
    names = {e["name"] for e in events}
    assert {"parent", "child", "marker", "depth", "thread_name"} <= names
    # the spanned trial arg rides through to the ledger
    parent = next(e for e in events if e["name"] == "parent")
    assert parent["args"]["trial"] == 7
    # the JSONL export parses line-by-line too (the SIGKILL-surviving form)
    loaded = load_trace_events(out_dir)
    assert [e for e in loaded if e.get("ph") == "X"]


# ---------------------------------------------------------------------------
# goodput ledger
# ---------------------------------------------------------------------------


def _synthetic_run(tracer, rid, steps=5, step_s=0.004, data_s=0.002):
    with tracer.span("trial.run", cat="trial", trial=rid):
        with tracer.span("trainer.setup", cat="setup"):
            time.sleep(0.01)
        for _ in range(steps):
            t0 = time.monotonic()
            time.sleep(data_s)
            t1 = time.monotonic()
            tracer.record_span("data.wait", "data", t0, t1)
            t2 = time.monotonic()
            time.sleep(step_s)
            tracer.record_span("step.dispatch", "step", t2, time.monotonic())
        tracer.counter("train.steps", float(steps))
        tracer.counter("train.samples", float(steps * 8))
        tracer.counter("train.tokens", float(steps * 8 * 64))
        with tracer.span("checkpoint.save", cat="checkpoint"):
            time.sleep(0.005)


@pytest.mark.no_lock_order  # asserts a step-vs-data WALL-CLOCK ratio on
# millisecond sleeps; the lock-order sentinel's per-acquire bookkeeping on
# the tracer/queue hot path skews exactly that ratio under suite load
def test_goodput_ledger_attributes_wall_clock():
    """The ledger must attribute ~100% of a fully instrumented synthetic
    run: per-trial breakdowns sum to ~100% of trial wall-clock and the
    named (non-"other") share clears the 95% acceptance bar."""
    tracer = Tracer()
    with tracer.span("experiment.run", cat="experiment"):
        threads = [
            threading.Thread(target=_synthetic_run, args=(tracer, r), name=f"dtpu-trial-{r}")
            for r in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    ledger = compute_ledger(tracer.chrome_events(), dropped=tracer.dropped())

    exp = ledger["experiment"]
    assert exp["wall_s"] > 0
    assert exp["attributed_pct"] >= 95.0
    assert len(ledger["trials"]) == 3
    for rid, trial in ledger["trials"].items():
        total_pct = sum(row["pct"] for row in trial["breakdown"].values())
        assert 99.0 <= total_pct <= 101.0  # sums to ~100% of wall-clock
        assert trial["attributed_pct"] >= 95.0
        assert trial["steps"] == 5
        assert trial["tokens"] == 5 * 8 * 64
        assert trial["tokens_per_s"] > 0
        # step should dominate data given the sleep ratio
        assert trial["breakdown"]["step"]["seconds"] > trial["breakdown"]["data"]["seconds"]
    # text view renders without blowing up
    text = format_ledger_text(ledger)
    assert "phase breakdown" in text and "trial 0" in text


def test_ledger_folds_step_bubble_counters_into_attribution():
    """The step.bubble rows (ISSUE 14) ride the same counter mechanism as
    step.comm: they must surface per trial and experiment-wide WITHOUT
    perturbing the span-nesting attribution — the breakdown still sums to
    ~100% and the named share clears the >= 95% bar."""
    ev = [
        {"ph": "X", "name": "trial.run", "cat": "trial", "ts": 0, "dur": 1e6,
         "pid": 1, "tid": 1, "args": {"trial": "t1"}},
        {"ph": "X", "name": "step.dispatch", "cat": "step", "ts": 10,
         "dur": 9.8e5, "pid": 1, "tid": 1},
        {"ph": "C", "name": "step.bubble.exposed_us", "ts": 500, "pid": 1,
         "tid": 1, "args": {"value": 110000.0}},
        {"ph": "C", "name": "step.bubble.fraction", "cat": "gauge", "ts": 500,
         "pid": 1, "tid": 1, "args": {"value": 3 / 19}},
        {"ph": "C", "name": "step.bubble.ticks_total", "cat": "gauge",
         "ts": 500, "pid": 1, "tid": 1, "args": {"value": 19.0}},
        {"ph": "C", "name": "step.bubble.ticks_idle", "cat": "gauge",
         "ts": 500, "pid": 1, "tid": 1, "args": {"value": 3.0}},
    ]
    led = compute_ledger(ev)
    trial = led["trials"]["t1"]
    bubble = trial["step.bubble"]
    assert bubble["exposed_s"] == pytest.approx(0.11)
    assert bubble["pct_of_step"] == pytest.approx(11.22, abs=0.01)
    assert bubble["fraction_modeled"] == pytest.approx(3 / 19, abs=1e-4)
    assert bubble["ticks_total"] == 19 and bubble["ticks_idle"] == 3
    assert bubble["model"] == "pipeline-tick-v1"
    assert led["experiment"]["step.bubble"]["exposed_s"] == pytest.approx(0.11)
    # the counters must not disturb the wall-clock attribution invariant
    assert trial["attributed_pct"] >= 95.0
    total_pct = sum(row["pct"] for row in trial["breakdown"].values())
    assert 99.0 <= total_pct <= 101.0
    text = format_ledger_text(led)
    assert "exposed bubble" in text and "ticks idle" in text

    # no bubble counters -> no bubble rows
    led2 = compute_ledger(ev[:2])
    assert "step.bubble" not in led2["trials"]["t1"]
    assert "step.bubble" not in led2["experiment"]


def test_ledger_attributes_restart_recovery_on_chaos_run(tmp_path):
    """A supervised chaos run (crash mid-step -> backoff -> restore ->
    finish) must show restart + restore time in the ledger, and still
    attribute >= 95% of the trial's wall-clock."""
    from determined_tpu import core, train
    from determined_tpu.config import ExperimentConfig, Length
    from determined_tpu.exec.run_trial import TrialSupervisor
    from determined_tpu.models.mnist import MnistTrial
    from determined_tpu.parallel.mesh import MeshConfig
    from determined_tpu.train._restart import RestartPolicy
    from tests.faults import FaultInjector

    tracer = get_tracer()
    tracer.reset()
    tracer.configure(enabled=True)
    tracer.start()

    sync_cfg = ExperimentConfig.parse(
        {"optimizations": {"async_checkpointing": False}}
    )

    def factory():
        core_ctx = core._dummy_init(checkpoint_dir=str(tmp_path / "ckpts"))
        ctx = train.init(
            hparams={"lr": 1e-2, "hidden": 16, "global_batch_size": 16,
                     "dataset_size": 64},
            mesh_config=MeshConfig(data=2),
            core_context=core_ctx,
            exp_config=sync_cfg,
            seed=7,
        )
        return train.Trainer(MnistTrial(ctx))

    inj = FaultInjector()
    inj.kill_at_step(6)
    supervisor = TrialSupervisor(
        factory,
        policy=RestartPolicy(max_restarts=2, backoff_base=0.05, jitter=0.0),
    )
    with inj.installed():
        with tracer.span("trial.run", cat="trial", trial=1):
            summary = supervisor.run(
                Length.batches(12),
                checkpoint_period=Length.batches(4),
                report_period=Length.batches(4),
            )
    tracer.stop()
    assert summary["steps_completed"] == 12 and summary["restarts"] == 1

    ledger = compute_ledger(tracer.chrome_events(), dropped=tracer.dropped())
    trial = ledger["trials"][1]
    bd = trial["breakdown"]
    # recovery time is attributed, not lost: the backoff sleep and the
    # checkpoint restore of attempt 2 both appear as named phases
    assert bd["restart"]["seconds"] >= 0.04
    assert "restore" in bd and bd["restore"]["seconds"] > 0
    assert trial["attributed_pct"] >= 95.0
    # the failure marker landed on the timeline too
    instants = [e for e in tracer.chrome_events() if e.get("ph") == "i"]
    assert any(e["name"] == "trial.failure" for e in instants)


def test_recording_overhead_is_bounded():
    """A/B the hot-loop record against the disabled path: the per-span cost
    must stay far below any real step time (<2% of even a 5ms step).  The
    bound is deliberately loose — CI boxes jitter — but catches any
    accidental lock/alloc/IO on the record path."""
    tracer = Tracer(ring_capacity=65536, flush_interval=0.05)
    tracer.start()
    n = 20000
    t0 = time.monotonic()
    for _ in range(n):
        a = time.monotonic()
        tracer.record_span("data.wait", "data", a, a)
        b = time.monotonic()
        tracer.record_span("step.dispatch", "step", b, b)
    enabled_s = time.monotonic() - t0

    tracer.configure(enabled=False)
    t0 = time.monotonic()
    for _ in range(n):
        a = time.monotonic()
        tracer.record_span("data.wait", "data", a, a)
        b = time.monotonic()
        tracer.record_span("step.dispatch", "step", b, b)
    disabled_s = time.monotonic() - t0
    tracer.stop()

    per_span_us = (enabled_s / (2 * n)) * 1e6
    assert per_span_us < 50.0, f"record_span costs {per_span_us:.1f}us"
    # disabled is (at least) not slower than enabled beyond noise
    assert disabled_s <= enabled_s * 2 + 0.05


# ---------------------------------------------------------------------------
# end to end: ASHA search -> trace export -> `dtpu experiment profile`
# ---------------------------------------------------------------------------


def test_asha_search_profiles_end_to_end(tmp_path, capsys):
    """The acceptance path: a 4-trial ASHA search on CPU devices emits a
    loadable Chrome trace and a ledger attributing >= 95% of wall-clock."""
    from determined_tpu.cli.main import exp_profile_local
    from determined_tpu.config import ExperimentConfig
    from determined_tpu.experiment import LocalExperiment
    from determined_tpu.models.mnist import MnistTrial

    ckpt_dir = str(tmp_path / "ck")
    cfg = ExperimentConfig.parse(
        {
            "name": "obs-asha",
            "hyperparameters": {
                "lr": {"type": "log", "minval": -4, "maxval": -1},
                "hidden": 16,
                "global_batch_size": 32,
                "dataset_size": 128,
            },
            "searcher": {
                "name": "asha",
                "metric": "validation_accuracy",
                "smaller_is_better": False,
                "max_trials": 4,
                "max_length": {"batches": 8},
                "num_rungs": 2,
                "divisor": 4,
                "max_concurrent_trials": 2,
            },
            "resources": {"mesh": {"data": 2}},
            "checkpoint_policy": "none",
            "observability": {"trace_export": True},
        }
    )
    exp = LocalExperiment(cfg, MnistTrial, checkpoint_dir=ckpt_dir)
    summary = exp.run()
    assert summary["trials"] >= 4

    # the export is a loadable Chrome trace with the expected tracks
    trace_path = os.path.join(ckpt_dir, "traces", "trace.json")
    with open(trace_path) as f:
        events = json.load(f)["traceEvents"]
    names = {e["name"] for e in events}
    assert {"experiment.run", "trial.run", "step.dispatch", "data.wait"} <= names
    # the run also left a goodput.json next to it
    with open(os.path.join(ckpt_dir, "traces", "goodput.json")) as f:
        ledger = json.load(f)
    assert ledger["experiment"]["attributed_pct"] >= 95.0
    assert len(ledger["trials"]) >= 4

    # and the CLI renders both views from the directory alone
    class Args:
        checkpoint_dir = ckpt_dir
        json = True
        xplane = None

    assert exp_profile_local(Args()) == 0
    out = json.loads(capsys.readouterr().out)
    exp_ledger = out["ledger"]["experiment"]
    assert exp_ledger["attributed_pct"] >= 95.0
    assert exp_ledger["productive_pct"] > 0
    jit = out["ledger"]["counters"]
    assert jit.get("jit_cache.hit", 0) + jit.get("jit_cache.miss", 0) >= 4


def test_profile_cli_errors_without_traces(tmp_path, capsys):
    from determined_tpu.cli.main import exp_profile_local

    class Args:
        checkpoint_dir = str(tmp_path)
        json = False
        xplane = None

    assert exp_profile_local(Args()) == 2
    assert "no trace events" in capsys.readouterr().err


def test_observability_config_validation():
    from determined_tpu.config import ExperimentConfig
    from determined_tpu.config.experiment import InvalidExperimentConfig

    cfg = ExperimentConfig.parse(
        {"observability": {"enabled": True, "trace_export": True, "ring_capacity": 64}}
    )
    assert cfg.observability.ring_capacity == 64
    with pytest.raises(InvalidExperimentConfig):
        ExperimentConfig.parse({"observability": {"bogus_knob": 1}})
    with pytest.raises(InvalidExperimentConfig):
        ExperimentConfig.parse({"observability": {"ring_capacity": 2}})
    with pytest.raises(InvalidExperimentConfig):
        ExperimentConfig.parse({"observability": {"flush_interval_s": 0}})


def test_ledger_rebases_resumed_run_epochs():
    """A resumed run appends to events.jsonl from a NEW process whose span
    timestamps restart near 0 and whose thread idents repeat; the ledger
    must rebase per-process epochs (clock_sync) and key tracks on
    (pid, tid) so the runs neither falsely nest nor merge."""

    def run_events(pid, epoch_unix, rid):
        return [
            {"ph": "M", "name": "clock_sync", "pid": pid, "tid": 0, "ts": 0,
             "args": {"epoch_unix_s": epoch_unix}},
            {"ph": "X", "name": "trial.run", "cat": "trial", "pid": pid,
             "tid": 111, "ts": 0.0, "dur": 1_000_000.0, "args": {"trial": rid}},
            {"ph": "X", "name": "step.dispatch", "cat": "step", "pid": pid,
             "tid": 111, "ts": 100.0, "dur": 900_000.0},
        ]

    # same tid (111) in both processes; run 2 starts 50s of wall later
    events = run_events(1000, 1_700_000_000.0, 1) + run_events(2000, 1_700_000_050.0, 1)
    ledger = compute_ledger(events)
    trial = ledger["trials"][1]
    # both run segments count toward the trial: 2s of wall, ~1.8s of step
    assert abs(trial["wall_s"] - 2.0) < 1e-3
    assert abs(trial["breakdown"]["step"]["seconds"] - 1.8) < 1e-3
    assert trial["attributed_pct"] >= 85.0
    # without pid separation the second trial.run would nest under the
    # first and its duration would vanish into double-counted self time
    assert len(ledger["threads"]) == 2


def test_a_profiling_window_carries_the_hosts_clock_at_both_ends(tmp_path):
    """``ProfilerContext.on(trace=True)`` / ``stop_trace()`` write three
    ``dtpu.clock_sync`` annotations each, with the monotonic time they were
    taken at, and the tracer gets an instant with the same time beside each:
    the xplane and ``events.jsonl`` can be laid on one another from what they
    hold (docs/observability.md "A device trace on the tracer's clock")."""
    import glob
    import types

    import jax
    import jax.numpy as jnp

    from determined_tpu.core._profiler import CLOCK_SYNC, ProfilerContext
    from determined_tpu.observability import get_tracer

    tracer = get_tracer()
    tracer.reset()
    tracer.configure(enabled=True)
    reported = []  # not the chief: the window's op table is not parsed here
    profiler = ProfilerContext(
        types.SimpleNamespace(rank=1), types.SimpleNamespace(report=lambda *a: reported.append(a)), trace_dir=str(tmp_path)
    )
    before = time.monotonic_ns()
    profiler.on(sampling=False, trace=True)
    with tracer.span("work", cat="test"):
        jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64))).block_until_ready()
    profiler.stop_trace()
    after = time.monotonic_ns()
    assert not profiler.tracing and not reported
    # the annotations, read back with nothing but jax
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert len(files) == 1
    marks = []
    for plane in jax.profiler.ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == CLOCK_SYNC:
                    marks.append((ev.start_ns, dict(ev.stats)["monotonic_ns"]))
    assert len(marks) == 6 and all(before <= ns <= after for _, ns in marks)
    # the tracer's instants carry the same readings, on its own clock: ts is
    # microseconds from `epoch_monotonic`
    instants = [e for e in tracer.chrome_events() if e["name"] == CLOCK_SYNC]
    assert sorted(e["args"]["monotonic_ns"] for e in instants) == sorted(ns for _, ns in marks)
    for e in instants:
        assert tracer.epoch_monotonic + e["ts"] / 1e6 == pytest.approx(e["args"]["monotonic_ns"] / 1e9, abs=1e-3)
    # one offset lays the trace on the monotonic clock: the six agree within a millisecond
    offsets = sorted(start - ns for start, ns in marks)
    assert offsets[-1] - offsets[0] < 1e6
    # and with it the tracer's span lies inside the profiling window
    offset = offsets[len(offsets) // 2]
    work = next(e for e in tracer.chrome_events() if e["name"] == "work")
    start_ns = (tracer.epoch_monotonic + work["ts"] / 1e6) * 1e9 + offset
    assert min(s for s, _ in marks) <= start_ns <= max(s for s, _ in marks)
    tracer.reset()


# ---------------------------------------------------------------------------
# a start, second by second (observability/_setup.py, docs/observability.md "Reading a start")
# ---------------------------------------------------------------------------

_A_START = """
import json, sys, time
sys.path.insert(0, {repo!r})
import determined_tpu.serve, determined_tpu.experiment      # the four packages that carry an import span:
import determined_tpu.models, determined_tpu.train          # .serve and .experiment bring the other two
import determined_tpu.serve, importlib
importlib.import_module("determined_tpu.models")            # a second import runs no module again
from determined_tpu.observability import get_tracer
t = get_tracer()
print("A_START " + json.dumps({{"epoch_wall": t.epoch_wall, "epoch_monotonic": t.epoch_monotonic,
                               "process_start": t.process_start, "events": t.chrome_events()}}))
"""


def test_process_start_lies_before_the_epoch_at_the_childs_creation_and_an_import_span_appears_once():
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = time.time()
    child = subprocess.Popen([sys.executable, "-c", _A_START.format(repo=repo)], stdout=subprocess.PIPE, text=True)
    created = time.time()  # Popen returns once the child exists
    out, _ = child.communicate(timeout=170)
    assert child.returncode == 0
    got = json.loads(next(x for x in out.splitlines() if x.startswith("A_START "))[len("A_START "):])
    starts = [e for e in got["events"] if e["name"] == "process.start"]
    assert len(starts) == 1 and starts[0]["ph"] == "i" and starts[0]["cat"] == "setup"
    assert starts[0]["args"] == {"source": "proc_stat"}
    # before the tracer's epoch (the interpreter came first), and on its clock
    assert starts[0]["ts"] < 0
    assert got["epoch_monotonic"] + starts[0]["ts"] / 1e6 == pytest.approx(got["process_start"], abs=1e-6)
    # on the wall's clock: within 50 ms of the creation the parent measured
    # (the kernel keeps a start to a tick of 10 ms)
    started_wall = got["epoch_wall"] - (got["epoch_monotonic"] - got["process_start"])
    assert before - 0.05 <= started_wall <= created + 0.05
    # each package's import span once, from its first line to its last, with what it imported inside
    spans = [e for e in got["events"] if e["ph"] == "X" and e["name"].startswith("import.")]
    assert sorted(e["name"] for e in spans) == [
        "import.determined_tpu.experiment", "import.determined_tpu.models",
        "import.determined_tpu.serve", "import.determined_tpu.train",
    ]
    assert all(e["cat"] == "setup" and e["dur"] > 0 for e in spans)


def test_where_the_start_cannot_be_read_the_instant_is_the_epoch_and_says_so(monkeypatch):
    from determined_tpu.observability import _tracer as tracer_mod

    monkeypatch.setattr(tracer_mod, "_process_start", lambda: None)
    tracer = Tracer()
    assert tracer.chrome_events() == []  # only the process's tracer leaves the instant by itself
    tracer.mark_process_start()
    (start,) = [e for e in tracer.chrome_events() if e["ph"] == "i"]
    assert start["name"] == "process.start" and start["ts"] == 0 and start["args"] == {"source": "tracer_epoch"}
    assert tracer.process_start == tracer.epoch_monotonic
    # a disabled tracer leaves none, and reset() drops it with everything else
    off = Tracer()
    off.configure(enabled=False)
    off.mark_process_start()
    assert off.chrome_events() == []
    tracer.reset()
    assert tracer.chrome_events() == []


def test_the_operators_line_is_made_once_a_process_from_the_tracers_events(monkeypatch, caplog):
    import logging

    from determined_tpu.observability import _setup, log_setup_line, setup_parts

    tracer = get_tracer()
    tracer.reset()
    log = logging.getLogger("determined_tpu.test_start")
    monkeypatch.setattr(_setup, "_logged", False)
    # no process.start (reset() since): nothing to say, and said once all the same
    with caplog.at_level("INFO", logger=log.name):
        assert log_setup_line(log, "replica ready") is None
    monkeypatch.setattr(_setup, "_logged", False)
    tracer.mark_process_start()
    now = time.monotonic()
    tracer.record_span("import.determined_tpu.serve", "setup", now - 3.0, now - 2.0)
    tracer.record_span("serve.setup", "serve", now - 2.0, now - 0.5)
    tracer.record_span("xla.trace", "compile", now - 1.5, now - 1.0, {"fun_name": "zeros"})
    tracer.record_span("serve.step", "serve", now - 0.25, now + 60.0)  # not over yet: left out
    with caplog.at_level("INFO", logger=log.name):
        line = log_setup_line(log, "replica ready")
        assert log_setup_line(log, "replica ready") is None  # once a process
    assert [r.getMessage() for r in caplog.records] == [line]
    assert line.startswith("replica ready in ") and "imports 1.0, programs 1.5 (trace and lower 0.5," in line
    assert "first work 0.0" in line
    parts = setup_parts(tracer.chrome_events(), now - tracer.epoch_monotonic)
    assert parts["whole"] == pytest.approx(now - tracer.process_start, abs=1e-4)
    assert sum(v for k, v in parts.items() if k != "whole") == pytest.approx(parts["whole"], abs=1e-9)
    # a disabled tracer makes no line
    monkeypatch.setattr(_setup, "_logged", False)
    tracer.configure(enabled=False)
    assert log_setup_line(log, "replica ready") is None
