"""The engine's phase clock (``serve/scheduler.py PhaseClock``, fed by
``serve/engine.py``): the phases are a closed set whose totals are the engine
thread's time, a request carries what the thread did between its tokens, and
the spans that came with it tile a step.  ``step_once`` is driven by hand, so
no assertion races a clock."""

import time

import jax
import jax.numpy as jnp
import pytest

from determined_tpu.models.transformer import TransformerConfig, TransformerLM
from determined_tpu.serve import DecodeKernels, ServeConfig, ServeEngine
from determined_tpu.serve.scheduler import (
    ADMISSION_FIRST_SAMPLE,
    ADMISSION_KV_ALLOC,
    ADMISSION_PREFILL,
    ADMISSION_REST,
    DECODE_WAIT,
    IDLE,
    PHASES,
    REST,
    SAMPLE_LAUNCH,
    TPOT_PARTS,
    GenRequest,
    PhaseClock,
)

ADMISSION = (ADMISSION_KV_ALLOC, ADMISSION_PREFILL, ADMISSION_FIRST_SAMPLE, ADMISSION_REST)
CFG = ServeConfig(block_size=4, num_blocks=64, max_batch=4, max_prompt_len=16, max_new_tokens=16, prefix_cache=False)


@pytest.fixture(scope="module")
def lm():
    from flax.core import meta as flax_meta

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        max_seq_len=64, dtype=jnp.float32, attention_impl="reference",
    )
    variables = flax_meta.unbox(jax.jit(TransformerLM(cfg).init)(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return cfg, variables


@pytest.fixture(scope="module")
def kernels(lm):
    return DecodeKernels(*lm, CFG)


@pytest.fixture()
def tracer():
    """The process tracer, empty and on; left as a fresh process has it."""
    from determined_tpu.observability import get_tracer

    t = get_tracer()
    t.reset()
    t.configure(enabled=True)
    yield t
    t.close()
    t.configure(enabled=True)
    t.reset()


def spans(tracer, name):
    return [e for e in tracer.chrome_events() if e.get("ph") == "X" and e["name"] == name]


def admission_seconds(req):
    return req.first_token_at - req.admitted_at


def test_the_parts_of_a_token_are_every_phase_once():
    named = [i for phases in TPOT_PARTS.values() for i in phases]
    assert sorted(named) == list(range(len(PHASES))) and len(set(PHASES)) == len(PHASES)
    assert PHASES[IDLE] == "idle" and PHASES[REST] == "rest" and PHASES[DECODE_WAIT] == "decode.wait"
    assert [PHASES[i] for i in TPOT_PARTS["prefill_stall"]] == [p for p in PHASES if p.startswith("admission.")]


def test_the_clock_credits_the_running_phase_up_to_each_stamp():
    clock = PhaseClock()
    t = clock.started_at
    assert clock.phase == IDLE and clock.reading() == ((0.0,) * len(PHASES), 0.0)
    clock.to(REST, t + 1.0)
    clock.to(DECODE_WAIT, t + 1.5)
    clock.to(REST, t + 4.0)
    totals, covered = clock.reading()
    assert (totals[IDLE], totals[REST], totals[DECODE_WAIT], covered) == (1.0, 0.5, 2.5, 4.0)
    # a reading at a later moment counts the running phase up to it and leaves the clock alone
    assert clock.read(t + 6.0)[REST] == 2.5 and sum(clock.read(t + 6.0)) == 6.0
    assert clock.reading() == (totals, 4.0)


def test_the_phases_add_up_to_the_threads_time(kernels):
    """Admissions, decode steps, retirements and an idle wait: the clock's
    totals are the time since the engine was made, whenever they are read."""
    eng = ServeEngine(kernels)
    clock = eng._clock
    reqs = [eng.submit([1, 2, 3 + i], max_new_tokens=3 + i, temperature=0.5, seed=i) for i in range(3)]
    assert eng.step_once()
    now = time.monotonic()
    assert sum(clock.read(now)) == pytest.approx(now - clock.started_at, abs=1e-6)
    reqs.append(eng.submit([4, 5, 6, 7, 8], max_new_tokens=2))      # joins the running batch
    while eng.step_once():
        pass
    assert all(r.done.is_set() and r.error is None for r in reqs)
    eng._idle_wait()                                                 # returns at once: the last submit had woken it
    eng._idle_wait()                                                 # what the thread does with nothing to do
    now = time.monotonic()
    totals = clock.read(now)
    assert sum(totals) == pytest.approx(now - clock.started_at, abs=1e-6)
    # every phase took some of it (but the sampler's launch, which lies inside the decode call's wait and is
    # counted there), the admissions exactly what the requests' own stamps say
    assert all((v > 0) == (i != SAMPLE_LAUNCH) for i, v in enumerate(totals)), dict(zip(PHASES, totals))
    assert sum(totals[i] for i in ADMISSION) == pytest.approx(sum(admission_seconds(r) for r in reqs), abs=1e-9)
    assert totals[IDLE] >= 0.04                                      # the wait's 50 ms
    # /stats reports the reading the engine published last, and it is closed too
    ss = eng.stats()["step_seconds"]
    assert sum(ss["phases"].values()) == pytest.approx(ss["uptime"], abs=2e-5)
    assert ss["uptime"] == pytest.approx(clock.at - clock.started_at, abs=1e-6)
    eng.stop()


def test_a_requests_four_parts_are_its_time_a_token(kernels, tracer):
    eng = ServeEngine(kernels)
    reqs = [eng.submit([9, 8, 7 + i], max_new_tokens=2 + 3 * i, temperature=0.7, seed=i) for i in range(4)]
    one = eng.submit([5, 5], max_new_tokens=1)
    while eng.step_once():
        pass
    for r in reqs:
        split = r.tpot_split_s
        assert set(split) == set(TPOT_PARTS) and all(v >= 0.0 for v in split.values())
        assert sum(split.values()) == pytest.approx(r.tpot_s, abs=1e-9)
        assert split["decode_wait"] > 0 and split["sample"] > 0 and split["host"] > 0
    # a request of one token has no gap to split; one that never got a token neither
    assert one.tpot_s is None and one.tpot_split_s is None and one.phases_at_first is not None
    assert GenRequest(prompt=[1], max_new_tokens=2).tpot_split_s is None
    # the span says the same, to 0.1 us a part: the four add up to tpot_ms within 1 us
    args = {e["args"]["request"]: e["args"] for e in spans(tracer, "serve.request")}
    for r in reqs:
        a = args[r.id]
        assert abs(sum(a[f"tpot_{part}_ms"] for part in TPOT_PARTS) - a["tpot_ms"]) < 0.001
        assert a["tpot_decode_wait_ms"] == pytest.approx(1000.0 * r.tpot_split_s["decode_wait"], abs=1e-4)
    assert all(args[one.id][f"tpot_{part}_ms"] is None for part in TPOT_PARTS) and args[one.id]["tpot_ms"] is None
    # /stats: the median of each part over the same requests
    split = eng.stats()["latency"]["tpot_split_ms"]
    assert {v["n"] for v in split.values()} == {4}
    waits = sorted(1000.0 * r.tpot_split_s["decode_wait"] for r in reqs)
    assert split["decode_wait"]["p50"] == pytest.approx((waits[1] + waits[2]) / 2, abs=0.002)
    eng.stop()


def test_a_decoding_request_carries_the_admissions_of_others_and_not_its_own(kernels):
    eng = ServeEngine(kernels)
    a = eng.submit([1, 2, 3], max_new_tokens=8)
    assert eng.step_once()                       # admits a, one decode step
    assert a.phases_at_first is not None and len(a.output) == 2
    b = eng.submit([4, 5, 6, 7], max_new_tokens=8)
    c = eng.submit([7, 6, 5, 4, 3], max_new_tokens=8)
    assert eng.step_once()                       # admits b, then c, while a waits for its third token
    assert len(a.output) == 3 and len(b.output) == len(c.output) == 2
    while eng.step_once():
        pass
    gaps = 7

    def stall(req):
        return req.tpot_split_s["prefill_stall"] * gaps

    # a waited through both admissions, b (admitted first) through c's, c through none; none through its own
    assert stall(a) == pytest.approx(admission_seconds(b) + admission_seconds(c), abs=1e-9)
    assert stall(b) == pytest.approx(admission_seconds(c), abs=1e-9)
    assert stall(c) == 0.0
    assert admission_seconds(a) > 0 and stall(a) > stall(b) > 0
    # and the gap that held them is a's largest
    assert a.itl_max_s >= admission_seconds(b) + admission_seconds(c)
    eng.stop()


def test_an_attempt_that_has_to_wait_for_blocks_is_no_admission(lm):
    """The head of the queue is tried again every step until blocks are free:
    those attempts leave no ``serve.admission`` span and no admission time."""
    tight = ServeConfig(block_size=4, num_blocks=9, max_batch=4, max_prompt_len=16, max_new_tokens=16, prefix_cache=False)
    eng = ServeEngine(DecodeKernels(*lm, tight))
    first = eng.submit([1, 2, 3, 4, 5, 6], max_new_tokens=12)        # 5 of the 8 blocks
    second = eng.submit([6, 5, 4, 3, 2, 1], max_new_tokens=12)       # has to wait for them
    assert eng.step_once() and second.admitted_at is None
    tried = eng._clock.read(eng._clock.at)
    assert sum(tried[i] for i in ADMISSION) == pytest.approx(admission_seconds(first), abs=1e-9)
    while eng.step_once():
        pass
    assert first.error is None and second.error is None and second.admitted_at >= first.finished_at
    totals = eng._clock.read(eng._clock.at)
    assert sum(totals[i] for i in ADMISSION) == pytest.approx(admission_seconds(first) + admission_seconds(second), abs=1e-9)
    # the request that decoded alone was stalled by nobody
    assert first.tpot_split_s["prefill_stall"] == 0.0 and second.tpot_split_s["prefill_stall"] == 0.0
    eng.stop()


class _NoStamps:
    """Kernels that leave no ``last_decode_stamps`` (a stand-in of a caller's own)."""

    def __init__(self, kernels):
        self._k = kernels
        self.serve_cfg, self.model_cfg, self.kinds = kernels.serve_cfg, kernels.model_cfg, kernels.kinds
        self.prefill_suffix = kernels.prefill_suffix

    def decode(self, tokens, positions, tables):
        return self._k.decode(tokens, positions, tables)


def test_kernels_without_stamps_leave_the_whole_call_to_the_dispatch(kernels):
    eng = ServeEngine(_NoStamps(kernels))
    req = eng.submit([1, 2, 3], max_new_tokens=4)
    while eng.step_once():
        pass
    now = time.monotonic()
    totals = dict(zip(PHASES, eng._clock.read(now)))
    assert req.error is None and totals["decode.wait"] == 0.0 and totals["decode.dispatch"] > 0
    assert sum(totals.values()) == pytest.approx(now - eng._clock.started_at, abs=1e-6)
    assert sum(req.tpot_split_s.values()) == pytest.approx(req.tpot_s, abs=1e-9) and req.tpot_split_s["decode_wait"] == 0.0
    eng.stop()


def test_the_engines_thread_counts_its_waits_as_idle_and_publishes_them(kernels):
    eng = ServeEngine(kernels).start()
    try:
        assert eng.generate([1, 2, 3], max_new_tokens=3).error is None
        time.sleep(0.15)                          # two or three waits of 50 ms
        ss = eng.stats()["step_seconds"]
    finally:
        eng.stop()
    assert ss["phases"]["idle"] >= 0.08 and sum(ss["phases"].values()) == pytest.approx(ss["uptime"], abs=2e-5)
    # stopped: the thread's last reading covers its whole life, and nothing runs on
    last = eng.stats()["step_seconds"]
    assert last["uptime"] >= ss["uptime"] and eng._clock.phase == IDLE
    assert sum(last["phases"].values()) == pytest.approx(last["uptime"], abs=2e-5)


class _SwallowsTheHook(_NoStamps):
    """Kernels that never run what the engine left for the call's wait (it is
    set on THIS object), with the stamps of the kernels underneath handed on."""

    @property
    def last_decode_stamps(self):
        return self._k.last_decode_stamps


@pytest.mark.parametrize("in_wait", [True, False], ids=["sampler_in_wait", "sampler_after_call"])
def test_the_new_spans_tile_the_sampling_and_a_step_says_where_its_time_went(kernels, tracer, in_wait):
    eng = ServeEngine(kernels if in_wait else _SwallowsTheHook(kernels))
    for i in range(3):
        eng.submit([1 + i, 2, 3], max_new_tokens=3 + i)
    while eng.step_once():
        pass
    eng.stop()
    by_step = lambda name: {e["args"]["step"]: e for e in spans(tracer, name)}  # noqa: E731
    steps, samples = by_step("serve.step"), by_step("serve.sample")
    launch, wait, d2h, lanes = (by_step(n) for n in ("serve.sample.launch", "serve.sample.wait", "serve.decode.d2h", "serve.lanes"))
    assert sorted(samples) == sorted(launch) == sorted(wait) == sorted(d2h) == sorted(lanes) and len(samples) >= 3
    waits = by_step("serve.decode.wait")
    for step, s in samples.items():
        # end to end, from the same stamps (events round to 0.1 us); the launch is the first part only where it
        # had to follow the decode call: the kernels run it inside their wait, and the sampling starts at the call's end
        parts = [wait[step], d2h[step], lanes[step]]
        if in_wait:
            assert waits[step]["ts"] - 0.2 <= launch[step]["ts"] and launch[step]["ts"] + launch[step]["dur"] <= waits[step]["ts"] + waits[step]["dur"] + 0.2
            assert launch[step]["dur"] > 0 and launch[step]["args"] == {"step": step}
        else:
            parts.insert(0, launch[step])
        assert parts[0]["ts"] == pytest.approx(s["ts"], abs=0.11)
        for before, after in zip(parts, parts[1:]):
            assert after["ts"] == pytest.approx(before["ts"] + before["dur"], abs=0.21)
        assert parts[-1]["ts"] + parts[-1]["dur"] == pytest.approx(s["ts"] + s["dur"], abs=0.21)
        assert all(p["dur"] > 0 and p["args"] == {"step": step} for p in parts)
    # a retirement is a span of its own after the sampling, in the step that saw the sequence finish
    retires = by_step("serve.retire")
    assert {step: e["args"]["retired"] for step, e in retires.items()} == {
        step: e["args"]["retired"] for step, e in steps.items() if e["args"]["retired"] and step in samples
    }
    for step, e in retires.items():
        assert e["ts"] >= samples[step]["ts"] + samples[step]["dur"] - 0.2
        assert e["ts"] + e["dur"] <= steps[step]["ts"] + steps[step]["dur"] + 0.2
    # the step's own split: the phases that took time, which add up to the span
    for step, e in steps.items():
        split = e["args"]["phase_ms"]
        assert set(split) <= set(PHASES) and "idle" not in split and all(v > 0 for v in split.values())
        assert sum(split.values()) == pytest.approx(e["dur"] / 1e3, abs=0.001 * len(split))
        assert ("retire" in split) == (step in retires)
        assert ("admission.prefill" in split) == bool(e["args"]["admitted"])
        assert split["decode.wait"] == pytest.approx(waits[step]["dur"] / 1e3, abs=0.002)
        assert ("sample.launch" in split) == (not in_wait)
        assert split["lanes"] == pytest.approx(lanes[step]["dur"] / 1e3, abs=0.002)


def test_a_disabled_tracer_costs_the_clock_nothing_and_the_clock_runs_all_the_same(kernels):
    from determined_tpu.observability import get_tracer

    tracer = get_tracer()
    tracer.reset()
    tracer.configure(enabled=False)
    try:
        eng = ServeEngine(kernels)
        req = eng.submit([3, 2, 1], max_new_tokens=5)
        while eng.step_once():
            pass
        eng.stop()
        assert tracer.stats()["events"] == 0
        assert sum(req.tpot_split_s.values()) == pytest.approx(req.tpot_s, abs=1e-9)
        ss = eng.stats()["step_seconds"]
        assert ss["steps"] == 4 and ss["phases"]["decode.wait"] > 0
    finally:
        tracer.configure(enabled=True)
        tracer.reset()
