"""The arithmetic of the readings and the trace reduction, on synthetic data."""

import pytest

import bench_testlib  # noqa: F401  (puts the harness on sys.path)
from benchlib import stats, trace as tr


def _even_marks(step=0.1, n=600, per=32.0):
    return [(k * step, k * per) for k in range(n + 1)]


def test_slice_rates_equal_the_mean_on_an_even_stream():
    marks = _even_marks()
    assert stats.slice_rates(marks, 1.0, 51.0, 10) == pytest.approx([320.0] * 10)
    assert stats.slice_rates(marks, 1.0, 51.0, 1) == pytest.approx([320.0])


def test_a_stall_shows_in_its_slice_and_in_the_whole_windows_rate():
    marks, t, work = [], 0.0, 0.0
    for k in range(600):
        t += 3.0 if k == 300 else 0.1  # one 3 s stall in mid-window
        work += 32.0
        marks.append((t, work))
    lo, hi = marks[10][0], marks[-10][0]
    rates = stats.slice_rates(marks, lo, hi, 10)
    assert sum(r < 0.7 * 320.0 for r in rates) == 1 and sum(r == pytest.approx(320.0) for r in rates) == 9
    # all the work over all the time: 3 s of 61 lost
    assert stats.slice_rates(marks, lo, hi, 1)[0] == pytest.approx(320.0 * (hi - lo - 2.9) / (hi - lo), rel=1e-3)


def test_slices_hold_whole_steps_only():
    rates = stats.slice_rates(_even_marks(step=0.37), 1.0, 40.0, 8)
    assert len(rates) == 8 and all(r == pytest.approx(32 / 0.37) for r in rates)


def test_percentile_and_spread():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(range(101), 90) == pytest.approx(90)
    assert stats.spread([100, 101, 102, 103, 104, 105]) == pytest.approx(3.5 / 102.5)


def _trace():
    # device 0: a `while` enclosing two ops, a gap of 400 us, then two more
    # operations; device 1: busy throughout
    d0 = [
        ("while.1", 0.0, 1000.0),
        ("fusion.1", 0.0, 400.0),
        ("flash_fwd", 500.0, 500.0),
        ("all-reduce.3", 401_000.0, 600.0),
        ("fusion.2", 401_600.0, 400.0),
    ]
    d1 = [("fusion.1", 0.0, 402_000.0)]
    return tr.TraceData(devices={"/device:TPU:0": d0, "/device:TPU:1": d1}, host=[])


def test_busy_union_and_idle_share():
    t = _trace()
    assert tr.window_of(t) == (0.0, 402_000.0)
    busy0 = 1000.0 + 600.0 + 400.0
    assert tr.busy_seconds(t) * 1e9 == pytest.approx((busy0 + 402_000.0) / 2)
    assert tr.idle_share(t) == pytest.approx(1 - (busy0 + 402_000.0) / 2 / 402_000.0)


def test_self_times_take_nested_operations_out():
    own = dict((n, d) for n, _, d in tr.self_times(_trace().devices["/device:TPU:0"]))
    assert own["while.1"] == pytest.approx(100.0)
    assert own["flash_fwd"] == 500.0


def test_operation_time_by_pattern_and_top_ops():
    t = _trace()
    assert tr.op_seconds(t, "flash") * 1e9 == pytest.approx(250.0)  # mean over 2 devices
    top = tr.top_ops(t, 3)
    assert tr.short_name("%fusion.509 = (f32[4096]{0:T(1024)S(1)}, f32[8]) fusion(bf16[4]) %x") == "fusion_f32[4096]"
    assert tr.short_name("%convert.3 = bf16[92544,2048]{1,0} convert(f32[92544,2048] %p)") == "convert_bf16[92544,2048]"
    assert top[0][0] == "fusion.1" and sum(s for _, s in tr.top_ops(t, 10)) == pytest.approx(1.0)


def test_reference_adamw_is_optaxs_clipped_adamw():
    import jax.numpy as jnp
    import numpy as np
    import optax

    from reference import adamw

    rng = np.random.default_rng(3)
    p, g = (jnp.asarray(rng.normal(size=(7, 5)), jnp.float32) for _ in range(2))
    g = 3.0 * g  # norm well over the clip
    hyper = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01}
    sched = optax.warmup_cosine_decay_schedule(0.0, 3e-4, 100, 10000)
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(sched, **hyper))
    state = tx.init(p)
    m = v = jnp.zeros_like(p)
    for count in range(4):
        updates, state = tx.update(g, state, p)
        want = optax.apply_updates(p, updates)
        scale = adamw.clip_scale(float(jnp.sqrt(jnp.sum(g * g))), 1.0)
        lr = adamw.warmup_cosine_lr(count, peak=3e-4, warmup_steps=100, decay_steps=10000)
        p, m, v = adamw.adamw_step(p, m, v, scale * g, count=count, lr=lr, **hyper)
        np.testing.assert_allclose(p, want, rtol=1e-6, atol=1e-9)
        np.testing.assert_allclose(m, state[1][0].mu, rtol=1e-6)
        np.testing.assert_allclose(v, state[1][0].nu, rtol=1e-6)
    assert adamw.clip_scale(0.5, 1.0) == 1.0


@pytest.mark.parametrize("count", [0, 1, 50, 100, 101, 5000, 10000, 20000])
def test_reference_learning_rate_is_optaxs_warmup_cosine(count):
    import optax

    from reference import adamw

    want = float(optax.warmup_cosine_decay_schedule(0.0, 3e-4, 100, 10000)(count))
    assert adamw.warmup_cosine_lr(count, peak=3e-4, warmup_steps=100, decay_steps=10000) == pytest.approx(want, rel=1e-5, abs=1e-12)


def test_idle_gaps_are_laid_against_host_spans():
    t = tr.TraceData(devices={"d": [("a", 0.0, 100.0), ("b", 100_100.0, 100.0), ("c", 100_210.0, 100.0)]}, host=[])
    spans = [("serve.sample", 10_000.0, 60_000.0), ("serve.step", 0.0, 80_000.0)]
    gaps = dict(tr.idle_gaps_by_host_span(t, spans))
    assert gaps["serve.sample"] * 1e9 == pytest.approx(60_000.0)
    assert gaps["serve.step"] * 1e9 == pytest.approx(19_900.0)  # the rest of the step
    assert gaps["host_outside_any_span"] * 1e9 == pytest.approx(20_100.0)
    assert gaps["between_ops_20_us"] * 1e9 == pytest.approx(10.0)


def test_clock_offset_from_sync_annotations():
    t = tr.TraceData(devices={}, host=[(tr.SYNC_NAME, 1000.0, 10.0), (tr.SYNC_NAME, 2000.0, 10.0), ("x", 5.0, 1.0)])
    assert tr.clock_offset_ns(t, [9_001_005.0, 9_002_005.0]) == pytest.approx(-9_000_000.0)
    with pytest.raises(ValueError):
        tr.clock_offset_ns(tr.TraceData(devices={}, host=[]), [1.0])


def test_a_recorded_cpu_trace_loads(tmp_path):
    """A small real trace through jax's own reader: the sync annotation is
    found on the host plane (a CPU has no device plane, so no device ops)."""
    import jax
    import jax.numpy as jnp

    from benchlib.observe import Profiler

    prof = Profiler(str(tmp_path / "trace"))
    prof.start()
    jnp.ones((64, 64)).sum().block_until_ready()
    prof.stop()
    data = prof.data()
    assert data is not None and data.devices == {}
    off = tr.clock_offset_ns(data, prof.sync_marks_ns)
    assert off < 0  # the trace's clock starts at its own zero
    assert "PLANE" in tr.describe(tr.newest_xplane(str(tmp_path / "trace")))
