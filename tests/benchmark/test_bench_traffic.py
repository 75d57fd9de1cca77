"""The traffic generator: the seed permutes and never resamples."""

import json
import os

import pytest

import bench_testlib as B
from benchlib import traffic as T

SEEDS = (0, 1, 7, 2**31 + 12345)


def _traffic(name):
    with open(os.path.join(B.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("shape", [
    {"shape": "uniform", "min": 128, "max": 512},
    {"shape": "lognormal", "median": 64, "sigma": 0.7, "min": 16, "max": 256},
    {"shape": "fixed", "value": 40},
])
def test_quantile_values_are_fixed_sorted_and_in_range(shape):
    vals = T.quantile_values(shape, 50)
    assert vals == sorted(vals) == T.quantile_values(shape, 50)
    lo = shape.get("min", shape.get("value"))
    hi = shape.get("max", shape.get("value"))
    assert lo <= vals[0] and vals[-1] <= hi


def test_lognormal_is_heavy_tailed_with_its_median():
    vals = T.quantile_values({"shape": "lognormal", "median": 64, "sigma": 0.7, "min": 16, "max": 256}, 200)
    assert abs(vals[100] - 64) <= 2
    assert vals[-1] - vals[100] > 2 * (vals[100] - vals[0])


def test_exponential_gaps_fill_their_stretch_exactly():
    gaps = T.exponential_gaps(2.0, 100)
    assert sum(gaps) == pytest.approx(50.0)
    assert gaps == sorted(gaps)


def _free(traffic):
    """The mix with its order left to the seed, as the closed loop's is."""
    return {k: v for k, v in traffic.items() if k != "balance_over_requests"}


def test_open_loop_totals_are_the_same_for_every_seed_and_order_differs():
    tr = _free(_traffic("chat-open"))
    scheds = [T.open_schedule(tr, s, 92544, 50.0) for s in SEEDS]
    for part in ("ramp", "window", "tail"):
        totals = {json.dumps(T.totals(getattr(s, part)), sort_keys=True) for s in scheds}
        assert len(totals) == 1, part
    first = [[len(r.prompt) for r in s.window] for s in scheds]
    assert len({tuple(x) for x in first}) == len(SEEDS)
    for s in scheds:
        dues = [r.due for r in s.ramp + s.window + s.tail]
        assert dues == sorted(dues)
        assert s.window[-1].due == pytest.approx(s.window_end)
        assert all(s.window_start < r.due <= s.window_end + 1e-9 for r in s.window)
        assert sum(r.shared_prefix >= 0 for r in s.window) == int(0.25 * len(s.window))


def test_open_loop_shared_prefixes_really_open_the_prompts():
    tr = _traffic("chat-open")
    s = T.open_schedule(tr, 3, 92544, 50.0)
    by_prefix = {}
    for r in s.window:
        if r.shared_prefix >= 0:
            by_prefix.setdefault(r.shared_prefix, set()).add(tuple(r.prompt[:256]))
            assert len(r.prompt) >= 256 + 16
    assert by_prefix and all(len(v) == 1 for v in by_prefix.values())


def test_same_seed_gives_the_same_requests():
    tr = _traffic("chat-open")
    a, b = T.open_schedule(tr, 5, 1000, 10.0), T.open_schedule(tr, 5, 1000, 10.0)
    assert [(r.prompt, r.max_new_tokens, r.due, r.seed) for r in a.window] == [
        (r.prompt, r.max_new_tokens, r.due, r.seed) for r in b.window
    ]


def test_closed_loop_totals_are_the_same_for_every_seed():
    tr = _traffic("decode-closed")
    plans = [T.closed_plan(tr, s, 92544) for s in SEEDS]
    assert len({json.dumps(T.totals(p.first), sort_keys=True) for p in plans}) == 1
    assert len({json.dumps(T.totals([r for c in p.later for r in c]), sort_keys=True) for p in plans}) == 1
    assert len({tuple(r.max_new_tokens for r in p.first) for p in plans}) == len(SEEDS)


def test_closed_loop_first_wave_finishes_one_lane_at_a_time():
    tr = _traffic("decode-closed")
    plan = T.closed_plan(tr, 11, 92544)
    left = sorted(r.max_new_tokens for r in plan.first)
    assert len(plan.first) == tr["clients"] == len(set(left))
    # spread over the longest request's life, not bunched: no gap between
    # two lanes' ends is longer than an eighth of it
    assert max(b - a for a, b in zip([0] + left, left)) <= tr["output_tokens"]["max"] / 8
    # a part-done request carries its done part as context, inside the engine's limits
    eng = tr["engine"]
    assert max(len(r.prompt) for r in plan.first) <= eng["max_prompt_len"]
    assert all(r.max_new_tokens <= eng["max_new_tokens"] for c in plan.later for r in c)
    assert all(len(r.prompt) <= tr["prompt_tokens"]["max"] for c in plan.later for r in c)


def test_new_contents_keep_the_sizes():
    tr = _traffic("decode-closed")
    r = T.closed_plan(tr, 1, 1000).later[0][0]
    n = T.with_new_contents(r, [1, 0, 1, 0], 1000)
    assert (len(n.prompt), n.max_new_tokens) == (len(r.prompt), r.max_new_tokens)
    assert n.prompt != r.prompt


def test_balanced_order_keeps_every_stretch_of_ten_alike():
    import numpy as np

    gaps = T.exponential_gaps(3.0, 150)
    sums = {}
    for seed in SEEDS[:3]:
        for group in (0, 10):
            order = T.seeded_order(150, np.random.default_rng(seed), group)
            assert sorted(order) == list(range(150))
            v = [gaps[i] for i in order]
            tens = [sum(v[k:k + 10]) for k in range(0, 150, 10)]
            sums.setdefault(group, []).append(max(tens) / min(tens))
    assert max(sums[10]) < 1.5 < min(sums[0])
    tr = _traffic("chat-open")
    assert tr["balance_over_requests"] == 10
    free = _free(tr)
    a, b = T.open_schedule(free, 1, 1000, 50.0), T.open_schedule(free, 2, 1000, 50.0)
    assert [r.due for r in a.window] != [r.due for r in b.window]


def test_a_balanced_order_is_one_schedule_and_leaves_the_seed_the_contents_only():
    tr = _traffic("chat-open")
    assert tr["balance_over_requests"] and "order" not in tr
    a, b = T.open_schedule(tr, 1, 1000, 50.0), T.open_schedule(tr, 2**31 + 9, 1000, 50.0)
    sizes = lambda s: [(r.due, len(r.prompt), r.max_new_tokens, r.shared_prefix >= 0) for r in s.ramp + s.window + s.tail]
    assert sizes(a) == sizes(b)
    assert [r.prompt for r in a.window] != [r.prompt for r in b.window]
    assert [r.seed for r in a.window] != [r.seed for r in b.window]
