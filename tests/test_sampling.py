"""The serving engine's two samplers (`serve/engine.py`): `sample_token`, on
the host, against the plain form it replaced (a float64 softmax handed to
`Generator.choice`, kept HERE as the reference), and `sample_lanes`, one
jitted call over every lane of a decode step, against `sample_token` on the
same uniforms and against the float64 distribution itself.  The sampler's
contract (docs/serving.md "Observability") is what these cases pin.  A
device case hands the call all of its lanes at once, temperatures mixed.
"""

import functools
import inspect
import warnings

import numpy as np
import pytest
from scipy import stats

import determined_tpu.serve as serve
from determined_tpu.serve.engine import lane_sampler, sample_token

INTERNLM2_VOCAB = 92544
DSV3_SHARE_VOCAB = 16160  # no multiple of 128: a short last block
BRUMBY_VOCAB = 151936
TEMPERATURES = (0.3, 0.7, 1.0, 2.0)
#: the uniform's distance from the float64 interval of the token the device
#: returns, as a share of the normaliser (docs/serving.md)
DEVICE_TOL = 1e-5
#: entries one call of the device's sampler is handed at most (256 MB of float32)
CALL_ENTRIES = 1 << 26


@functools.lru_cache(maxsize=None)
def _compiled_sampler(lanes, vocab):
    """The engine's jitted sampler at one shape.  tests/conftest.py compiles
    for the CPU at LLVM's level 0, which costs these cases (up to 8,000 lanes
    of 151,936 entries a case) twice their time: this one program is
    compiled as a deployment compiles it."""
    import jax

    shapes = [jax.ShapeDtypeStruct(s, np.float32) for s in ((lanes, vocab), (2, lanes))]
    fast = {"xla_backend_optimization_level": 3, "xla_llvm_disable_expensive_passes": False}
    return lane_sampler().lower(*shapes).compile(compiler_options=fast if jax.default_backend() == "cpu" else {})


def device_tokens(rows, temperature, uniform, row_of_lane=None):
    """`sample_lanes` on every lane (lane i: `rows[row_of_lane[i]]`, or
    `rows[i]`), in calls of equal lane counts."""
    rows = np.asarray(rows, np.float32)
    row_of_lane = np.arange(len(rows)) if row_of_lane is None else np.asarray(row_of_lane)
    lanes, vocab = len(row_of_lane), rows.shape[1]
    temperature = np.broadcast_to(np.asarray(temperature, np.float32), lanes)
    uniform = np.broadcast_to(np.asarray(uniform, np.float32), lanes)
    calls = next(n for n in range(-(-lanes * vocab // CALL_ENTRIES), lanes + 1) if lanes % n == 0)
    out = []
    for part in np.split(np.arange(lanes), calls):
        ids, counted = _compiled_sampler(len(part), vocab)(rows[row_of_lane[part]], np.stack([temperature[part], uniform[part]]))
        assert ids.dtype == np.int32 and counted.shape == (0,)
        out.append(np.asarray(ids))
    return np.concatenate(out)


def reference_sample_token(logits, temperature, rng):
    """`sample_token` as it was before PR 28, word for word."""
    if temperature <= 0.0:
        return int(np.argmax(logits))
    z = logits.astype(np.float64) / float(temperature)
    z -= z.max()
    p = np.exp(z)
    total = p.sum()
    if not np.isfinite(total) or total <= 0.0:
        return int(np.argmax(np.nan_to_num(logits, nan=-np.inf)))
    return int(rng.choice(len(p), p=p / total))


def _rows(vocab, spread, n=4, seed=0):
    g = np.random.default_rng([vocab, int(spread * 10), seed])
    return (g.standard_normal((n, vocab)) * spread).astype(np.float32)


class _StubGenerator:
    """A generator whose every uniform is the one it was given."""

    def __init__(self, uniform):
        self.uniform = uniform
        self.draws = 0

    def random(self):
        self.draws += 1
        return self.uniform


def test_sampler_keeps_its_name_module_and_signature():
    # the benchmark's traced runs replace this module attribute, and
    # tests/test_transformer.py uses it as the token oracle
    assert serve.sample_token is sample_token
    assert sample_token.__module__ == "determined_tpu.serve.engine"
    assert list(inspect.signature(sample_token).parameters) == ["logits", "temperature", "rng"]


DRAWS = 2000
VOCABS = [1, 5, 127, 128, 129, 256, 32768, INTERNLM2_VOCAB]


@functools.lru_cache(maxsize=None)
def _host_tokens(vocab, temperature, spread):
    """`sample_token`'s 2,000 tokens of one case, and its generator after them."""
    rows = _rows(vocab, spread)
    rng = np.random.default_rng(vocab)
    return [sample_token(rows[i % len(rows)], temperature, rng) for i in range(DRAWS)], rng


@functools.lru_cache(maxsize=None)
def _device_tokens(vocab, spread):
    """The device's tokens for the four temperatures' cases of one vocabulary
    and spread, on the uniforms `sample_token` draws there: every call holds
    lanes of all four temperatures."""
    rows = _rows(vocab, spread)
    uniform = np.random.default_rng(vocab).random(DRAWS)  # the same for each temperature: its generator starts anew
    lane_row = np.tile(np.arange(DRAWS) % len(rows), len(TEMPERATURES))
    lane_t = np.repeat(TEMPERATURES, DRAWS)
    # interleaved, so that a call's share of the lanes has every temperature
    order = np.arange(len(lane_t)).reshape(len(TEMPERATURES), DRAWS).T.ravel()
    ids = np.empty(len(order), np.int64)
    ids[order] = device_tokens(rows, lane_t[order], np.tile(uniform, len(TEMPERATURES))[order], lane_row[order])
    return {t: ids[i * DRAWS : (i + 1) * DRAWS] for i, t in enumerate(TEMPERATURES)}, uniform


@pytest.mark.parametrize("spread", [1.0, 30.0])
@pytest.mark.parametrize("temperature", TEMPERATURES)
@pytest.mark.parametrize(
    "sampler, vocab",
    [("host", v) for v in VOCABS] + [("device", v) for v in VOCABS + [DSV3_SHARE_VOCAB, BRUMBY_VOCAB]],
)
def test_same_token_as_float64_softmax_and_choice(sampler, vocab, temperature, spread):
    """Twin generators, one uniform a token each: the float32 exponentials
    move a boundary of the cumulative distribution across the uniform on
    well under 0.5 % of draws, and nothing else may differ.

    The device's call beside `sample_token` on the same uniforms: float32
    sums (and the uniform in float32) move a boundary across it on under
    1 % of draws where the mass is spread over the whole vocabulary and on
    none where it is not, and each token's float64 interval holds the
    uniform to `DEVICE_TOL`."""
    rows = _rows(vocab, spread)
    if sampler == "host":
        toks, rng = _host_tokens(vocab, temperature, spread)
        rng_ref = np.random.default_rng(vocab)
        same = 0
        for i, tok in enumerate(toks):
            assert 0 <= tok < vocab
            same += tok == reference_sample_token(rows[i % len(rows)], temperature, rng_ref)
        assert same >= 0.995 * DRAWS, f"{DRAWS - same} of {DRAWS} draws differ"
        assert rng.bit_generator.state == rng_ref.bit_generator.state
        return
    by_temperature, uniform = _device_tokens(vocab, spread)
    toks = by_temperature[temperature]
    same = int((toks == np.asarray(_host_tokens(vocab, temperature, spread)[0])).sum())
    assert same >= 0.99 * DRAWS, f"{DRAWS - same} of {DRAWS} draws differ"  # measured: 99.6 % at worst (spread 1, 92,544)
    for r, row in enumerate(rows):
        mine = np.arange(DRAWS) % len(rows) == r
        t, u = toks[mine], uniform[mine]
        z = row.astype(np.float64) / temperature
        p = np.exp(z - z.max())
        cdf = np.r_[0.0, np.cumsum(p)]
        total = cdf[-1]
        assert ((0 <= t) & (t < vocab)).all() and (p[t] > 0).all()
        assert (cdf[t] - DEVICE_TOL * total <= u * total).all() and (u * total < cdf[t + 1] + DEVICE_TOL * total).all()


@functools.lru_cache(maxsize=None)
def _device_chi_square_draws(draws):
    """Both temperatures' lanes of the chi-square test in one call, alternating."""
    row = _rows(300, 0.5, n=1, seed=1)[0]
    uniform = np.random.default_rng(28).random(2 * draws)
    toks = device_tokens(row[None], np.tile([0.7, 1.5], draws), uniform, np.zeros(2 * draws, int))
    return {0.7: toks[0::2], 1.5: toks[1::2]}


@pytest.mark.parametrize("temperature", [0.7, 1.5])
@pytest.mark.parametrize("sampler", ["host", "device"])
def test_draws_follow_the_float64_softmax(sampler, temperature):
    """Chi-square of 50,000 draws over a vocabulary of 300: two whole
    blocks and a short last one."""
    vocab, draws = 300, 50_000
    row = _rows(vocab, 0.5, n=1, seed=1)[0]
    z = row.astype(np.float64) / temperature
    p = np.exp(z - z.max())
    p /= p.sum()
    assert (draws * p).min() > 5  # every cell is large enough for the test
    rng = np.random.default_rng(28)
    counts = np.bincount(
        [sample_token(row, temperature, rng) for _ in range(draws)] if sampler == "host"
        else _device_chi_square_draws(draws)[temperature],
        minlength=vocab,
    )
    chi2 = float(((counts - draws * p) ** 2 / (draws * p)).sum())
    # one seeded stream, so no flake: a sound sampler exceeds this once in 10,000 seeds
    assert chi2 < stats.chi2.ppf(1 - 1e-4, vocab - 1), chi2


@pytest.mark.parametrize(
    "row, temperature, expected_draws",
    [
        (_rows(5, 1.0)[0], 0.7, 1),
        (_rows(300, 30.0)[0], 0.3, 1),
        (_rows(INTERNLM2_VOCAB, 1.0)[0], 1.0, 1),
        (_rows(300, 1.0)[0], 0.0, 0),  # greedy draws nothing
        (np.full(300, np.nan, np.float32), 0.7, 0),  # nor does the degenerate fallback
    ],
    ids=["v5", "v300-peaked", "v92544", "greedy", "degenerate"],
)
def test_one_uniform_a_token_from_the_requests_generator(row, temperature, expected_draws):
    rng, twin = np.random.default_rng(7), np.random.default_rng(7)
    sample_token(row, temperature, rng)
    for _ in range(expected_draws):
        twin.random()
    assert rng.bit_generator.state == twin.bit_generator.state


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_the_callers_row_is_not_written_to(temperature):
    """The row is a view into the step's `[lanes, vocab]` logits, which
    `np.asarray` of a device array hands over read-only."""
    step_logits = _rows(300, 3.0)
    before = step_logits.tobytes()
    step_logits.flags.writeable = False
    for lane in range(len(step_logits)):
        sample_token(step_logits[lane], temperature, np.random.default_rng(lane))
    assert step_logits.tobytes() == before


def _with(row, index, value):
    row = row.copy()
    row[index] = value
    return row


_ONE_NAN = _with(_rows(300, 1.0)[0], 17, np.nan)
#: case -> (row of 300, the token it must yield or None for any in range)
DEGENERATE_ROWS = {
    "one-nan": (_ONE_NAN, int(np.nanargmax(_ONE_NAN))),
    "all-nan": (np.full(300, np.nan, np.float32), 0),
    "one-plus-inf": (_with(_rows(300, 1.0)[1], 200, np.inf), 200),
    "all-minus-inf": (np.full(300, -np.inf, np.float32), 0),
    "all-equal": (np.full(300, 3.25, np.float32), None),
    "all-equal-huge": (np.full(300, np.finfo(np.float32).max, np.float32), None),
    "opposite-extremes": (
        np.array([np.finfo(np.float32).max, np.finfo(np.float32).min] * 150, np.float32),
        None,
    ),
}


DEGENERATE_TEMPERATURES = [0.7, 1e-30, 1e-320, 1e30]


@functools.lru_cache(maxsize=None)
def _device_degenerate_tokens():
    """Every degenerate row at every temperature, and a sound sampled and a
    greedy lane between them, as the lanes of ONE call: a lane's fallback is its own."""
    lanes = [(case, t) for case in DEGENERATE_ROWS for t in DEGENERATE_TEMPERATURES]
    sound = _rows(300, 1.0)[2]
    rows = np.stack([DEGENERATE_ROWS[case][0] for case, _ in lanes] + [sound, sound])
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        toks = device_tokens(rows, [t for _, t in lanes] + [0.7, 0.0], 0.37)
    assert toks[-2] == sample_token(sound, 0.7, _StubGenerator(0.37)) and toks[-1] == int(np.argmax(sound))
    return dict(zip(lanes, toks.tolist()))


@pytest.mark.parametrize("temperature", DEGENERATE_TEMPERATURES)
@pytest.mark.parametrize("case", list(DEGENERATE_ROWS))
@pytest.mark.parametrize("sampler", ["host", "device"])
def test_degenerate_rows_yield_a_token_and_raise_nothing(sampler, case, temperature):
    """A numerically degenerate model costs a bad token, never an exception
    (or a warning turned into one) in the scheduler's loop."""
    row, expected = DEGENERATE_ROWS[case]
    if sampler == "host":
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            tok = sample_token(row, temperature, np.random.default_rng(3))
    else:
        tok = _device_degenerate_tokens()[case, temperature]
    assert isinstance(tok, int) and 0 <= tok < len(row)
    if expected is not None:
        assert tok == expected


@pytest.mark.parametrize("sampler", ["host", "device"])
def test_entries_of_minus_inf_are_never_drawn(sampler):
    masked = np.r_[0:130, 250:300]  # a whole block, part of the next, the tail
    row = _with(_rows(300, 1.0)[0], masked, -np.inf)
    rng = np.random.default_rng(11)
    if sampler == "host":
        drawn = {sample_token(row, 0.7, rng) for _ in range(2000)}
    else:
        drawn = set(device_tokens(np.tile(row, (2000, 1)), np.tile(TEMPERATURES, 500), rng.random(2000)).tolist())
    assert len(drawn) > 50 and not drawn & set(masked.tolist())


def _tie_row(case):
    row = _rows(300, 2.0)[0].copy()
    top = row.max() + 1.0
    if case == "tie":
        row[[40, 41, 299]] = top
    elif case == "tie-at-the-ends":
        row[[0, 299]] = top
    elif case == "all-equal":
        row[:] = top
    return row


TIE_CASES = ["distinct", "tie", "tie-at-the-ends", "all-equal"]


@pytest.mark.parametrize("temperature", [0.0, -1.0])
@pytest.mark.parametrize("case", TIE_CASES)
@pytest.mark.parametrize("sampler", ["host", "device"])
def test_temperature_zero_is_argmax_first_index_on_ties(sampler, case, temperature):
    row = _tie_row(case)
    if sampler == "host":
        rng = _StubGenerator(0.5)
        assert sample_token(row, temperature, rng) == int(np.argmax(row))
        assert rng.draws == 0
        return
    # the four rows greedy beside the same four sampled, in one call: the
    # uniform a greedy lane is handed moves nothing
    rows = np.stack([_tie_row(c) for c in TIE_CASES] * 2)
    for uniform in (0.0, 0.5, 1.0):
        toks = device_tokens(rows, [temperature] * 4 + [0.7] * 4, uniform)
        assert toks[TIE_CASES.index(case)] == int(np.argmax(row))


# entries of -1e4 underflow to a probability of exactly zero
_LIVE_THEN_DEAD = np.r_[np.zeros(200), np.full(100, -1e4)].astype(np.float32)
EDGE_ROWS = {
    "flat-300": np.zeros(300, np.float32),
    "flat-128": np.zeros(128, np.float32),
    "one-entry": np.zeros(1, np.float32),
    "dead-tail": _LIVE_THEN_DEAD,  # the short last block is all dead
    "dead-head": _LIVE_THEN_DEAD[::-1].copy(),
    "dead-last-blocks": np.r_[np.zeros(5), np.full(295, -1e4)].astype(np.float32),
    "spread-92544": _rows(INTERNLM2_VOCAB, 30.0)[0],
}


@pytest.mark.parametrize("uniform", [0.0, float(np.nextafter(1.0, 0.0))], ids=["u0", "u-just-below-1"])
@pytest.mark.parametrize("case", list(EDGE_ROWS))
@pytest.mark.parametrize("sampler", ["host", "device"])
def test_the_extreme_uniforms_stay_inside_the_vocabulary(sampler, case, uniform):
    """`rng.random()` lies in [0, 1), but its product with the normaliser can
    round up to it, and a block's own cumulative sum can round below its
    block sum: both searches hold the uniform below their last sum, so the
    token is in range AND one the distribution can yield.  (The device is
    handed the uniform in float32, where the one just below 1 IS 1: its
    searches take the last entry that adds anything.)"""
    row = EDGE_ROWS[case]
    device = device_tokens(np.tile(row, (4, 1)), TEMPERATURES, uniform) if sampler == "device" else None
    for lane, temperature in enumerate(TEMPERATURES):
        if sampler == "host":
            rng = _StubGenerator(uniform)
            tok = sample_token(row, temperature, rng)
            assert rng.draws == 1
        else:
            tok = int(device[lane])
        assert 0 <= tok < len(row)
        live = np.flatnonzero(np.exp((row - row.max()) / np.float32(temperature)) > 0)
        assert tok in live
        if case != "spread-92544":  # there float64 sums absorb the smallest live entries
            assert tok == (live[0] if uniform == 0.0 else live[-1])
