"""The serving engines' host sampler (`serve/engine.py` `sample_token`)
against the plain form it replaced: a float64 softmax handed to
`Generator.choice`.  That form is kept HERE as the reference; the sampler's
contract (docs/serving.md "Observability") is what these cases pin, and a
sampler that moves onto the device is held to the same ones.
"""

import inspect
import warnings

import numpy as np
import pytest
from scipy import stats

import determined_tpu.serve as serve
from determined_tpu.serve.engine import sample_token

INTERNLM2_VOCAB = 92544


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


@pytest.mark.parametrize("spread", [1.0, 30.0])
@pytest.mark.parametrize("temperature", [0.3, 0.7, 1.0, 2.0])
@pytest.mark.parametrize("vocab", [1, 5, 127, 128, 129, 256, 32768, INTERNLM2_VOCAB])
def test_same_token_as_float64_softmax_and_choice(vocab, temperature, spread):
    """Twin generators, one uniform a token each: the float32 exponentials
    move a boundary of the cumulative distribution across the uniform on
    well under 0.5 % of draws, and nothing else may differ."""
    draws = 2000
    rows = _rows(vocab, spread)
    rng, rng_ref = np.random.default_rng(vocab), np.random.default_rng(vocab)
    same = 0
    for i in range(draws):
        row = rows[i % len(rows)]
        tok = sample_token(row, temperature, rng)
        assert 0 <= tok < vocab
        same += tok == reference_sample_token(row, temperature, rng_ref)
    assert same >= 0.995 * draws, f"{draws - same} of {draws} draws differ"
    assert rng.bit_generator.state == rng_ref.bit_generator.state


@pytest.mark.parametrize("temperature", [0.7, 1.5])
def test_draws_follow_the_float64_softmax(temperature):
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
        [sample_token(row, temperature, rng) for _ in range(draws)], minlength=vocab
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


@pytest.mark.parametrize("temperature", [0.7, 1e-30, 1e-320, 1e30])
@pytest.mark.parametrize("case", list(DEGENERATE_ROWS))
def test_degenerate_rows_yield_a_token_and_raise_nothing(case, temperature):
    """A numerically degenerate model costs a bad token, never an exception
    (or a warning turned into one) in the scheduler's loop."""
    row, expected = DEGENERATE_ROWS[case]
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        tok = sample_token(row, temperature, np.random.default_rng(3))
    assert isinstance(tok, int) and 0 <= tok < len(row)
    if expected is not None:
        assert tok == expected


def test_entries_of_minus_inf_are_never_drawn():
    masked = np.r_[0:130, 250:300]  # a whole block, part of the next, the tail
    row = _with(_rows(300, 1.0)[0], masked, -np.inf)
    rng = np.random.default_rng(11)
    drawn = {sample_token(row, 0.7, rng) for _ in range(2000)}
    assert drawn and not drawn & set(masked.tolist())


@pytest.mark.parametrize("temperature", [0.0, -1.0])
@pytest.mark.parametrize("case", ["distinct", "tie", "tie-at-the-ends", "all-equal"])
def test_temperature_zero_is_argmax_first_index_on_ties(case, temperature):
    row = _rows(300, 2.0)[0].copy()
    top = row.max() + 1.0
    if case == "tie":
        row[[40, 41, 299]] = top
    elif case == "tie-at-the-ends":
        row[[0, 299]] = top
    elif case == "all-equal":
        row[:] = top
    rng = _StubGenerator(0.5)
    assert sample_token(row, temperature, rng) == int(np.argmax(row))
    assert rng.draws == 0


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
def test_the_extreme_uniforms_stay_inside_the_vocabulary(case, uniform):
    """`rng.random()` lies in [0, 1), but its product with the normaliser can
    round up to it, and a block's own cumulative sum can round below its
    block sum: both searches hold the uniform below their last sum, so the
    token is in range AND one the distribution can yield."""
    row = EDGE_ROWS[case]
    for temperature in (0.3, 0.7, 1.0, 2.0):
        rng = _StubGenerator(uniform)
        tok = sample_token(row, temperature, rng)
        assert 0 <= tok < len(row) and rng.draws == 1
        live = np.flatnonzero(np.exp((row - row.max()) / np.float32(temperature)) > 0)
        assert tok in live
        if case != "spread-92544":  # there float64 sums absorb the smallest live entries
            assert tok == (live[0] if uniform == 0.0 else live[-1])
