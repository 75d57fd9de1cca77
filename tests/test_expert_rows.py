"""The dropless expert layer's two row movements (ops/expert_rows.py) against
the ``jax.numpy`` forms they replaced, and the layer through them forward and
backward whatever the picks.  Cut from tests/test_routed_experts.py, which
keeps the grouped product and the layer.  CPU, small sizes; Pallas kernels in
interpret mode."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.models.moe import RoutedExperts, _sorted_rows
from determined_tpu.ops import expert_rows, grouped_matmul as gm
from tests.model_cases import dense_experts as _dense_experts, routed_layer as _layer


# ---------------------------------------------------------------------------
# the two row movements (ops/expert_rows.py) against the jax.numpy forms they replaced
# ---------------------------------------------------------------------------


def _rows_of_tokens(x, row_pick, row_live, k):
    """``rows[r] = x[token of r]`` for the rows a held pick owns, zero elsewhere."""
    rows = jnp.take(x, row_pick // k, axis=0)
    return jnp.where(row_live[:, None], rows, jnp.zeros((), rows.dtype))


def _tokens_of_rows(rows, pick_row, pick_held):
    """``out[t] = sum over t's held picks of rows[row of the pick]``, float32:
    a gather of ALL ``tokens x k`` picks and a masked sum."""
    picked = jnp.take(rows, pick_row, axis=0)                          # [T, k, d]
    return jnp.sum(jnp.where(pick_held[:, :, None], picked, 0).astype(jnp.float32), axis=1)


def _picks(case):
    """(picks [T, k], first, count) whose held picks are the case's."""
    rng = np.random.default_rng(7)

    def draw(tokens, experts, k, allowed=None):
        score = rng.random((tokens, experts))
        if allowed is not None:
            score[:, [e for e in range(experts) if e not in allowed]] = -1.0
        return np.argsort(-score, axis=1)[:, :k].astype(np.int32)

    if case == "all experts held":
        return draw(48, 8, 3), 0, 8
    if case == "16 of 64 held":
        return draw(64, 64, 8), 16, 16
    if case == "every token picks the same experts":
        return np.tile(np.array([[1, 2, 5]], np.int32), (64, 1)), 0, 4
    if case == "an expert with no row":
        return draw(40, 8, 3, allowed=[0, 1, 3, 5, 6, 7]), 1, 4          # held 1-4: 2 and 4 get nothing
    if case == "a token with no held pick":
        picks = draw(40, 8, 3)
        picks[::3] = [5, 6, 7]                                            # every third token: nothing on 0-2
        return picks, 0, 3
    raise AssertionError(case)


CASES = [
    "all experts held", "16 of 64 held", "every token picks the same experts", "an expert with no row",
    "a token with no held pick",
]


def _sorted(case):
    picks, first, count = _picks(case)
    rows = _sorted_rows(jnp.asarray(picks), first, count)
    held = (picks >= first) & (picks < first + count)
    assert int(rows.load.sum()) == held.sum() == int(rows.tile_rows.sum()) == int(rows.row_live.sum())
    if case == "an expert with no row":
        assert 0 in np.asarray(rows.load).tolist()
    if case == "a token with no held pick":
        assert not held[::3].any()
    return picks, rows


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", CASES)
def test_tokens_of_rows_sums_the_rows_a_token_owns_and_reads_no_other(case, dtype):
    picks, rows = _sorted(case)
    tokens, k = picks.shape
    layout = rows.layout
    values = jax.random.normal(jax.random.key(2), (layout.rows, 24)).astype(dtype)
    want = _tokens_of_rows(values, rows.pick_row, rows.pick_held)
    # dead tiles and the rows of a live tile that no pick owns may hold anything
    poisoned = jnp.where(rows.row_live[:, None], values, jnp.nan)
    got = expert_rows.tokens_of_rows(poisoned, rows.row_pick // k, rows.tile_rows, layout, tokens)
    assert got.dtype == jnp.float32 and got.shape == (tokens, 24) and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=1e-6 if dtype == jnp.float32 else 0.02)
    none_held = ~np.asarray(rows.pick_held).any(axis=1)
    assert (np.asarray(got)[none_held] == 0.0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", CASES)
def test_rows_of_tokens_copies_owned_rows_and_zeroes_the_rest_of_a_live_tile(case, dtype):
    picks, rows = _sorted(case)
    tokens, k = picks.shape
    layout = rows.layout
    x = jax.random.normal(jax.random.key(3), (tokens, 24)).astype(dtype)
    want = _rows_of_tokens(x, rows.row_pick, rows.row_live, k)
    got = expert_rows.rows_of_tokens(x, rows.row_pick // k, rows.tile_rows, layout)
    assert got.dtype == dtype and got.shape == (layout.rows, 24)
    live = np.asarray(gm.live_rows_mask(layout))                        # rows of dead tiles are never read
    assert (np.asarray(got, np.float32)[live] == np.asarray(want, np.float32)[live]).all()


@pytest.mark.parametrize("case", CASES)
def test_the_layer_and_its_gradients_match_a_dense_loop_whatever_the_picks(case):
    """Through ``_held_experts`` forward and backward: the router is made to
    pick what the case says."""
    experts, k, held = {
        "all experts held": (8, 3, None), "16 of 64 held": (64, 8, (16, 16)),
        "every token picks the same experts": (8, 3, (0, 4)), "an expert with no row": (8, 3, (1, 4)),
        "a token with no held pick": (8, 3, (0, 2)),
    }[case]
    x = jnp.abs(jax.random.normal(jax.random.key(0), (2, 20, 16))) + 0.1
    layer = _layer(held, experts=experts, k=k)
    params = dict(layer.init(jax.random.key(1), x)["params"])
    if case == "every token picks the same experts":
        params["router"] = jnp.zeros((16, 8)).at[:, jnp.array([1, 2, 5])].set(jnp.array([3.0, 2.0, 1.0]))
    if case == "an expert with no row":
        params["router"] = params["router"].at[:, jnp.array([2, 4])].set(-5.0)   # x > 0: never among the top 3
    first, count = held or (0, experts)
    (y, aux), state = layer.apply({"params": params}, x, mutable=["intermediates"])
    load = np.asarray(state["intermediates"]["load"][0])
    picks = np.asarray(state["intermediates"]["picks"][0])
    live_rows = int(state["intermediates"]["live_rows"][0])
    assert load.sum() <= live_rows and live_rows % 8 == 0
    if case == "an expert with no row":
        assert load.tolist()[1] == 0 and load.tolist()[3] == 0
    if case == "a token with no held pick":
        assert (~((picks >= first) & (picks < first + count)).any(axis=1)).any()
    want, want_aux = _dense_experts(x.reshape(-1, 16), params, k, first, count)
    np.testing.assert_allclose(y.reshape(-1, 16), want, atol=2e-6)
    np.testing.assert_allclose(aux, want_aux, rtol=1e-6)

    def scalar(fn):
        return lambda p, x: (lambda out: jnp.sum(jnp.sin(out[0])) + out[1])(fn(p, x))

    got = jax.grad(scalar(lambda p, x: layer.apply({"params": p}, x)), (0, 1))(params, x)
    ref = jax.grad(scalar(lambda p, x: _dense_experts(x.reshape(-1, 16), p, k, first, count)), (0, 1))(params, x)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b.reshape(a.shape), atol=5e-6), got, ref)


def _avals(jaxpr):
    """Every array a (closed) jaxpr holds, those of its nested jaxprs too."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in (*eqn.invars, *eqn.outvars) if hasattr(v.aval, "shape"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_no_array_of_tokens_times_k_rows_exists_in_the_layer_or_its_gradient():
    """The ``[T x k, d]`` round trip of the gathers over all picks cannot come
    back unnoticed: at (tokens 512, k 8, d 128), 2 of 16 experts held, no array of
    the forward or the backward pass has 4,096 rows of 128 columns."""
    x = jax.random.normal(jax.random.key(0), (1, 512, 128))
    layer = RoutedExperts(num_experts=16, top_k=8, d_ff=32, held=(0, 2), dtype=jnp.float32, partition=False)
    params = layer.init(jax.random.key(1), x)["params"]

    def loss(p, x):
        y, aux = layer.apply({"params": p}, x)
        return jnp.sum(jnp.sin(y)) + aux

    for fn in (lambda p, x: layer.apply({"params": p}, x), jax.grad(loss, (0, 1))):
        shapes = {tuple(a.shape) for a in _avals(jax.make_jaxpr(fn)(params, x).jaxpr)}
        assert (512, 128) in shapes                                      # the walk reaches the layer's arrays
        wide = [s for s in shapes if len(s) >= 2 and s[-1] == 128 and math.prod(s[:-1]) >= 512 * 8]
        assert not wide, wide
