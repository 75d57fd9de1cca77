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

from determined_tpu.models import moe
from determined_tpu.models.moe import RoutedExperts, _sorted_rows
from determined_tpu.ops import expert_rows, grouped_matmul as gm
from tests.model_cases import dense_experts as _dense_experts, routed_layer as _layer, sorted_rows_by_argsort


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


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its nested jaxprs too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


#: the shapes the cells run, at fewer tokens but their own k and count (and so their own choice between the layout's
#: two passes): (tokens, the router's outputs, k, first, count, tokens the serving forward masks as not live)
CELL_SHAPES = {
    "k 22 of 512, 128 held (Nemotron-3)": (24, 512, 22, 128, 128, 0),
    "k 12 of 96 and identity picks, 32 held, idle lanes (LongCat)": (20, 96, 12, 32, 32, 5),
    "k 8 of 64, 16 held (Mellum2)": (1024, 64, 8, 16, 16, 0),
    "k 1 of 16, 8 held (ZAYA1)": (3072, 16, 1, 0, 8, 0),
}


def _cell_picks(shape):
    tokens, outputs, k, first, count, idle = CELL_SHAPES[shape]
    rng = np.random.default_rng(11)
    picks = np.argsort(-rng.random((tokens, outputs)), axis=1)[:, :k].astype(np.int32)
    if "LongCat" in shape:
        assert (picks >= 64).any()                      # outputs 64.. are identity experts: never held
        picks[rng.choice(tokens, idle, replace=False)] = outputs   # what `live` makes of an idle lane's picks
    return picks, first, count


@pytest.mark.parametrize("serving", [False, True], ids=["training", "serving"])
@pytest.mark.parametrize("case", CASES + list(CELL_SHAPES))
def test_the_counted_layout_is_the_sorted_one_value_for_value(case, serving):
    """``_sorted_rows`` (no sort, no gather a row) against the argsort form it
    replaced (``tests/model_cases.py``): every field on every pick, tile and
    expert, and ``row_pick`` on every row a pick owns (the others are masked
    by ``row_live`` or lie past a tile's ``tile_rows``: any in-range pick)."""
    picks, first, count = _cell_picks(case) if case in CELL_SHAPES else _picks(case)
    got = jax.jit(lambda p: _sorted_rows(p, first, count, serving=serving))(jnp.asarray(picks))
    want = jax.jit(lambda p: sorted_rows_by_argsort(p, first, count, serving=serving))(jnp.asarray(picks))
    for field in ("pick_held", "pick_row", "row_live", "tile_rows", "load"):
        np.testing.assert_array_equal(getattr(got, field), getattr(want, field), err_msg=field)
        assert getattr(got, field).dtype == getattr(want, field).dtype, field
    for field in ("group_start", "tile_group", "live_tiles"):
        np.testing.assert_array_equal(getattr(got.layout, field), getattr(want.layout, field), err_msg=field)
        assert getattr(got.layout, field).dtype == jnp.int32
    assert (got.layout.rows, got.layout.tile) == (want.layout.rows, want.layout.tile)
    live = np.asarray(want.row_live)
    np.testing.assert_array_equal(np.asarray(got.row_pick)[live], np.asarray(want.row_pick)[live])
    assert got.row_pick.dtype == jnp.int32 and 0 <= int(got.row_pick.min()) and int(got.row_pick.max()) < picks.size
    held = (picks >= first) & (picks < first + count)
    assert int(got.load.sum()) == held.sum() and (case not in CELL_SHAPES or held.sum() > 0)


@pytest.mark.parametrize("cell, tokens, k, count, serving, rows, by", [
    ("Nemotron-3 decode", 64, 22, 128, True, 3456, "tile"), ("Nemotron-3 walk", 256, 22, 128, True, 13824, "tile"),
    ("LongCat decode", 64, 12, 32, True, 1792, "tile"), ("DSV3 decode", 64, 8, 16, True, 1024, "tile"),
    ("Mellum2 step", 8192, 8, 16, False, 69632, "block"), ("ZAYA1 step", 24576, 1, 8, False, 26624, "block"),
])
def test_the_pass_the_layout_takes_at_a_cells_own_shape(cell, tokens, k, count, serving, rows, by, monkeypatch):
    """Traced at the cells' real shapes, nothing computed: many experts and few
    tokens compare a tile's rows with its expert's tokens, few experts and many
    tokens a block's tokens with two tiles' rows (PERF.md section 5 "PR 60" has
    both passes' times at these shapes), and neither sorts or gathers a row."""
    took = []
    for name in ("tile", "block"):
        fn = getattr(moe, f"_owners_by_{name}")
        monkeypatch.setattr(moe, f"_owners_by_{name}", lambda *a, _fn=fn, _name=name: (took.append(_name), _fn(*a))[1])
    jaxpr = jax.make_jaxpr(lambda p: _sorted_rows(p, 0, count, serving=serving))(jax.ShapeDtypeStruct((tokens, k), jnp.int32))
    assert took == [by] and jaxpr.out_avals[3].shape == (rows,)
    text = str(jaxpr)
    assert " sort[" not in text and "argsort" not in text and "while[" not in text
    # no gather with an index a row or a pick: by tile, two of WHOLE rows ``[T]``, one a tile; by block, none that wide
    tiles = jaxpr.out_avals[4].shape[0]
    wide = [e.outvars[0].aval.shape for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "gather" and e.outvars[0].aval.size > tiles]
    assert wide == ([(tiles, tokens)] * 2 if by == "tile" else [])


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
    for eqn in _eqns(jaxpr):
        yield from (v.aval for v in (*eqn.invars, *eqn.outvars) if hasattr(v.aval, "shape"))


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
