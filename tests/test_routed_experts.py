"""The dropless top-k expert layer (models/moe.py ``RoutedExperts``), the
grouped product under it (ops/grouped_matmul.py), windows in attention
(ops/flash_attention.py, ops/attention.py), layer types and YaRN in the
transformer, and what the program refuses by name.  CPU, small sizes, seeded
weights; Pallas kernels in interpret mode."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from determined_tpu.models.moe import RoutedExperts, _sorted_rows
from determined_tpu.models.transformer import (
    FULL,
    SLIDING,
    TransformerConfig,
    TransformerLM,
    _check_decodable,
    yarn_inv_freq,
)
from determined_tpu.ops import expert_rows, grouped_matmul as gm
from determined_tpu.ops.attention import dot_product_attention, reference_attention
from determined_tpu.ops.flash_attention import flash_attention

YARN = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192,
    "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.2772588722239782,
}


# ---------------------------------------------------------------------------
# the grouped product
# ---------------------------------------------------------------------------


def _grouped(sizes, tile=8, k=12, n=20, max_rows=40, seed=0):
    rng = np.random.default_rng(seed)
    layout = gm.tile_layout(jnp.asarray(sizes, jnp.int32), max_rows, tile)
    lhs, group = np.zeros((layout.rows, k), np.float32), np.full(layout.rows, -1)
    for e, count in enumerate(sizes):
        start = int(layout.group_start[e])
        lhs[start:start + count] = rng.normal(size=(count, k))
        group[start:start + count] = e
    return layout, lhs, group, rng.normal(size=(len(sizes), k, n)).astype(np.float32)


@pytest.mark.parametrize("sizes", [[5, 0, 17, 3], [0, 0, 0, 40], [8, 8, 8, 8], [0, 0, 0, 0], [1, 39, 0, 0]])
def test_the_grouped_products_match_a_loop_over_groups(sizes):
    layout, lhs, group, rhs = _grouped(sizes)
    live = np.asarray(gm.live_rows_mask(layout))
    assert layout.rows == gm.buffer_rows(40, 4, 8) and live.sum() == 8 * int(layout.live_tiles[0])
    # rows of dead tiles hold anything and are never read: compare live rows
    want = np.stack([lhs[r] @ rhs[group[r]] if group[r] >= 0 else np.zeros(20) for r in range(layout.rows)])
    got = np.asarray(gm.gmm(jnp.asarray(lhs), jnp.asarray(rhs), layout))
    np.testing.assert_allclose(got[live], want[live], atol=1e-5)
    ct = np.random.default_rng(1).normal(size=want.shape).astype(np.float32) * (group >= 0)[:, None]
    # the gradient to the rows: the same kernel over the transposed matrices
    want_lhs = np.stack([ct[r] @ rhs[group[r]].T if group[r] >= 0 else np.zeros(12) for r in range(layout.rows)])
    d_lhs = np.asarray(gm.gmm(jnp.asarray(ct), jnp.asarray(rhs), layout, transpose_rhs=True))
    np.testing.assert_allclose(d_lhs[live], want_lhs[live], atol=1e-5)
    # the gradient to the matrices: an empty group's is zero, not what memory held
    want_rhs = np.stack([
        sum((np.outer(lhs[r], ct[r]) for r in range(layout.rows) if group[r] == e), np.zeros((12, 20)))
        for e in range(len(sizes))
    ])
    d_rhs = np.asarray(gm.tgmm(jnp.asarray(lhs), jnp.asarray(ct), layout, len(sizes)))
    assert d_rhs.dtype == np.float32
    np.testing.assert_allclose(d_rhs, want_rhs, atol=1e-5)


def test_every_group_owns_a_tile_and_the_buffer_holds_the_worst_split():
    for sizes in ([40, 0, 0, 0], [10, 10, 10, 10], [1, 1, 1, 37], [0, 0, 0, 0]):
        layout = gm.tile_layout(jnp.asarray(sizes, jnp.int32), 40, 8)
        starts = np.asarray(layout.group_start)
        tiles = np.maximum(-(-np.asarray(sizes) // 8), 1)
        assert (starts % 8 == 0).all() and (np.diff(starts) == tiles[:-1] * 8).all()
        assert starts[-1] + tiles[-1] * 8 == int(layout.live_tiles[0]) * 8 <= layout.rows
        # every tile's group, and dead tiles name the last group (no new block is fetched for them)
        want = np.repeat(np.arange(4), tiles)
        got = np.asarray(layout.tile_group)
        assert (got[:len(want)] == want).all() and (got[len(want):] == 3).all()


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------


def _dense_experts(x, p, k, first, count):
    """Every held expert on every token, then the picks: plain."""
    probs = jax.nn.softmax(x @ p["router"], -1)
    top, idx = jax.lax.top_k(probs, k)
    w = top / top.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(first, first + count):
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        hidden = jax.nn.silu(x @ p["w_gate"][e - first]) * (x @ p["w_up"][e - first])
        y = y + mine[:, None] * (hidden @ p["w_down"][e - first])
    experts = probs.shape[-1]
    share = jnp.mean(jax.nn.one_hot(idx, experts).sum(1), 0) / k
    return y, experts * jnp.sum(share * probs.mean(0))


def _layer(held=None, experts=8, k=3, **kw):
    return RoutedExperts(num_experts=experts, top_k=k, d_ff=12, held=held, dtype=jnp.float32, partition=False, **kw)


@pytest.mark.parametrize("held", [None, (0, 2), (6, 2), (3, 5)])
def test_routed_experts_and_their_gradients_match_a_dense_loop(held):
    x = jax.random.normal(jax.random.key(0), (2, 24, 16))
    layer = _layer(held)
    params = layer.init(jax.random.key(1), x)["params"]
    first, count = held or (0, 8)
    assert params["router"].shape == (16, 8) and params["w_gate"].shape == (count, 16, 12)
    (y, aux), state = layer.apply({"params": params}, x, mutable=["intermediates"])
    want, want_aux = _dense_experts(x.reshape(-1, 16), params, 3, first, count)
    np.testing.assert_allclose(y.reshape(-1, 16), want, atol=1e-6)
    np.testing.assert_allclose(aux, want_aux, rtol=1e-6)
    # what the layer sows: every token's picks, and the picks that landed on each held expert
    picks = state["intermediates"]["picks"][0]
    load = state["intermediates"]["load"][0]
    assert picks.shape == (48, 3) and (np.asarray(load) == [(np.asarray(picks) == e).sum() for e in range(first, first + count)]).all()

    def scalar(fn):
        return lambda p, x: (lambda out: jnp.sum(jnp.sin(out[0])) + out[1])(fn(p, x))

    got = jax.grad(scalar(lambda p, x: layer.apply({"params": p}, x)), (0, 1))(params, x)
    ref = jax.grad(scalar(lambda p, x: _dense_experts(x.reshape(-1, 16), p, 3, first, count)), (0, 1))(params, x)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b.reshape(a.shape), atol=2e-6), got, ref)


def test_the_four_shares_add_up_to_the_whole_layer_in_a_loop_and_under_shard_map():
    """Experts 0-1, 2-3, 4-5, 6-7 held by four devices: their outputs sum to
    the layer that holds all eight (nothing stands in for absent experts)."""
    x = jax.random.normal(jax.random.key(0), (2, 16, 16))
    whole = _layer()
    params = whole.init(jax.random.key(1), x)["params"]
    want, want_aux = whole.apply({"params": params}, x)

    def share(i):
        mine = {k: v if k == "router" else v[2 * i:2 * i + 2] for k, v in params.items()}
        return _layer((2 * i, 2)).apply({"params": mine}, x)

    parts = [share(i) for i in range(4)]
    np.testing.assert_allclose(sum(y for y, _ in parts), want, atol=1e-6)
    assert all(float(aux) == pytest.approx(float(want_aux), rel=1e-6) for _, aux in parts)  # routing is over all 8 everywhere

    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
    sharded = _layer(expert_axis_name="expert")
    specs = {k: P() if k == "router" else P("expert") for k in params}
    got, aux = jax.jit(jax.shard_map(
        lambda p, x: sharded.apply({"params": p}, x), mesh=mesh, in_specs=(specs, P()), out_specs=(P(), P()),
        check_vma=False,
    ))(params, x)
    np.testing.assert_allclose(got, want, atol=1e-6)  # the psum that is the combine
    assert float(aux) == pytest.approx(float(want_aux), rel=1e-6)


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    x = jnp.abs(jax.random.normal(jax.random.key(0), (1, 64, 16))) + 0.1
    layer = _layer((0, 4))
    params = dict(layer.init(jax.random.key(1), x)["params"])
    # experts 1, 2 and 5 win for every token: 64 rows each on two held experts, none on the others
    params["router"] = jnp.zeros((16, 8)).at[:, jnp.array([1, 2, 5])].set(jnp.array([3.0, 2.0, 1.0]))
    (y, _), state = layer.apply({"params": params}, x, mutable=["intermediates"])
    assert np.asarray(state["intermediates"]["load"][0]).tolist() == [0, 64, 64, 0]
    want, _ = _dense_experts(x.reshape(-1, 16), params, 3, 0, 4)
    np.testing.assert_allclose(y.reshape(-1, 16), want, atol=1e-6)
    assert float(jnp.min(jnp.linalg.norm(y, axis=-1))) > 0.0  # every token got its experts' output


def test_an_expert_axis_that_does_not_divide_the_experts_is_refused():
    mesh = Mesh(np.array(jax.devices()[:3]), ("expert",))
    layer = _layer(expert_axis_name="expert")
    with pytest.raises(ValueError, match="8 experts must divide"):
        jax.shard_map(
            lambda x: layer.init(jax.random.key(0), x), mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )(jnp.zeros((1, 4, 16)))


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


# ---------------------------------------------------------------------------
# windows in attention
# ---------------------------------------------------------------------------


def _qkv(seq, heads=4, kv=2, d=16):
    keys = jax.random.split(jax.random.key(3), 3)
    return (jax.random.normal(keys[0], (1, heads, seq, d)), jax.random.normal(keys[1], (1, kv, seq, d)),
            jax.random.normal(keys[2], (1, kv, seq, d)))


@pytest.mark.parametrize("seq,block_q,block_k,window", [
    (256, 64, 64, 64),     # the window is a block
    (256, 64, 32, 100),    # divides neither block
    (256, 32, 64, 37),
    (128, 128, 128, 50),   # one block: the single-pass kernel
    (256, 64, 64, 1),      # a query sees itself alone
    (256, 64, 64, 255),    # all but one key of the last query
])
def test_flash_with_a_window_matches_the_reference_forward_and_backward(seq, block_q, block_k, window):
    q, k, v = _qkv(seq)
    flash = lambda q, k, v: flash_attention(q, k, v, block_q=block_q, block_k=block_k, window=window)  # noqa: E731
    ref = lambda q, k, v: reference_attention(q, k, v, window=window)  # noqa: E731
    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(ref(*a))), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_the_reference_window_is_the_stated_mask():
    q, k, v = _qkv(32)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1)) / 4.0
    i, j = jnp.arange(32)[:, None], jnp.arange(32)[None, :]
    probs = jax.nn.softmax(jnp.where((j <= i) & (i - j < 8), scores, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bhkd->bhqd", probs, jnp.repeat(v, 2, axis=1))
    np.testing.assert_allclose(reference_attention(q, k, v, window=8), want, atol=1e-6)
    np.testing.assert_allclose(dot_product_attention(q, k, v, impl="reference", window=8), want, atol=1e-6)


def test_no_window_is_bit_equal_to_the_kernel_without_the_argument():
    q, k, v = _qkv(256)
    plain = flash_attention(q, k, v, block_q=64, block_k=64)
    assert (flash_attention(q, k, v, block_q=64, block_k=64, window=None) == plain).all()
    assert (flash_attention(q, k, v, block_q=64, block_k=64, window=256) == plain).all()  # covers the sequence: no window
    grads = [
        jax.grad(lambda *a: jnp.sum(jnp.sin(flash_attention(*a, block_q=64, block_k=64, **kw))), (0, 1, 2))(q, k, v)
        for kw in ({}, {"window": None})
    ]
    assert all((a == b).all() for a, b in zip(*grads))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="causal"):
        reference_attention(q, k, v, causal=False, window=8)


def test_sharded_flash_attention_takes_the_window():
    from determined_tpu.ops.attention import sharded_flash_attention

    q, k, v = (jnp.concatenate([t, t + 1.0]) for t in _qkv(128))
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("data", "tensor"))
    got = jax.jit(lambda q, k, v: sharded_flash_attention(q, k, v, mesh, window=40))(q, k, v)
    np.testing.assert_allclose(got, reference_attention(q, k, v, window=40), atol=2e-6)


# ---------------------------------------------------------------------------
# layer types, head_dim, YaRN
# ---------------------------------------------------------------------------


def test_yarn_frequencies_match_numbers_worked_by_hand():
    """theta 500,000, head_dim 128, factor 16, original 8,192, beta 32 / 1:
    corr(32) = 128 ln(8192 / (64 pi)) / (2 ln 500000) = 18.08 -> low 18;
    corr(1) = 34.98 -> high 35."""
    inv = yarn_inv_freq(128, 500000.0, factor=16, original_max_position_embeddings=8192, beta_fast=32, beta_slow=1)
    ln = math.log(500000.0)
    assert math.floor(128 * math.log(8192 / (64 * math.pi)) / (2 * ln)) == 18
    assert math.ceil(128 * math.log(8192 / (2 * math.pi)) / (2 * ln)) == 35
    assert inv.shape == (64,) and inv.dtype == np.float32
    assert inv[0] == 1.0
    assert inv[17] == pytest.approx(0.0306345, rel=1e-5)      # i < low: plain, 500000^(-34/128)
    assert inv[25] == pytest.approx(0.00364743, rel=1e-5)     # ramp 7/17: plain x (10/17 + 7/17 / 16)
    assert inv[36] == pytest.approx(3.89233e-05, rel=1e-5)    # i > high: plain / 16
    assert inv[63] == pytest.approx(500000.0 ** (-126 / 128) / 16, rel=1e-5)


def _tiny(**kw):
    base = dict(
        vocab_size=64, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=12, d_ff=48, max_seq_len=32,
        dtype=jnp.float32, attention_impl="reference", partition_params=False,
        layer_types=(SLIDING, SLIDING, FULL), sliding_window=8,
        rope_parameters={FULL: YARN, SLIDING: {"rope_type": "default", "rope_theta": 500000}},
    )
    return TransformerConfig(**{**base, **kw})


def test_head_dim_is_a_stated_field_and_layer_types_reach_every_block():
    cfg = _tiny()
    assert cfg.head_dim == 12 and TransformerConfig(d_model=64, n_heads=4).head_dim == 16
    assert hash(cfg) == hash(_tiny())  # rope_parameters are frozen: the config stays hashable
    assert cfg.rope(SLIDING).inv_freq is None and cfg.rope(SLIDING).theta == 500000.0
    assert cfg.rope(FULL).attention_factor == YARN["attention_factor"] and len(cfg.rope(FULL).inv_freq) == 6
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 64)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(1), tokens)
    assert params["params"]["block_0"]["attn"]["wq"]["kernel"].shape == (32, 4, 12)
    assert params["params"]["block_0"]["attn"]["wo"]["kernel"].shape == (4, 12, 32)
    out = model.apply(params, tokens)
    # each departure changes the output: a layer's window, the full layer's rotary section, its factor
    for other in (
        _tiny(layer_types=(SLIDING, FULL, FULL)), _tiny(sliding_window=9),
        _tiny(rope_parameters={SLIDING: {"rope_type": "default", "rope_theta": 500000}}, rope_theta=500000.0),
        _tiny(rope_parameters={FULL: {**YARN, "attention_factor": 1.0}, SLIDING: {"rope_type": "default", "rope_theta": 500000}}),
    ):
        assert float(jnp.max(jnp.abs(TransformerLM(other).apply(params, tokens) - out))) > 1e-4
    # and flash (interpret mode) runs the same layers
    flash = TransformerLM(_tiny(attention_impl="flash")).apply(params, tokens)
    np.testing.assert_allclose(flash, out, atol=2e-5)


@pytest.mark.parametrize("kw,says", [
    (dict(layer_types=(SLIDING, FULL)), "layer_types needs one"),
    (dict(layer_types=(SLIDING, FULL, "chunked")), "layer_types needs one"),
    (dict(sliding_window=None), "sliding_window"),
    (dict(moe_experts=4, moe_top_k=5), "moe_top_k"),
    (dict(moe_experts=8, moe_top_k=2, moe_experts_held=(6, 3)), "moe_experts_held"),
    (dict(moe_experts=8, moe_top_k=2, moe_experts_held=(-1, 2)), "moe_experts_held"),
    (dict(moe_experts=8, moe_experts_held=(0, 2)), "belong to moe_top_k"),
    (dict(rope_parameters={FULL: {"rope_type": "llama3"}}), "rope_type"),
])
def test_the_config_refuses_what_does_not_fit_together(kw, says):
    with pytest.raises(ValueError, match=says):
        _tiny(**kw)


def test_what_cannot_honour_a_window_refuses_by_name():
    tokens = jnp.zeros((1, 32), jnp.int32)
    # ring attention knows no window
    with pytest.raises(ValueError, match="ring attention.*sliding_attention"):
        TransformerLM(_tiny(attention_impl="ring")).init(jax.random.key(0), tokens)
    # the serving forward does (since PR 38: a ring a lane, tests/test_window_serving.py), its wide prefill aside
    _check_decodable(_tiny())
    with pytest.raises(ValueError, match="the wide prefill runs full layers only"):
        from determined_tpu.models.transformer import transformer_prefill

        transformer_prefill(_tiny(), {}, tokens, jnp.ones(1, jnp.int32), jnp.zeros((1, 8), jnp.int32), {"k": jnp.zeros((1, 2, 4, 8))})
    _check_decodable(_tiny(layer_types=None, sliding_window=None))
    _check_decodable(_tiny(layer_types=(FULL,) * 3, sliding_window=None, rope_parameters=None))


# ---------------------------------------------------------------------------
# the optimizer's sweep over stacks of expert matrices, and the Trainer's counters
# ---------------------------------------------------------------------------


def test_fused_adamw_sweeps_a_stack_of_matrices_a_matrix_at_a_time(monkeypatch):
    import importlib

    adamw = importlib.import_module("determined_tpu.ops.fused_adamw")
    # the expert leaves of the Mellum2 cell: 896 halves to no multiple of 128 and one matrix is past the budget
    assert adamw._plan_blocks((16, 2304, 896)) == ((16, 12), (1, 192, 896), 1)
    assert adamw._plan_blocks((16, 896, 2304)) == ((16, 14), (1, 64, 2304), 1)
    # plans that were there stay what they were
    assert adamw._plan_blocks((32, 128, 4096)) == ((32, 4), (1, 128, 1024), 2)
    assert adamw._plan_blocks((4096, 14336)) == ((512, 1), (8, 14336), 1)
    # the same plan at a size the interpreter sweeps, against the jnp update
    monkeypatch.setattr(adamw, "_plan_blocks", lambda shape: adamw._plan_matrix_rows(shape, 8 * 24))
    assert adamw._plan_blocks((3, 16, 24)) == ((3, 2), (1, 8, 24), 1)
    keys = jax.random.split(jax.random.key(0), 4)
    p, m, g = (jax.random.normal(k, (3, 16, 24)) for k in keys[:3])
    v = jnp.abs(jax.random.normal(keys[3], (3, 16, 24)))
    scalars = jnp.array([[1e-3, 0.5, 0.1, 0.001]], jnp.float32)
    kw = dict(b1=0.9, b2=0.999, eps=1e-8, wd=0.01)
    got = adamw._leaf_pallas(p, m, v, g, scalars, **kw)
    want = adamw._leaf_jnp(p, m, v, g, scalars, **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_the_trainer_pushes_the_expert_layers_load_as_counters(tmp_path):
    from determined_tpu import core, train
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.observability import get_tracer

    hparams = dict(
        lr=1e-3, global_batch_size=2, seq_len=32, dataset_size=8, vocab_size=64, d_model=32, n_layers=2, n_heads=4,
        head_dim=12, d_ff=48, bf16=False, attention="reference", fused_ce=False, fused_adamw=False,
        layer_types=[SLIDING, FULL], sliding_window=8, moe_experts=8, moe_every=1, moe_top_k=3,
        moe_intermediate_size=16, moe_experts_held=[2, 4], moe_aux_weight=0.001,
    )
    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)
    try:
        ctx = train.init(
            hparams=hparams, core_context=core._dummy_init(checkpoint_dir=str(tmp_path)), seed=1,
            devices=jax.devices()[:1],  # dropless experts: one device (or a pipeline stage)
        )
        trial = LMTrial(ctx)
        # what the goodput ledger divides by: head_dim 12, a window of 8 and a full layer, 3 x 4 / 8 experts a token
        params = 64 * 32 + 2 * (32 * 12 * (2 * 4 + 2 * 4) + 32 * 8 + 1.5 * 3 * 32 * 16)
        assert trial.flops_per_token == 6 * params + 12 * (8 + 32) * 4 * 12
        train.Trainer(trial).fit({"batches": 4}, report_period={"batches": 2}, checkpoint_policy="none")
        events = [e for e in tracer.chrome_events() if e.get("ph") == "C"]
    finally:
        tracer.configure(enabled=was)  # other tests of this worker read the tracer as they found it
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e["args"]["value"])
    assert by_name["train.steps"][-2:] == [2.0, 2.0]
    assert by_name["moe.picks"][-2:] == [2 * 2 * 64 * 3.0] * 2         # 2 steps x 2 layers x 64 tokens x 3 picks
    held, top, mean = (by_name[k][-1] for k in ("moe.held_picks", "moe.expert_load_max", "moe.expert_load_mean"))
    assert 0 < held < by_name["moe.picks"][-1] and held == pytest.approx(mean * 2 * 4)  # mean over 2 layers x 4 held experts
    assert top >= mean and "moe_aux_loss" in by_name
    # rows the kernels touch: whole tiles of 64 (192 rows at most over 4 held experts), never fewer than the held picks
    live = by_name["moe.live_rows"][-1]
    assert live % 64 == 0 and held <= live <= 2 * 2 * gm.buffer_rows(192, 4, 64)


# ---------------------------------------------------------------------------
# pipeline stages: the period of layer_types, and the expert axis
# ---------------------------------------------------------------------------

PIPE_HPARAMS = dict(
    lr=1e-3, global_batch_size=8, seq_len=32, vocab_size=128, d_model=32, n_layers=4, n_heads=4, head_dim=12,
    dataset_size=32, bf16=False, attention="reference", warmup_steps=1, fused_ce=False, fused_adamw=False,
    sliding_window=8, moe_experts=4, moe_every=1, moe_top_k=2, moe_intermediate_size=16,
    # the auxiliary term is a microbatch's under the pipeline and the batch's without: parity is the main loss's
    moe_aux_weight=0.0,
)


def _pipe_context(tmp_path, mesh_config, layer_types, tag="", devices=None):
    from determined_tpu import core, train

    return train.init(
        hparams=dict(PIPE_HPARAMS, layer_types=layer_types), mesh_config=mesh_config,
        core_context=core._dummy_init(checkpoint_dir=str(tmp_path / f"ckpt{tag}")), seed=7, devices=devices,
    )


def test_pipe_needs_the_period_of_layer_types_to_divide_a_chunk(tmp_path):
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig

    mesh = MeshConfig(pipe=2, data=4)
    with pytest.raises(ValueError, match="period of layer_types"):
        LMTrial(_pipe_context(tmp_path, mesh, [SLIDING, SLIDING, SLIDING, FULL]))._cfg()
    cfg = LMTrial(_pipe_context(tmp_path, mesh, [SLIDING, FULL, SLIDING, FULL], tag="b"))._cfg()
    assert cfg.layer_types == (SLIDING, FULL, SLIDING, FULL) and cfg.moe_top_k == 2
    # outside pipeline stages dropless experts are GSPMD's to partition, and a Mosaic kernel cannot be
    with pytest.raises(ValueError, match="one device or inside"):
        LMTrial(_pipe_context(tmp_path, MeshConfig(data=2), [SLIDING, FULL, SLIDING, FULL], tag="c", devices=jax.devices()[:2]))._cfg()


@pytest.mark.slow
def test_pipe_stages_run_layer_types_and_dropless_experts_over_the_expert_axis(tmp_path):
    """pipe2 x expert2 x data2 against one device: layer j of every chunk is
    one stacked leaf with one layer type, each device holds its half of the
    experts and the psum is the combine; no token is dropped on either side,
    so the main loss agrees step for step."""
    from determined_tpu import train
    from determined_tpu.config import Length
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig

    def losses(ctx):
        seen = []
        report = ctx.core.train.report_training_metrics
        ctx.core.train.report_training_metrics = lambda s, m: (seen.append(m["loss"]), report(s, m))
        train.Trainer(LMTrial(ctx)).fit(Length.batches(3), report_period=Length.batches(1), checkpoint_policy="none")
        return seen

    types = [SLIDING, FULL, SLIDING, FULL]
    one = losses(_pipe_context(tmp_path, MeshConfig(data=1), types, tag="a", devices=jax.devices()[:1]))
    staged = losses(_pipe_context(tmp_path, MeshConfig(pipe=2, expert=2, data=2), types, tag="b"))
    assert len(one) == 3 and all(np.isfinite(staged))
    np.testing.assert_allclose(one, staged, rtol=2e-4, atol=2e-5)
