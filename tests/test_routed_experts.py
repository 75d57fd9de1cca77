"""The dropless top-k expert layer (models/moe.py ``RoutedExperts``) and the
grouped product under it (ops/grouped_matmul.py).  Its row movements are
tests/test_expert_rows.py; windows in attention, layer types, YaRN and the
Trainer's counters are tests/test_layer_types.py.  CPU, small sizes, seeded
weights; Pallas kernels in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from determined_tpu.ops import grouped_matmul as gm
from determined_tpu.models import moe
from determined_tpu.models.transformer import TransformerConfig
from tests.model_cases import (
    dense_experts as _dense_experts, routed_layer as _layer, row_weights_by_gather, sorted_rows_by_argsort,
)

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


#: (tokens, the router's width, k, first, count, a gated expert?, the pass the layout takes at that shape)
LAYOUTS = {
    "k 8 of 64, 16 held": (96, 64, 8, 16, 16, True, "tile"),
    "k 4 of 16, 8 held, two matrices": (40, 16, 4, 4, 8, False, "tile"),
    "k 1 of 4, 2 held": (1024, 4, 1, 1, 2, True, "block"),
    "k 2 of 8, 4 held, two matrices": (1024, 8, 2, 2, 4, False, "block"),
}


@pytest.mark.parametrize("case", list(LAYOUTS))
def test_the_held_experts_and_their_gradients_are_the_same_bits_under_the_counted_layout(case, monkeypatch):
    """``_held_experts`` forward and backward through ``_sorted_rows`` against
    the same through the argsort form it replaced (``tests/model_cases.py``):
    the layout is the same integers, so float32 outputs and gradients are the
    same BITS; and ``_row_weights`` (compares, or one gather past
    ``_COMPARE_TOKENS``) against its plain gather."""
    tokens, outputs, k, first, count, gated, by = LAYOUTS[case]
    took = []
    for name in ("tile", "block"):
        fn = getattr(moe, f"_owners_by_{name}")
        monkeypatch.setattr(moe, f"_owners_by_{name}", lambda *a, _fn=fn, _name=name: (took.append(_name), _fn(*a))[1])
    d, d_ff = 16, 12
    keys = jax.random.split(jax.random.key(3), 7)
    picks = jnp.argsort(-jax.random.uniform(keys[0], (tokens, outputs)), axis=1)[:, :k].astype(jnp.int32)
    weights = jax.random.uniform(keys[1], (tokens, k), jnp.float32, 0.1, 1.0)
    x = jax.random.normal(keys[2], (tokens, d))
    w_gate = jax.random.normal(keys[3], (count, d, d_ff)) * 0.3 if gated else None
    w_up, w_down = jax.random.normal(keys[4], (count, d, d_ff)) * 0.3, jax.random.normal(keys[5], (count, d_ff, d)) * 0.3
    cot = jax.random.normal(keys[6], (tokens, d))

    def run(layout_of):
        rows = layout_of(picks, first, count)

        def out(x, w_gate, w_up, w_down, weights):
            return moe._held_experts(
                x, w_gate, w_up, w_down, weights,
                rows.row_pick, rows.row_live, rows.pick_row, rows.pick_held, rows.tile_rows, *rows.layout,
            )

        y, vjp = jax.vjp(out, x, w_gate, w_up, w_down, weights)
        return rows, y, vjp(cot)

    rows, y, grads = jax.jit(lambda: run(moe._sorted_rows))()
    want_rows, want_y, want_grads = jax.jit(lambda: run(sorted_rows_by_argsort))()
    assert took == [by] and int(rows.load.sum()) > 0 and float(jnp.abs(y).max()) > 0
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want_y))
    for name, got, want in zip(("x", "w_gate", "w_up", "w_down", "weights"), grads, want_grads):
        if got is not None:
            assert float(jnp.abs(want).max()) > 0, name
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=name)
    scale = moe._row_weights(weights, rows.row_pick, rows.row_live, rows.pick_row, rows.pick_held, rows.layout.tile)
    np.testing.assert_array_equal(np.asarray(scale), np.asarray(row_weights_by_gather(weights, want_rows.row_pick, want_rows.row_live)))


def _held_experts_as_xla_stated_them(x, w_gate, w_up, w_down, weights, picks, first):
    """``_held_experts`` as the layer stated it before any kernel (no buffer, no
    layout): every pick's expert on the pick's token, the activation and the
    routing weight as XLA's elementwise passes had them, a masked sum a token.
    float32; its gradients are autodiff's."""
    count = w_up.shape[0]
    held = (picks >= first) & (picks < first + count)
    e = jnp.clip(picks - first, 0, count - 1)                            # [T, k]
    up = jnp.einsum("td,tkdf->tkf", x, w_up[e])
    act = jnp.square(jax.nn.relu(up)) if w_gate is None else jax.nn.silu(jnp.einsum("td,tkdf->tkf", x, w_gate[e])) * up
    out = jnp.einsum("tkf,tkfd->tkd", act * weights[:, :, None], w_down[e])
    return jnp.sum(jnp.where(held[:, :, None], out, 0.0), axis=1)


#: the two training cells' expert layers at few tokens and narrow widths, their own k, held share and expert; and
#: Nemotron's two-matrix expert
CELL_LAYERS = {
    "k 8 of 64, 16 held (Mellum2)": (96, 64, 8, 0, 16, True),
    "k 1 of 16, 8 held (ZAYA1)": (640, 16, 1, 0, 8, True),
    "k 4 of 16, 8 held, two matrices": (40, 16, 4, 4, 8, False),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CELL_LAYERS))
def test_the_held_experts_and_all_five_gradients_are_the_plain_statements(case, dtype):
    """``_held_experts`` through its kernels (the row movements, the grouped
    products, hidden and its derivative over live tiles, the second product to
    the rows added into the first) against the plain statement above: the
    value and the gradients to x, the three matrices and the routing weights."""
    tokens, outputs, k, first, count, gated = CELL_LAYERS[case]
    d, d_ff = 16, 12
    keys = jax.random.split(jax.random.key(5), 7)
    picks = jnp.argsort(-jax.random.uniform(keys[0], (tokens, outputs)), axis=1)[:, :k].astype(jnp.int32)
    weights = jax.random.uniform(keys[1], (tokens, k), jnp.float32, 0.1, 1.0)
    x = jax.random.normal(keys[2], (tokens, d)).astype(dtype)
    w_gate = jax.random.normal(keys[3], (count, d, d_ff)) * 0.3 if gated else None
    w_up, w_down = jax.random.normal(keys[4], (count, d, d_ff)) * 0.3, jax.random.normal(keys[5], (count, d_ff, d)) * 0.3
    cot = jax.random.normal(keys[6], (tokens, d))
    rows = moe._sorted_rows(picks, first, count)
    assert 0 < int(rows.layout.live_tiles[0]) < rows.layout.rows // rows.layout.tile    # dead tiles beside the live ones

    def through_kernels(x, w_gate, w_up, w_down, weights):
        return moe._held_experts(
            x, w_gate, w_up, w_down, weights,
            rows.row_pick, rows.row_live, rows.pick_row, rows.pick_held, rows.tile_rows, *rows.layout,
        )

    def plain(x, w_gate, w_up, w_down, weights):
        return _held_experts_as_xla_stated_them(x.astype(jnp.float32), w_gate, w_up, w_down, weights, picks, first)

    operands = (x, w_gate, w_up, w_down, weights)
    y, vjp = jax.jit(lambda *a: jax.vjp(through_kernels, *a))(*operands)
    want_y, want_vjp = jax.jit(lambda *a: jax.vjp(plain, *a))(*operands)
    # float32: sums in another order; bfloat16: the products' operands and results rounded, against a scale of ~1
    close = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else dict(rtol=0.05, atol=0.08)
    assert y.dtype == jnp.float32 and float(jnp.abs(want_y).max()) > 0.1
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), **close)
    for name, got, want in zip(("x", "w_gate", "w_up", "w_down", "weights"), vjp(cot), want_vjp(cot)):
        assert (got is None) == (want is None) == (name == "w_gate" and not gated), name
        if got is not None:
            assert got.dtype == want.dtype and float(jnp.abs(want).max()) > 0.1, name
            scale = 1.0 if dtype == jnp.float32 else float(jnp.abs(want).max())   # a matrix's gradient sums hundreds of rows
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=close["rtol"], atol=close["atol"] * scale, err_msg=name
            )


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
def test_the_serving_experts_logits_are_the_training_layers(gated):
    """``serve_routed_experts`` (its own layout: an expert without rows owns no
    tile, tiles of 16) through the same ``_expert_products`` as the training
    layer, softmax-routed top-8 of 64 with 16 held: the same params and tokens
    give the training layer's output."""
    act = "swiglu" if gated else "relu2"
    cfg = TransformerConfig(
        vocab_size=96, d_model=32, n_layers=1, n_heads=4, max_seq_len=64, dtype=jnp.float32, partition_params=False,
        moe_experts=64, moe_top_k=8, moe_intermediate_size=24, moe_experts_held=(16, 16), moe_expert_act=act,
    )
    layer = moe.RoutedExperts(num_experts=64, top_k=8, d_ff=24, held=(16, 16), dtype=jnp.float32, partition=False, expert_act=act)
    x = jax.random.normal(jax.random.key(6), (3, 20, 32), jnp.float32)
    p = jax.jit(layer.init)(jax.random.key(2), x)["params"]
    assert ("w_gate" in p) == gated
    got, (held, hit) = jax.jit(lambda p, x: moe.serve_routed_experts(cfg, p, x))(p, x)
    want = jax.jit(lambda p, x: layer.apply({"params": p}, x)[0])(p, x)
    assert 0 < int(hit) <= 16 and int(held) >= int(hit) and float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


def test_the_rows_weights_are_the_gathers_bits_on_either_side_of_the_line(monkeypatch):
    """``_row_weights`` takes its compares up to ``_COMPARE_TOKENS`` tokens and
    one gather a row past them: both sides of the line at one shape."""
    tokens, k, first, count = 300, 3, 0, 6
    picks = jnp.argsort(-jax.random.uniform(jax.random.key(0), (tokens, 8)), axis=1)[:, :k].astype(jnp.int32)
    weights = jax.random.uniform(jax.random.key(1), (tokens, k), jnp.float32, 0.1, 1.0)
    rows = moe._sorted_rows(picks, first, count)
    told = (weights, rows.row_pick, rows.row_live, rows.pick_row, rows.pick_held, rows.layout.tile)
    want = row_weights_by_gather(weights, rows.row_pick, rows.row_live)
    assert tokens <= moe._COMPARE_TOKENS and "gather" not in str(jax.make_jaxpr(lambda: moe._row_weights(*told))())
    np.testing.assert_array_equal(np.asarray(moe._row_weights(*told)), np.asarray(want))
    monkeypatch.setattr(moe, "_COMPARE_TOKENS", tokens - 1)
    assert "gather" in str(jax.make_jaxpr(lambda: moe._row_weights(*told))())
    np.testing.assert_array_equal(np.asarray(moe._row_weights(*told)), np.asarray(want))


@pytest.mark.parametrize("idle", [False, True], ids=["every lane live", "idle lanes"])
def test_the_serving_experts_at_k_22_in_a_latent_are_the_same_bits_under_the_counted_layout(idle, monkeypatch):
    """``serve_routed_experts`` at Nemotron-3's form (top-22 of 64 sigmoid-routed
    two-matrix experts in a latent, 32 held, a shared expert: tests/test_mixer_block.py's
    sizes at the cell's k) through the counted layout and through the argsort form."""
    cfg = TransformerConfig(
        vocab_size=96, d_model=64, n_layers=1, n_heads=4, max_seq_len=64, dtype=jnp.float32, partition_params=False,
        moe_experts=64, moe_top_k=22, moe_intermediate_size=24, moe_experts_held=(16, 32), moe_router="sigmoid_grouped",
        moe_routed_scaling=5.0, moe_shared_experts=1, moe_shared_intermediate_size=40, moe_expert_act="relu2", moe_latent_size=32,
    )
    layer = moe.RoutedExperts(
        num_experts=64, top_k=22, d_ff=24, held=(16, 32), dtype=jnp.float32, partition=False, router_kind="sigmoid_grouped",
        routed_scaling=5.0, shared_experts=1, shared_d_ff=40, expert_act="relu2", latent_size=32,
    )
    x = jax.random.normal(jax.random.key(5), (4, 10, 64), jnp.float32)
    p = jax.jit(layer.init)(jax.random.key(1), x)["params"]
    live = jnp.ones((4, 10), bool).at[1].set(False).at[3, 4:].set(False) if idle else None
    got, counted = jax.jit(lambda p, x: moe.serve_routed_experts(cfg, p, x, live))(p, x)
    monkeypatch.setattr(moe, "_sorted_rows", sorted_rows_by_argsort)
    want, want_counted = jax.jit(lambda p, x: moe.serve_routed_experts(cfg, p, x, live))(p, x)
    assert int(counted[0]) == int(want_counted[0]) > 0 and int(counted[1]) == int(want_counted[1])
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the training layer states the same experts: same params, same tokens, close to the serving forward
    if not idle:
        trained = jax.jit(lambda p, x: layer.apply({"params": p}, x)[0])(p, x)
        np.testing.assert_allclose(np.asarray(trained), np.asarray(got), rtol=2e-5, atol=2e-6)


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
