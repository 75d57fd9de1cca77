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
from tests.model_cases import dense_experts as _dense_experts, routed_layer as _layer

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
