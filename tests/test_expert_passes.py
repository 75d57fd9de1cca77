"""What lies between the expert layer's grouped products
(``ops/expert_rows.py over_live_tiles`` under ``models/moe.py _hidden_rows`` and
``_hidden_grads``, and ``gmm(..., add=)``) against the XLA expressions it
replaced, kept here as the oracle; and that the layer holds no such pass over
the whole buffer any more.  The row movements are tests/test_expert_rows.py,
the layer tests/test_routed_experts.py.  CPU, small sizes; Pallas kernels in
interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from determined_tpu.models import moe
from determined_tpu.models.moe import RoutedExperts
from determined_tpu.ops import expert_rows, grouped_matmul as gm

# ---------------------------------------------------------------------------
# the oracle: the passes as XLA ran them over ALL rows until PR 63
# ---------------------------------------------------------------------------


def _hidden(gate, up, scale):
    if gate is None:
        act = jnp.square(jax.nn.relu(up.astype(jnp.float32)))
    else:
        act = nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    return act, (act * scale[:, None]).astype(up.dtype)


def _hidden_grads(d_hidden, gate, up, scale):
    dt, (act, _) = up.dtype, _hidden(gate, up, scale)
    d_hidden = d_hidden.astype(jnp.float32)
    d_scale = jnp.sum(d_hidden * act, axis=-1)
    d_act = d_hidden * scale[:, None]
    if gate is None:
        return (d_act * 2.0 * jax.nn.relu(up.astype(jnp.float32))).astype(dt), d_scale
    g32, u32 = gate.astype(jnp.float32), up.astype(jnp.float32)
    sig = jax.nn.sigmoid(g32)
    return (d_act * u32 * sig * (1.0 + g32 * (1.0 - sig))).astype(dt), (d_act * g32 * sig).astype(dt), d_scale


#: (tile, tiles): a buffer smaller than one grid step (80 rows, no multiple of 128 either), one of a step and a
#: ragged second (1,280 = 1,024 + 256 rows), the training tile in two steps (1,536 = 1,024 + 512) and in six, whose
#: count divides neither the rows nor a result's size (5,632 = 5 x 1,024 + 512: a walk's buffer, tests/test_dsa_serving.py)
BUFFERS = {
    "tile 16, 80 rows": (16, 5), "tile 16, 1280 rows": (16, 80), "tile 256, 1536 rows": (256, 6), "tile 256, 5632 rows": (256, 22),
}
LIVE = ("one live tile", "a live tile with no owned row", "every tile live")
WIDTH = 40


def _layout(buffer, live):
    tile, tiles = BUFFERS[buffer]
    count = {"one live tile": 1, "a live tile with no owned row": tiles - 2, "every tile live": tiles}[live]
    none = jnp.zeros((1,), jnp.int32)   # the passes ask a layout for its live tiles and nothing else
    return gm.TileLayout(none, jnp.zeros((tiles,), jnp.int32), jnp.asarray([count], jnp.int32), tiles * tile, tile), count


def _operands(layout, live, dtype, gated):
    keys = jax.random.split(jax.random.key(layout.rows), 4)
    d_hidden, gate, up = (jax.random.normal(k, (layout.rows, WIDTH)).astype(dtype) for k in keys[:3])
    scale = jax.random.uniform(keys[3], (layout.rows,), jnp.float32, 0.1, 1.0)
    if live == "a live tile with no owned row":
        # as the row movements leave it: zero rows, so zero products, and no routing weight
        owned = (jnp.arange(layout.rows) // layout.tile != 1)
        d_hidden, gate, up = (jnp.where(owned[:, None], a, jnp.zeros((), dtype)) for a in (d_hidden, gate, up))
        scale = jnp.where(owned, scale, 0.0)
    return d_hidden, gate if gated else None, up, scale


def _close(got, want, dtype):
    # the same float32 expressions on the same values, rounded once: a last place of the compute dtype at most
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-6 if dtype == jnp.float32 else 2 ** -7, atol=1e-6
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
@pytest.mark.parametrize("live", LIVE)
@pytest.mark.parametrize("buffer", list(BUFFERS))
def test_hidden_and_its_derivative_over_live_tiles_are_xlas_on_every_row_of_a_live_tile(buffer, live, gated, dtype):
    layout, count = _layout(buffer, live)
    d_hidden, gate, up, scale = _operands(layout, live, dtype, gated)
    rows = count * layout.tile
    hidden, = jax.jit(lambda g, u, s: expert_rows.over_live_tiles(moe._hidden_rows, (g, u), s, layout))(gate, up, scale)
    assert hidden.shape == (layout.rows, WIDTH) and hidden.dtype == dtype
    _close(hidden[:rows], _hidden(gate, up, scale)[1][:rows], dtype)
    got = jax.jit(lambda d, g, u, s: expert_rows.over_live_tiles(moe._hidden_grads, (d, g, u), s, layout, sums=True))(
        d_hidden, gate, up, scale
    )
    want = _hidden_grads(d_hidden, gate, up, scale)
    assert len(got) == len(want) == 2 + gated and got[-1].shape == (layout.rows,) and got[-1].dtype == jnp.float32
    for g, w in zip(got[:-1], want[:-1]):
        assert g.shape == (layout.rows, WIDTH) and g.dtype == dtype
        _close(g[:rows], w[:rows], dtype)
    np.testing.assert_allclose(got[-1][:rows], want[-1][:rows], rtol=1e-5, atol=1e-5)   # float32 sums whatever the dtype
    if live == "a live tile with no owned row":
        unowned = slice(layout.tile, 2 * layout.tile)
        assert all(not np.asarray(a[unowned], np.float32).any() for a in (hidden, *got))   # tgmm's zero operand
    # a dead tile is neither computed nor written: its rows hold what the interpreter laid out, one value, where
    # live rows hold arithmetic (the rows' sums lie 1,024 a grid step along the lanes: past the last live STEP)
    for a in (hidden, *got):
        dead = a[rows:] if a.ndim == 2 else a[-(-rows // 1024) * 1024:]
        assert np.unique(np.asarray(dead, np.float32)).size <= 1 < np.unique(np.asarray(a[:rows], np.float32)).size


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("live", ["one live tile", "every tile live"])
def test_a_product_added_into_another_is_their_sum_and_leaves_dead_tiles_as_they_were(live, dtype):
    """``gmm(..., add=first)``: the rows' gradient of a gated expert, the
    second product landing on the first where it lies."""
    tile, tiles, k, n = 8, 6, 12, 20
    count = 2 if live == "one live tile" else tiles
    # the product asks a layout for its tiles' groups and its live tiles: dead tiles name the last live tile's group
    groups = jnp.asarray([0, 2, 2, 2, 2, 2] if live == "one live tile" else [0, 0, 1, 2, 3, 3], jnp.int32)
    layout = gm.TileLayout(jnp.zeros((4,), jnp.int32), groups, jnp.asarray([count], jnp.int32), tiles * tile, tile)
    keys = jax.random.split(jax.random.key(4), 4)
    a, b = (jax.random.normal(key, (layout.rows, k)).astype(dtype) for key in keys[:2])
    wa, wb = (jax.random.normal(key, (4, n, k)).astype(dtype) for key in keys[2:])
    sentinel = jnp.full((layout.rows, n), 7.0, dtype)
    got = jax.jit(lambda a, b, wa, wb, first: (
        gm.gmm(b, wb, layout, transpose_rhs=True, add=gm.gmm(a, wa, layout, transpose_rhs=True)),
        gm.gmm(b, wb, layout, transpose_rhs=True, add=first),
    ))(a, b, wa, wb, sentinel)
    want = gm.gmm(a, wa, layout, transpose_rhs=True).astype(jnp.float32) + gm.gmm(b, wb, layout, transpose_rhs=True).astype(jnp.float32)
    rows = count * tile
    np.testing.assert_allclose(
        np.asarray(got[0][:rows], np.float32), np.asarray(want[:rows]),
        # the second product joins the sum in float32, where XLA's add took it rounded: a last place of ITS size
        **(dict(rtol=1e-6, atol=1e-5) if dtype == jnp.float32 else dict(rtol=2 ** -6, atol=0.06)),
    )
    assert got[0].dtype == dtype and (np.asarray(got[1][rows:], np.float32) == 7.0).all()   # not read, not written
    assert rows == layout.rows or (np.asarray(got[1][:rows], np.float32) != 7.0).any()


# ---------------------------------------------------------------------------
# the layer holds no pass over the whole buffer
# ---------------------------------------------------------------------------


def _eqns_outside_kernels(jaxpr):
    """Every equation of a jaxpr and of its nested jaxprs, a kernel's own body
    (values in VMEM, a tile at a time) left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _eqns_outside_kernels(sub)


@pytest.mark.parametrize("expert_act", ["swiglu", "relu2"])
def test_no_xla_operation_runs_over_the_buffers_rows_in_the_layer_or_its_gradient(expert_act):
    """Between two grouped products nothing takes a static extent any more: at
    (tokens 512, k 8, d 128, d_ff 32), 2 of 16 experts held, every array of
    ``[1,536 rows, d or d_ff]`` (or ``[6 tiles, 256, .]``) in the forward or the
    backward pass is a kernel's result (or its free reshape), none an XLA
    operation's: the elementwise passes cannot come back unnoticed."""
    x = jax.random.normal(jax.random.key(0), (1, 512, 128))
    layer = RoutedExperts(
        num_experts=16, top_k=8, d_ff=32, held=(0, 2), dtype=jnp.float32, partition=False, expert_act=expert_act
    )
    params = layer.init(jax.random.key(1), x)["params"]
    rows, tile = gm.buffer_rows(512 * 2, 2, 256), 256

    def loss(p, x):
        y, aux = layer.apply({"params": p}, x)
        return jnp.sum(jnp.sin(y)) + aux

    gated = expert_act == "swiglu"
    # forward: hidden.  Its gradient: hidden twice (forward, and again for tgmm) and the derivative's one or two
    for fn, passes in ((lambda p, x: layer.apply({"params": p}, x), 1), (jax.grad(loss, (0, 1)), 3 + gated)):
        made = {}
        for eqn in _eqns_outside_kernels(jax.make_jaxpr(fn)(params, x).jaxpr):
            for v in eqn.outvars:
                shape = getattr(v.aval, "shape", ())
                if shape in [(rows, w) for w in (32, 128)] + [(rows // tile, tile, w) for w in (32, 128)]:
                    made.setdefault(eqn.primitive.name, []).append(shape)
        # the kernels (row movements, products, the passes between them), what wraps them, a free reshape: nothing else
        assert set(made) <= {"pallas_call", "pjit", "jit", "reshape", "custom_vjp_call", "custom_vjp_call_jaxpr"}, made
        assert sum(s == (rows // tile, tile, 32) for s in made["pallas_call"]) == passes, made
        assert (rows, 32) in made["pallas_call"] and (rows // tile, tile, 128) in made["pallas_call"]   # a product, a row movement
