"""Mixture-of-Experts layer with expert parallelism, TPU-first.

The reference has NO MoE/expert-parallel code (SURVEY §2.10: absent —
DeepSpeed passthrough at most); this is a capability the TPU build adds.
Design follows the GShard/Switch pjit formulation rather than explicit
all-to-all plumbing: expert weights are stacked ``[experts, ...]`` tensors
whose leading dim carries the ``"expert"`` logical axis, and token routing
is expressed as dense dispatch/combine einsums — under ``pjit`` over a mesh
with an ``expert`` axis, XLA partitions the expert dim and inserts the
all-to-all collectives itself (the "let the compiler place collectives"
recipe).  Top-2 gating with capacity limiting and the standard
load-balancing auxiliary loss (Switch Transformer eq. 4).

Shapes (g = tokens per group, e = experts, c = capacity, d/f = model/ff):
  gates      [g, e]      softmax router probabilities
  dispatch   [g, e, c]   0/1 token->expert-slot assignment
  combine    [g, e, c]   dispatch * gate prob (weighted un-routing)
  x          [g, d]  ->  expert inputs  [e, c, d]   (einsum with dispatch)
  expert ffn [e, c, d] @ w1[e, d, f] -> silu -> @ w2[e, f, d]
  y          [g, d]      (einsum with combine)
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn


def _top2_dispatch(
    gates: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Build dispatch/combine tensors for top-2 routing with capacity.

    Tokens beyond an expert's capacity are dropped (standard GShard
    behavior); the combine weights renormalize over the surviving routes.
    Returns (dispatch [g,e,c], combine [g,e,c], aux_loss scalar).
    """
    g, e = gates.shape
    # top-1 and top-2 expert per token
    idx1 = jnp.argmax(gates, axis=-1)                          # [g]
    mask1 = jax.nn.one_hot(idx1, e, dtype=gates.dtype)         # [g, e]
    gates2 = gates * (1.0 - mask1)
    idx2 = jnp.argmax(gates2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, e, dtype=gates.dtype)

    # load-balancing aux loss (Switch eq. 4): e * sum_e(fraction_tokens_e
    # * mean_prob_e) — equals 1 at perfect balance regardless of e, so the
    # aux weight means the same thing at any expert count
    density = mask1.mean(axis=0)                               # [e]
    density_proxy = gates.mean(axis=0)                         # [e]
    aux = (density * density_proxy).sum() * e

    # position of each token in its expert's queue (top-1 first)
    pos1 = (jnp.cumsum(mask1, axis=0) - 1.0) * mask1           # [g, e]
    used1 = jnp.sum(mask1, axis=0, keepdims=True)              # [1, e]
    pos2 = ((jnp.cumsum(mask2, axis=0) - 1.0) + used1) * mask2
    keep1 = mask1 * (pos1 < capacity)
    keep2 = mask2 * (pos2 < capacity)

    p1 = (gates * keep1).sum(axis=-1)                          # [g]
    p2 = (gates * keep2).sum(axis=-1)
    denom = jnp.maximum(p1 + p2, 1e-9)
    w1 = p1 / denom
    w2 = p2 / denom

    def slots(keep, pos):
        slot = jax.nn.one_hot(
            (pos * keep).sum(axis=-1).astype(jnp.int32), capacity,
            dtype=gates.dtype,
        )                                                       # [g, c]
        return keep[:, :, None] * slot[:, None, :]              # [g, e, c]

    d1, d2 = slots(keep1, pos1), slots(keep2, pos2)
    dispatch = d1 + d2
    combine = d1 * w1[:, None, None] + d2 * w2[:, None, None]
    return dispatch, combine, aux


class MoE(nn.Module):
    """Top-2 expert-parallel SwiGLU FFN (drop-in for a dense MLP block).

    Tokens route within fixed-size GROUPS (GShard's formulation): dispatch
    and combine are ``[groups, group_size, e, c]`` with ``c ~
    2*group_size/e``, so their size is linear in the token count —
    grouping capacity over the whole flattened batch would make them
    quadratic and OOM real configs (64k tokens x 8 experts would need
    ~1e10-element dispatch tensors).
    """

    num_experts: int
    d_ff: int
    capacity_factor: float = 1.25
    group_size: int = 4096
    dtype: Any = jnp.bfloat16
    partition: bool = True  # False under manual-SPMD pipeline stages
    # Manual-SPMD expert parallelism (inside pipeline-stage shard_map):
    # expert weights arrive sharded over this axis (only e/n local experts
    # per device); routing/gating stays replicated, each device computes
    # the FFN for ITS experts against the full token set, and the combine
    # is a psum over the axis — the intra-stage expert "all-to-all".
    expert_axis_name: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """[batch, seq, d] -> ([batch, seq, d], aux_loss)."""
        from determined_tpu.models.transformer import _maybe_partition

        b, s, d = x.shape
        g = b * s
        e = self.num_experts
        # pad up to a group multiple rather than shrinking groups: a
        # divisor fallback can degenerate to tiny groups (prime token
        # counts), collapsing capacity and dropping every top-2 route.
        # Padded (zero) tokens route uniformly and consume at most the pad
        # fraction of capacity; their outputs are sliced away.
        grp = min(self.group_size, g)
        pad = (-g) % grp
        n_groups = (g + pad) // grp
        capacity = max(int(self.capacity_factor * grp * 2 / e), 1)

        xf = x.reshape(g, d)
        if pad:
            xf = jnp.concatenate([xf, jnp.zeros((pad, d), x.dtype)], axis=0)
        xg = xf.reshape(n_groups, grp, d)
        router = self.param(
            "router",
            _maybe_partition(
                self.partition, nn.initializers.lecun_normal(), ("embed", "expert")
            ),
            (d, e),
            jnp.float32,
        )
        # routing decisions in f32: bf16 softmax ties misroute tokens
        with jax.named_scope("moe.route"):
            gates = jax.nn.softmax(jnp.einsum("ngd,de->nge", xg.astype(jnp.float32), router))
            dispatch, combine, aux = jax.vmap(lambda gate: _top2_dispatch(gate, capacity))(gates)
            aux = aux.mean()

        # under manual SPMD the params hold only this device's experts
        e_param = e
        my_expert0 = None
        if self.expert_axis_name is not None:
            n_exp = jax.lax.axis_size(self.expert_axis_name)
            if e % n_exp:
                raise ValueError(f"num_experts={e} not divisible by axis {n_exp}")
            e_param = e // n_exp
            my_expert0 = jax.lax.axis_index(self.expert_axis_name) * e_param

        def expert_param(name, shape, logical):
            return self.param(
                name,
                _maybe_partition(
                    self.partition, nn.initializers.lecun_normal(), logical
                ),
                shape,
                jnp.float32,
            )

        w_in = expert_param("w_in", (e_param, d, self.d_ff), ("expert", "embed", "mlp"))
        w_gate = expert_param("w_gate", (e_param, d, self.d_ff), ("expert", "embed", "mlp"))
        w_out = expert_param("w_out", (e_param, self.d_ff, d), ("expert", "mlp", "embed"))

        if my_expert0 is not None:
            # keep only the dispatch/combine slices for MY experts; the
            # cross-device combine is the psum below
            dispatch = jax.lax.dynamic_slice_in_dim(dispatch, my_expert0, e_param, axis=2)
            combine = jax.lax.dynamic_slice_in_dim(combine, my_expert0, e_param, axis=2)

        cd = self.dtype
        # dispatch: [n,g,e,c] x [n,g,d] -> [n,e,c,d]; under an
        # "expert"-sharded mesh axis XLA turns these einsums into the
        # all-to-alls
        with jax.named_scope("moe.dispatch"):
            expert_in = jnp.einsum(
                "ngec,ngd->necd", dispatch.astype(cd), xg.astype(cd)
            )
        with jax.named_scope("moe.experts"):
            h = jnp.einsum("necd,edf->necf", expert_in, w_in.astype(cd))
            gate = jnp.einsum("necd,edf->necf", expert_in, w_gate.astype(cd))
            h = nn.silu(gate) * h
            expert_out = jnp.einsum("necf,efd->necd", h, w_out.astype(cd))
        with jax.named_scope("moe.combine"):
            y = jnp.einsum("ngec,necd->ngd", combine.astype(cd), expert_out)
            if self.expert_axis_name is not None:
                y = jax.lax.psum(y, self.expert_axis_name)
            y = y.reshape(n_groups * grp, d)[:g]
            return y.reshape(b, s, d), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Dropless top-k routing over the experts one device holds
# ---------------------------------------------------------------------------
#
# The layer above builds dense [g, e, c] dispatch tensors: fine for a few
# experts and top-2, unusable at 64 experts and top-8 (one group of 4,096
# tokens would need a 168 M-element tensor and more FLOPs to dispatch than
# to run the experts), and it drops tokens past a capacity.  The layer below
# routes over ALL experts, keeps the picks that land on the experts it holds,
# sorts those rows by expert into a tile-aligned buffer sized for the worst
# case, runs gate/up/down as grouped products (ops/grouped_matmul.py) and
# sums the weighted rows back into their tokens (one custom VJP,
# ``_held_experts``: the backward pass keeps the two hidden products only).
# Both directions of the dispatch (ops/expert_rows.py) touch the rows a held
# pick owns and nothing else: the buffer is worst-case, the work follows the
# picks that landed here (2 of a token's 8 where a chip holds 16 of 64
# experts), and no ``[tokens x k, d]`` array exists.  No capacity, no dropped
# token, and nothing stands in for experts that live elsewhere: what they
# would add is simply not added here (under ``expert_axis_name`` the ``psum``
# adds it).


def _round_up_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


class SortedRows(NamedTuple):
    """Where the picks that land on a held expert lie in the buffer."""

    pick_held: jax.Array   # [T, k] whether a pick landed on a held expert
    pick_row: jax.Array    # [T, k] the row of a held pick (any other: the last row, never read)
    row_live: jax.Array    # [rows] whether a pick owns the row
    row_pick: jax.Array    # [rows] the flat pick (token * k + j) of an owned row
    tile_rows: jax.Array   # [tiles] rows of each tile that a pick owns: its first ones
    load: jax.Array        # [count] picks on each held expert
    layout: Any            # gm.TileLayout


def _owners_by_tile(place: jax.Array, flat: jax.Array, layout: Any) -> jax.Array:
    """``[tiles, tile]``: the flat pick that owns each row PLUS ONE, 0 where none
    does, a TILE at a time: a tile is one expert's, so its rows are compared with
    that expert's column of ``place [T, count]`` (a token's row on each expert,
    -1: not picked; ``flat``: its flat pick there): ``rows x T`` compares."""
    tile_place = jnp.take(place.T, layout.tile_group, axis=0)          # [tiles, T] whole rows, one a tile
    tile_flat = jnp.take(flat.T, layout.tile_group, axis=0)
    row = jnp.arange(layout.rows).reshape(-1, layout.tile)
    owns = tile_place[:, None, :] == row[:, :, None]                   # [tiles, tile, T] at most one token a row
    return jnp.sum(jnp.where(owns, tile_flat[:, None, :] + 1, 0), axis=-1)


def _owners_by_block(place: jax.Array, flat: jax.Array, before: jax.Array, layout: Any) -> jax.Array:
    """The same a BLOCK of ``tile`` tokens at a time, for few experts and many
    tokens: a block's picks on one expert are at most ``tile`` consecutive rows,
    which lie in TWO tiles, the first of them known from ``before [T, count]``
    (an expert's picks before a token).  So a (block, expert) pair compares its
    tokens with two tiles' rows (``T x count x 2 tile`` in all), and a tile
    collects the pairs that name it (``tiles x pairs x 2 tile``: a row is
    written by one pair at most)."""
    tokens, count = place.shape
    tile, tiles = layout.tile, layout.rows // layout.tile
    blocks = -(-tokens // tile)
    pad = ((0, blocks * tile - tokens), (0, 0))
    by_block = lambda a: jnp.pad(a, pad, constant_values=-1).T.reshape(count, blocks, tile)          # noqa: E731
    first_tile = (layout.group_start[:, None] + jax.lax.slice(before, (0, 0), before.shape, (tile, 1)).T) // tile   # [count, blocks]
    at = by_block(place) - (first_tile * tile)[:, :, None]             # a token's row in its pair's two tiles; < 0: no pick
    owns = at[:, :, None, :] == jnp.arange(2 * tile)[None, None, :, None]                            # [count, blocks, 2 tile, tile]
    pair_pick = jnp.sum(jnp.where(owns, by_block(flat)[:, :, None, :] + 1, 0), axis=-1).reshape(-1, tile)
    pair_tile = (first_tile[:, :, None] + jnp.arange(2)).reshape(-1)                                 # [pairs x 2]
    named = pair_tile[None, :] == jnp.arange(tiles)[:, None]                                         # [tiles, pairs x 2]
    return jnp.sum(jnp.where(named[:, :, None], pair_pick[None], 0), axis=1)


def _sorted_rows(picks: jax.Array, first: Any, count: int, *, serving: bool = False) -> SortedRows:
    """``picks [T, k]`` (experts, distinct a token) -> the held ones sorted by
    expert into a tile-aligned buffer sized for the worst case.  ``serving``
    (no backward pass): an expert without rows owns no tile, so its matrices
    are not read, and a tile is at least the 16 rows a bf16 sublane tile packs.

    Counted, not sorted: a token picks an expert at most once, so an expert's
    rows are its tokens in token order and a pick's place among them is a
    running count (``[T, count]``); which pick owns a row is found by comparing
    rows with those counts, a tile or a block of tokens at a time, whichever
    the static shapes make fewer compares (many experts and few tokens, a
    serving step: by tile; few experts and many tokens, a training step: by
    block).  No sort, and no gather with one index a pick or a row.  A row
    that no pick owns holds ``row_pick`` 0."""
    from determined_tpu.ops import grouped_matmul as gm

    tokens, k = picks.shape
    local = picks - first
    pick_held = (local >= 0) & (local < count)
    on = local[:, :, None] == jnp.arange(count)                        # [T, k, count] a pick's held expert (none: not held)
    hit = jnp.any(on, axis=1)                                          # [T, count] whether the token picked it
    upto = jnp.cumsum(hit, axis=0, dtype=jnp.int32)                    # its picks up to and with this token's
    load = upto[-1]
    # a token picks a held expert at most min(k, count) times
    max_rows = tokens * min(k, count)
    tile = min(gm.DEFAULT_TILE, max(16 if serving else 8, _round_up_pow2(max_rows // count)))
    layout = gm.tile_layout(load, max_rows, tile, empty_groups_own_tile=not serving)
    place = jnp.where(hit, layout.group_start[None, :] + upto - 1, -1)  # [T, count] the row of the token's pick on an expert
    pick_row = jnp.where(
        pick_held, jnp.sum(jnp.where(on, place[:, None, :], 0), axis=-1), layout.rows - 1   # not held: never read
    ).astype(jnp.int32)
    flat = jnp.arange(tokens)[:, None] * k + jnp.sum(jnp.where(on, jnp.arange(k)[None, :, None], 0), axis=1)
    pairs = -(-tokens // tile) * count
    if pairs * 2 * tile * (tile + layout.rows // tile) < layout.rows * tokens:
        owner = _owners_by_block(place, flat, upto - hit, layout)
    else:
        owner = _owners_by_tile(place, flat, layout)
    row_live = owner > 0
    row_pick = jnp.maximum(owner - 1, 0).astype(jnp.int32)
    tile_rows = jnp.sum(row_live, axis=1, dtype=jnp.int32)
    return SortedRows(pick_held, pick_row, row_live.reshape(-1), row_pick.reshape(-1), tile_rows, load, layout)


#: tokens up to which a row's weight is found by comparing the row's owner with every token: one scalar gather a
#: row costs a v5e ~7 ns whatever the sizes, a compare ~1 ps, so the two meet near 7,000 tokens (PERF.md, PR 60)
_COMPARE_TOKENS = 4096


def _row_weights(
    weights: jax.Array, row_pick: jax.Array, row_live: jax.Array, pick_row: jax.Array, pick_held: jax.Array, tile: int
) -> jax.Array:
    """The routing weight ``[T, k]`` of each row's pick, zero for rows no pick
    owns.  By compares, as the layout, while the tokens are few (a serving
    step): a tile is one expert's and a token picks it once, so a token brings
    ONE weight into a tile (``[tiles, T]``), and a row takes its owner's.
    Past ``_COMPARE_TOKENS`` (a training step) by one gather a row."""
    tokens, k = weights.shape
    if tokens > _COMPARE_TOKENS:
        return jnp.where(row_live, jnp.take(weights.reshape(-1), row_pick), 0.0)
    tiles = row_pick.shape[0] // tile
    here = pick_held[None] & ((pick_row // tile)[None] == jnp.arange(tiles)[:, None, None])   # [tiles, T, k]
    brought = jnp.sum(jnp.where(here, weights[None], 0.0), axis=-1)                           # [tiles, T]
    owner = jnp.where(row_live, row_pick // k, -1).reshape(tiles, tile)
    mine = owner[:, :, None] == jnp.arange(tokens)                                            # [tiles, tile, T]
    return jnp.sum(jnp.where(mine, brought[:, None, :], 0.0), axis=-1).reshape(-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(13, 14))
def _held_experts(
    x, w_gate, w_up, w_down, weights, row_pick, row_live, pick_row, pick_held, tile_rows,
    group_start, tile_group, live_tiles, rows, tile,
):
    """``y[t] = sum over t's held picks of weights[t, j] * W_down,e (silu(W_gate,e
    x[t]) * W_up,e x[t])``, float32, through the tile-aligned buffer that
    ``gm.TileLayout(group_start, tile_group, live_tiles, rows, tile)`` lays out
    (``tile_rows [tiles]``: the rows of each tile that a pick owns).  ``w_gate``
    None: the two-matrix expert, ``W_down,e relu(W_up,e x[t])^2``."""
    return _held_experts_fwd(
        x, w_gate, w_up, w_down, weights, row_pick, row_live, pick_row, pick_held, tile_rows,
        group_start, tile_group, live_tiles, rows, tile,
    )[0]


def _hidden(gate, up, scale):
    """(an expert's hidden values in float32: ``silu(gate) * up``, or
    ``relu(up)^2`` for the two-matrix expert, whose ``gate`` is None; the same
    times a row's routing weight ``scale [rows, 1]`` in the compute dtype: the
    down projection's input).  The ONE statement of the activation and of the
    routing weight's place: the kernels' bodies below call it on a tile."""
    if gate is None:
        act = jnp.square(jax.nn.relu(up.astype(jnp.float32)))
    else:
        act = nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    return act, (act * scale).astype(up.dtype)


def _hidden_rows(gate, up, scale):
    """The down projection's input alone, as a kernel's body returns it (``ops/expert_rows.py over_live_tiles``)."""
    return (_hidden(gate, up, scale)[1],)


def _hidden_grads(d_hidden, gate, up, scale):
    """:func:`_hidden`'s derivative, the one statement of it: ``d_hidden``, the
    gradient to the down projection's input -> (to ``gate`` unless it is None,
    to ``up``, to ``scale``: float32 ``[rows, 1]``)."""
    dt, d_hidden = up.dtype, d_hidden.astype(jnp.float32)
    d_scale = jnp.sum(d_hidden * _hidden(gate, up, scale)[0], axis=-1, keepdims=True)
    d_act = d_hidden * scale
    if gate is None:  # the two-matrix expert: relu(up)^2
        return (d_act * 2.0 * jax.nn.relu(up.astype(jnp.float32))).astype(dt), d_scale
    g32, u32 = gate.astype(jnp.float32), up.astype(jnp.float32)
    sig = jax.nn.sigmoid(g32)
    return (d_act * u32 * sig * (1.0 + g32 * (1.0 - sig))).astype(dt), (d_act * g32 * sig).astype(dt), d_scale


def _expert_products(mm, xr, w_gate, w_up, w_down, scale, layout):
    """The ONE statement of the held experts on their rows ``xr [rows, d]``,
    under the training layer and the serving forward alike: ``mm(lhs, rhs)`` is
    the grouped product each of them runs.  Returns (the rows' outputs with
    the routing weight inside, the hidden products a backward pass keeps)."""
    from determined_tpu.ops import expert_rows

    dt = xr.dtype
    gate = None if w_gate is None else mm(xr, w_gate.astype(dt))
    up = mm(xr, w_up.astype(dt))
    # the routing weight goes in before the down projection: the combine is then a plain sum
    hidden, = expert_rows.over_live_tiles(_hidden_rows, (gate, up), scale, layout)
    return mm(hidden, w_down.astype(dt)), gate, up


def _held_experts_fwd(
    x, w_gate, w_up, w_down, weights, row_pick, row_live, pick_row, pick_held, tile_rows,
    group_start, tile_group, live_tiles, rows, tile,
):
    from determined_tpu.ops import expert_rows, grouped_matmul as gm

    layout = gm.TileLayout(group_start, tile_group, live_tiles, rows, tile)
    row_token = row_pick // weights.shape[1]
    with jax.named_scope("moe.dispatch"):
        xr = expert_rows.rows_of_tokens(x, row_token, tile_rows, layout)
        scale = _row_weights(weights, row_pick, row_live, pick_row, pick_held, tile)
    with jax.named_scope("moe.experts"):
        out, gate, up = _expert_products(lambda lhs, rhs: gm.gmm(lhs, rhs, layout), xr, w_gate, w_up, w_down, scale, layout)
    with jax.named_scope("moe.combine"):
        y = expert_rows.tokens_of_rows(out, row_token, tile_rows, layout, x.shape[0])
    # kept for the backward pass: the two hidden products.  The rows are
    # gathered again there and silu(gate) * up is an elementwise pass: no
    # matrix product is recomputed, and a layer keeps rows x 2 d_ff, not
    # rows x (2 d_model + 3 d_ff)
    return y, (x, w_gate, w_up, w_down, weights, row_pick, row_live, pick_row, pick_held, tile_rows,
               group_start, tile_group, live_tiles, gate, up)


def _held_experts_bwd(rows, tile, res, d_y):
    from determined_tpu.ops import expert_rows, grouped_matmul as gm

    (x, w_gate, w_up, w_down, weights, row_pick, row_live, pick_row, pick_held, tile_rows,
     group_start, tile_group, live_tiles, gate, up) = res
    layout = gm.TileLayout(group_start, tile_group, live_tiles, rows, tile)
    dt, count = x.dtype, w_up.shape[0]
    row_token = row_pick // weights.shape[1]
    with jax.named_scope("moe.combine"):
        d_out = expert_rows.rows_of_tokens(d_y.astype(dt), row_token, tile_rows, layout)
    with jax.named_scope("moe.dispatch"):
        xr = expert_rows.rows_of_tokens(x, row_token, tile_rows, layout)
        scale = _row_weights(weights, row_pick, row_live, pick_row, pick_held, tile)
    with jax.named_scope("moe.experts"):
        # nothing between two grouped products visits a dead tile either (ops/expert_rows.py over_live_tiles)
        hidden, = expert_rows.over_live_tiles(_hidden_rows, (gate, up), scale, layout)
        d_w_down = gm.tgmm(hidden, d_out, layout, count).astype(w_down.dtype)
        d_hidden = gm.gmm(d_out, w_down.astype(dt), layout, transpose_rhs=True)
        grads = expert_rows.over_live_tiles(_hidden_grads, (d_hidden, gate, up), scale, layout, sums=True)
        d_up, d_scale = grads[-2:]
        d_w_gate = d_xr = None
        if gate is not None:  # the gated expert.  Its product to the rows first: the other is added into it where it lies
            d_w_gate = gm.tgmm(xr, grads[0], layout, count).astype(w_gate.dtype)
            d_xr = gm.gmm(grads[0], w_gate.astype(dt), layout, transpose_rhs=True)
        d_w_up = gm.tgmm(xr, d_up, layout, count).astype(w_up.dtype)
        d_xr = gm.gmm(d_up, w_up.astype(dt), layout, transpose_rhs=True, add=d_xr)
    with jax.named_scope("moe.dispatch"):
        d_x = expert_rows.tokens_of_rows(d_xr, row_token, tile_rows, layout, x.shape[0]).astype(dt)
    with jax.named_scope("moe.combine"):
        d_weights = jnp.where(pick_held, jnp.take(d_scale, pick_row), 0.0).astype(weights.dtype)
    return (d_x, d_w_gate, d_w_up, d_w_down, d_weights) + (None,) * 8


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


class RoutedExperts(nn.Module):
    """Dropless softmax top-k SwiGLU experts, for the experts held here.

    The router scores all ``num_experts``; the ``top_k`` largest
    probabilities are renormalised to sum to one; ``y = sum over the picks
    that land on a held expert of w_e * W_down,e (silu(W_gate,e x) * W_up,e
    x)``.  ``held = (first, count)`` says which experts the parameters hold
    (absent: all of them, or this device's share of ``expert_axis_name``
    inside ``shard_map``, where the ``psum`` adds the other devices' picks).
    The auxiliary loss is Switch's (eq. 4) over all experts and all picks:
    ``num_experts * sum_e (share of picks on e) * (mean probability of e)``.

    One algorithm for every expert count and top-k; the tile of the grouped
    product follows the rows an expert can expect.  ``sow``s
    ``intermediates/picks [T, k]`` (the experts each token chose),
    ``intermediates/load [count]`` (picks that landed on each held expert),
    ``intermediates/live_rows`` (rows of the buffer in tiles a group owns: what
    the kernels touch, the held picks and each group's padding to a tile) and
    ``intermediates/buffer_rows`` (all its rows: the worst case, which costs
    memory and no time).

    Every router but ``"mlp"`` and ``"softmax_bias"`` renormalises its picks'
    weights to a constant sum: at ``top_k`` 1 the one weight is that constant, no
    gradient reaches the router through the experts, and the layer refuses the
    pair by name.  Under ``"softmax_bias"`` (:func:`route_softmax_bias`) the
    router has ``zero_experts`` more outputs than experts: a pick on one of
    those adds ``w x`` (:func:`_identity_part`) and is never ``held``, so it
    owns no row of the buffer.
    Under ``"mlp"`` the call takes the layer before's router state and returns
    ``(y, aux, state)``: :func:`route_mlp`.

    ``expert_act`` "relu2": an expert is ``W_down,e relu(W_up,e x)^2``, two
    matrices and no gate (Nemotron-H's).  ``latent_size``: the held experts read
    ``x W_latent_in`` and their weighted sum goes through ``W_latent_out``, two
    projections of ``d x latent_size`` all experts share (Nemotron-3's latent
    experts); the router and the shared experts (``shared_d_ff`` wide in all)
    still read the full-width ``x``.
    """

    num_experts: int
    top_k: int
    d_ff: int
    held: Any = None              # (first, count) | None
    dtype: Any = jnp.bfloat16
    partition: bool = True
    expert_axis_name: Any = None
    # "sigmoid_grouped": :func:`route_sigmoid_grouped` over ``router`` and the
    # selection bias ``router_bias`` in place of the softmax (no auxiliary
    # loss: the bias is what balances such a router); "sigmoid": the plain
    # form, :func:`route_sigmoid`, with no bias; "mlp": :func:`route_mlp`
    # over ``router_hidden`` values a token, top-1; ``shared_experts`` of
    # width ``d_ff`` each (``shared_w_*``) take every token beside the picks
    router_kind: str = "softmax"
    router_hidden: int = 0
    norm_eps: float = 1e-6        # of the "mlp" router's RMSNorm over its state
    n_group: int = 1
    topk_group: int = 1
    routed_scaling: float = 1.0
    shared_experts: int = 0
    # "mean": the shared experts' sum over their number (Cohere's "average")
    shared_combine: str = "sum"
    # what the shared experts add is multiplied by ``sigmoid(x . shared_gate)``, one learned vector of d_model
    shared_gate: bool = False
    # identity experts, the router's outputs ``num_experts ..``: a pick there adds
    # ``w x`` and owns no row (:func:`_identity_part`); under "softmax_bias"
    zero_experts: int = 0
    # "relu2": an expert (routed or shared) is TWO matrices, ``W_down relu(W_up x)^2``, and the layer has no
    # ``w_gate`` / ``shared_w_gate``.  latent_size: the routed experts' input and output width, between
    # ``w_latent_in`` [d, latent] and ``w_latent_out`` [latent, d] that all of them share (the router and the
    # shared experts read the full-width input).  shared_d_ff: the shared experts' whole width (None:
    # ``shared_experts * d_ff``)
    expert_act: str = "swiglu"
    latent_size: Any = None
    shared_d_ff: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, state: Any = None) -> Any:
        """[batch, seq, d] -> ([batch, seq, d], aux_loss); under the "mlp"
        router, with the layer before's router ``state`` [batch, seq,
        router_hidden] (None: there is none), -> (y, aux_loss, this layer's)."""
        from determined_tpu.models.transformer import _maybe_partition

        b, s, d = x.shape
        tokens, e, k = b * s, self.num_experts, self.top_k
        outputs = e + self.zero_experts  # the router's width: the real experts, then the identity experts
        mlp = self.router_kind == "mlp"
        if k == 1 and not mlp:
            raise ValueError(
                f"router_kind={self.router_kind!r} with top_k=1 renormalises the one pick's weight to a "
                "constant, so the router gets no gradient from the experts: a top-1 layer takes router_kind='mlp'"
            )
        if mlp and (k != 1 or self.router_hidden < 1 or self.shared_experts):
            raise ValueError("router_kind='mlp' is top-1 over router_hidden >= 1 values a token, with no shared expert")
        if self.expert_axis_name is not None:
            n = jax.lax.axis_size(self.expert_axis_name)
            if self.held is not None or e % n:
                raise ValueError(
                    f"experts over axis {self.expert_axis_name!r}: {e} experts must "
                    f"divide by its size {n}, and `held` is the axis's to say"
                )
            count = e // n
            first = jax.lax.axis_index(self.expert_axis_name) * count
        else:
            first, count = self.held if self.held is not None else (0, e)

        def param(name, shape, logical):
            init = _maybe_partition(self.partition, nn.initializers.lecun_normal(), logical)
            return self.param(name, init, shape, self.param_dtype)

        if mlp:
            p = {
                name: self.param(name, _maybe_partition(self.partition, init, logical), shape, self.param_dtype)
                for name, (shape, logical, init) in _mlp_router_shapes(d, self.router_hidden, e).items()
            }
        else:
            router = param("router", (d, outputs), ("embed", None))
            p = {"router": router}
        gated = self.expert_act != "relu2"
        d_in = self.latent_size or d  # the width the routed experts work in
        w_gate = param("w_gate", (count, d_in, self.d_ff), ("expert", "embed", "mlp")) if gated else None
        w_up = param("w_up", (count, d_in, self.d_ff), ("expert", "embed", "mlp"))
        w_down = param("w_down", (count, self.d_ff, d_in), ("expert", "mlp", "embed"))
        if self.latent_size:
            p["w_latent_in"] = param("w_latent_in", (d, d_in), ("embed", None))
            p["w_latent_out"] = param("w_latent_out", (d_in, d), (None, "embed"))

        if mlp or self.router_kind in ("sigmoid_grouped", "softmax_bias"):
            # a fresh bias is small against the scores it is added to: 0.01 beside a sigmoid's 0.5 or a few experts'
            # probabilities; a softmax over hundreds of outputs scores 1 / outputs on average, and a bias of 0.01
            # would pick the same outputs for every token whatever the router says: a quarter of the mean score
            spread = 0.25 / outputs if self.router_kind == "softmax_bias" else 0.01
            p["router_bias"] = self.param("router_bias", nn.initializers.normal(spread), (outputs,), jnp.float32)
        if self.shared_experts:
            wide = self.shared_d_ff or self.shared_experts * self.d_ff
            if gated:
                p["shared_w_gate"] = param("shared_w_gate", (d, wide), ("embed", "mlp"))
            p["shared_w_up"] = param("shared_w_up", (d, wide), ("embed", "mlp"))
            p["shared_w_down"] = param("shared_w_down", (wide, d), ("mlp", "embed"))
            if self.shared_gate:
                init = _maybe_partition(self.partition, nn.initializers.normal(d ** -0.5), ("embed",))
                p["shared_gate"] = self.param("shared_gate", init, (d,), self.param_dtype)

        xf = x.reshape(tokens, d)
        with jax.named_scope("moe.route"):
            if mlp:
                probs, state = route_mlp(
                    p, xf.astype(self.dtype), None if state is None else state.reshape(tokens, -1), self.norm_eps
                )
                picks = jnp.argmax(probs + p["router_bias"][None, :], axis=-1)[:, None]   # the bias picks and never weighs
                weights = jnp.take_along_axis(probs, picks, axis=1)                        # the pick's probability, as it is
                aux = _switch_aux(probs, picks, e)
                state = state.reshape(b, s, -1)
                self.sow("intermediates", "pick_weight", jnp.mean(weights))
            elif self.router_kind != "softmax":
                weights, picks = _route(
                    p, xf, kind=self.router_kind, top_k=k, n_group=self.n_group,
                    topk_group=self.topk_group, scaling=self.routed_scaling,
                )
                aux = jnp.zeros((), jnp.float32)
            else:
                # routing in float32: a bf16 softmax ties and misroutes tokens
                probs = jax.nn.softmax(xf.astype(jnp.float32) @ router, axis=-1)   # [T, E]
                top_p, picks = jax.lax.top_k(probs, k)                              # [T, k]
                weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
                aux = _switch_aux(probs, picks, e)
        self.sow("intermediates", "picks", picks)

        with jax.named_scope("moe.dispatch"):
            rows = _sorted_rows(picks, first, count)
        self.sow("intermediates", "load", rows.load)
        self.sow("intermediates", "live_rows", rows.layout.live_tiles[0] * rows.layout.tile)
        self.sow("intermediates", "buffer_rows", jnp.asarray(rows.layout.rows))

        xe = xf.astype(self.dtype)
        if self.latent_size:
            with jax.named_scope("moe.latent"):
                xe = xe @ p["w_latent_in"].astype(self.dtype)
        y = _held_experts(
            xe, w_gate, w_up, w_down, weights,
            rows.row_pick, rows.row_live, rows.pick_row, rows.pick_held, rows.tile_rows, *rows.layout,
        )
        if self.expert_axis_name is not None:
            with jax.named_scope("moe.combine"):  # in the latent, where there is one: the narrower payload
                y = jax.lax.psum(y, self.expert_axis_name)
        if self.latent_size:
            with jax.named_scope("moe.latent"):
                y = _latent_out(p, y, self.dtype)
        if self.zero_experts:
            with jax.named_scope("moe.identity"):
                y = y + _identity_part(weights, picks, e, outputs, xf)[0].astype(y.dtype)
        if self.shared_experts:
            with jax.named_scope("moe.shared"):
                y = y + _shared_experts(p, xf.astype(self.dtype), self.shared_experts, self.shared_combine).astype(y.dtype)
        y, aux = y.astype(x.dtype).reshape(b, s, d), aux.astype(jnp.float32)
        return (y, aux, state) if mlp else (y, aux)


def _switch_aux(probs: jax.Array, picks: jax.Array, experts: int) -> jax.Array:
    """Switch's load-balancing term (eq. 4) over all experts and all picks:
    ``experts * sum_e (share of the picks on e) * (mean probability of e)``."""
    share = jnp.sum(picks.reshape(-1, 1) == jnp.arange(experts)[None, :], axis=0, dtype=jnp.float32) / picks.size
    return experts * jnp.sum(share * jnp.mean(probs, axis=0))


def _mlp_router_shapes(d: int, hidden: int, experts: int) -> Any:
    """The "mlp" router's leaves: name -> (shape, logical axes, initialiser)."""
    kernel, zeros, ones = nn.initializers.lecun_normal(), nn.initializers.zeros, nn.initializers.ones
    return {
        "router_down": ((d, hidden), ("embed", None), kernel),
        "router_down_bias": ((hidden,), (None,), zeros),
        "router_mix": ((hidden,), (None,), ones),
        "router_norm": ((hidden,), (None,), ones),
        "router_w1": ((hidden, hidden), (None, None), kernel),
        "router_b1": ((hidden,), (None,), zeros),
        "router_w2": ((hidden, hidden), (None, None), kernel),
        "router_b2": ((hidden,), (None,), zeros),
        "router_w3": ((hidden, experts), (None, None), kernel),
    }


def route_mlp(p: Any, xf: jax.Array, before: Any, eps: float) -> Tuple[jax.Array, jax.Array]:
    """ZAYA1's router on ``xf [T, d]``: a state ``r = xf W_down + b`` of
    ``router_hidden`` values a token, to which the layer before's state
    ``before [T, hidden]`` (None: there is none) is added times ``router_mix``;
    the probabilities are ``softmax(W3 gelu(W2 gelu(W1 rmsnorm(r) + b1) + b2))``
    over all experts (the norm with ``eps``, the exact gelu).  Returns
    (probabilities ``[T, E]``, the state after its mix: what the next layer is
    handed), both float32.  The down-projection runs in ``xf``'s dtype with a
    float32 sum; everything after it in float32 at the highest matmul
    precision: a router that rounds its scores ties and misroutes tokens."""
    f32 = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    r = jnp.dot(xf, p["router_down"].astype(xf.dtype), preferred_element_type=jnp.float32) + f32("router_down_bias")
    if before is not None:
        r = r + f32("router_mix") * before.astype(jnp.float32)
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    h = r * jax.lax.rsqrt(jnp.mean(jnp.square(r), axis=-1, keepdims=True) + eps) * f32("router_norm")
    h = jax.nn.gelu(dot(h, f32("router_w1")) + f32("router_b1"), approximate=False)
    h = jax.nn.gelu(dot(h, f32("router_w2")) + f32("router_b2"), approximate=False)
    return jax.nn.softmax(dot(h, f32("router_w3")), axis=-1), r


def _two_largest_sum(values: jax.Array) -> jax.Array:
    """The sum of the two largest of ``values`` along the last axis, to the bit
    what ``sum(top_k(values, 2)[0])`` gives, by two ``max`` passes: the largest,
    then the largest of the rest, which is the largest again where it stands
    twice (``top_k`` leaves the second copy in).  A ``top_k`` over the last of
    three axes is a whole sort of every row on the chip."""
    largest = jnp.max(values, axis=-1)
    is_largest = values == largest[..., None]
    rest = jnp.max(jnp.where(is_largest, -jnp.inf, values), axis=-1)
    return largest + jnp.where(jnp.sum(is_largest, axis=-1) > 1, largest, rest)


def _among_largest(values: jax.Array, count: int) -> jax.Array:
    """Which of a row's ``values [T, n]`` are among its ``count`` largest,
    ``[T, n]`` bool with ``count`` set a row: those that fewer than ``count``
    others beat, where ``j`` beats ``i`` if it is larger, or equal and
    ``j < i``: ``top_k``'s own rule on a tie (the lower index first), by
    ``n x n`` compares and a count instead of a sort."""
    index = jnp.arange(values.shape[-1])
    theirs, mine = values[:, None, :], values[:, :, None]
    beats = (theirs > mine) | ((theirs == mine) & (index[None, None, :] < index[None, :, None]))
    return jnp.sum(beats, axis=-1) < count


def _picked(scores: jax.Array, picks: jax.Array) -> jax.Array:
    """``scores[t, picks[t, j]]`` as ``[T, k]``, to the bit what
    ``take_along_axis`` gives, by compares and a ``max`` over the experts: a
    gather of one index an element costs the chip ~10 ns an index
    (``_COMPARE_TOKENS``' note; 10.6 of a 28 us route at 128 lanes x 8 picks:
    PERF.md section 5 "PR 67"), the compares under 1 us.  A ``max`` and not a
    sum: XLA folds a sum over the experts into the sum over the picks that
    follows it and adds in another order."""
    hit = picks[:, :, None] == jnp.arange(scores.shape[-1])[None, None, :]
    return jnp.max(jnp.where(hit, scores[:, None, :], -jnp.inf), axis=-1)


def route_sigmoid_grouped(
    logits: jax.Array, bias: jax.Array, *, top_k: int, n_group: int, topk_group: int, scaling: float
) -> Tuple[jax.Array, jax.Array]:
    """The router DeepSeek-V3 publishes, on float32 ``logits [T, E]``: scores
    ``sigmoid(logits)``; selection by ``scores + bias`` (the bias selects and
    never weighs); a group of ``E / n_group`` consecutive experts scores the
    sum of its two largest selection values and the ``topk_group`` best groups
    stay; the ``top_k`` largest selection values inside them are the picks;
    ``weights = scores[picks] / (their sum + 1e-20) * scaling``.  Returns
    (weights [T, k] float32, picks [T, k]).  The selection is exact and
    stable on a tie (the lower index first, as ``top_k`` has it), and the
    groups are chosen without a sort; where every group stays (one group, as
    Nemotron-H and GLM-5.2 publish it) no group is scored at all."""
    tokens, e = logits.shape
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    select = scores + bias.astype(jnp.float32)[None, :]
    inside = select
    if topk_group < n_group:
        group_kept = _among_largest(_two_largest_sum(select.reshape(tokens, n_group, e // n_group)), topk_group)
        inside = jnp.where(jnp.repeat(group_kept, e // n_group, axis=1), select, -jnp.inf)
    _, picks = jax.lax.top_k(inside, top_k)
    top = _picked(scores, picks)
    return top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * scaling, picks


def route_sigmoid(logits: jax.Array, *, top_k: int) -> Tuple[jax.Array, jax.Array]:
    """The plain sigmoid router, on float32 ``logits [T, E]``: the ``top_k``
    largest ``sigmoid(logits)`` are the picks, their scores over their sum the
    weights.  Returns (weights [T, k] float32, picks [T, k])."""
    top, picks = jax.lax.top_k(jax.nn.sigmoid(logits.astype(jnp.float32)), top_k)
    return top / jnp.sum(top, axis=-1, keepdims=True), picks


def route_softmax_bias(logits: jax.Array, bias: jax.Array, *, top_k: int, scaling: float) -> Tuple[jax.Array, jax.Array]:
    """The router LongCat-Flash publishes, on float32 ``logits [T, E + Z]``
    (real experts, then identity experts): scores ``softmax(logits)`` over all
    outputs; the ``top_k`` largest ``scores + bias`` are the picks (the bias
    picks and never weighs); ``weights = scores[picks] * scaling``, NOT
    renormalised.  Returns (weights [T, k] float32, picks [T, k])."""
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, picks = jax.lax.top_k(scores + bias.astype(jnp.float32)[None, :], top_k)
    return jnp.take_along_axis(scores, picks, axis=1) * scaling, picks


def _identity_part(weights: jax.Array, picks: jax.Array, first: int, end: int, xf: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """What a token's picks on identity experts (the router's outputs ``first ..
    end - 1``) add: ``x`` times the sum of those picks' weights, float32 ``[T,
    d]``; and which picks those are ``[T, k]``.  No row of any buffer, no
    matrix: the experts that cost nothing."""
    zero = (picks >= first) & (picks < end)
    weight = jnp.sum(jnp.where(zero, weights, 0.0), axis=-1)
    return weight[:, None] * xf.astype(jnp.float32), zero


def _route(
    p: Any, xf: jax.Array, *, kind: str, top_k: int, n_group: int, topk_group: int, scaling: float
) -> Tuple[jax.Array, jax.Array]:
    """(weights [T, k] float32, picks [T, k]) of ``xf [T, d]`` under a router
    of ``kind``.  In float32 at the highest matmul precision: a router that
    rounds its scores ties and misroutes tokens."""
    logits = jnp.matmul(
        xf.astype(jnp.float32), p["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    )
    if kind == "sigmoid_grouped":
        return route_sigmoid_grouped(
            logits, p["router_bias"], top_k=top_k, n_group=n_group, topk_group=topk_group, scaling=scaling
        )
    if kind == "sigmoid":
        return route_sigmoid(logits, top_k=top_k)
    if kind == "softmax_bias":
        return route_softmax_bias(logits, p["router_bias"], top_k=top_k, scaling=scaling)
    top_p, picks = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return top_p / jnp.sum(top_p, axis=-1, keepdims=True), picks


def _shared_experts(p: Any, xf: jax.Array, count: int = 1, combine: str = "sum") -> jax.Array:
    """The shared experts of ``xf [T, d]``: one expert as wide as all ``count``
    of them, which is their sum (a SwiGLU; without a ``shared_w_gate`` the
    two-matrix ``relu(.)^2`` expert); their mean under ``combine`` "mean"; with
    a ``shared_gate`` leaf, times ``sigmoid(xf . shared_gate)`` a token."""
    dt = xf.dtype
    if "shared_w_gate" in p:
        hidden = nn.silu(xf @ p["shared_w_gate"].astype(dt)) * (xf @ p["shared_w_up"].astype(dt))
    else:
        hidden = jnp.square(jax.nn.relu(xf @ p["shared_w_up"].astype(dt)))
    out = hidden @ p["shared_w_down"].astype(dt)
    if "shared_gate" in p:  # a scalar a token, its sigmoid taken in float32
        gate = jnp.dot(xf, p["shared_gate"].astype(dt), preferred_element_type=jnp.float32)
        out = out * jax.nn.sigmoid(gate)[:, None].astype(dt)
    return out / count if combine == "mean" else out


def _latent_out(p: Any, y: jax.Array, dtype: Any) -> jax.Array:
    """The held experts' float32 sum ``y [T, latent]`` back at the model's
    width: one product for all of a token's picks (``w_latent_out`` is linear
    and has no bias, so the shares of a layer's experts add up)."""
    return jnp.dot(y.astype(dtype), p["w_latent_out"].astype(dtype), preferred_element_type=jnp.float32)


# Each Mosaic call of the serving forward sits in a jitted function of its own
# (``ops/expert_rows.py``'s two already do): a bare ``pallas_call`` reaches the
# optimized program without its ``op_name``, and a reader of a device trace
# could not tell which scope its time belongs to (PERF.md, PR 33).
@functools.partial(jax.jit, static_argnums=(5, 6))
def _gmm(lhs, rhs, group_start, tile_group, live_tiles, rows, tile):
    from determined_tpu.ops import grouped_matmul as gm

    return gm.gmm(lhs, rhs, gm.TileLayout(group_start, tile_group, live_tiles, rows, tile))


def serve_routed_experts(cfg: Any, p: Any, x: jax.Array, live: Any = None) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """``RoutedExperts``' forward over its unboxed leaves ``p``, for the
    serving programs (``models/serving.py _serve_layer``; ``cfg`` is the
    ``TransformerConfig``): ``x [b, s, d]`` -> (``y [b, s, d]``, (picks that
    landed on a held expert, held experts with at least one row; with identity
    experts also the picks that landed on one: ``w x`` each, no row)).  Tokens
    that ``live [b, s]`` does not mark (idle lanes, a prompt's padding) take no
    expert's rows.  No backward pass follows, so an expert without rows owns
    no tile of the buffer and its matrices are not read (``_sorted_rows``)."""
    from determined_tpu.ops import expert_rows

    b, s, d = x.shape
    first, count = cfg.moe_experts_held or (0, cfg.moe_experts)
    xf = x.reshape(b * s, d)
    dt = xf.dtype
    with jax.named_scope("serve.moe.route"):
        weights, picks = _route(
            p, xf, kind=cfg.moe_router, top_k=cfg.moe_top_k, n_group=cfg.moe_n_group,
            topk_group=cfg.moe_topk_group, scaling=cfg.moe_routed_scaling,
        )
        outputs = cfg.moe_experts + cfg.moe_zero_experts
        if live is not None:
            picks = jnp.where(live.reshape(-1, 1), picks, outputs)  # no expert: never held, and no identity expert
        rows = _sorted_rows(picks, first, count, serving=True)
    xe = xf
    if cfg.moe_latent_size:
        with jax.named_scope("serve.moe.latent"):
            xe = xf @ p["w_latent_in"].astype(dt)
    with jax.named_scope("serve.moe.experts"):
        layout = rows.layout
        row_token = rows.row_pick // weights.shape[1]
        xr = expert_rows.rows_of_tokens(xe, row_token, rows.tile_rows, layout)
        scale = _row_weights(weights, rows.row_pick, rows.row_live, rows.pick_row, rows.pick_held, layout.tile)
        out, _, _ = _expert_products(
            lambda lhs, rhs: _gmm(lhs, rhs, *layout), xr, p.get("w_gate"), p["w_up"], p["w_down"], scale, layout
        )
        y = expert_rows.tokens_of_rows(out, row_token, rows.tile_rows, layout, xf.shape[0])
    if cfg.moe_latent_size:
        with jax.named_scope("serve.moe.latent"):
            y = _latent_out(p, y, dt)
    if cfg.moe_shared_experts:
        with jax.named_scope("serve.moe.shared"):
            y = y + _shared_experts(p, xf, cfg.moe_shared_experts, cfg.moe_shared_combine).astype(y.dtype)
    counted = (jnp.sum(rows.load), jnp.sum(rows.load > 0))
    if cfg.moe_zero_experts:
        with jax.named_scope("serve.moe.identity"):
            added, zero = _identity_part(weights, picks, cfg.moe_experts, outputs, xf)
            y = y + added.astype(y.dtype)
        counted += (jnp.sum(zero),)
    return y.astype(x.dtype).reshape(b, s, d), counted
