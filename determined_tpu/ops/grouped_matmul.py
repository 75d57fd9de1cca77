"""Grouped matrix product over ragged groups: Pallas TPU kernels.

``rows [M, K]`` are sorted by group (an expert's tokens lie together) and
``rhs [G, K, N]`` holds one matrix a group: ``out[r] = rows[r] @ rhs[group
of r]``.  The group sizes are known only on the device, so the layout is
tile-aligned (:func:`tile_layout`): every group starts on a multiple of
``tile`` rows and owns at least one tile, so a row tile belongs to exactly
one group and the kernel is a matmul whose weight block is picked by a
prefetched table.  The buffer is sized for the worst case (no row is ever
dropped); tiles past the live count are skipped, cost a grid step and no
copy (their block indices are clamped to the last live tile's), and their
rows of the output are never written: callers read live rows only.

- :func:`gmm`: ``rows @ rhs[group]`` (and ``rows @ rhs[group]^T``: the
  gradient to the rows);
- :func:`tgmm`: ``rows^T @ grads`` summed a group: the gradient to ``rhs``,
  accumulated in float32 in the output block while consecutive tiles stay
  with one group.  Rows of a live tile that no group owns must be ZERO in
  one of the two operands, so that they add nothing.

``models/moe.py`` (``_held_experts``) builds the expert layer's forward and
backward passes from the two.

``jax.lax.ragged_dot`` was measured first on a v5e at the widths of the
expert layer (2304 x 896, 16 groups of 2,048 rows): 47 TFLOP/s forward and
20 TFLOP/s for the gradient to the weights, of 197 (PERF.md, PR 27), which is
why this file exists.  Off a TPU the kernels run in interpret mode.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from determined_tpu.ops import kernel_form

#: rows a tile: a weight block stays resident while a group's tiles pass.
#: Every group wastes half a tile on average, so a finer tile is less padding
#: to compute and a step time that follows the split of the rows less: at 512
#: two runs with the same count of rows differed by 5 % in these kernels' time
#: (my chip runs, PR 27); under 256 a tile's matmul no longer hides a grid step
DEFAULT_TILE = 256
#: two buffers of a [2304, 896] float32 block and the tiles beside them
_VMEM_LIMIT = 96 * 1024 * 1024


class TileLayout(NamedTuple):
    """Where each group's rows lie in the tile-aligned buffer."""

    group_start: jax.Array   # [G] first row of each group (a multiple of tile)
    tile_group: jax.Array    # [tiles] group of each tile (dead tiles: the last live tile's)
    live_tiles: jax.Array    # [1] tiles that hold a group's rows
    rows: int                # static: rows of the buffer
    tile: int


def buffer_rows(max_rows: int, groups: int, tile: int) -> int:
    """Rows that hold ``max_rows`` rows in ``groups`` tile-aligned groups,
    whatever the split: each group wastes less than a tile and owns one."""
    return (-(-max_rows // tile) + groups) * tile


def tile_layout(
    group_sizes: jax.Array, max_rows: int, tile: int, *, empty_groups_own_tile: bool = True
) -> TileLayout:
    """``group_sizes [G]`` (summing to at most ``max_rows``) -> the layout.

    ``empty_groups_own_tile=False`` (a forward pass alone: serving) gives a
    group without rows no tile, so that its matrices are never read; one tile
    stays live whatever the sizes, and a dead tile names the last live tile's
    group.  ``tgmm`` needs the default: every group's block is written."""
    groups = group_sizes.shape[0]
    rows = buffer_rows(max_rows, groups, tile)
    if empty_groups_own_tile:
        tiles = jnp.maximum(-(-group_sizes // tile), 1)          # a group owns >= 1 tile
    else:
        tiles = -(-group_sizes // tile)
        tiles = jnp.where((jnp.arange(groups) == 0) & (jnp.sum(tiles) == 0), 1, tiles)
    ends = jnp.cumsum(tiles)
    live = ends[-1]
    index = jnp.arange(rows // tile)
    # a tile's group: the groups that end at or before it, counted (no search, no gather)
    tile_group = jnp.sum(ends[None, :] <= index[:, None], axis=1)
    if not empty_groups_own_tile:
        # the last live tile's group is the last group that owns a tile
        tile_group = jnp.where(index < live, tile_group, jnp.max(jnp.where(tiles > 0, jnp.arange(groups), 0)))
    return TileLayout(
        group_start=((ends - tiles) * tile).astype(jnp.int32),
        tile_group=jnp.minimum(tile_group, groups - 1).astype(jnp.int32),
        live_tiles=live.astype(jnp.int32)[None],
        rows=rows,
        tile=tile,
    )


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _gmm_kernel(tile_group, live_tiles, lhs_ref, rhs_ref, *refs, transpose_rhs: bool):
    *add_ref, out_ref = refs  # with a tile to add to: it lies where the result goes

    @pl.when(pl.program_id(0) < live_tiles[0])
    def _compute():
        contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        product = jax.lax.dot_general(lhs_ref[...], rhs_ref[0], contract, preferred_element_type=jnp.float32)
        for ref in add_ref:
            product += ref[...].astype(jnp.float32)
        out_ref[...] = product.astype(out_ref.dtype)


def _tgmm_kernel(tile_group, live_tiles, lhs_ref, grad_ref, out_ref):
    i = pl.program_id(0)
    live = i < live_tiles[0]
    group = tile_group[i]

    @pl.when(live & ((i == 0) | (group != tile_group[jnp.maximum(i - 1, 0)])))
    def _first_tile_of_group():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(live)
    def _accumulate():
        out_ref[0] += jax.lax.dot_general(
            lhs_ref[...], grad_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def _row_tile(tile: int, width: int) -> pl.BlockSpec:
    # a dead tile re-reads (re-writes) the last live one: no copy is made
    return pl.BlockSpec((tile, width), lambda i, tile_group, live: (jnp.minimum(i, live[0] - 1), 0))


def _group_block(k: int, n: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, k, n), lambda i, tile_group, live: (tile_group[i], 0, 0))


def _params() -> pltpu.CompilerParams:
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT
    )


def gmm(
    lhs: jax.Array, rhs: jax.Array, layout: TileLayout, *, transpose_rhs: bool = False,
    add: Optional[jax.Array] = None,
) -> jax.Array:
    """``lhs [rows, K] @ rhs[group] [K, N]`` (``transpose_rhs``: ``rhs [G, N,
    K]``, contracted over its last dim) -> ``[rows, N]`` in ``lhs``'s dtype.
    ``add [rows, N]`` (another product's result): the product is added to it in
    float32, tile by live tile, and the result takes its place in memory: the
    sum of two products costs no pass of its own.
    Rows of dead tiles are left as they are in memory: never read them."""
    rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    assert rows == layout.rows and rhs.shape[2 if transpose_rhs else 1] == k
    assert add is None or (add.shape, add.dtype) == ((rows, n), lhs.dtype)
    added = [] if add is None else [add]
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // layout.tile,),
            in_specs=[_row_tile(layout.tile, k), _group_block(*rhs.shape[1:])] + [_row_tile(layout.tile, n)] * len(added),
            out_specs=_row_tile(layout.tile, n),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        input_output_aliases={4: 0} if added else {},   # operands are counted from the two prefetched tables
        compiler_params=_params(),
        interpret=kernel_form.interpreted_off_chip(),
        name="moe_gmm",
    )(layout.tile_group, layout.live_tiles, lhs, rhs, *added)


def tgmm(lhs: jax.Array, grads: jax.Array, layout: TileLayout, groups: int) -> jax.Array:
    """``sum over a group's rows of lhs[r]^T grads[r]`` -> ``[G, K, N]``
    float32.  Every group owns a live tile, so every block is written."""
    rows, k = lhs.shape
    n = grads.shape[1]
    assert rows == layout.rows == grads.shape[0]
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // layout.tile,),
            in_specs=[_row_tile(layout.tile, k), _row_tile(layout.tile, n)],
            out_specs=pl.BlockSpec(
                (1, k, n),
                lambda i, tile_group, live: (tile_group[jnp.minimum(i, live[0] - 1)], 0, 0),
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        compiler_params=_params(),
        interpret=kernel_form.interpreted_off_chip(),
        name="moe_tgmm",
    )(layout.tile_group, layout.live_tiles, lhs, grads)


def live_rows_mask(layout: TileLayout) -> jax.Array:
    """``[rows]``: whether a row lies in a live tile."""
    return jnp.arange(layout.rows) < layout.live_tiles[0] * layout.tile


__all__: Tuple[str, ...] = (
    "DEFAULT_TILE", "TileLayout", "buffer_rows", "tile_layout", "gmm", "tgmm", "live_rows_mask",
)
