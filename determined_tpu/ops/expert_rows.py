"""The dropless expert layer's work on its rows that is no matrix product:
Pallas TPU kernels over live tiles.

``models/moe.py`` sorts the picks that land on a held expert into the
tile-aligned buffer of :mod:`grouped_matmul` (``TileLayout``), whose static
size is the worst case.  Everything here does work in proportion to the tiles
a group OWNS, found on the device (``layout.live_tiles``), as the grouped
products do: nothing in the expert layer touches a dead tile, and the
worst-case buffer costs memory and no time.

The two row movements follow the rows a pick owns: ``tile_rows [tiles]`` says
how many rows of each layout tile a pick owns (they are the tile's first
rows; 0 for a dead tile) and ``row_token [rows]`` the token of each.

- :func:`rows_of_tokens`: ``out[r] = x[row_token[r]]`` for owned rows, ZERO
  for the other rows of a live tile (``tgmm`` needs them zero), dead tiles
  not written at all (``gmm`` / ``tgmm`` never read them);
- :func:`tokens_of_rows`: ``out[t] = sum over the rows t owns of rows[r]`` in
  float32, its transpose.  A token's rows are added in the rows' order, the
  same from run to run.

What lies BETWEEN two grouped products is elementwise on a row and its
routing weight: the activation with the weight (``hidden``), and in the
backward pass hidden again and its derivative with a sum over each row.

- :func:`over_live_tiles`: a ``jax.numpy`` function of a tile (the caller's:
  ``models/moe.py _hidden_rows``, ``_hidden_grads``, which call the layer's
  one statement of the activation) run on every live tile and on no other.
  Until PR 63 these were XLA's passes, whose extents are static: 3.5 ms a
  layer over the 69,632 rows of the Mellum2 cell's buffer, of which 24 % are
  live; the kernels take 0.55 (below).  The third such pass, the sum of the
  two products that make a gated expert's gradient to its rows, is no kernel
  at all: the second product adds into the first's result where it lies
  (``grouped_matmul.gmm(..., add=)``).

How the movements work.  A row of a ``[rows, d]`` operand cannot be copied
alone out of HBM: a one-row slice is not aligned to the operand's ``(8, 128)``
tiling (a bf16 row even shares its 32-bit words with its neighbour), and
Mosaic refuses the DMA.  So whole tiles stream through VMEM by ``BlockSpec``
(dead ones skipped with clamped block indices, as in ``grouped_matmul``) and
the irregular part is done there, one sublane a row, on 32-bit copies: the
token side keeps a ``[tokens, cols]`` float32 block resident and adds each
owned row into its token's sublane; the row side keeps ``x`` resident and
copies a token's sublane into the row's.  The columns are split so that the
resident block fits VMEM.  Measured on a v5e at the Mellum2 cell's shapes
(8,192 tokens, k 8, d 2,304, ~14,800 owned rows in ~66 live tiles of 272): the
token side 0.48 ms and the row side 0.41 ms a call, alone and inside the step,
where XLA's gather of all ``tokens x k`` picks with its masked sum took 3.7 ms
and its gather into all 69,632 rows 0.65 ms (PERF.md, PR 33).  With every
expert held (65,536 owned rows) they take 1.5 and 1.4 ms where XLA took 3.4
and 1.8.

A tile belongs to one group and a token picks an expert at most once, so the
tokens of one tile's rows are DISTINCT: the token side loads a few rows'
sublanes before it stores them.

How the passes work.  A grid step streams up to ``_STEP_ROWS`` rows of every
operand through VMEM (a serving step's whole buffer is one to four steps; a
dead step stays on the last live one's blocks: no copy, no write) and a loop
inside it runs the body on the step's live tiles, one layout tile a trip, so
a dead tile is never computed; one that shares a step with a live tile is
written back as VMEM held it, and no reader reads it.  A row's routing weight
comes in along the LANES (``[steps, 8, 128]``: a ``[rows, 1]`` operand would
pad every value to 128 in HBM) and is turned down the sublanes, beside its
row, by one 128 x 128 transpose a chunk; the rows' sums go back the same way.
Both are bound by HBM on the chip: 8.4 and 13.5 us a block of 1,024 rows of
896 (three and five bf16 operands and results), 0.15 and 0.24 ms a call at 65
live tiles of 272 where XLA's passes over all rows take 0.55 and 0.93; at
Nemotron's decode buffer (3,456 rows of 2,688 at tiles of 16) 0.032 ms
against 0.057 (PERF.md, PR 63: device time from a trace; on the host's clock
a call costs 0.2-0.45 ms of dispatch whatever it does).

No result looks like a grouped product's to a reader that tells Mosaic calls
apart by result shape (2-D bf16, 3-D float32), nor like AdamW's (a tuple led
by float32) or attention's (4-D bf16): the token side returns 2-D float32,
the row side and the passes 3-D ``[tiles, tile, width]`` arrays in the
compute dtype (the derivative a tuple led by one), reshaped after (free).
Off a TPU the kernels run in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from determined_tpu.ops import kernel_form
from determined_tpu.ops.grouped_matmul import TileLayout

#: bytes of the resident float32 block (Pallas keeps two of an output block)
_RESIDENT_BYTES = 32 * 1024 * 1024
_VMEM_LIMIT = 100 * 1024 * 1024
#: rows a grid step: a step costs ~0.35 us whatever it does, and a 1-D block in
#: SMEM is a multiple of 1,024 entries
_STEP_ROWS = 1024
#: rows whose sublanes are in flight together (my chip runs, PR 33: 1 -> 4 is
#: 0.85 -> 0.55 ms on the token side; 8 reads 0.50 there where 4 reads 0.48,
#: and 0.38 on the row side where 4 reads 0.41: one number for both)
_UNROLL = 4


def _columns(tokens: int, d: int) -> int:
    """The widest split of ``d`` into lane-aligned equal parts whose
    ``[tokens, cols]`` float32 block fits the budget (``d`` itself where it
    fits or cannot be split)."""
    parts = [d // n for n in range(1, d // 128 + 1) if d % (n * 128) == 0]
    return next((c for c in parts if tokens * c * 4 <= _RESIDENT_BYTES), parts[-1] if parts else d)


def _live_step(i, live, sub: int):
    """The grid step whose blocks step ``i`` holds, ``sub`` layout tiles a step:
    a dead step re-reads (re-writes) the last live one, so no copy is made."""
    return jnp.minimum(i, (live[0] + sub - 1) // sub - 1)


class _Plan:
    """The static shapes both kernels share: ``sub`` layout tiles a grid step,
    ``per`` layout tiles an SMEM block of ``row_token`` holds."""

    def __init__(self, rows: int, tile: int, tokens: int, d: int):
        assert tile % _UNROLL == 0, tile
        self.tile = tile
        self.sub = next(s for s in (8, 4, 2, 1) if (rows // tile) % s == 0 and s * tile <= _STEP_ROWS)
        self.block = self.sub * tile
        self.smem = _STEP_ROWS if rows > _STEP_ROWS else rows
        assert self.smem % self.block == 0, (rows, tile)
        self.per = self.smem // tile
        self.cols = _columns(tokens, d)
        self.grid = (d // self.cols, rows // self.block)

    def live_step(self, i, live):
        return _live_step(i, live, self.sub)

    def token_spec(self) -> pl.BlockSpec:
        return pl.BlockSpec(
            (self.smem,), lambda c, i, live, n: (self.live_step(i, live) * self.block // self.smem,),
            memory_space=pltpu.SMEM,
        )

    def row_tokens(self, row_token: jax.Array) -> jax.Array:
        return jnp.pad(row_token.astype(jnp.int32), (0, -row_token.shape[0] % self.smem))

    def call(self, kernel, in_spec, out_spec, out_shape, scratch, name, interpret):
        return pl.pallas_call(
            functools.partial(kernel, tile=self.tile, per=self.per, sub=self.sub),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=self.grid, in_specs=[self.token_spec(), in_spec],
                out_specs=out_spec, scratch_shapes=scratch,
            ),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
            ),
            interpret=interpret,
            name=name,
        )


# ---------------------------------------------------------------------------
# kernels.  Scalar prefetch: live_tiles [1], tile_rows [tiles]; token_ref is the
# SMEM block of row_token that holds this step's tiles
# ---------------------------------------------------------------------------


def _tokens_of_rows_kernel(live, tile_rows, token_ref, rows_ref, out_ref, row32_ref, *, tile, per, sub):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _first_step_of_the_columns():
        out_ref[...] = jnp.zeros_like(out_ref)

    for s in range(sub):
        t = i * sub + s
        owned = tile_rows[t]

        @pl.when(owned > 0)
        def _add():
            row32_ref[...] = rows_ref[s * tile:(s + 1) * tile, :].astype(jnp.float32)
            first = (t % per) * tile

            def few(g, carry):
                # distinct tokens (one group a tile): every load before a store
                r0 = g * _UNROLL
                tokens = [token_ref[first + r0 + u] for u in range(_UNROLL)]
                sums = [
                    out_ref[pl.ds(tokens[u], 1), :] + row32_ref[pl.ds(r0 + u, 1), :] for u in range(_UNROLL)
                ]
                for u in range(_UNROLL):
                    out_ref[pl.ds(tokens[u], 1), :] = sums[u]
                return carry

            jax.lax.fori_loop(0, owned // _UNROLL, few, 0)

            def one(r, carry):
                out_ref[pl.ds(token_ref[first + r], 1), :] += row32_ref[pl.ds(r, 1), :]
                return carry

            jax.lax.fori_loop(owned // _UNROLL * _UNROLL, owned, one, 0)


def _rows_of_tokens_kernel(live, tile_rows, token_ref, x_ref, out_ref, x32_ref, row32_ref, *, tile, per, sub):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _first_step_of_the_columns():
        x32_ref[...] = x_ref[...].astype(jnp.float32)

    for s in range(sub):
        t = i * sub + s
        owned = tile_rows[t]

        @pl.when(t < live[0])
        def _gather():
            first = (t % per) * tile

            def few(g, carry):
                # past `owned` the tokens are still tokens, and their rows are zeroed below
                for u in range(_UNROLL):
                    r = g * _UNROLL + u
                    row32_ref[pl.ds(r, 1), :] = x32_ref[pl.ds(token_ref[first + r], 1), :]
                return carry

            jax.lax.fori_loop(0, (owned + _UNROLL - 1) // _UNROLL, few, 0)
            row = jax.lax.broadcasted_iota(jnp.int32, row32_ref.shape, 0)
            # where, not times: a sublane no copy filled may hold anything
            out_ref[s] = jnp.where(row < owned, row32_ref[...], 0.0).astype(out_ref.dtype)


def _over_live_tiles_kernel(live, scale_ref, *refs, body, given, outs, sums, tile, sub):
    """``refs``: the operands that are ``given``, ``outs`` results ``[sub, tile,
    width]`` (then the rows' sums along the lanes, if ``sums``), a scratch for
    the rows' scale down the sublanes (and one for their sums)."""
    refs = iter(refs)
    ins = [next(refs) if there else None for there in given]
    out_refs = [next(refs) for _ in range(outs)]
    sums_ref = next(refs) if sums else None
    scale_col, sums_col = next(refs), next(refs, None)
    chunks = scale_ref.shape[1]
    here = jnp.clip(live[0] - pl.program_id(0) * sub, 0, sub)          # this step's live tiles are its first ones

    @pl.when(here > 0)
    def _a_live_step():
        # 128 rows' scales lie along the lanes of one sublane; the body wants each beside its row: a 128 x 128
        # transpose a chunk leaves a row's scale in every lane of the row's sublane
        for c in range(chunks):
            scale_col[c * 128:(c + 1) * 128, :] = jnp.broadcast_to(scale_ref[0, c:c + 1, :], (128, 128)).T

        def one_tile(t, carry):
            rows = pl.ds(pl.multiple_of(t * tile, tile), tile)
            results = body(*(None if ref is None else ref[rows, :] for ref in ins), scale_col[rows, :][:, :1])
            for out_ref, value in zip(out_refs, results):
                out_ref[t] = value.astype(out_ref.dtype)
            if sums:
                sums_col[rows, :] = jnp.broadcast_to(results[-1], (tile, 128))
            return carry

        jax.lax.fori_loop(0, here, one_tile, 0)
        for c in range(chunks if sums else 0):
            sums_ref[0, c:c + 1, :] = sums_col[c * 128:(c + 1) * 128, :].T[:1, :]


# ---------------------------------------------------------------------------
# entry points.  Each kernel sits in a jitted function of its own: a step holds
# a dozen calls of one shape, and a function jitted once is lowered once where
# a bare ``pallas_call`` is lowered at every call site, at every start of a
# process, cached program or not (3 s of the sandbox's CPU for four layers)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("rows", "tile", "tokens", "interpret"))
def _tokens_of_rows(values, row_token, tile_rows, live_tiles, *, rows, tile, tokens, interpret):
    d = values.shape[1]
    plan = _Plan(rows, tile, tokens, d)
    return plan.call(
        _tokens_of_rows_kernel,
        pl.BlockSpec((plan.block, plan.cols), lambda c, i, live, n: (plan.live_step(i, live), c)),
        pl.BlockSpec((tokens, plan.cols), lambda c, i, live, n: (0, c)),
        jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        [pltpu.VMEM((tile, plan.cols), jnp.float32)],
        "moe_tokens_of_rows", interpret,
    )(live_tiles, tile_rows, plan.row_tokens(row_token), values)


@functools.partial(jax.jit, static_argnames=("rows", "tile", "interpret"))
def _rows_of_tokens(x, row_token, tile_rows, live_tiles, *, rows, tile, interpret):
    tokens, d = x.shape
    plan = _Plan(rows, tile, tokens, d)
    out = plan.call(
        _rows_of_tokens_kernel,
        pl.BlockSpec((tokens, plan.cols), lambda c, i, live, n: (0, c)),
        pl.BlockSpec((plan.sub, tile, plan.cols), lambda c, i, live, n: (plan.live_step(i, live), 0, c)),
        # three dimensions, merged below (free): see the module's last paragraph
        jax.ShapeDtypeStruct((rows // tile, tile, d), x.dtype),
        [pltpu.VMEM((tokens, plan.cols), jnp.float32), pltpu.VMEM((tile, plan.cols), jnp.float32)],
        "moe_rows_of_tokens", interpret,
    )(live_tiles, tile_rows, plan.row_tokens(row_token), x)
    return out.reshape(rows, d)


@functools.partial(jax.jit, static_argnames=("body", "rows", "tile", "sums", "interpret"))
def _over_live_tiles(operands, scale, live_tiles, *, body, rows, tile, sums, interpret):
    given = tuple(x is not None for x in operands)
    arrays = [x for x in operands if x is not None]
    dtype = arrays[0].dtype
    # up to eight blocks a step, two buffers each, and the float32 values of a tile beside them
    wide = 16 * max(x.shape[1] for x in arrays) * dtype.itemsize
    block = min(rows, next((b for b in (_STEP_ROWS, _STEP_ROWS // 2, _STEP_ROWS // 4) if b * wide <= _VMEM_LIMIT), 128))
    assert block % tile == 0, (rows, tile)
    sub, steps, chunks = block // tile, -(-rows // block), -(-block // 128)
    tiles_of = jax.eval_shape(
        body, *(None if x is None else jax.ShapeDtypeStruct((tile, x.shape[1]), x.dtype) for x in operands),
        jax.ShapeDtypeStruct((tile, 1), jnp.float32),
    )
    results = tiles_of[:-1] if sums else tiles_of
    lanes = pl.BlockSpec((1, chunks, 128), lambda i, live: (_live_step(i, live, sub), 0, 0))
    out = pl.pallas_call(
        functools.partial(
            _over_live_tiles_kernel, body=body, given=given, outs=len(results), sums=sums, tile=tile, sub=sub
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[lanes] + [pl.BlockSpec((block, x.shape[1]), lambda i, live: (_live_step(i, live, sub), 0)) for x in arrays],
            out_specs=[
                pl.BlockSpec((sub, tile, r.shape[1]), lambda i, live: (_live_step(i, live, sub), 0, 0)) for r in results
            ] + [lanes] * sums,
            scratch_shapes=[pltpu.VMEM((chunks * 128, 128), jnp.float32)] * (1 + sums),
        ),
        # three dimensions, merged below (free): see the module's last paragraph
        out_shape=[jax.ShapeDtypeStruct((rows // tile, tile, r.shape[1]), dtype) for r in results]
        + [jax.ShapeDtypeStruct((steps, chunks, 128), jnp.float32)] * sums,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe" + body.__name__,
    )(live_tiles, jnp.pad(scale, (0, steps * chunks * 128 - rows)).reshape(steps, chunks, 128), *arrays)
    merged = [x.reshape(rows, -1) for x in out[:len(results)]]
    if sums:
        merged.append(out[-1].reshape(steps, -1)[:, :block].reshape(-1)[:rows])
    return merged


def tokens_of_rows(
    rows: jax.Array, row_token: jax.Array, tile_rows: jax.Array, layout: TileLayout, tokens: int
) -> jax.Array:
    """``rows [layout.rows, d]`` -> ``[tokens, d]`` float32: each token's owned
    rows summed.  Rows no pick owns, and dead tiles, are never read."""
    assert rows.shape[0] == layout.rows
    return _tokens_of_rows(
        rows, row_token, tile_rows, layout.live_tiles,
        rows=layout.rows, tile=layout.tile, tokens=tokens, interpret=kernel_form.interpreted_off_chip(),
    )


def rows_of_tokens(x: jax.Array, row_token: jax.Array, tile_rows: jax.Array, layout: TileLayout) -> jax.Array:
    """``x [tokens, d]`` -> ``[layout.rows, d]`` in ``x``'s dtype: a token's
    row for every owned row, zeros for a live tile's other rows.  Rows of dead
    tiles are left as they are in memory: never read them."""
    return _rows_of_tokens(
        x, row_token, tile_rows, layout.live_tiles, rows=layout.rows, tile=layout.tile, interpret=kernel_form.interpreted_off_chip(),
    )


def over_live_tiles(
    body: Callable[..., Tuple[jax.Array, ...]], operands: Sequence[Optional[jax.Array]], scale: jax.Array,
    layout: TileLayout, *, sums: bool = False,
) -> List[jax.Array]:
    """``body`` on every live tile: ``operands`` ``[layout.rows, width]`` each
    (None: an operand the body does without, handed on as None) and ``scale
    [layout.rows]`` float32 -> what ``body(*tiles [tile, width], scale [tile,
    1])`` returns, a tuple of ``[tile, width]`` arrays, as ``[layout.rows,
    width]`` arrays in the operands' dtype; with ``sums`` the body's last result
    is a float32 ``[tile, 1]`` (a sum over each row) and comes back as
    ``[layout.rows]`` float32.  ``body`` is a function of a module (the jitted
    function under this is keyed by it) written in ``jax.numpy`` on two
    dimensions.  Rows of dead tiles are neither read nor written."""
    return _over_live_tiles(
        tuple(operands), scale, layout.live_tiles, body=body, rows=layout.rows, tile=layout.tile, sums=sums,
        interpret=kernel_form.interpreted_off_chip(),
    )


__all__: Tuple[str, ...] = ("over_live_tiles", "rows_of_tokens", "tokens_of_rows")
