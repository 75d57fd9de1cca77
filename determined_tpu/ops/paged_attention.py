"""Paged decode attention: one query token a lane against the lane's own
blocks of the paged KV pool, read where the pool lies.

The pool is ``[n_layers, num_blocks, block_size, kv_heads * head_dim]``
(``models/transformer.py kv_cache_shape``): one block of one layer is a
contiguous ``[block_size, kv_heads * head_dim]`` slab holding every KV head
of ``block_size`` tokens.  Both forms below index it with the layer and the
block ids together, so no step ever materializes a layer's pool, and both
run ONE algorithm: a blockwise online softmax (running maximum, denominator
and accumulator in float32, K and V in the pool's dtype) over tiles of
``tile_blocks`` table columns.

* :func:`_paged_attention_kernel` — the Pallas TPU kernel.  Grid: one
  program a lane.  Block tables, lengths and the layer arrive as
  scalar-prefetch arguments; a lane walks its own
  ``ceil(length / tile_tokens)`` tiles (an empty lane none, a short lane
  does not wait for the longest).  How a tile reaches VMEM is ONE schedule
  for this kernel, its window form and the latent kernel
  (:func:`_copy_schedule`, by name :data:`COPY_SCHEDULE`): a tile copies
  the blocks that hold a token the query sees and no others, and a copy is
  in flight whenever a tile is multiplied, the next lane's first tile while
  a lane's last is (:func:`walk_counts` states the same in integers for the
  engine's ``/stats``).  The ``n_rep`` query heads of a KV head are served
  from ONE copy of its K/V, in one of two layouts of the tile's two
  products, chosen from ``n_rep`` (:func:`attn_products`), never by the
  caller:

  - *a KV head at a time*, where a KV head's rows of the float32 scores
    fill whole sublane tiles (``n_rep`` a multiple of 8: Command A+'s 16).
    For each KV head's 128-aligned column slice of the tile, ``q[g] [n_rep,
    head_dim] @ K_g^T``; one softmax update over the stacked ``[heads,
    tile]`` scores; ``p_g [terms * n_rep, tile] @ V_g`` into an accumulator
    ``[heads, head_dim]``.  K and V pass through the MXU once each and
    nothing is multiplied by zero.
  - *block-diagonal* below that (InternLM2's 2, Mistral's 4): the wrapper
    lays the query out as ``[heads, kv_heads * head_dim]``, a head's vector
    in its KV head's columns and zeros elsewhere, so a tile costs two wide
    MXU calls (``q_bd @ K^T``, ``p @ V``) and the result is the diagonal
    blocks of the accumulator.  With 16 or 32 rows in all the zeros cost
    nothing (K and V must pass through the array once whatever the rows
    are); at 128 rows they cost 8 x the products and a ``[128, 1024]``
    float32 accumulator.

  On a v5e over 8 KV heads of 128 in bfloat16 (32 lanes, contexts ~7.5 k)
  a 256-token tile takes 1.44 / 1.46 / 1.60 / 2.25 us block-diagonal at 2 /
  4 / 8 / 16 query heads a KV head and 1.44 / 1.44 us a KV head at a time
  at 8 / 16, where the tile's copy is 1.28 us (my chip runs, PR 42: PERF.md
  section 5).  Taken on a TPU when the shapes tile: ``head_dim`` a multiple
  of 128 and ``block_size`` a multiple of the pool dtype's sublane packing.
* :func:`_paged_attention_jnp` — the same mathematics in plain
  ``jax.numpy`` for every other shape and backend, batched over lanes (it
  walks to the longest lane's last tile), and the kernel's parity reference.

The tile width is chosen here from the shapes (:func:`_tile_blocks`), not
by the caller.

A sliding-window layer (``window``) keeps its tokens in a RING a lane: the
table's ``T`` columns are the ring's blocks, and logical block ``c`` of the
lane's context lies in column ``c % T`` (``models/transformer.py
window_store_shape``).  Both forms then walk the tiles that hold the lane's
newest ``window`` tokens, ``[length - window, length)``, and nothing older:
what a window layer reads a step is ``min(length, window)`` tokens a lane,
whatever the context.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from determined_tpu.ops import kernel_form
from determined_tpu.ops.flash_attention import NEG_INF

# Tokens a tile aims for.  A tile is one trip of the walk: its matmuls,
# its softmax update and the copies of its live blocks (K and V each).  Wide
# tiles amortize the serial chain wait -> QK -> max -> exp -> PV of a trip;
# a lane's last tile is MULTIPLIED whole (its dead rows masked), though only
# its live blocks are copied, so the width still bounds that waste.
# On a v5e at InternLM2's shapes (16 x 128 heads, 8 KV, blocks of 16, ~27 k
# live tokens over 32 lanes) 24 layers took 6.9 / 5.2 / 5.1 / 5.9 / 7.6 ms at
# 64 / 128 / 256 / 512 / 1024 tokens a tile (my chip run, PR 25).  Both
# layouts of the products keep it: a KV head at a time a tile's time is its
# copies' (1.44 us against 1.28 us of read at Command A+'s shapes, PR 42).
TILE_TOKENS = 256
# VMEM the kernel's four tile buffers (K and V, two slots each) may take
TILE_BUFFER_BYTES = 4 * 1024 * 1024


def _tile_blocks(block_size: int, table_width: int, token_bytes: int) -> int:
    """Blocks a tile: TILE_TOKENS, less where a token's K (or V) row is so
    wide that four such tiles would not fit TILE_BUFFER_BYTES, and never
    more than the table holds."""
    tokens = min(TILE_TOKENS, TILE_BUFFER_BYTES // (4 * token_bytes))
    return max(1, min(table_width, tokens // block_size))


def _sublane_packing(dtype) -> int:
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def kernel_takes(head_dim: int, block_size: int, dtype) -> bool:
    """Whether the shapes tile for the Pallas kernel: per-head column
    slices must be lane-aligned and a block must fill whole sublane tiles."""
    return (
        jnp.dtype(dtype).itemsize in (2, 4)
        and head_dim % 128 == 0
        and block_size % _sublane_packing(dtype) == 0
    )


def attn_products(n_rep: int) -> str:
    """Which layout the kernel's two products take at ``n_rep`` query heads a
    KV head (see the module's text): ``"per_kv_head"`` where a KV head's rows
    of the float32 scores fill whole sublane tiles, else ``"block_diagonal"``.
    The name is what ``/stats`` and the ``serve.setup.kv_pool`` span say."""
    return "per_kv_head" if n_rep % _sublane_packing(jnp.float32) == 0 else "block_diagonal"


def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    layer,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    scale: float,
    tile_blocks: Optional[int] = None,
    impl: Optional[str] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Attention of one decode step of one layer over the paged pool.

    ``q`` [b, n_heads, head_dim]; ``k_pool`` / ``v_pool`` the whole pools
    ``[n_layers, num_blocks, block_size, kv_heads * head_dim]``; ``layer``
    the layer to read; ``block_tables`` [b, T] int32; ``positions`` [b]
    int32, the position of the lane's query token (it attends to
    ``0..position``), -1 for an empty lane, whose output is zeros.  Returns
    ``[b, n_heads, head_dim]`` float32.

    ``impl`` is ``"kernel"``, ``"kernel_interpret"`` (the kernel in the
    Pallas TPU interpreter: tests), ``"jnp"`` or None: the kernel on a TPU
    when :func:`kernel_takes` the shapes, else the ``jax.numpy`` form.
    ``tile_blocks`` overrides the tile width (tests).

    ``window``: the layer slides and each row of ``block_tables`` is a lane's
    ring (see the module's text): the query attends to the positions
    ``position - window < j <= position``.
    """
    block_size = k_pool.shape[2]
    head_dim = q.shape[-1]
    if tile_blocks is None:
        tile_blocks = _tile_blocks(
            block_size, block_tables.shape[1], k_pool.shape[3] * k_pool.dtype.itemsize
        )
    impl = kernel_form.resolve_impl(
        impl, kernel_takes(head_dim, block_size, k_pool.dtype),
        f"the paged-attention kernel takes heads of whole 128-wide lane tiles (128, 256, ...) and whole "
        f"sublane tiles a block; it refuses a head of {head_dim} (head_dim % 128 == {head_dim % 128}) or a block of "
        f"{block_size} tokens in {k_pool.dtype} (got head_dim={head_dim}, block_size={block_size}, {k_pool.dtype})",
    )
    if window is not None and (window < 1 or block_tables.shape[1] * block_size < window):
        raise ValueError(f"a window of {window} tokens needs a ring of at least as many (got {block_tables.shape[1]} blocks of {block_size})")
    return _paged_attention(
        q, k_pool, v_pool, jnp.asarray(layer, jnp.int32), block_tables, positions,
        scale=scale, tile_blocks=tile_blocks, impl=impl, window=window,
    )


# one jitted function, the layer an argument (``ops/kernel_form.py`` says why);
# ``window`` None: a full layer
@functools.partial(jax.jit, static_argnames=("scale", "tile_blocks", "impl", "window"))
def _paged_attention(
    q, k_pool, v_pool, layer, block_tables, positions, *, scale, tile_blocks, impl, window
):
    lengths = jnp.maximum(positions.astype(jnp.int32) + 1, 0)
    if impl == "jnp":
        return _paged_attention_jnp(
            q, k_pool, v_pool, layer, block_tables, lengths, scale, tile_blocks, window
        )
    return _paged_attention_pallas(
        q, k_pool, v_pool, layer, block_tables, lengths, scale, tile_blocks,
        interpret=impl == "kernel_interpret", window=window,
    )


# ---------------------------------------------------------------------------
# jax.numpy form
# ---------------------------------------------------------------------------


def _paged_attention_jnp(
    q, k_pool, v_pool, layer, block_tables, lengths, scale, tile_blocks, window=None
):
    b, n_heads, head_dim = q.shape
    block_size = k_pool.shape[2]
    kv_heads = k_pool.shape[3] // head_dim
    n_rep = n_heads // kv_heads
    t = block_tables.shape[1]
    tile_tokens = tile_blocks * block_size
    qg = q.reshape(b, kv_heads, n_rep, head_dim)
    n_tiles = (jnp.max(lengths) + tile_tokens - 1) // tile_tokens
    first_tile = 0
    if window is not None:
        starts = jnp.maximum(lengths - window, 0)  # a lane's oldest position still seen
        # the batch walks from the tile of its oldest seen position (empty lanes aside)
        first_tile = jnp.min(jnp.where(lengths > 0, starts, jnp.max(lengths))) // tile_tokens

    def body(i, carry):
        m, l, acc = carry
        if window is None:
            # columns past the table's end re-read its last column, masked below
            cols = jnp.minimum(i * tile_blocks + jnp.arange(tile_blocks), t - 1)
        else:
            cols = (i * tile_blocks + jnp.arange(tile_blocks)) % t  # the ring
        tbl = jnp.take(block_tables, cols, axis=1)  # [b, tile_blocks]
        # layer and block ids in ONE gather: the pool is never sliced
        keys = k_pool[layer, tbl].reshape(b, tile_tokens, kv_heads, head_dim)
        vals = v_pool[layer, tbl].reshape(b, tile_tokens, kv_heads, head_dim)
        s = (
            jnp.einsum("bgrd,btgd->bgrt", qg, keys, preferred_element_type=jnp.float32)
            * scale
        )
        k_idx = i * tile_tokens + jnp.arange(tile_tokens)
        live = k_idx[None, :] < lengths[:, None]  # [b, tile_tokens]
        if window is not None:
            live = live & (k_idx[None, :] >= starts[:, None])
        s = jnp.where(live[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # the where is for an empty lane alone (m still NEG_INF, so exp
        # gives 1): it must come out as zeros, as the kernel leaves it
        p = jnp.where(live[:, None, None, :], jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bgrt,btgd->bgrd", p, vals, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    init = (
        jnp.full((b, kv_heads, n_rep, 1), NEG_INF, jnp.float32),
        jnp.zeros((b, kv_heads, n_rep, 1), jnp.float32),
        jnp.zeros((b, kv_heads, n_rep, head_dim), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(first_tile, n_tiles, body, init)
    return (acc / jnp.maximum(l, 1e-30)).reshape(b, n_heads, head_dim)


# ---------------------------------------------------------------------------
# The copy schedule of the three Pallas kernels
# ---------------------------------------------------------------------------

#: the schedule by name: what ``CacheKind.report`` says on ``serve.setup.kv_pool``
#: and in ``/stats`` beside ``attn_products``
COPY_SCHEDULE = {"tile_copies": "live_blocks", "lane_prefetch": True}
#: blocks whose descriptors one trip of a tile's start loop issues.  On a v5e the
#: latent kernel (32 blocks a tile, one stream) took 8.1 / 6.7 / 6.5 / 6.5 us a
#: lane at DSV3's shape at 1 / 4 / 8 / 16 a trip, the GQA kernel the same at
#: any; the kernel's text at 8 is shorter than with a whole tile unrolled (my
#: chip runs and compiles, PR 58: PERF.md section 5)
COPY_GROUP = 8


def _live_blocks(length, block_size: int, window: Optional[int], xp=jnp):
    """``(start, lo, hi)`` of a lane whose query sees ``length`` tokens: the
    oldest position it sees (``length - window`` under a window, not below 0)
    and the logical blocks ``[lo, hi)`` of the lane's context that hold a token
    it sees, which are the blocks a walk copies.  In integers alone, so that the
    kernels (``xp`` ``jax.numpy``, a lane's scalar) and the host's count
    (:func:`walk_counts`: ``numpy``, every lane at once) state it once."""
    start = 0 if window is None else xp.maximum(length - window, 0)
    return start, start // block_size, (length + block_size - 1) // block_size


class WalkCounts(NamedTuple):
    """A decode step's walk over ONE layer, summed over the lanes."""

    live_tokens: int    # tokens some lane's query sees
    copied_tokens: int  # tokens of the blocks the kernel copies: the live blocks, whole
    lanes: int          # lanes that walk a tile
    lanes_in_flight: int  # of them, those whose first tile the lane before had started


def walk_counts(positions, block_size: int, window: Optional[int] = None) -> WalkCounts:
    """What the kernels' copy schedule does with a step's lanes, on the host:
    ``positions`` [lanes] as :func:`paged_decode_attention` takes them (-1 an
    idle lane).  ``copied_tokens / live_tokens`` is what a step reads over what
    it must; a lane's first tile is in flight when it begins wherever the lane
    before it walked a tile."""
    lengths = np.maximum(np.asarray(positions, np.int64) + 1, 0)
    start, lo, hi = _live_blocks(lengths, block_size, window, xp=np)
    walks = lengths > 0
    return WalkCounts(
        int(np.sum(lengths - start)), int(np.sum(hi - lo)) * block_size,
        int(np.sum(walks)), int(np.sum(walks[1:] & walks[:-1])),
    )


def _copy_schedule(
    lengths_ref, tables_ref, state_ref, streams: Sequence[Tuple[Any, Any, Any]], layer,
    *, tile_blocks: int, block_size: int, table_width: int, window: Optional[int],
):
    """The ONE statement of how the kernels bring a lane's tiles into VMEM, for
    the program of lane ``pl.program_id(0)``.  ``streams``: an ``(hbm pool, VMEM
    buffer [2, tile_tokens, width], DMA semaphores [2])`` each (K and V; the
    latent rows alone).  Returns ``(length, start, first, n_tiles, trip)``: the
    lane walks the tiles ``first .. first + n_tiles - 1`` and ``trip(i)`` returns
    the buffers' slot that holds tile ``first + i``, copied.

    * *A tile copies the blocks that hold a token the query sees*
      (:func:`_live_blocks`), and no others: not the columns past the lane's
      last block, not a ring's blocks older than the window.  Rows of a buffer
      no copy wrote are masked in the scores, and ``p`` is exactly 0 there; so
      that ``0 x row`` is 0 in the second product the buffers are ZEROED once, by
      the call's first program, after which a row only ever holds zeros or a
      pool's row (selecting the dead rows away would cost a pass over V every
      tile; the zeroing is a few hundred vector stores a call).
    * *A copy is in flight whenever a tile is multiplied, across lanes*: while
      tile ``i`` is multiplied the lane's tile ``i + 1`` is on its way into the
      other slot, and after the lane's LAST tile the FIRST tile of the next lane
      (scratch and semaphores outlive a grid step; every lane's length and table
      are in SMEM).  The slot a lane starts in and whether its first tile is in
      flight are carried in ``state_ref`` (SMEM, two words).  A lane starts its
      own first tile only where nobody could have: program 0, and after a lane
      that walked no tile.  Nothing is started for a lane that walks no tile or
      past the last program, so every copy is waited for inside the call.

    The start and the wait of a tile are the two halves of ``copies``, made
    from one range of blocks: the start a loop over them, ``COPY_GROUP`` a trip
    (the kernel's text holds that many descriptors a site, not ``tile_blocks``),
    the wait their count in bytes."""
    b, n_lanes = pl.program_id(0), pl.num_programs(0)

    def walk(lane):
        """``(length, start, lo, hi, first, n_tiles)`` of ``lane``; no tile past the last program."""
        length = jnp.where(lane < n_lanes, lengths_ref[jnp.minimum(lane, n_lanes - 1)], 0)
        start, lo, hi = _live_blocks(length, block_size, window)
        first = lo // tile_blocks
        return length, start, lo, hi, first, (hi + tile_blocks - 1) // tile_blocks - first

    def copies(lane, tile, lo, hi, slot):
        """``(start, wait)`` of the copies of ``lane``'s blocks ``[lo, hi)`` that lie in ``tile``, into ``slot``."""
        base = tile * tile_blocks
        lo, hi = jnp.maximum(lo, base), jnp.minimum(hi, base + tile_blocks)
        n = jnp.maximum(hi - lo, 0)

        def start_block(c, _=None):
            # a ring holds logical block c in column c % T; a table's c is below T wherever the lane fits it
            col = c % table_width if window is not None else jnp.minimum(c, table_width - 1)
            blk = tables_ref[lane * table_width + col]
            rows = pl.ds(pl.multiple_of((c - base) * block_size, block_size), block_size)
            for hbm, buf, sems in streams:
                pltpu.make_async_copy(hbm.at[layer, blk], buf.at[slot, rows], sems.at[slot]).start()

        def start():
            # COPY_GROUP blocks a trip, so that their table reads and addresses overlap; then the rest one by one
            group = min(COPY_GROUP, tile_blocks)

            def start_group(g, _):
                for j in range(group):
                    start_block(lo + g * group + j)

            jax.lax.fori_loop(0, n // group, start_group, None)
            jax.lax.fori_loop(lo + n // group * group, hi, start_block, None)

        def wait():
            # a DMA semaphore counts bytes and every block's copy is as long: n of them are waited
            # for as the powers of two in n, so a full tile is ONE wait, whatever its blocks
            for k in range(tile_blocks.bit_length()):
                rows = pl.ds(0, block_size << k)

                @pl.when((n >> k) & 1 == 1)
                def _blocks():
                    for _, buf, sems in streams:
                        pltpu.make_async_copy(buf.at[slot, rows], buf.at[slot, rows], sems.at[slot]).wait()

        return start, wait

    length, start, lo, hi, first, n_tiles = walk(b)
    _, _, next_lo, next_hi, next_first, next_tiles = walk(b + 1)

    @pl.when(b == 0)
    def _call_begins():
        state_ref[0] = 0  # the slot the lane starts in
        state_ref[1] = 0  # whether its first tile is in flight
        for _, buf, _ in streams:
            buf[...] = jnp.zeros_like(buf)

    slot0, in_flight = state_ref[0], state_ref[1]
    state_ref[0] = (slot0 + n_tiles) % 2
    state_ref[1] = ((n_tiles > 0) & (next_tiles > 0)).astype(jnp.int32)

    @pl.when(in_flight == 0)
    def _own_first():
        start_first, _ = copies(b, first, lo, hi, slot0)
        start_first()

    def trip(i):
        slot = (slot0 + i) % 2
        # what is in flight while tile i is multiplied: the lane's next tile,
        # after its last the next lane's first (an empty range where that lane walks none)
        last = i + 1 == n_tiles
        start_next, _ = copies(
            jnp.where(last, b + 1, b), jnp.where(last, next_first, first + i + 1),
            jnp.where(last, next_lo, lo), jnp.where(last, next_hi, hi), 1 - slot,
        )
        _, wait_this = copies(b, first + i, lo, hi, slot)
        start_next()
        wait_this()
        return slot

    return length, start, first, n_tiles, trip


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------


def _split_terms(p: jax.Array, dtype) -> jax.Array:
    """``p`` (float32) as a stack of ``dtype`` terms whose sum is ``p`` to
    float32 precision, along axis 0.  The MXU multiplies in the pool's
    dtype; three bf16 terms carry all 24 mantissa bits, so ``p @ V`` keeps
    float32 probabilities against V as stored and pays one pass over V."""
    if jnp.dtype(dtype) == jnp.float32:
        return p
    terms = []
    rest = p
    for _ in range(3):
        hi = rest.astype(dtype)
        terms.append(hi)
        rest = rest - hi.astype(jnp.float32)
    return jnp.concatenate(terms, axis=0)


def _paged_attention_kernel(
    layer_ref, lengths_ref, tables_ref,           # scalar prefetch (SMEM)
    q_ref, k_hbm, v_hbm,                          # inputs
    o_ref,                                        # output
    k_buf, v_buf, sems, state_ref,                # scratch
    *, scale: float, tile_blocks: int, block_size: int, table_width: int,
    kv_heads: int, head_dim: int, n_rep: int, per_kv_head: bool, window: Optional[int] = None,
):
    tile_tokens = tile_blocks * block_size
    rows = o_ref.shape[0]
    # under ``window`` the table is a ring and the walk starts at the tile of the
    # oldest position the query still sees, which always holds one it does see
    length, start, first, n_tiles, trip = _copy_schedule(
        lengths_ref, tables_ref, state_ref, ((k_hbm, k_buf, sems.at[0]), (v_hbm, v_buf, sems.at[1])), layer_ref[0],
        tile_blocks=tile_blocks, block_size=block_size, table_width=table_width, window=window,
    )

    if not per_kv_head:
        q = q_ref[...]                                # [rows, kv_heads*head_dim]

    def of_head(buf, slot, g):
        """KV head ``g``'s columns of the tile in ``slot``: [tile_tokens, head_dim]."""
        return buf[slot, :, g * head_dim:(g + 1) * head_dim]

    def sum_terms(pv, n):
        """``pv`` [terms * n, ...] -> the sum of its terms [n, ...]."""
        total = pv[:n]
        for t in range(1, pv.shape[0] // n):
            total = total + pv[t * n:(t + 1) * n]
        return total

    def body(i, carry):
        m, l, acc = carry
        slot = trip(i)
        if per_kv_head:
            s = jnp.concatenate([
                jax.lax.dot_general(
                    q_ref[g], of_head(k_buf, slot, g), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) for g in range(kv_heads)
            ], axis=0) * scale                        # [rows, tile_tokens], a KV head's n_rep rows together
        else:
            k = k_buf[slot]                           # [tile_tokens, kv_heads*head_dim]
            v = v_buf[slot]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale                                 # [rows, tile_tokens]
        k_idx = (first + i) * tile_tokens + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if window is None:
            s = jnp.where(k_idx < length, s, NEG_INF)
        else:
            s = jnp.where((k_idx < length) & (k_idx >= start), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                        # masked: exp(NEG_INF - m) = 0
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        if per_kv_head:
            total = jnp.concatenate([
                sum_terms(jax.lax.dot_general(
                    _split_terms(p[g * n_rep:(g + 1) * n_rep], v_buf.dtype), of_head(v_buf, slot, g),
                    (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
                ), n_rep) for g in range(kv_heads)    # [terms*n_rep, head_dim] a KV head
            ], axis=0)                                # [rows, head_dim]
        else:
            pv = jax.lax.dot_general(
                _split_terms(p, v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                         # [terms*rows, kv_heads*head_dim]
            total = sum_terms(pv, rows)
        return m_new, l_new, acc * alpha + total

    init = (
        jnp.full((rows, 1), NEG_INF, jnp.float32),
        jnp.zeros((rows, 1), jnp.float32),
        jnp.zeros((rows, head_dim if per_kv_head else kv_heads * head_dim), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, n_tiles, body, init)
    acc = acc / jnp.maximum(l, 1e-30)
    if per_kv_head:
        o_ref[...] = acc
        return
    # row h holds head h's result in the columns of its KV head
    row_kv = jax.lax.broadcasted_iota(jnp.int32, (rows, head_dim), 0) // n_rep
    out = jnp.zeros((rows, head_dim), jnp.float32)
    for g in range(kv_heads):
        out = jnp.where(row_kv == g, acc[:, g * head_dim:(g + 1) * head_dim], out)
    o_ref[...] = out


def _paged_attention_pallas(
    q, k_pool, v_pool, layer, block_tables, lengths, scale, tile_blocks,
    *, interpret: bool, window: Optional[int] = None,
):
    b, n_heads, head_dim = q.shape
    _, _, block_size, kvd = k_pool.shape
    kv_heads = kvd // head_dim
    n_rep = n_heads // kv_heads
    t = block_tables.shape[1]
    per_kv_head = attn_products(n_rep) == "per_kv_head"
    if per_kv_head:
        rows = n_heads
        q_in = q.reshape(b, kv_heads, n_rep, head_dim)
        q_spec = pl.BlockSpec((None, kv_heads, n_rep, head_dim), lambda bi, *_: (bi, 0, 0, 0))
    else:
        # whole sublane tiles of rows for the MXU's left operand; padding rows
        # are zero queries, dropped below
        packing = _sublane_packing(q.dtype)
        rows = -(-n_heads // packing) * packing
        # block-diagonal query: head h's vector in the columns of KV head h // n_rep
        kv_of_head = jnp.arange(n_heads) // n_rep
        q_bd = jnp.where(
            (kv_of_head[:, None] == jnp.arange(kv_heads)[None, :])[None, :, :, None],
            q[:, :, None, :],
            jnp.zeros((), q.dtype),
        ).reshape(b, n_heads, kvd)
        q_in = jnp.pad(q_bd, ((0, 0), (0, rows - n_heads), (0, 0)))
        q_spec = pl.BlockSpec((None, rows, kvd), lambda bi, *_: (bi, 0, 0))
    tile_tokens = tile_blocks * block_size
    kernel = functools.partial(
        _paged_attention_kernel,
        scale=scale, tile_blocks=tile_blocks, block_size=block_size,
        table_width=t, kv_heads=kv_heads, head_dim=head_dim, n_rep=n_rep, window=window,
        per_kv_head=per_kv_head,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                q_spec,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, rows, head_dim), lambda bi, *_: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, tile_tokens, kvd), k_pool.dtype),
                pltpu.VMEM((2, tile_tokens, kvd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, head_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=kernel_form.interpret_params(interpret),
        name="paged_decode_attention" if window is None else "paged_window_attention",
    )(
        layer.reshape(1),
        lengths,
        block_tables.reshape(-1).astype(jnp.int32),
        q_in, k_pool, v_pool,
    )
    return out[:, :n_heads, :]


# ---------------------------------------------------------------------------
# Latent rows: ONE row a token serves every head's scores and values
# ---------------------------------------------------------------------------
#
# Multi-head latent attention in its absorbed form: a lane's query is
# ``n_heads`` vectors in the latent space, ``[q_lat | q_rope | zeros]``, and
# the pool ``[n_layers, num_blocks, block_size, width]`` holds one row a
# token, ``[c_kv | k_r | zeros]`` (``models/serving.py init_kv_cache``).
# Scores are the query against the whole row; values are the row's first
# ``value_dim`` columns, so a tile is copied once and feeds both products:
# ``q @ tile^T`` and ``p @ tile[:, :value_dim]``.  All heads share the tile,
# so they are the MXU's rows as they are (no block-diagonal layout), and a
# token costs ``2 x heads x (width + value_dim)`` operations for ``width``
# values read: at 128 heads the chip's compute and bandwidth weigh the same.
# The probabilities enter the second product in the pool's dtype (a flash
# kernel's usual rounding); three-term splitting, as the GQA kernel does it,
# would make this kernel compute bound at twice the time.

#: tokens a tile of the latent kernel aims for (one buffer pair, not two)
LATENT_TILE_TOKENS = 512


def latent_kernel_takes(width: int, value_dim: int, block_size: int, dtype) -> bool:
    """Whether the latent shapes tile for the Pallas kernel."""
    return (
        jnp.dtype(dtype).itemsize in (2, 4)
        and width % 128 == 0
        and value_dim % 128 == 0
        and block_size % _sublane_packing(dtype) == 0
    )


def paged_latent_attention(
    q: jax.Array,
    pool: jax.Array,
    layer,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    scale: float,
    value_dim: int,
    tile_blocks: Optional[int] = None,
    impl: Optional[str] = None,
) -> jax.Array:
    """Latent attention of one decode step of one layer over the paged pool.

    ``q`` [b, n_heads, width] in the pool's dtype; ``pool`` ``[n_layers,
    num_blocks, block_size, width]``; ``layer``, ``block_tables``,
    ``positions``, ``impl`` and ``tile_blocks`` as
    :func:`paged_decode_attention`'s.  Returns ``[b, n_heads, value_dim]``
    float32: ``softmax(q . row * scale) @ row[:value_dim]`` over the lane's
    rows ``0..position``; zeros for an empty lane.
    """
    block_size, width = pool.shape[2], pool.shape[3]
    if tile_blocks is None:
        tokens = min(LATENT_TILE_TOKENS, TILE_BUFFER_BYTES // (2 * width * pool.dtype.itemsize))
        tile_blocks = max(1, min(block_tables.shape[1], tokens // block_size))
    impl = kernel_form.resolve_impl(
        impl, latent_kernel_takes(width, value_dim, block_size, pool.dtype),
        f"the latent paged-attention kernel needs width % 128 == 0, value_dim % 128 == 0 and "
        f"whole sublane tiles a block (got width={width}, value_dim={value_dim}, "
        f"block_size={block_size}, {pool.dtype})",
    )
    return _paged_latent(
        q.astype(pool.dtype), pool, jnp.asarray(layer, jnp.int32), block_tables, positions,
        scale=scale, value_dim=value_dim, tile_blocks=tile_blocks, impl=impl,
    )


# as ``_paged_attention``
@functools.partial(jax.jit, static_argnames=("scale", "value_dim", "tile_blocks", "impl"))
def _paged_latent(q, pool, layer, block_tables, positions, *, scale, value_dim, tile_blocks, impl):
    lengths = jnp.maximum(positions.astype(jnp.int32) + 1, 0)
    if impl == "jnp":
        return _paged_latent_jnp(q, pool, layer, block_tables, lengths, scale, value_dim, tile_blocks)
    return _paged_latent_pallas(
        q, pool, layer, block_tables, lengths, scale, value_dim, tile_blocks,
        interpret=impl == "kernel_interpret",
    )


def _paged_latent_jnp(q, pool, layer, block_tables, lengths, scale, value_dim, tile_blocks):
    b, n_heads, width = q.shape
    block_size = pool.shape[2]
    t = block_tables.shape[1]
    tile_tokens = tile_blocks * block_size
    n_tiles = (jnp.max(lengths) + tile_tokens - 1) // tile_tokens

    def body(i, carry):
        m, l, acc = carry
        cols = jnp.minimum(i * tile_blocks + jnp.arange(tile_blocks), t - 1)
        rows = pool[layer, jnp.take(block_tables, cols, axis=1)].reshape(b, tile_tokens, width)
        s = jnp.einsum("bhw,btw->bht", q, rows, preferred_element_type=jnp.float32) * scale
        live = (i * tile_tokens + jnp.arange(tile_tokens))[None, :] < lengths[:, None]
        s = jnp.where(live[:, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(live[:, None, :], jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bht,btc->bhc", p.astype(rows.dtype), rows[..., :value_dim], preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    init = (
        jnp.full((b, n_heads, 1), NEG_INF, jnp.float32),
        jnp.zeros((b, n_heads, 1), jnp.float32),
        jnp.zeros((b, n_heads, value_dim), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, n_tiles, body, init)
    return acc / jnp.maximum(l, 1e-30)


def _paged_latent_kernel(
    layer_ref, lengths_ref, tables_ref,           # scalar prefetch (SMEM)
    q_ref, pool_hbm,                              # inputs
    o_ref,                                        # output
    buf, sems, acc_ref, state_ref,                # scratch
    *, scale: float, tile_blocks: int, block_size: int, table_width: int, value_dim: int,
):
    tile_tokens = tile_blocks * block_size
    rows = q_ref.shape[0]
    length, _, _, n_tiles, trip = _copy_schedule(
        lengths_ref, tables_ref, state_ref, ((pool_hbm, buf, sems),), layer_ref[0],
        tile_blocks=tile_blocks, block_size=block_size, table_width=table_width, window=None,
    )

    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[...]                                    # [rows, width]

    def body(i, carry):
        m, l = carry
        tile = buf[trip(i)]                              # [tile_tokens, width]
        s = jax.lax.dot_general(
            q, tile, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                     # [rows, tile_tokens]
        k_idx = i * tile_tokens + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_idx < length, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                        # masked: exp(NEG_INF - m) = 0
        alpha = jnp.exp(m - m_new)
        pv = jax.lax.dot_general(
            p.astype(tile.dtype), tile[:, :value_dim], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                             # [rows, value_dim]
        acc_ref[...] = acc_ref[...] * alpha + pv
        return m_new, l * alpha + jnp.sum(p, axis=1, keepdims=True)

    init = (jnp.full((rows, 1), NEG_INF, jnp.float32), jnp.zeros((rows, 1), jnp.float32))
    _, l = jax.lax.fori_loop(0, n_tiles, body, init)
    o_ref[...] = acc_ref[...] / jnp.maximum(l, 1e-30)


def _paged_latent_pallas(q, pool, layer, block_tables, lengths, scale, value_dim, tile_blocks, *, interpret: bool):
    b, n_heads, width = q.shape
    block_size = pool.shape[2]
    t = block_tables.shape[1]
    packing = _sublane_packing(q.dtype)
    rows = -(-n_heads // packing) * packing           # padding rows are zero queries, dropped below
    q = jnp.pad(q, ((0, 0), (0, rows - n_heads), (0, 0)))
    tile_tokens = tile_blocks * block_size
    kernel = functools.partial(
        _paged_latent_kernel, scale=scale, tile_blocks=tile_blocks, block_size=block_size,
        table_width=t, value_dim=value_dim,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, rows, width), lambda bi, *_: (bi, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, rows, value_dim), lambda bi, *_: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, tile_tokens, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, value_dim), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, value_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=kernel_form.interpret_params(interpret),
        name="paged_latent_attention",
    )(layer.reshape(1), lengths, block_tables.reshape(-1).astype(jnp.int32), q, pool)
    return out[:, :n_heads, :]


# ---------------------------------------------------------------------------
# Learned sparse selection: an indexer's scores, its exact top-k, and latent
# attention over the rows it picked
# ---------------------------------------------------------------------------
#
# DeepSeek Sparse Attention's lightning indexer: a query's score of a cached
# token is ``sum_j w_j relu(q_j . k)`` over the indexer's heads ``j``, against
# ONE index key ``k`` a token, and latent attention reads the ``topk`` tokens of
# largest score alone.  The index keys lie in a pool of their own, ``[indexer
# layers, num_blocks, block_size, index_head_dim]``, under the latent pool's
# block ids: a token's key is written where its latent row is.  The scores are
# float32; the selection is exact (``jax.lax.top_k``; ties go to the lower
# position), and what it picks are POSITIONS of the lane's own context, turned
# into places in the pool through the lane's block table.


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """The indexer's scores, the one statement of them: ``q`` [b, s, heads, dim]
    and ``keys`` [b, k, dim] in the compute dtype, ``w`` [b, s, heads] float32 ->
    ``sum_j w[..., j] relu(q[..., j, :] . keys)`` [b, s, k] float32.  A head at a
    time, so that the largest array is the result's own."""

    def add_head(acc, head):
        q_j, w_j = head  # [b, s, dim], [b, s]
        dots = jnp.einsum("bsd,bkd->bsk", q_j, keys, preferred_element_type=jnp.float32)
        return acc + jnp.maximum(dots, 0.0) * w_j[..., None], None

    init = jnp.zeros((*q.shape[:2], keys.shape[1]), jnp.float32)
    return jax.lax.scan(add_head, init, (q.transpose(2, 0, 1, 3), w.astype(jnp.float32).transpose(2, 0, 1)))[0]


def index_topk(scores: jax.Array, seen: jax.Array, topk: int) -> Tuple[jax.Array, jax.Array]:
    """The exact selection: for each query of ``scores`` [b, s, k] the ``min(topk,
    k)`` keys of largest score among those it may see (``seen``, broadcast
    against ``scores``), ties to the lower position.  Returns (the picks [b, s,
    topk] int32, positions among the ``k`` keys; which of them are picks at all
    [b, s, topk]: a query that sees fewer than ``topk`` keys picks them all and
    the rest of its row is not valid)."""
    vals, picks = jax.lax.top_k(jnp.where(seen, scores, NEG_INF), min(topk, scores.shape[-1]))
    return picks.astype(jnp.int32), vals > NEG_INF / 2


def index_topk_mask(scores: jax.Array, seen: jax.Array, topk: int) -> jax.Array:
    """:func:`index_topk`'s selection as a mask ``[b, s, k]`` (True where a query
    picked the key), pick for pick and tie for tie, without its sort and without
    a scatter: what a form that reads every key under a mask asks for (the walk,
    the oracles, the whole-sequence form; a decode step that gathers rows needs
    the positions, and calls :func:`index_topk`).  A float32 score's bits, its
    sign folded, order as the scores do; 32 halvings of that range find the
    ``topk``-th largest value a query sees, every key above it is a pick, and of
    the keys AT it the lowest positions fill what is left.  On a v5e a walk
    chunk's ``[1, 256, 24576]`` takes 0.68 ms this way and 6.5 ms by ``top_k``
    and a scatter (my chip run, PR 61)."""
    k = min(topk, scores.shape[-1])
    seen = seen & (scores > NEG_INF / 2)  # as ``index_topk``: a score of NEG_INF is no pick
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32) + 0.0, jnp.int32)  # + 0.0: -0.0 ties with 0.0
    ordered = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    u = jax.lax.bitcast_convert_type(ordered, jnp.uint32) ^ jnp.uint32(0x80000000)
    u = jnp.where(seen, u, jnp.uint32(0))  # below every score a query may see
    lo = jnp.zeros((*u.shape[:-1], 1), jnp.uint32)

    def halve(_, bounds):  # the largest t with at least k keys >= t
        lo, hi = bounds
        mid = lo + (hi - lo) // 2 + ((hi - lo) & 1)
        enough = jnp.sum(u >= mid, axis=-1, keepdims=True) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    t, _ = jax.lax.fori_loop(0, 32, halve, (lo, jnp.full_like(lo, 0xFFFFFFFF)))
    above, at = u > t, u == t
    left = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (at & (jnp.cumsum(at, axis=-1) <= left))) & seen


#: tokens a tile of the index-score kernel aims for: a key is a fifth of a latent row
INDEX_TILE_TOKENS = 1024


def index_kernel_takes(dim: int, block_size: int, dtype) -> bool:
    """Whether the index keys' shapes tile for the Pallas kernel."""
    return jnp.dtype(dtype).itemsize in (2, 4) and dim % 128 == 0 and block_size % _sublane_packing(dtype) == 0


def paged_index_scores(
    q: jax.Array, w: jax.Array, pool: jax.Array, layer, block_tables: jax.Array, positions: jax.Array, *,
    tile_blocks: Optional[int] = None, impl: Optional[str] = None,
) -> jax.Array:
    """An indexer's scores of one decode step over the paged index keys: ``q``
    [b, heads, dim] and ``w`` [b, heads] (float32) of each lane's query, ``pool``
    ``[indexer layers, num_blocks, block_size, dim]``; ``layer``,
    ``block_tables``, ``positions``, ``impl`` and ``tile_blocks`` as
    :func:`paged_decode_attention`'s.  Returns ``[b, T * block_size]`` float32:
    :func:`index_scores` of the lane's query against the key of each position of
    its table, ``NEG_INF`` past the lane's own position (every position of an
    idle lane).  The kernel walks a lane's live blocks by
    :func:`_copy_schedule`, as the attention kernels do."""
    block_size, dim = pool.shape[2], pool.shape[3]
    if tile_blocks is None:
        tile_blocks = max(1, min(block_tables.shape[1], INDEX_TILE_TOKENS // block_size))
    impl = kernel_form.resolve_impl(
        impl, index_kernel_takes(dim, block_size, pool.dtype),
        f"the index-score kernel needs index_head_dim % 128 == 0 and whole sublane tiles a block "
        f"(got index_head_dim={dim}, block_size={block_size}, {pool.dtype})",
    )
    return _paged_index(
        q.astype(pool.dtype), w.astype(jnp.float32), pool, jnp.asarray(layer, jnp.int32), block_tables, positions,
        tile_blocks=tile_blocks, impl=impl,
    )


# as ``_paged_attention``
@functools.partial(jax.jit, static_argnames=("tile_blocks", "impl"))
def _paged_index(q, w, pool, layer, block_tables, positions, *, tile_blocks, impl):
    lengths = jnp.maximum(positions.astype(jnp.int32) + 1, 0)
    b, t = block_tables.shape
    tokens = t * pool.shape[2]
    if impl == "jnp":
        scores = index_scores(q[:, None], w[:, None], pool[layer, block_tables].reshape(b, tokens, -1))[:, 0]
        return jnp.where(jnp.arange(tokens)[None, :] < lengths[:, None], scores, NEG_INF)
    return _paged_index_pallas(q, w, pool, layer, block_tables, lengths, tile_blocks, interpret=impl == "kernel_interpret")


def _paged_index_kernel(
    layer_ref, lengths_ref, tables_ref,           # scalar prefetch (SMEM)
    q_ref, w_ref, pool_hbm,                       # inputs
    o_ref,                                        # output [tiles, tile_tokens]
    buf, sems, state_ref,                         # scratch
    *, tile_blocks: int, block_size: int, table_width: int,
):
    tile_tokens = tile_blocks * block_size
    length, _, _, n_tiles, trip = _copy_schedule(
        lengths_ref, tables_ref, state_ref, ((pool_hbm, buf, sems),), layer_ref[0],
        tile_blocks=tile_blocks, block_size=block_size, table_width=table_width, window=None,
    )
    o_ref[...] = jnp.full(o_ref.shape, NEG_INF, jnp.float32)   # the tiles the lane does not walk
    q, w = q_ref[...], w_ref[...]                               # [heads, dim], [heads, 1]

    def body(i, _):
        tile = buf[trip(i)]                                     # [tile_tokens, dim]
        dots = jax.lax.dot_general(q, tile, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        scores = jnp.sum(jnp.maximum(dots, 0.0) * w, axis=0, keepdims=True)   # [1, tile_tokens]
        k_idx = i * tile_tokens + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        o_ref[pl.ds(i, 1), :] = jnp.where(k_idx < length, scores, NEG_INF)
        return None

    jax.lax.fori_loop(0, n_tiles, body, None)


def _paged_index_pallas(q, w, pool, layer, block_tables, lengths, tile_blocks, *, interpret: bool):
    b, heads, dim = q.shape
    block_size = pool.shape[2]
    t = block_tables.shape[1]
    tile_tokens = tile_blocks * block_size
    tiles = -(-t // tile_blocks)
    kernel = functools.partial(_paged_index_kernel, tile_blocks=tile_blocks, block_size=block_size, table_width=t)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, heads, dim), lambda bi, *_: (bi, 0, 0)),
                pl.BlockSpec((None, heads, 1), lambda bi, *_: (bi, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, tiles, tile_tokens), lambda bi, *_: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, tile_tokens, dim), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, tiles, tile_tokens), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=kernel_form.interpret_params(interpret),
        name="paged_index_scores",
    )(layer.reshape(1), lengths, block_tables.reshape(-1).astype(jnp.int32), q, w[..., None], pool)
    return out.reshape(b, tiles * tile_tokens)[:, : t * block_size]


def paged_picked_attention(
    q: jax.Array, pool: jax.Array, layer, block_tables: jax.Array, picks: jax.Array, valid: jax.Array, *,
    scale: float, value_dim: int,
) -> jax.Array:
    """Latent attention of one decode step of one layer over the rows an
    indexer picked: ``q``, ``pool``, ``layer``, ``block_tables`` and the result
    as :func:`paged_latent_attention`'s; ``picks`` [b, k] the positions of the
    lane's context its query reads and ``valid`` [b, k] which of them are picks
    at all (:func:`index_topk`; none of an idle lane's: zeros).  A pick becomes
    a place in the pool through the lane's block table, here.  The picked rows
    are copied out of the pool, a row a copy (scope ``serve.mla.gather``), and
    attended in one dense product (``serve.mla.attend``): what is read does not
    grow with the context (reading every live row under a mask wins only while
    a lane holds under ~6 times the picks: PERF.md section 5)."""
    block_size = pool.shape[2]
    with jax.named_scope("serve.mla.gather"):
        places = jnp.take_along_axis(block_tables, jnp.where(valid, picks // block_size, 0), axis=1)
        rows = pool[layer, places, picks % block_size]          # [b, k, width]
    with jax.named_scope("serve.mla.attend"):
        s = jnp.einsum("bhw,bkw->bhk", q.astype(pool.dtype), rows, preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        p = jnp.where(valid[:, None, :], jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)  # an idle lane: zeros
        out = jnp.einsum("bhk,bkc->bhc", p.astype(rows.dtype), rows[..., :value_dim], preferred_element_type=jnp.float32)
        return out / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)


# ---------------------------------------------------------------------------
# A chunk of queries a lane: the prefill walk's read
# ---------------------------------------------------------------------------


def paged_chunk_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: Optional[jax.Array],
    layer,
    block_tables: jax.Array,
    chunk,
    *,
    scale: float,
    value_dim: Optional[int] = None,
    window: Optional[int] = None,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Causal attention of one CHUNK of queries a lane over the paged pool:
    the decode forms' mathematics with a block of queries, in ``jax.numpy``.

    ``q`` [b, kv_heads, n_rep, s, width]: the queries at the absolute
    positions ``chunk * s .. chunk * s + s - 1`` (``chunk`` may be traced;
    ``s`` a multiple of the block size), grouped by the KV head they read.
    GQA: ``k_pool`` / ``v_pool`` as :func:`paged_decode_attention`'s.  Latent
    rows: ``kv_heads`` 1, ``n_rep`` every head, ``v_pool`` None, and the
    values are the first ``value_dim`` columns of ``k_pool``'s rows.
    Returns ``[b, kv_heads, n_rep, s, value width]`` float32.

    Keys are folded a tile of ``s`` tokens at a time into a float32 online
    softmax, tiles ``0 .. chunk``: the work follows the keys up to the
    chunk's end, and the largest array is one tile's ``[heads, s, s]`` scores,
    whatever the table's width.  A query sees ``k_pos <= q_pos``; every query
    sees key 0, so no row of the running maximum is left at ``NEG_INF``.
    Columns past the table's end re-read its last column, which only a query
    past the table's end could see.

    ``window``: the layer slides and each row of ``block_tables`` is a lane's
    ring (see the module's text), at least ``window + s`` tokens long.  A query
    sees ``q_pos - window < k_pos <= q_pos``; the tiles walked are those from
    the chunk's first query's oldest key on, at most ``window / s + 2``.

    ``mask`` [b, s, T * block_size] (an indexer's picks a query): a query sees
    the keys it marks alone, of those its position lets it see.
    """
    b, g, r, s, width = q.shape
    block_size = k_pool.shape[2]
    tile_blocks = s // block_size
    t = block_tables.shape[1]
    values = value_dim if v_pool is None else v_pool.shape[3] // g
    q_pos = chunk * s + jnp.arange(s)

    def body(i, carry):
        m, l, acc = carry
        if window is None:
            cols = jnp.minimum(i * tile_blocks + jnp.arange(tile_blocks), t - 1)
        else:
            cols = (i * tile_blocks + jnp.arange(tile_blocks)) % t  # the ring
        tbl = jnp.take(block_tables, cols, axis=1)  # [b, tile_blocks]
        keys = k_pool[layer, tbl].reshape(b, s, g, width)
        vals = keys[..., :values] if v_pool is None else v_pool[layer, tbl].reshape(b, s, g, values)
        sc = jnp.einsum("bgrqw,btgw->bgrqt", q, keys, preferred_element_type=jnp.float32) * scale
        k_pos = i * s + jnp.arange(s)
        seen = k_pos[None, :] <= q_pos[:, None]  # [s, s]; all of it before the last tile
        if window is not None:
            seen = seen & (k_pos[None, :] > q_pos[:, None] - window)
        if mask is not None:  # [b, 1, 1, s, s] against the scores' [b, g, r, s, s]; a tile past the table's end marks nothing
            start = jnp.minimum(i * s, mask.shape[2] - s)
            seen = (seen & (i * s < mask.shape[2]))[None] & jax.lax.dynamic_slice_in_dim(mask, start, s, axis=2)
            seen = seen[:, None, None]
        sc = jnp.where(seen, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)  # masked: exp(NEG_INF - m) = 0
        if window is not None or mask is not None:
            p = jnp.where(seen, p, 0.0)  # a late query sees nothing of the walk's first tile: its m is still NEG_INF
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bgrqt,btgv->bgrqv", p.astype(vals.dtype), vals, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    init = (
        jnp.full((b, g, r, s, 1), NEG_INF, jnp.float32),
        jnp.zeros((b, g, r, s, 1), jnp.float32),
        jnp.zeros((b, g, r, s, values), jnp.float32),
    )
    first_tile = 0 if window is None else jnp.maximum(chunk * s - window + 1, 0) // s
    _, l, acc = jax.lax.fori_loop(first_tile, chunk + 1, body, init)
    return acc / (l if mask is None else jnp.maximum(l, 1e-30))  # under a mask a padded query may see nothing
