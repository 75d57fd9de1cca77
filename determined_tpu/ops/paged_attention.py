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
  does not wait for the longest), copying each tile's blocks HBM -> VMEM
  with the next tile's copies in flight.  The ``n_rep`` query heads of a KV
  head are served from ONE copy of its K/V, in one of two layouts of the
  tile's two products, chosen from ``n_rep`` (:func:`attn_products`), never
  by the caller:

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
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from determined_tpu.ops.flash_attention import NEG_INF

# Tokens a tile aims for.  A tile is one trip of the walk: its matmuls,
# its softmax update and its 2 * tile_blocks block copies.  Wide tiles
# amortize the serial chain wait -> QK -> max -> exp -> PV of a trip; the
# last tile of a lane is copied whole, so the width also bounds the waste.
# On a v5e at InternLM2's shapes (16 x 128 heads, 8 KV, blocks of 16, ~27 k
# live tokens over 32 lanes) 24 layers took 6.9 / 5.2 / 5.1 / 5.9 / 7.6 ms at
# 64 / 128 / 256 / 512 / 1024 tokens a tile (my chip run, PR 25).  Both
# layouts of the products keep it: a KV head at a time a tile's time is its
# copies' (1.44 us against 1.28 us of read at Command A+'s shapes, PR 42).
TILE_TOKENS = 256
# VMEM the kernel's four tile buffers (K and V, two slots each) may take
TILE_BUFFER_BYTES = 4 * 1024 * 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


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
    tiles = kernel_takes(head_dim, block_size, k_pool.dtype)
    if impl is None:
        impl = "kernel" if _on_tpu() and tiles else "jnp"
    if impl != "jnp" and not tiles:
        raise ValueError(
            f"the paged-attention kernel needs head_dim % 128 == 0 and whole "
            f"sublane tiles a block (got head_dim={head_dim}, "
            f"block_size={block_size}, {k_pool.dtype})"
        )
    if window is not None:
        if window < 1 or block_tables.shape[1] * block_size < window:
            raise ValueError(f"a window of {window} tokens needs a ring of at least as many (got {block_tables.shape[1]} blocks of {block_size})")
        return _paged_window_attention(
            q, k_pool, v_pool, jnp.asarray(layer, jnp.int32), block_tables, positions,
            scale=scale, tile_blocks=tile_blocks, impl=impl, window=window,
        )
    return _paged_attention(
        q, k_pool, v_pool, jnp.asarray(layer, jnp.int32), block_tables, positions,
        scale=scale, tile_blocks=tile_blocks, impl=impl,
    )


# The layer is an ARGUMENT of one jitted function, so a model's layers share
# one trace and one lowering of it: lowering the kernel to Mosaic takes the
# host ~0.2 s, and a 24-layer decode program that inlined it paid that 24
# times at every start, cached program or not (3.4 s of ``setup_s`` on the
# chip: my chip run, PR 25).
@functools.partial(jax.jit, static_argnames=("scale", "tile_blocks", "impl"))
def _paged_attention(
    q, k_pool, v_pool, layer, block_tables, positions, *, scale, tile_blocks, impl
):
    lengths = jnp.maximum(positions.astype(jnp.int32) + 1, 0)
    if impl == "jnp":
        return _paged_attention_jnp(
            q, k_pool, v_pool, layer, block_tables, lengths, scale, tile_blocks
        )
    return _paged_attention_pallas(
        q, k_pool, v_pool, layer, block_tables, lengths, scale, tile_blocks,
        interpret=impl == "kernel_interpret",
    )


# the window layers' call: a jitted function of its own, so that its kernel
# keeps its name and its scope in the optimized program (PERF.md, PR 33)
@functools.partial(jax.jit, static_argnames=("scale", "tile_blocks", "impl", "window"))
def _paged_window_attention(
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
    k_buf, v_buf, sems,                           # scratch
    *, scale: float, tile_blocks: int, block_size: int, table_width: int,
    kv_heads: int, head_dim: int, n_rep: int, per_kv_head: bool, window: Optional[int] = None,
):
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = lengths_ref[b]
    tile_tokens = tile_blocks * block_size
    n_tiles = (length + tile_tokens - 1) // tile_tokens
    rows = o_ref.shape[0]
    if window is None:
        tile_of = lambda i: i  # noqa: E731 (trip i of the walk is tile i)
    else:
        # the table is a ring: the walk starts at the tile of the oldest
        # position the query still sees, which always holds one it does see
        start = jnp.maximum(length - window, 0)
        first = start // tile_tokens
        n_tiles = n_tiles - first
        tile_of = lambda i: first + i  # noqa: E731

    def copies(tile, slot):
        """The tile's 2 * tile_blocks block copies into buffer ``slot``.
        Every tile is copied whole: columns past the lane's last block hold
        block ids all the same (the allocator's scratch block 0, or the
        table's last column; a ring's older blocks), and their scores are
        masked."""
        out = []
        for j in range(tile_blocks):
            if window is None:
                col = jnp.minimum(tile * tile_blocks + j, table_width - 1)
            else:
                col = (tile * tile_blocks + j) % table_width
            blk = tables_ref[b * table_width + col]
            dst = pl.ds(j * block_size, block_size)
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, blk], k_buf.at[slot, dst], sems.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[layer, blk], v_buf.at[slot, dst], sems.at[1, slot]))
        return out

    @pl.when(n_tiles > 0)
    def _first():
        for c in copies(tile_of(0), 0):
            c.start()

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
        slot = i % 2

        @pl.when(i + 1 < n_tiles)
        def _next():
            for c in copies(tile_of(i + 1), 1 - slot):
                c.start()

        for c in copies(tile_of(i), slot):
            c.wait()
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
        k_idx = tile_of(i) * tile_tokens + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
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
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, head_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
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
    tiles = latent_kernel_takes(width, value_dim, block_size, pool.dtype)
    if impl is None:
        impl = "kernel" if _on_tpu() and tiles else "jnp"
    if impl != "jnp" and not tiles:
        raise ValueError(
            f"the latent paged-attention kernel needs width % 128 == 0, value_dim % 128 == 0 and "
            f"whole sublane tiles a block (got width={width}, value_dim={value_dim}, "
            f"block_size={block_size}, {pool.dtype})"
        )
    return _paged_latent(
        q.astype(pool.dtype), pool, jnp.asarray(layer, jnp.int32), block_tables, positions,
        scale=scale, value_dim=value_dim, tile_blocks=tile_blocks, impl=impl,
    )


# one trace and one lowering for every layer, as ``_paged_attention``; and a
# Pallas call inside a jitted function of its own keeps its name in the
# optimized program, which is how a trace's reader finds it (``jit.scopes``)
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
    buf, sems, acc_ref,                           # scratch
    *, scale: float, tile_blocks: int, block_size: int, table_width: int, value_dim: int,
):
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = lengths_ref[b]
    tile_tokens = tile_blocks * block_size
    n_tiles = (length + tile_tokens - 1) // tile_tokens
    rows = q_ref.shape[0]

    def copies(tile, slot):
        """The tile's block copies into buffer ``slot``, whole (see the GQA kernel)."""
        out = []
        for j in range(tile_blocks):
            col = jnp.minimum(tile * tile_blocks + j, table_width - 1)
            blk = tables_ref[b * table_width + col]
            out.append(pltpu.make_async_copy(
                pool_hbm.at[layer, blk], buf.at[slot, pl.ds(j * block_size, block_size)], sems.at[slot]))
        return out

    @pl.when(n_tiles > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[...]                                    # [rows, width]

    def body(i, carry):
        m, l = carry
        slot = i % 2

        @pl.when(i + 1 < n_tiles)
        def _next():
            for c in copies(i + 1, 1 - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        tile = buf[slot]                              # [tile_tokens, width]
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
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, value_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_latent_attention",
    )(layer.reshape(1), lengths, block_tables.reshape(-1).astype(jnp.int32), q, pool)
    return out[:, :n_heads, :]


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
        sc = jnp.where(seen, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)  # masked: exp(NEG_INF - m) = 0
        if window is not None:
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
    return acc / l
