"""What a layer keeps of the past when it is served, said once: the table of
cache kinds under the serving forward (``models/serving.py``).

A kind is what a layer keeps and how it is addressed, ONE record
(:class:`CacheKind`) in :data:`CACHE_KINDS`: ``paged_kv`` (K and V rows a token
in the paged pool), ``paged_latent`` (one latent row a token in the pool),
``paged_indexed`` (a latent row a token and, in the layers that hold an indexer,
an index key a token beside it: attention reads the rows the indexer picks),
``state_slot`` (a float32 state a decode lane), ``window_ring`` (K and V rows
of the newest tokens in a ring a decode lane), ``ssm_slot`` (a Mamba-2
mixer's float32 state and its convolution's tail a decode lane) and
``delta_slot`` (a Gated-DeltaNet mixer's float32 delta-rule state and its
convolution's tail a decode lane: a seventh record and not ``ssm_slot`` under
another recurrence, because its leaves' shapes, its projections and its kernel
are its own; what the two share, a lane's tail and the counting of live lanes,
is stated once below, ``_tail_walk`` / ``_tail_step`` / ``_lanes_count``);
``docs/serving.md`` "What a request holds" has them side by side.  A layer is
of every kind whose ``layer_types`` name its type: an ``attention_mamba2`` layer
is of ``paged_kv`` AND ``ssm_slot``, and its mixers run one after the other on
the one norm, each adding its output to the stream; under ``mixer_block`` a
``mamba2`` layer is of ``ssm_slot`` alone, a ``full_attention`` layer of
``paged_kv`` alone, and an ``experts`` layer of NO kind (it keeps nothing of
the past, owns no row of any array and :func:`layer_kinds` is empty).  The forward's layer function calls every
kind's mixer the same way, and the engine (``serve/engine.py``) derives
admission, its refusals and ``/stats`` from the records: neither names a leaf
or asks which kind a model has, and nothing outside this file adds to the table.

A mixer is ``mix(p, x, h, cache, j) -> (x, cache)``: ``p`` the leaves of the
layer's subtree the kind names (``params``), ``x`` [b, s, d] the residual stream, ``h`` its norm, ``j`` the layer's row
in the kind's arrays.  It projects, writes this call's rows (or folds them into
the state), attends so that a token sees itself, and adds the output projection
to the stream.  A kind whose layers hand something on beside the stream inside one call (``hands``: the picks of
the newest layer that holds an indexer, which the layers after it attend over) has mixers of one more argument and
one more result, ``mix(p, x, h, cache, j, handed) -> (x, cache, handed)``, ``handed`` None at the first layer.
A kind builds one from the rows of a call (:class:`Rows`) in
each form an entry point may pick: ``step`` (a decode step by the paged path),
``walk`` (a chunk of the prefill walk) and the tests' reference forms ``table``
(a decode step over gathered rows) and ``wide`` (the whole prompt in one pass).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from determined_tpu.models import transformer
from determined_tpu.models.transformer import (
    FULL, HYBRID, LINEAR, MAMBA2, RETENTION, SLIDING, TransformerConfig, _gate_log, _gated, _gdn_conv, _gdn_out, _gdn_project,
    _gdn_split, _head_gated, _index_project, _latent_attend_local, _latent_project, _rms, _rope, _rope_first,
    _ssm_conv, _ssm_out, _ssm_project, _ssm_split, _times, gdn_bytes_per_slot, gdn_pool_shapes, kv_bytes_per_token, kv_cache_shape,
    ssm_bytes_per_slot, recent_rows_shapes, ssm_pool_shapes, state_bytes_per_slot, state_pool_shapes, window_ring_blocks,
    window_store_shape,
)
from determined_tpu.ops.gated_delta import gdn_chunk, gdn_decode
from determined_tpu.ops.attention import NEG_INF, _repeat_kv, reference_attention
from determined_tpu.ops.paged_attention import (
    COPY_SCHEDULE, attn_products, index_scores, index_topk, index_topk_mask, paged_chunk_attention, paged_decode_attention, paged_index_scores,
    paged_latent_attention, paged_picked_attention,
)
from determined_tpu.ops.retention import FOLD_EVERY, retention_chunk, retention_decode
from determined_tpu.ops.ssm import ssm_chunk, ssm_decode

#: what a request holds of a kind.  BLOCKS: rows a token in blocks the
#: allocator hands out, shareable by prefix, prefilled from any block edge.
#: LANE: a fixed store of its decode lane (a ring, a slot): not shareable, and
#: prefilled from 0 into a lane known before the prefill
BLOCKS, LANE = "blocks", "lane"


@dataclasses.dataclass(frozen=True)
class Rows:
    """The rows of one call of the serving forward: what its entry point knows
    of them, made once and read by every kind's mixer."""

    positions: jax.Array      # where rotary embeddings turn them: [s] alike in every lane, or [b, s]
    block_tables: jax.Array   # [b, T] each lane's blocks of the paged pool
    live: jax.Array           # which are a request's: a decode step's active lanes [b], a prefill's tokens [b, s]
    where: Optional[Tuple[jax.Array, jax.Array]]  # (block, slot) each lies at in the pool; None: no layer reads one
    block_size: Optional[int]
    lanes: Optional[jax.Array] = None  # [b] the decode lane of each row; None: row ``b`` is lane ``b``
    # a decode step's: the position of the token each lane consumes [b], -1 idle; the same, an idle lane at 0
    lane_positions: Optional[jax.Array] = None
    pos: Optional[jax.Array] = None
    # the walk's: this chunk's index among chunks of its width, the index the call's first chunk has there (or none
    # has), a token's place in its chunk [chunk], and the narrow chunks it holds (a wide chunk: several)
    chunk: Any = 0
    first_chunk: Any = 0
    offsets: Optional[jax.Array] = None
    parts: int = 1


def _narrow_chunks(rows: Rows, step: Callable, carry: Any, xs: Tuple[jax.Array, ...], axes: Tuple[int, ...], out_axes: Tuple[int, ...]):
    """``step(carry, rows, *xs) -> (carry, ys)`` over the narrow chunks of a walk's chunk, in order: what a layer
    keeps of the past is written and read a narrow chunk at a time, as that chunk's own iteration would have, while
    the products either side take the call's rows whole.  ``xs`` are cut along ``axes`` (the call's token axis in
    each), the part's ``rows`` are those of a narrow chunk's own iteration, and ``ys`` come back side by side along
    ``out_axes``.  A narrow chunk is its own one part: ``step`` is called on what came, and no operation is added.
    A wide chunk's parts are a ``scan``: one loop a layer whatever the parts, its body the narrow iteration's."""
    if rows.parts == 1:
        return step(carry, rows, *xs)
    parts, n = rows.parts, rows.offsets.shape[0] // rows.parts

    def cut(a, axis):  # [.., parts * n, ..] -> [parts, .., n, ..]
        axis %= a.ndim
        return jnp.moveaxis(a.reshape(a.shape[:axis] + (parts, n) + a.shape[axis + 1:]), axis, 0)

    def join(a, axis):  # and back
        axis %= a.ndim - 1
        a = jnp.moveaxis(a, 0, axis)
        return a.reshape(a.shape[:axis] + (parts * n,) + a.shape[axis + 2:])

    where = None if rows.where is None else tuple(cut(a, 1) for a in rows.where)

    def body(carry, part):
        i, positions, live, where, part_xs = part
        part_rows = dataclasses.replace(
            rows, positions=positions, live=live, where=where, chunk=rows.chunk * parts + i, first_chunk=None,
            offsets=rows.offsets[:n], parts=1,
        )
        return step(carry, part_rows, *part_xs)

    every = (jnp.arange(parts), cut(rows.positions, 0), cut(rows.live, 1), where, tuple(cut(x, axis) for x, axis in zip(xs, axes)))
    carry, ys = jax.lax.scan(body, carry, every)
    return carry, tuple(join(y, axis) for y, axis in zip(ys, out_axes))


# -- K and V rows: the paged pool's and the window ring's ---------------------


def _attn_proj(p: Dict[str, Any], x: jax.Array, dtype: Any) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q/k/v projections as ``Attention`` computes them, to [b, heads, s, d]."""
    # under the caller's scope (``serve.attn.qkv``, with the rope that follows)
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"]["kernel"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"]["kernel"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"]["kernel"].astype(dtype))
    return q, k, v


def _pool_rows(x: jax.Array, lead: Tuple[int, ...]) -> jax.Array:
    """Projected k or v ``[b, kv_heads, s, head_dim]`` as the pool stores a
    token, ``[*lead, kv_heads * head_dim]``: ``lead`` is (b, s), or (b,) where s is 1."""
    return x.transpose(0, 2, 1, 3).reshape(*lead, x.shape[1] * x.shape[3])


def _kv_mixer(cfg: TransformerConfig, kind: "CacheKind", rows: Rows, where, attend, drop: bool = False, walk: bool = False):
    """The mixer of a kind that keeps K and V rows: q/k/v and rope, this call's
    rows into the kind's two leaves at ``where`` = (block, slot), each [b, s] (or
    [b] where s is 1; under ``drop`` a block id past the store's end drops the
    row: idle lanes, padding), then ``attend(q, k, v, cache, j)`` against the
    store that now holds them, with q [b, n_heads, s, head_dim] and this call's
    own k, v [b, kv_heads, s, head_dim], to [b, n_heads, s, head_dim].
    As ``Attention`` states them: under ``attn_output_gate`` a head of ``wq`` is
    ``[query | gate]`` and what the head attended to is multiplied by
    ``sigmoid(gate)`` before ``wo``; under ``qk_norm`` q and k pass an RMSNorm a
    head; rotary turns the first ``partial_rotary_factor`` of a head.
    ``walk``: ``attend`` is ``rows -> attend`` of a narrow chunk's rows, and a
    wide chunk's narrow chunks are written and attend one after the other
    (``_narrow_chunks``: a ring holds ONE narrow chunk more than its window),
    under the one projection either side."""
    keys, vals = kind.leaves
    rope, dt, mode = cfg.rope(kind.layer_types[0]), cfg.dtype, "drop" if drop else None
    # a cache of two kinds: the device trace tells them apart
    scope = "serve.attn.window" if kind.layer_types[0] == SLIDING else "serve.attn.full"
    split = functools.partial(jax.named_scope, scope) if cfg.window_layers else contextlib.nullcontext

    def mix(p, x, h, cache, j):
        with jax.named_scope("serve.attn.qkv"):
            q, k, v = _attn_proj(p, _times(h, cfg.attention_in_multiplier), dt)
            if cfg.attn_output_gate:
                q, gate = q[..., : cfg.head_dim], q[..., cfg.head_dim:]
            if cfg.qk_norm:
                q, k = _rms(q, p["q_norm"], cfg.norm_eps), _rms(k, p["k_norm"], cfg.norm_eps)
            q = _rope_first(q, rows.positions, rope, cfg.partial_rotary_factor)
            k = _rope_first(_times(k, cfg.key_multiplier), rows.positions, rope, cfg.partial_rotary_factor)

        def chunk(store, rows, q, k, v, phys, slots):
            with jax.named_scope("serve.kv.write"):
                store = (
                    store[0].at[j, phys, slots].set(_pool_rows(k, phys.shape), mode=mode),
                    store[1].at[j, phys, slots].set(_pool_rows(v, phys.shape), mode=mode),
                )
            with jax.named_scope("serve.attn.attend"):  # whichever form the entry point picked
                with split():
                    return store, ((attend(rows) if walk else attend)(q, k, v, {**cache, keys: store[0], vals: store[1]}, j),)

        store, (att,) = _narrow_chunks(rows, chunk, (cache[keys], cache[vals]), (q, k, v, *where), (2, 2, 2, 1, 1), (2,))
        cache = {**cache, keys: store[0], vals: store[1]}
        if cfg.attn_output_gate:
            with jax.named_scope("serve.attn.gate"):
                att = _gated(att, gate)
        with jax.named_scope("serve.attn.attend"):
            att = att.transpose(0, 2, 1, 3)  # [b, s, h, hd]
        with jax.named_scope("serve.attn.out"):
            out = jnp.einsum("bshk,hkD->bsD", att, p["wo"]["kernel"].astype(dt))
            return x + _times(out, cfg.attention_out_multiplier), cache

    return mix


def _attend_local(q, k, v, cache, j):
    """Causal, over this call's own keys: the wide prefill's prompts start at position 0."""
    return reference_attention(q, k, v, causal=True)


def _attend_paged(cfg: TransformerConfig, kind: "CacheKind", block_tables: jax.Array, positions: jax.Array, window: Optional[int] = None):
    """One query a lane against the lane's live blocks, read where they lie
    in the kind's store (``ops/paged_attention.py``); ``positions`` [b], -1 = idle.
    ``window``: the layer slides, ``block_tables`` are the lanes' rings, and a
    lane reads its newest ``window`` tokens there."""
    keys, vals = kind.leaves

    def attend(q, k, v, cache, j):
        att = paged_decode_attention(
            q[:, :, 0, :], cache[keys], cache[vals], j, block_tables, positions, scale=cfg.head_dim ** -0.5,
            window=window,
        )
        return att.astype(cfg.dtype)[:, :, None, :]

    return attend


def _attend_chunk(cfg: TransformerConfig, kind: "CacheKind", block_tables: jax.Array, chunk: jax.Array, window: Optional[int] = None):
    """The prefill walk's read: the queries of chunk ``chunk`` (positions
    ``chunk * s ..``) against the keys up to the chunk's end, read from the
    store a tile at a time (``ops/paged_attention.py paged_chunk_attention``);
    under ``window`` from the lanes' rings, and no key older than the window."""
    keys, vals = kind.leaves

    def attend(q, k, v, cache, j):
        b, h, s, d = q.shape
        att = paged_chunk_attention(
            q.reshape(b, cfg.kv_heads, h // cfg.kv_heads, s, d), cache[keys], cache[vals], j, block_tables, chunk,
            scale=cfg.head_dim ** -0.5, window=window,
        )
        return att.astype(cfg.dtype).reshape(b, h, s, d)

    return attend


def _masked_attention(cfg: TransformerConfig, q: jax.Array, keys: jax.Array, vals: jax.Array, mask: jax.Array) -> jax.Array:
    """Queries against gathered keys under ``mask`` (True = may see: ``[s, keys]``
    where the lanes are alike, else ``[b, s, keys]``) and a float32 softmax."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, keys, preferred_element_type=jnp.float32)
    logits = logits * cfg.head_dim ** -0.5
    seen = mask[None, None] if mask.ndim == 2 else mask[:, None]
    probs = jax.nn.softmax(jnp.where(seen, logits, NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vals.dtype), vals)


def _table_mask(rows: Rows) -> jax.Array:
    """A decode step's mask over the gathered table, [b, 1, T * block_size]:
    every cache position up to and including the lane's current token."""
    with jax.named_scope("serve.attn.attend"):
        kv_len = rows.block_tables.shape[1] * rows.block_size
        return ((jnp.arange(kv_len)[None, :] <= rows.positions) & rows.live[:, None])[:, None, :]


def _attend_table(cfg: TransformerConfig, block_tables: jax.Array, mask: jax.Array):
    """Queries against every token of every table column, gathered from the
    pool with the KV heads repeated, under ``mask``: decode without the paged
    path, the oracle that path is tested against."""
    keys, vals = PAGED_KV.leaves

    def gathered(pool: jax.Array, j: int) -> jax.Array:  # [b, n_heads, T * block_size, head_dim]
        b, t = block_tables.shape
        found = pool[j, block_tables].reshape(b, t * pool.shape[2], cfg.kv_heads, -1)
        return _repeat_kv(found.transpose(0, 2, 1, 3), cfg.n_heads // cfg.kv_heads)

    def attend(q, k, v, cache, j):
        return _masked_attention(cfg, q, gathered(cache[keys], j), gathered(cache[vals], j), mask)

    return attend


def _attend_ring_table(cfg: TransformerConfig, positions: jax.Array):
    """A window layer's decode without the paged path: every lane's whole
    ring, gathered, each slot masked by the position it must hold.  Slot ``s``
    of a lane at position ``pos`` holds ``p = pos - (pos - s) % ring`` if it
    holds anything of this request; the query sees it if ``p >= 0`` and ``p >
    pos - window``.  What an earlier request left in the lane is never seen."""
    keys, vals = WINDOW_RING.leaves

    def attend(q, k, v, cache, j):
        b = q.shape[0]
        ring = cache[keys].shape[1] // b * cache[keys].shape[2]
        found = lambda pool: _repeat_kv(  # noqa: E731
            pool[j].reshape(b, ring, cfg.kv_heads, -1).transpose(0, 2, 1, 3), cfg.n_heads // cfg.kv_heads
        )
        pos = positions[:, None]
        held = pos - (pos - jnp.arange(ring)[None, :]) % ring  # [b, ring]
        mask = (held >= 0) & (held > pos - cfg.sliding_window) & (pos >= 0)
        return _masked_attention(cfg, q, found(cache[keys]), found(cache[vals]), mask[:, None, :])

    return attend


def _paged_shapes(cfg: TransformerConfig, sizes: Any) -> Tuple[Tuple[int, ...], ...]:
    return (kv_cache_shape(cfg, sizes.num_blocks, sizes.block_size),) * (1 if cfg.latent else 2)


def _rows_report(cfg: TransformerConfig, sizes: Any = None, live: int = 0, gauges: Any = None) -> Dict[str, Any]:
    """``rows_per_token``: the rows a token owns in the pool over all layers, said
    where a block has more than one attention sublayer (``shortcut_block``: two a block)."""
    return {"rows_per_token": cfg.paged_layers} if cfg.attn_sublayers > 1 else {}


def layers_by_kind(cfg: TransformerConfig) -> Dict[str, int]:
    """How many layers are of each kind of the model, by the kind's name, and
    under ``none`` how many are of no kind (expert layers alone keep nothing of the past)."""
    counts = {kind.name: len(kind.layers(cfg)) for kind in cache_kinds(cfg)}
    return {**counts, "none": sum(1 for i in range(cfg.n_layers) if not layer_kinds(cfg, i))}


def _layers_report(cfg: TransformerConfig) -> Dict[str, Any]:
    """``layers_by_kind``, said by the kinds of a model whose layers are not alike (``mixer_block``)."""
    return {"layers_by_kind": layers_by_kind(cfg)} if cfg.mixer_block else {}


def _kv_report(cfg: TransformerConfig, sizes: Any = None, live: int = 0, gauges: Any = None) -> Dict[str, Any]:
    """``attn_products``: what a tile of the GQA decode kernel multiplies at this
    model's heads (``ops/paged_attention.py``), and how its tiles are copied
    (``tile_copies``, ``lane_prefetch``: that file's ``COPY_SCHEDULE``); absent
    for latent layers, whose heads all share a row, and where no layer reads K
    and V.  Under ``mixer_block`` also ``layers_by_kind``."""
    if cfg.latent or len(cfg.rowless_layers) == cfg.n_layers:
        return {}
    return {"attn_products": attn_products(cfg.n_heads // cfg.kv_heads), **COPY_SCHEDULE, **_rows_report(cfg), **_layers_report(cfg)}


def _latent_report(cfg: TransformerConfig, sizes: Any = None, live: int = 0, gauges: Any = None) -> Dict[str, Any]:
    """How the latent decode kernel's tiles are copied (``COPY_SCHEDULE``), where a
    layer keeps latent rows; and ``rows_per_token``."""
    return {**COPY_SCHEDULE, **_rows_report(cfg)} if cfg.latent and cfg.paged_layers and not cfg.indexer_types else {}


def _ring_step(cfg: TransformerConfig, rows: Rows, cache: Dict[str, jax.Array], table: bool = False):
    """Row ``b`` of a decode step IS lane ``b``, whose ring holds the lane's
    newest tokens by position: the step reads ``min(position + 1, window)`` of
    them and nothing older, by the paged path over the ring (``table``: gathered)."""
    lanes, store = rows.pos.shape[0], cache[WINDOW_RING.leaves[0]].shape[1]
    ring_blocks = store // lanes
    with jax.named_scope("serve.kv.write"):  # lane b's ring; an idle lane's row is dropped
        first = jnp.arange(lanes, dtype=jnp.int32) * ring_blocks
        phys = jnp.where(rows.live, first + (rows.pos // rows.block_size) % ring_blocks, store)
    if table:
        attend = _attend_ring_table(cfg, rows.lane_positions)
    else:
        rings = first[:, None] + jnp.arange(ring_blocks, dtype=jnp.int32)[None, :]
        attend = _attend_paged(cfg, WINDOW_RING, rings, rows.lane_positions, cfg.sliding_window)
    return _kv_mixer(cfg, WINDOW_RING, rows, (phys, rows.where[1]), attend, drop=True)


def _ring_walk(cfg: TransformerConfig, cache: Dict[str, jax.Array], lanes: jax.Array, chunk_tokens: int):
    """The walk keeps a prompt's rows in the ring of the decode lane it will run
    in.  A chunk's rows go to the slots of their positions, and its queries read
    the ring back to ``window - 1`` positions before each of them: the ring is
    one chunk longer than the window, so no row a query of the chunk still sees
    is overwritten; a wide chunk's narrow chunks are written and read one after
    the other, as their own iterations would have."""
    _, store, block_size, _ = cache[WINDOW_RING.leaves[0]].shape
    ring_blocks = window_ring_blocks(cfg, block_size, chunk_tokens)
    if store % ring_blocks:
        raise ValueError(
            f"the window store ({store} blocks) is not whole rings of {ring_blocks} blocks: it was sized "
            f"for another prefill chunk than {chunk_tokens} tokens"
        )
    with jax.named_scope("serve.kv.write"):
        first = lanes * ring_blocks
        rings = first[:, None] + jnp.arange(ring_blocks, dtype=jnp.int32)[None, :]

    def at_chunk(rows: Rows):
        with jax.named_scope("serve.kv.write"):  # rows outside [start, len) are dropped
            cols = (rows.chunk * (rows.parts * chunk_tokens // block_size) + rows.offsets // block_size) % ring_blocks
            phys = jnp.where(rows.live, first[:, None] + cols[None, :], store)
        attend = lambda rows: _attend_chunk(cfg, WINDOW_RING, rings, rows.chunk, cfg.sliding_window)  # noqa: E731
        return _kv_mixer(cfg, WINDOW_RING, rows, (phys, rows.where[1]), attend, drop=True, walk=True)

    return at_chunk


def _ring_shapes(cfg: TransformerConfig, sizes: Any) -> Tuple[Tuple[int, ...], ...]:
    lanes, chunk_tokens = sizes.max_batch, sizes.prefill_chunk
    if lanes is None or chunk_tokens is None or chunk_tokens % sizes.block_size:
        raise ValueError(
            "a model with sliding-window layers needs its lanes and its prefill chunk (whole blocks) "
            f"to size the window store (got lanes={lanes}, chunk_tokens={chunk_tokens})"
        )
    return (window_store_shape(cfg, lanes, sizes.block_size, chunk_tokens),) * 2


def _ring_count(cfg: TransformerConfig, active: jax.Array, pos: jax.Array) -> jax.Array:
    """The cached tokens this step's attention reads, by kind of layer."""
    lens = jnp.where(active, pos + 1, 0).astype(jnp.float32)
    n_window = len(cfg.window_layers)
    return jnp.stack([
        jnp.sum(lens) * (cfg.n_layers - n_window), jnp.sum(jnp.minimum(lens, cfg.sliding_window)) * n_window,
    ])


def _ring_report(cfg: TransformerConfig, sizes: Any, live: int = 0, gauges: Any = None) -> Dict[str, Any]:
    """``window_store`` (empty where no layer slides): the bytes the store takes
    whatever the contexts, and the tokens a lane's ring holds a layer."""
    if not cfg.window_layers:
        return {"window_store": {}}
    ring_tokens = window_ring_blocks(cfg, sizes.block_size, sizes.prefill_chunk) * sizes.block_size
    return {"window_store": {"window_store_bytes": _nbytes(WINDOW_RING, cfg, sizes), "ring_tokens": ring_tokens}, **_kv_report(cfg)}


def _ring_setup(cfg: TransformerConfig, sizes: Any) -> Dict[str, Any]:
    # what a token costs in each kind of cache, then what /stats says of the store
    layer, n_window = kv_bytes_per_token(cfg) // cfg.n_layers, len(cfg.window_layers)
    said = _ring_report(cfg, sizes)
    return {"bytes_per_token_full": layer * (cfg.n_layers - n_window), "bytes_per_token_window": layer * n_window,
            **said.pop("window_store"), **said}


# -- a latent row a token -------------------------------------------------------
#
# Its forms ``attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, j)`` with q_nope
# [b, n_heads, s, qk_nope], q_rope [b, n_heads, s, qk_rope] (after rope), this
# call's own latent rows c_kv [b, s, kv_lora] (after their norm) and k_r [b, s,
# qk_rope] (after rope), ``wkv_b`` [kv_lora, n_heads, qk_nope + v] and the pool
# that already holds the rows; they return [b, s, n_heads, v_head_dim].
# ``_latent_attend_local`` (``models/transformer.py``) expands keys and values a
# head from the rows, as the equations are published; the others stay in the
# latent space (``q_lat_h = q_nope_h W^K_h``, ``o_h = (sum p c_kv) W^V_h``: the
# same mathematics, and one row a token serves every head's scores and values).


def _latent_mixer(cfg: TransformerConfig, rows: Rows, attend, select=None):
    """Latent attention's projections, this call's rows ``[c_kv after its norm |
    k_r after rope | zeros]`` into the pool at ``rows.where``, ``attend``
    against the pool that now holds them, under ``attn_output_gate`` the gate a
    head, and the output projection.  Under
    ``indexer_types`` (``select``: :func:`_indexer`) the mixer is of the kind
    that hands on: a layer that holds an indexer first makes its own picks,
    every layer attends over the picks it holds or was handed, and returns them."""
    (phys, slots), leaf = rows.where, PAGED_LATENT.leaves[0]
    rope = cfg.rope(FULL)

    def mix(p, x, h, cache, j, picks=None):
        with jax.named_scope("serve.mla"):
            q_nope, q_rope, c_kv, k_r, c_q = _latent_project(cfg, p, h, rows.positions, rope)
            with jax.named_scope("serve.kv.write"):
                row = jnp.concatenate([c_kv, k_r], axis=-1)
                row = jnp.pad(row, ((0, 0), (0, 0), (0, cache[leaf].shape[-1] - row.shape[-1])))
                cache = {**cache, leaf: cache[leaf].at[j, phys, slots].set(row.reshape(*phys.shape, -1))}
        row = cfg.index_layer(j) if select is not None else None  # under an indexer every layer keeps latent rows: row j of the pool is layer j's
        if row is not None:
            with jax.named_scope("serve.dsa"):
                cache, picks = select(p, c_q, h, cache, row)
        with jax.named_scope("serve.mla"):
            att = attend(q_nope, q_rope, c_kv, k_r, p["wkv_b"], cache, j, picks)
            if cfg.attn_output_gate:
                with jax.named_scope("serve.mla.gate"):  # one value a head from the normed input
                    att = _head_gated(cfg, p, h, att)
            return x + jnp.einsum("bshv,hvD->bsD", att, p["wo"].astype(cfg.dtype)), cache, picks

    return mix if select is not None else lambda p, x, h, cache, j: mix(p, x, h, cache, j)[:2]


def _indexer(cfg: TransformerConfig, rows: Rows, score, keys: Optional[int] = None, as_mask: bool = True):
    """The indexer of a layer that holds one, stated once under every form of
    the serving forward: ``select(p, c_q, h, cache, r) -> (cache, picks)`` with
    ``r`` the layer's row in the index array.  Its projections
    (``_index_project``), this call's index keys into the array at
    ``rows.where`` (where the tokens' latent rows went), ``score(q, w, k, keys
    array, r)`` -> the float32 scores [b, s, keys] of this call's queries
    against the lanes' keys (the form's own read: ``_index_paged``,
    ``_index_gathered``, ``_index_local``), and the exact top ``index_topk``
    among the ``keys`` positions of a lane's context (absent: the table's) that
    a query may see, those up to its own: the picks are positions of the lane's
    own context.  What a layer hands on is the picks themselves (a decode step's
    paged form turns them into places in the pool) or, ``as_mask``, the same
    selection as a mask ``[b, s, keys]``, made once for the layers that share it
    (of the walk's wide chunk a narrow chunk's queries at a time: ``_narrow_chunks``)."""
    (phys, slots), leaf = rows.where, PAGED_INDEXED.leaves[1]
    rope = cfg.rope(FULL)
    keys = keys or rows.block_tables.shape[1] * rows.block_size
    with jax.named_scope("serve.dsa.topk"):
        seen = jnp.arange(keys) <= rows.positions[..., None]  # [(b,) s, keys]
        if rows.live.ndim == 1:  # a decode step's idle lanes see none
            seen = seen & rows.live[:, None, None]

    def select(p, c_q, h, cache, r):
        with jax.named_scope("serve.dsa.project"):
            q, w, k = _index_project(cfg, p, c_q, h, rows.positions, rope)
        with jax.named_scope("serve.dsa.write"):
            cache = {**cache, leaf: cache[leaf].at[r, phys, slots].set(k.reshape(*phys.shape, -1))}

        def chunk(_, rows, q, w, seen):  # a wide chunk's scores and its mask a narrow chunk's queries at a time: [b, chunk, keys]
            with jax.named_scope("serve.dsa.index"):  # the score pass alone: what its roofline share times
                scores = score(q, w, k, cache[leaf], r)
            with jax.named_scope("serve.dsa.topk"):
                return None, ((index_topk_mask if as_mask else index_topk)(scores, seen, cfg.index_topk),)

        return cache, _narrow_chunks(rows, chunk, None, (q, w, seen), (1, 1, -2), (1,))[1][0]  # a decode step's picks: one part

    return select


def _index_paged(rows: Rows):
    """One query a lane against the lane's live index keys, read where they lie (``ops/paged_attention.py``)."""
    return lambda q, w, k, keys, r: paged_index_scores(q[:, 0], w[:, 0], keys, r, rows.block_tables, rows.lane_positions)[:, None]


def _index_gathered(rows: Rows):
    """This call's queries against every key of every table column, gathered: the walk's read, and the decode oracle's."""
    return lambda q, w, k, keys, r: index_scores(q, w, keys[r, rows.block_tables].reshape(q.shape[0], -1, keys.shape[-1]))


def _index_local(q, w, k, keys, r):
    """Against this call's own keys: the wide prefill's prompts start at position 0."""
    return index_scores(q, w, k)


def _latent_split(cfg, wkv_b, q_nope):
    """(queries in the latent space [b, h, s, kv_lora], W^V [kv_lora, h, v])."""
    w = wkv_b.astype(cfg.dtype)
    return jnp.einsum("bhsn,chn->bhsc", q_nope, w[..., : cfg.qk_nope_head_dim]), w[..., cfg.qk_nope_head_dim:]


def _latent_attend_paged(cfg: TransformerConfig, block_tables: jax.Array, positions: jax.Array):
    """One query a lane against the lane's live latent rows, read where they
    lie in the pool (``ops/paged_attention.py``); ``positions`` [b], -1 = idle.
    Under ``picks`` against the rows those name alone."""
    (leaf,) = PAGED_LATENT.leaves

    def attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, j, picks=None):
        q_lat, w_v = _latent_split(cfg, wkv_b, q_nope)
        q = jnp.concatenate([q_lat, q_rope], axis=-1)[:, :, 0]
        q = jnp.pad(q, ((0, 0), (0, 0), (0, cache[leaf].shape[-1] - q.shape[-1])))
        if picks is not None:  # under the op's own scopes: serve.mla.gather, serve.mla.attend
            out = paged_picked_attention(
                q, cache[leaf], j, block_tables, picks[0][:, 0], picks[1][:, 0], scale=cfg.attn_scale, value_dim=cfg.kv_lora_rank
            )
        else:
            with jax.named_scope("serve.mla.attend"):  # the kernel alone: what its roofline share times
                out = paged_latent_attention(
                    q, cache[leaf], j, block_tables, positions, scale=cfg.attn_scale, value_dim=cfg.kv_lora_rank
                )
        return jnp.einsum("bhc,chv->bhv", out.astype(cfg.dtype), w_v)[:, None]

    return attend


def _latent_attend_chunk(cfg: TransformerConfig, rows: Rows):
    """The prefill walk's read (``_attend_chunk``) in the latent space: every
    head's queries ``[q_lat | q_rope | zeros]`` against the pool's rows, whose
    first ``kv_lora_rank`` columns are the values; under ``mask`` (an indexer's
    picks, ``[b, s, keys]``) a query sees the keys it marks alone.  The call's
    rows are all in the pool by then; each narrow chunk of them reads the tiles
    up to its own end (``_narrow_chunks``)."""
    (leaf,) = PAGED_LATENT.leaves

    def attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, j, mask=None):
        q_lat, w_v = _latent_split(cfg, wkv_b, q_nope)
        q = jnp.concatenate([q_lat, q_rope], axis=-1)
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, cache[leaf].shape[-1] - q.shape[-1]),))

        def chunk(_, rows, q, mask=None):
            return None, (paged_chunk_attention(
                q[:, None], cache[leaf], None, j, rows.block_tables, rows.chunk, scale=cfg.attn_scale, value_dim=cfg.kv_lora_rank, mask=mask
            ),)

        with jax.named_scope("serve.mla.attend"):
            xs, axes = ((q,), (2,)) if mask is None else ((q, mask), (2, 1))
            _, (out,) = _narrow_chunks(rows, chunk, None, xs, axes, (3,))
        return jnp.einsum("bhsc,chv->bshv", out[:, 0].astype(cfg.dtype), w_v)

    return attend


def _latent_attend_table(cfg: TransformerConfig, block_tables: jax.Array, mask: jax.Array):
    """Queries against every row of every table column, gathered from the
    pool, under ``mask`` (as ``_attend_table``'s; and under ``picked``, an
    indexer's picks as a mask, the keys it marks alone) and a float32 softmax:
    decode without the paged path, the oracle that path is tested against."""
    (leaf,) = PAGED_LATENT.leaves

    def attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, j, picked=None):
        b, t = block_tables.shape
        r = cfg.kv_lora_rank
        found = cache[leaf][j, block_tables].reshape(b, t * cache[leaf].shape[2], -1)
        lat, rot = found[..., :r], found[..., r: r + cfg.qk_rope_head_dim]
        q_lat, w_v = _latent_split(cfg, wkv_b, q_nope)
        logits = jnp.einsum("bhsc,bkc->bhsk", q_lat, lat, preferred_element_type=jnp.float32)
        logits = logits + jnp.einsum("bhsr,bkr->bhsk", q_rope, rot, preferred_element_type=jnp.float32)
        seen = mask[None, None] if mask.ndim == 2 else mask[:, None]
        if picked is not None:
            seen = seen & picked[:, None]
        probs = jax.nn.softmax(jnp.where(seen, logits * cfg.attn_scale, NEG_INF), axis=-1)
        out = jnp.einsum("bhsk,bkc->bhsc", probs.astype(lat.dtype), lat)
        return jnp.einsum("bhsc,chv->bshv", out, w_v)

    return attend


def _indexed_shapes(cfg: TransformerConfig, sizes: Any) -> Tuple[Tuple[int, ...], ...]:
    """The latent pool, and the index keys of the layers that hold an indexer under the same block ids."""
    return _paged_shapes(cfg, sizes) + ((len(cfg.index_layers), sizes.num_blocks, sizes.block_size, cfg.index_head_dim),)


def _indexed_count(cfg: TransformerConfig, active: jax.Array, pos: jax.Array) -> jax.Array:
    """Summed over the lanes: the cached tokens the step's indexers score (the
    live context in each layer that holds one), the rows its attention reads
    (``min(context, index_topk)`` in each layer) and the rows a program that
    read every live row would have."""
    lens = jnp.where(active, pos + 1, 0).astype(jnp.float32)
    live = jnp.sum(lens)
    return jnp.stack([live * len(cfg.index_layers), jnp.sum(jnp.minimum(lens, cfg.index_topk)) * cfg.paged_layers, live * cfg.paged_layers])


def _index_array(cfg: TransformerConfig, sizes: Any) -> Dict[str, Any]:
    """The index array: the layers that own a row of it, a token's bytes over them, its bytes."""
    per_token = len(cfg.index_layers) * cfg.index_head_dim * jnp.dtype(cfg.dtype).itemsize
    return {"layers": len(cfg.index_layers), "bytes_per_token": per_token, "bytes": _nbytes(PAGED_INDEXED, cfg, sizes, slice(1, 2))}


def _indexed_report(cfg: TransformerConfig, sizes: Any, live: int = 0, gauges: Any = None) -> Dict[str, Any]:
    """``index_keys``: the second array of a model whose attention reads the keys an indexer picks, and ``index_topk``."""
    if not cfg.index_layers:
        return {}
    return {"index_keys": {**_index_array(cfg, sizes), "index_topk": cfg.index_topk}, **COPY_SCHEDULE, **_rows_report(cfg)}


def _indexed_setup(cfg: TransformerConfig, sizes: Any) -> Dict[str, Any]:
    return {**{"index_" + k: v for k, v in _index_array(cfg, sizes).items()}, "index_topk": cfg.index_topk, **COPY_SCHEDULE}


# -- a state a lane ---------------------------------------------------------------
#
# Its forms ``retain(q, k, v, log_g, cache, j)`` with q [b, n_heads, s,
# head_dim], k, v [b, kv_heads, s, head_dim] and the gate's logarithm [b,
# kv_heads, s]; they return ([b, n_heads, s, head_dim], the cache with the lanes'
# slots updated): write and attend in one, the state is both.


def _retention_proj(cfg: TransformerConfig, p: Dict[str, Any], h: jax.Array, positions: jax.Array):
    """A retention layer's q, k, v ``[b, heads, s, d]`` as ``Retention`` makes
    them (a norm a head, rotary) and the gate's logarithm ``[b, kv_heads, s]``."""
    q, k, v = _attn_proj(p, h, cfg.dtype)
    log_g = _gate_log(cfg, h @ p["wg"]["kernel"].astype(cfg.dtype)).transpose(0, 2, 1)
    if cfg.qk_norm:
        q, k = _rms(q, p["q_norm"], cfg.norm_eps), _rms(k, p["k_norm"], cfg.norm_eps)
    rope = cfg.rope(RETENTION)
    return _rope(q, positions, rope), _rope(k, positions, rope), v, log_g


def _state_mixer(cfg: TransformerConfig, rows: Rows, retain):
    """A power-retention layer writes no row and attends to none: ``retain``
    updates its lanes' slots of the state pool and answers from them."""

    def mix(p, x, h, cache, j):
        with jax.named_scope("serve.retention.qkvg"):
            q, k, v, log_g = _retention_proj(cfg, p, h, rows.positions)
        with jax.named_scope("serve.retention.state"):  # decay, update, query, normalise
            att, cache = retain(q, k, v, log_g, cache, j)
        with jax.named_scope("serve.retention.out"):
            return x + jnp.einsum("bshk,hkD->bsD", att.transpose(0, 2, 1, 3), p["wo"]["kernel"].astype(cfg.dtype)), cache

    return mix


def _state_chunk(cfg: TransformerConfig, rows: Rows, cache: Any = None):
    """``s`` tokens a row after what the slots of the rows' lanes hold (nothing,
    in the walk's first chunk: a sequence starts from a zeroed slot), and into
    them: the prefill walk's chunk, and the wide prefill as one chunk.  A chunk
    is folded whole: it leaves its lanes no recent row pending.  A wide chunk's
    narrow chunks are answered and folded one after the other, the state in
    hand (``_narrow_chunks``: the chunk kernel sees a narrow chunk's tokens a
    call either way), and the slots are read and written once a chunk."""
    state_leaf, norm_leaf, *_, pending_leaf = STATE_SLOT.leaves
    lanes = jnp.arange(rows.live.shape[0]) if rows.lanes is None else rows.lanes
    fresh = rows.chunk == rows.first_chunk

    def retain(q, k, v, log_g, cache, j):
        state, norm = cache[state_leaf][j, lanes], cache[norm_leaf][j, lanes]
        state, norm = jnp.where(fresh, 0.0, state), jnp.where(fresh, 0.0, norm)

        def chunk(held, rows, q, k, v, log_g):
            out, *held = retention_chunk(q, k, v, log_g, *held, rows.live)
            return tuple(held), (out,)

        (state, norm), (out,) = _narrow_chunks(rows, chunk, (state, norm), (q, k, v, log_g), (2, 2, 2, 2), (2,))
        return out.astype(cfg.dtype), {
            **cache, state_leaf: cache[state_leaf].at[j, lanes].set(state), norm_leaf: cache[norm_leaf].at[j, lanes].set(norm),
            pending_leaf: cache[pending_leaf].at[j, lanes].set(0),
        }

    return _state_mixer(cfg, rows, retain)


def _state_step(cfg: TransformerConfig, rows: Rows, cache: Any = None):
    """One token a lane, row ``b`` of the batch IS lane ``b``: the token joins
    the lane's recent rows and is answered from them and from the slot as it
    lies; a lane whose rows are whole folds them into its slot, the one write
    of ``FOLD_EVERY`` tokens (``ops/retention.py retention_decode``: the Pallas
    kernel on a TPU, in place); a lane that is not live leaves all alone."""
    state_leaf, norm_leaf, *recent = STATE_SLOT.leaves

    def retain(q, k, v, log_g, cache, j):
        out, state, norm, newest = retention_decode(
            q[:, :, 0], k[:, :, 0], v[:, :, 0], log_g[:, :, 0], cache[state_leaf], cache[norm_leaf],
            tuple(cache[leaf] for leaf in recent), j, rows.live,
        )
        return out.astype(cfg.dtype)[:, :, None, :], {**cache, state_leaf: state, norm_leaf: norm, **dict(zip(recent, newest))}

    return _state_mixer(cfg, rows, retain)


def _state_shapes(cfg: TransformerConfig, sizes: Any) -> Tuple[Tuple[int, ...], ...]:
    if sizes.max_batch is None:
        raise ValueError("a model with power-retention layers needs its lanes to size the state pool")
    return state_pool_shapes(cfg, sizes.max_batch) + recent_rows_shapes(cfg, sizes.max_batch)


def _state_count(cfg: TransformerConfig, active: jax.Array, pos: jax.Array) -> jax.Array:
    """The lanes whose state this step updated, and the bytes they hold."""
    lanes = jnp.sum(active.astype(jnp.float32))
    return jnp.stack([lanes, lanes * (len(cfg.retention_layers) * state_bytes_per_slot(cfg))])


def _state_gauge(cfg: TransformerConfig, active: jax.Array, cache: Dict[str, jax.Array]) -> jax.Array:
    """The recent rows the live lanes hold after this step, not yet folded into their slots (a layer's: all alike)."""
    return jnp.sum(jnp.where(active, cache[STATE_SLOT.leaves[-1]][0], 0)).astype(jnp.float32)[None]


def _state_report(cfg: TransformerConfig, sizes: Any, live: int = 0, gauges: Any = None) -> Dict[str, Any]:
    """``state``: the slots (one a lane), how many hold a sequence, the bytes
    one holds over the retention layers, after how many tokens a lane's recent
    rows are folded into its slot and how many rows the live lanes held pending
    after the newest decode step (between 0 and ``live x (fold_every - 1)``);
    ``block_ids_address_nothing``: no layer reads the pool ``kv_cache``
    counts, and admission is by free lane alone."""
    if not cfg.retention_layers:
        return {}
    per_slot = len(cfg.retention_layers) * state_bytes_per_slot(cfg)
    pending = int((gauges or {}).get(STATE_SLOT.gauges[0], 0)) if live else 0  # the newest step's; no lane, no row
    return {
        "state": {"slots": sizes.max_batch, "live": live, "bytes_per_slot": per_slot, "fold_every": FOLD_EVERY, "pending_rows": pending},
        "block_ids_address_nothing": not any(kind.holds == BLOCKS for kind in cache_kinds(cfg)),
    }


def _state_setup(cfg: TransformerConfig, sizes: Any) -> Dict[str, Any]:
    slots = _state_report(cfg, sizes)["state"]
    pool, recent = _nbytes(STATE_SLOT, cfg, sizes, slice(2)), _nbytes(STATE_SLOT, cfg, sizes, slice(2, None))
    return {"slots": slots["slots"], "bytes_per_slot": slots["bytes_per_slot"], "state_pool_bytes": pool, "recent_rows_bytes": recent}


# -- a Mamba-2 mixer's state and its convolution's tail a lane -------------------
#
# Its forms are two functions of a call's rows: ``conv(p, xbc, cache, j)`` takes
# what the in-projection made for the convolution [b, s, channels], puts the
# lanes' tails before it, keeps the new tails and returns (the convolution's
# output, the cache); ``scan(x, B, C, dt, A, D, cache, j)`` runs the tokens
# through the lanes' states and returns (y [b, s, heads, P] float32, the cache).


def _tail_walk(cache: Dict[str, jax.Array], leaf: str, j, lanes: jax.Array, fresh, rows: Rows, new: jax.Array):
    """A walk's chunk of a convolution's input ``new`` [b, s, channels] after the
    tails of the rows' lanes (zeros in the call's first chunk): (the window the
    convolution reads [b, s + taps - 1, channels], the lanes' new tails: the rows
    before the first token that does not exist)."""
    tail = jnp.where(fresh, 0, cache[leaf][j, lanes])
    window = jnp.concatenate([tail, new], axis=1)
    last = jnp.sum(rows.live, axis=1)[:, None] + jnp.arange(tail.shape[1])[None, :]
    return window, jnp.take_along_axis(window, last[..., None], axis=1)


def _tail_step(cache: Dict[str, jax.Array], leaf: str, j, rows: Rows, new: jax.Array):
    """A decode step's row ``new`` [lanes, 1, channels] after each lane's tail,
    which moves on by a row where the lane is live: (the window, the new tails)."""
    tail = cache[leaf][j]
    window = jnp.concatenate([tail, new], axis=1)
    return window, jnp.where(rows.live[:, None, None], window[:, 1:], tail)


def _lanes_count(layers: int, bytes_per_slot: int, active: jax.Array) -> jax.Array:
    """What a kind that holds a state a lane in ``layers`` layers counts a step: the
    lanes whose state the step updated, and the bytes of state they hold."""
    lanes = jnp.sum(active.astype(jnp.float32))
    return jnp.stack([lanes, lanes * (layers * bytes_per_slot)])


def _ssm_mixer(cfg: TransformerConfig, conv, scan):
    """A Mamba-2 mixer reads the norm the layer's attention heads read (under
    ``mixer_block`` it is the layer's only mixer), writes no row a token and
    adds its own output to the stream.  Its scopes are ``serve.ssm.*`` beside
    attention heads and ``serve.mamba2.*`` where it is a layer alone: a share
    by scope then tells the two block forms' cells apart by its rule."""
    scope = "serve.mamba2" if cfg.mixer_block else "serve.ssm"

    def mix(p, x, h, cache, j):
        with jax.named_scope(scope + ".in"):  # projection, multipliers, convolution, the step
            z, xbc, dt = _ssm_project(cfg, p, h)
            xbc, cache = conv(p, xbc, cache, j)
            parts = _ssm_split(cfg, xbc, dt, p)
        with jax.named_scope(scope + ".state"):  # decay, update, read-out
            y, cache = scan(*parts, cache, j)
        with jax.named_scope(scope + ".out"):  # gated norm, the out-projection
            return x + _ssm_out(cfg, p, y, z), cache

    return mix


def _ssm_walk(cfg: TransformerConfig, rows: Rows, cache: Any = None):
    """``s`` tokens a row after what the slots and tails of the rows' lanes hold
    (nothing, in the walk's first chunk: a sequence starts from a zeroed slot
    and a zeroed tail), and into them: the prefill walk's chunk, and the wide
    prefill as one chunk.  A token that does not exist (``rows.live``: the
    padded end of a prompt) advances neither.  The convolution takes the call's
    rows whole; the scan takes a wide chunk's narrow chunks one after the other,
    the state in hand, and the slots are read and written once a chunk."""
    state_leaf, tail_leaf = SSM_SLOT.leaves
    lanes = jnp.arange(rows.live.shape[0]) if rows.lanes is None else rows.lanes
    fresh = rows.chunk == rows.first_chunk

    def conv(p, xbc, cache, j):
        window, tail = _tail_walk(cache, tail_leaf, j, lanes, fresh, rows, xbc)
        return _ssm_conv(cfg, p, window), {**cache, tail_leaf: cache[tail_leaf].at[j, lanes].set(tail)}

    def scan(x, b, c, dt, a, skip, cache, j):
        state = jnp.where(fresh, 0.0, cache[state_leaf][j, lanes])

        def chunk(state, rows, x, b, c, dt):
            y, state = ssm_chunk(x, b, c, dt, a, skip, state, rows.live)
            return state, (y,)

        state, (y,) = _narrow_chunks(rows, chunk, state, (x, b, c, dt), (1, 1, 1, 1), (1,))
        return y, {**cache, state_leaf: cache[state_leaf].at[j, lanes].set(state)}

    return _ssm_mixer(cfg, conv, scan)


def _ssm_step(cfg: TransformerConfig, rows: Rows, cache: Any = None):
    """One token a lane, row ``b`` of the batch IS lane ``b``: the tail moves on
    by a row, the slot is decayed, takes the token and answers it
    (``ops/ssm.py ssm_decode``: the Pallas kernel on a TPU, in place); a lane
    that is not live leaves both alone."""
    state_leaf, tail_leaf = SSM_SLOT.leaves

    def conv(p, xbc, cache, j):
        window, tail = _tail_step(cache, tail_leaf, j, rows, xbc)
        return _ssm_conv(cfg, p, window), {**cache, tail_leaf: cache[tail_leaf].at[j].set(tail)}

    def scan(x, b, c, dt, a, skip, cache, j):
        y, state = ssm_decode(x[:, 0], b[:, 0], c[:, 0], dt[:, 0], a, skip, cache[state_leaf], j, rows.live)
        return y[:, None], {**cache, state_leaf: state}

    return _ssm_mixer(cfg, conv, scan)


def _ssm_shapes(cfg: TransformerConfig, sizes: Any) -> Tuple[Tuple[int, ...], ...]:
    if sizes.max_batch is None:
        raise ValueError("a model with Mamba-2 layers needs its lanes to size the state pool")
    return ssm_pool_shapes(cfg, sizes.max_batch)


def _ssm_count(cfg: TransformerConfig, active: jax.Array, pos: jax.Array) -> jax.Array:
    return _lanes_count(len(cfg.ssm_layers), ssm_bytes_per_slot(cfg), active)


def _ssm_report(cfg: TransformerConfig, sizes: Any, live: int = 0, gauges: Any = None) -> Dict[str, Any]:
    """``ssm``: the slots (one a lane), how many hold a sequence, and the bytes
    of state one holds over the Mamba-2 layers."""
    if not cfg.ssm_layers:
        return {}
    slots = {"slots": sizes.max_batch, "live": live, "bytes_per_slot": len(cfg.ssm_layers) * ssm_bytes_per_slot(cfg)}
    return {"ssm": slots, **_layers_report(cfg)}


def _ssm_setup(cfg: TransformerConfig, sizes: Any) -> Dict[str, Any]:
    slots = _ssm_report(cfg, sizes)["ssm"]
    said = {"ssm_slots": slots["slots"], "ssm_bytes_per_slot": slots["bytes_per_slot"], "ssm_pool_bytes": _nbytes(SSM_SLOT, cfg, sizes)}
    return {**said, **_layers_report(cfg)}


# -- a Gated-DeltaNet mixer's delta-rule state and its convolution's tail a lane ---
#
# Its forms are two functions of a call's rows, as the Mamba-2 mixer's: ``conv(p,
# qkv, cache, j)`` -> (the convolution's output, the cache) and ``rule(q, k, v, g,
# beta, cache, j)`` -> (o [b, s, value heads, V] float32, the cache).


def _gdn_mixer(cfg: TransformerConfig, conv, rule):
    """A Gated-DeltaNet mixer is its layer's only mixer before the experts: it
    reads the layer's first norm, writes no row a token and adds its output to
    the stream.  Its scopes are ``serve.gdn.*`` under a decay a head and
    ``serve.kda.*`` under a decay a channel (Kimi Delta Attention's form of it): a
    share by scope then tells the two forms' cells apart by its rule, as
    ``_ssm_mixer``'s; the kind, its leaves and its counters are one."""
    scope = "serve.kda" if cfg.linear_channel_decay else "serve.gdn"

    def mix(p, x, h, cache, j):
        with jax.named_scope(scope + ".proj"):  # the in-projections (two; a decay a channel has a third of its own)
            qkv, z, b, a = _gdn_project(cfg, p, h)
        with jax.named_scope(scope + ".conv"):  # the tail, the convolution, the heads' norms, decay and write strength
            qkv, cache = conv(p, qkv, cache, j)
            parts = _gdn_split(cfg, qkv, b, a, p)
        with jax.named_scope(scope + ".state"):  # decay, S^T k, the corrected update, the read-out
            o, cache = rule(*parts, cache, j)
        with jax.named_scope(scope + ".out"):  # the gated norm, the out-projection
            return x + _gdn_out(cfg, p, o, z), cache

    return mix


def _gdn_walk(cfg: TransformerConfig, rows: Rows, cache: Any = None):
    """``s`` tokens a row after what the slots and tails of the rows' lanes hold
    (nothing, in the walk's first chunk), and into them: the prefill walk's chunk,
    and the wide prefill as one chunk (``_ssm_walk`` says the same of its own).
    The chunked form takes a wide chunk's narrow chunks one after the other, the
    state in hand, and the slots are read and written once a chunk."""
    state_leaf, tail_leaf = DELTA_SLOT.leaves
    lanes = jnp.arange(rows.live.shape[0]) if rows.lanes is None else rows.lanes
    fresh = rows.chunk == rows.first_chunk

    def conv(p, qkv, cache, j):
        window, tail = _tail_walk(cache, tail_leaf, j, lanes, fresh, rows, qkv)
        return _gdn_conv(p, window), {**cache, tail_leaf: cache[tail_leaf].at[j, lanes].set(tail)}

    def rule(q, k, v, g, beta, cache, j):
        state = jnp.where(fresh, 0.0, cache[state_leaf][j, lanes])

        def chunk(state, rows, q, k, v, g, beta):
            with jax.named_scope("serve.gdn.chunk"):  # under either form's ``.state``
                o, state = gdn_chunk(q, k, v, g, beta, state, rows.live, chunk=cfg.linear_chunk)
            return state, (o,)

        state, (o,) = _narrow_chunks(rows, chunk, state, (q, k, v, g, beta), (1,) * 5, (1,))
        return o, {**cache, state_leaf: cache[state_leaf].at[j, lanes].set(state)}

    return _gdn_mixer(cfg, conv, rule)


def _gdn_step(cfg: TransformerConfig, rows: Rows, cache: Any = None):
    """One token a lane, row ``b`` of the batch IS lane ``b``: the tail moves on
    by a row, the slot is decayed, corrected by the token and answers it
    (``ops/gated_delta.py gdn_decode``: the Pallas kernel on a TPU, in place); a
    lane that is not live leaves both alone."""
    state_leaf, tail_leaf = DELTA_SLOT.leaves

    def conv(p, qkv, cache, j):
        window, tail = _tail_step(cache, tail_leaf, j, rows, qkv)
        return _gdn_conv(p, window), {**cache, tail_leaf: cache[tail_leaf].at[j].set(tail)}

    def rule(q, k, v, g, beta, cache, j):
        o, state = gdn_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], cache[state_leaf], j, rows.live)
        return o[:, None], {**cache, state_leaf: state}

    return _gdn_mixer(cfg, conv, rule)


def _gdn_shapes(cfg: TransformerConfig, sizes: Any) -> Tuple[Tuple[int, ...], ...]:
    if sizes.max_batch is None:
        raise ValueError("a model with linear_attention layers needs its lanes to size the state pool")
    return gdn_pool_shapes(cfg, sizes.max_batch)


def _gdn_count(cfg: TransformerConfig, active: jax.Array, pos: jax.Array) -> jax.Array:
    return _lanes_count(len(cfg.linear_layers), gdn_bytes_per_slot(cfg), active)


def _gdn_report(cfg: TransformerConfig, sizes: Any, live: int = 0, gauges: Any = None) -> Dict[str, Any]:
    """``gdn``: the slots (one a lane), how many hold a sequence, and the bytes
    of state one holds over the Gated-DeltaNet layers."""
    if not cfg.linear_layers:
        return {}
    return {"gdn": {"slots": sizes.max_batch, "live": live, "bytes_per_slot": len(cfg.linear_layers) * gdn_bytes_per_slot(cfg)}}


def _gdn_setup(cfg: TransformerConfig, sizes: Any) -> Dict[str, Any]:
    slots = _gdn_report(cfg, sizes)["gdn"]
    return {"gdn_slots": slots["slots"], "gdn_bytes_per_slot": slots["bytes_per_slot"], "gdn_pool_bytes": _nbytes(DELTA_SLOT, cfg, sizes)}


# -- the table ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """One kind of cache: everything the forward and the engine know of it."""

    name: str
    #: its layers in a config: those of one of ``layer_types`` in a model whose full layers' attention is
    #: ``latent`` (or is not; None: the kind's layers keep no attention row, and are its layers beside either).
    #: A type that several kinds name makes a layer of several kinds.  The first is the type whose rotary
    #: parameters and scope the kind's mixer takes
    layer_types: Tuple[str, ...]
    latent: Optional[bool]
    #: the arrays it owns in the cache, ``shapes(cfg, sizes)`` -> a shape each (``sizes``: a ``ServeConfig``'s
    #: ``num_blocks``, ``block_size``, ``max_batch``, ``prefill_chunk``) and ``dtypes(cfg)`` -> a dtype each
    leaves: Tuple[str, ...]
    shapes: Callable
    dtypes: Callable
    #: what a request holds of it, BLOCKS or LANE; for LANE, why ``prefix_cache`` cannot be served
    holds: str
    #: its mixers: ``step``, ``table`` and ``wide`` are ``(cfg, rows, cache) -> mix`` (``wide`` None: the kind has
    #: no wide prefill); ``walk(cfg, cache, lanes, chunk_tokens)``, called before the loop, returns ``rows -> mix``
    step: Callable
    walk: Callable
    table: Callable
    wide: Optional[Callable]
    no_prefix_cache: Optional[str] = None
    #: the subtree of a block whose leaves its mixer reads (a block's second attention sublayer: ``<params>_1``)
    params: str = "attn"
    #: what a decode step counts for it, by name, and ``count(cfg, active [b], pos [b])`` -> float32, one each
    counters: Tuple[str, ...] = ()
    count: Optional[Callable] = None
    #: what a decode step reads off the cache as it leaves it, by name, and ``gauge(cfg, active [b], cache)`` ->
    #: float32, one each: the newest step's value for ``report``, never summed and no step counter
    gauges: Tuple[str, ...] = ()
    gauge: Optional[Callable] = None
    #: ``walked(cfg)`` -> (rows, window): the rows of its arrays a paged decode kernel walks a step, one call
    #: each, and the window it walks under (``ops/paged_attention.py walk_counts``); None: no kernel walks it
    walked: Optional[Callable] = None
    #: ``report(cfg, sizes, live lanes, the newest step's gauges by name)``: what it adds to ``/stats``, asked of
    #: EVERY kind of the table (one without layers in the model says so itself: nothing, or an empty entry);
    #: ``setup(cfg, sizes)``: what a kind of the model adds to the ``serve.setup.kv_pool`` span
    report: Callable = lambda cfg, sizes, live, gauges=None: {}
    setup: Callable = lambda cfg, sizes: {}
    #: its layers are those of a model whose attention reads the keys an indexer picks (``indexer_types``), or is not;
    #: such layers hand their picks on: ``mix(p, x, h, cache, j, handed) -> (x, cache, handed)``
    indexed: bool = False

    def layers(self, cfg: TransformerConfig) -> Tuple[int, ...]:
        """The layers of ``cfg`` that are of this kind, in order: layer ``layers(cfg)[j]`` owns row ``j`` of its
        arrays, or with ``cfg.attn_sublayers`` attention sublayers a block the rows ``j * attn_sublayers ..``, one
        a sublayer (:func:`layer_kinds`)."""
        if self.latent not in (None, cfg.latent) or bool(cfg.indexer_types) != self.indexed:
            return ()
        return tuple(i for i in range(cfg.n_layers) if cfg.layer_type(i) in self.layer_types)


def _nbytes(kind: CacheKind, cfg: TransformerConfig, sizes: Any, leaves: slice = slice(None)) -> int:
    """The bytes of a kind's arrays, or of those of its ``leaves`` alone."""
    return sum(math.prod(shape) * jnp.dtype(dt).itemsize for shape, dt in zip(kind.shapes(cfg, sizes)[leaves], kind.dtypes(cfg)[leaves]))


def _compute_dtype(leaves: int):
    """The ``dtypes`` of a kind whose ``leaves`` arrays are all in the compute dtype."""
    return lambda cfg: (cfg.dtype,) * leaves


def _every_chunk(build: Callable):
    """The ``walk`` of a kind that prepares nothing before the loop: ``build(cfg, rows)`` a chunk."""
    return lambda cfg, cache, lanes, chunk_tokens: functools.partial(build, cfg)


PAGED_KV = CacheKind(
    name="paged_kv", layer_types=(FULL, HYBRID), latent=False, holds=BLOCKS,
    leaves=("k", "v"), shapes=_paged_shapes, dtypes=_compute_dtype(2),
    step=lambda cfg, rows, cache: _kv_mixer(
        cfg, PAGED_KV, rows, rows.where, _attend_paged(cfg, PAGED_KV, rows.block_tables, rows.lane_positions)),
    walk=_every_chunk(lambda cfg, rows: _kv_mixer(
        cfg, PAGED_KV, rows, rows.where, lambda rows: _attend_chunk(cfg, PAGED_KV, rows.block_tables, rows.chunk), walk=True)),
    table=lambda cfg, rows, cache: _kv_mixer(
        cfg, PAGED_KV, rows, rows.where, _attend_table(cfg, rows.block_tables, _table_mask(rows))),
    wide=lambda cfg, rows, cache: _kv_mixer(cfg, PAGED_KV, rows, rows.where, _attend_local),
    walked=lambda cfg: (cfg.paged_layers, None),
    report=_kv_report, setup=_kv_report,
)

PAGED_LATENT = CacheKind(
    name="paged_latent", layer_types=(FULL,), latent=True, holds=BLOCKS,
    leaves=("kv",), shapes=_paged_shapes, dtypes=_compute_dtype(1),
    step=lambda cfg, rows, cache: _latent_mixer(cfg, rows, _latent_attend_paged(cfg, rows.block_tables, rows.lane_positions)),
    walk=_every_chunk(lambda cfg, rows: _latent_mixer(cfg, rows, _latent_attend_chunk(cfg, rows))),
    table=lambda cfg, rows, cache: _latent_mixer(cfg, rows, _latent_attend_table(cfg, rows.block_tables, _table_mask(rows))),
    wide=lambda cfg, rows, cache: _latent_mixer(cfg, rows, _latent_attend_local(cfg)),
    walked=lambda cfg: (cfg.paged_layers, None),
    report=_latent_report, setup=_latent_report,
)

PAGED_INDEXED = CacheKind(
    # the latent kind's row a token and its forms; beside it ONE index key a token in each layer that holds an indexer
    # (row ``cfg.index_layer(i)`` of the second array), written where the token's latent row is: both are held as blocks,
    # and a key depends on its own token and position alone, so a shared prefix serves both
    name="paged_indexed", layer_types=(FULL,), latent=True, indexed=True, holds=BLOCKS,
    leaves=("kv", "ik"), shapes=_indexed_shapes, dtypes=_compute_dtype(2),
    step=lambda cfg, rows, cache: _latent_mixer(
        cfg, rows, _latent_attend_paged(cfg, rows.block_tables, rows.lane_positions),
        _indexer(cfg, rows, _index_paged(rows), as_mask=False)),
    walk=_every_chunk(lambda cfg, rows: _latent_mixer(
        cfg, rows, _latent_attend_chunk(cfg, rows), _indexer(cfg, rows, _index_gathered(rows)))),
    table=lambda cfg, rows, cache: _latent_mixer(
        cfg, rows, _latent_attend_table(cfg, rows.block_tables, _table_mask(rows)),
        _indexer(cfg, rows, _index_gathered(rows))),
    wide=lambda cfg, rows, cache: _latent_mixer(
        cfg, rows, _latent_attend_local(cfg), _indexer(cfg, rows, _index_local, keys=rows.positions.shape[-1])),
    # what the step's indexers score, what its attention reads and what a program without selection would have read
    counters=("serve.dsa.index_tokens", "serve.dsa.selected_tokens", "serve.dsa.live_tokens"), count=_indexed_count,
    # the kernel that walks a lane's live blocks is the indexers' score pass, over the index array's rows
    walked=lambda cfg: (len(cfg.index_layers), None),
    report=_indexed_report, setup=_indexed_setup,
)

STATE_SLOT = CacheKind(
    name="state_slot", layer_types=(RETENTION,), latent=False, shapes=_state_shapes, holds=LANE,
    # the state and its normaliser; then the recent rows a lane: keys, values, the gate's running logarithm, rows pending
    leaves=("rs", "rz", "rk", "rv", "rg", "rn"),
    # the state read where it is stated: the benchmark's check sets another there; k and v as they reach the layer
    dtypes=lambda cfg: (transformer.STATE_DTYPE,) * 2 + (cfg.dtype, cfg.dtype, transformer.STATE_DTYPE, jnp.int32),
    no_prefix_cache=(
        "prefix_cache shares a prompt's full blocks between requests, and a block holds no state: a "
        "power-retention layer keeps a request's whole context in its own lane's state slot, and a prefill "
        "from the first un-cached token would need the state as it stood at that block's edge (a snapshot "
        "nobody keeps). Set prefix_cache: false"
    ),
    step=_state_step, walk=_every_chunk(_state_chunk), table=_state_step, wide=_state_chunk,
    # the lanes whose state the step updated, and the bytes of state those hold over the retention layers
    counters=("serve.state.live_lanes", "serve.state.bytes"), count=_state_count,
    gauges=("serve.state.pending_rows",), gauge=_state_gauge,
    report=_state_report, setup=_state_setup,
)

WINDOW_RING = CacheKind(
    name="window_ring", layer_types=(SLIDING,), latent=False, holds=LANE,
    leaves=("wk", "wv"), shapes=_ring_shapes, dtypes=_compute_dtype(2),
    no_prefix_cache=(
        "prefix_cache shares a prompt's full blocks between requests, and a shared block holds no "
        "window state: the sliding-window layers keep a request's newest tokens in its own lane's ring, "
        "which a prefill from the first un-cached token would leave without the prefix. Set prefix_cache: false"
    ),
    step=_ring_step, walk=_ring_walk, table=functools.partial(_ring_step, table=True), wide=None,
    # the cached tokens the step's attention reads, summed over the lanes, in the full and in the window layers
    counters=("serve.kv.full_tokens", "serve.kv.window_tokens"), count=_ring_count,
    walked=lambda cfg: (len(cfg.window_layers), cfg.sliding_window),
    report=_ring_report, setup=_ring_setup,
)

SSM_SLOT = CacheKind(
    name="ssm_slot", layer_types=(HYBRID, MAMBA2), latent=False, leaves=("ssm", "conv"), shapes=_ssm_shapes, holds=LANE,
    params="ssm",
    # the state where it is stated (the benchmark's check sets another there); the tail as the convolution reads it
    dtypes=lambda cfg: (transformer.STATE_DTYPE, cfg.dtype),
    no_prefix_cache=(
        "prefix_cache shares a prompt's full blocks between requests, and a shared block holds no state: a "
        "Mamba-2 mixer keeps a request's whole context in its own lane's state slot and convolution tail, and a "
        "prefill from the first un-cached token would need both as they stood at that block's edge (a snapshot "
        "nobody keeps). Set prefix_cache: false"
    ),
    step=_ssm_step, walk=_every_chunk(_ssm_walk), table=_ssm_step, wide=_ssm_walk,
    # the lanes whose state the step updated, and the bytes of state those hold over the Mamba-2 layers
    counters=("serve.ssm.live_lanes", "serve.ssm.bytes"), count=_ssm_count,
    report=_ssm_report, setup=_ssm_setup,
)

DELTA_SLOT = CacheKind(
    name="delta_slot", layer_types=(LINEAR,), latent=None, leaves=("gdn", "gconv"), shapes=_gdn_shapes, holds=LANE,
    params="gdn",
    # the state where it is stated (the benchmark's check sets another there); the tail as the convolution reads it
    dtypes=lambda cfg: (transformer.STATE_DTYPE, cfg.dtype),
    no_prefix_cache=(
        "prefix_cache shares a prompt's full blocks between requests, and a shared block holds no state: a "
        "Gated-DeltaNet (linear_attention) layer keeps a request's whole context in its own lane's delta-rule state "
        "slot and convolution tail, and a prefill from the first un-cached token would need both as they stood at "
        "that block's edge (a snapshot nobody keeps). Set prefix_cache: false"
    ),
    step=_gdn_step, walk=_every_chunk(_gdn_walk), table=_gdn_step, wide=_gdn_walk,
    # the lanes whose state the step updated, and the bytes of state those hold over the Gated-DeltaNet layers
    counters=("serve.gdn.live_lanes", "serve.gdn.bytes"), count=_gdn_count,
    report=_gdn_report, setup=_gdn_setup,
)

#: every kind, in the order a layer's mixers run, a decode step's counters and a walk's chunk take them
CACHE_KINDS: Tuple[CacheKind, ...] = (PAGED_KV, PAGED_LATENT, PAGED_INDEXED, STATE_SLOT, WINDOW_RING, SSM_SLOT, DELTA_SLOT)


def cache_kinds(cfg: TransformerConfig) -> Tuple[CacheKind, ...]:
    """The kinds ``cfg``'s layers are of, in the table's order."""
    return tuple(kind for kind in CACHE_KINDS if kind.layers(cfg))


def layer_kinds(cfg: TransformerConfig, i: int, sub: int = 0) -> Tuple[Tuple[CacheKind, int, str], ...]:
    """The kinds of layer ``i``'s attention sublayer ``sub`` (of ``cfg.attn_sublayers``) in the table's order, each
    with the sublayer's row in the kind's arrays (the layer's place among the layers of that kind, times the
    sublayers a block, and ``sub``) and the subtree of the block whose leaves its mixer reads: the kind's
    ``params``, for a block's second sublayer ``<params>_1``.  One kind, two rows, two subtrees."""
    return tuple(
        (kind, kind.layers(cfg).index(i) * cfg.attn_sublayers + sub, kind.params + (f"_{sub}" if sub else ""))
        for kind in CACHE_KINDS if i in kind.layers(cfg)
    )


def pool_block_size(cfg: TransformerConfig, cache: Dict[str, jax.Array]) -> Optional[int]:
    """Tokens a block of the paged pool, read off the cache; None where no kind of the model holds blocks."""
    for kind in cache_kinds(cfg):
        if kind.holds == BLOCKS:
            return cache[kind.leaves[0]].shape[2]
    return None
