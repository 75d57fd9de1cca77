"""The serving forward: the decoder-only transformer of ``models/transformer.py``
over a KV cache, as pure functions of the unboxed parameter tree.

Training/eval run the full-sequence forward of ``models/transformer.py``; serving needs the
autoregressive form: prefill the prompt once, then one-token decode steps
reading/writing a **paged** KV cache (vLLM's PagedAttention layout, Kwon
et al., SOSP '23).  The cache is a pool of fixed-size blocks
``[n_layers, num_blocks, block_size, kv_heads * head_dim]`` (a block is one
contiguous ``[block_size, kv_heads * head_dim]`` slab: what the decode
kernel in ``ops/paged_attention.py`` copies in one DMA and feeds the MXU
as it lies); each sequence owns a *block table* mapping its logical block
index to a physical block id.  Everything below is a pure function over
the UNBOXED param tree that
``TransformerLM.init`` produces (the ``["params"]`` subtree), so the
serve engine can jit prefill/decode with static shapes — batch lanes,
table width, and prompt padding are fixed by ServeConfig, and the decode
step traces exactly once no matter how request lengths mix (guarded by
the RetraceSentinel in ``serve/engine.py``).

Physical block 0 is a scratch block the allocator never hands out:
padded prefill positions and inactive decode lanes write there, keeping
the scatter shape static without masking arithmetic inside the kernel.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from determined_tpu.models import transformer
from determined_tpu.models.transformer import (
    RETENTION,
    SLIDING,
    TransformerConfig,
    _gate_log,
    _latent_attend_local,
    _latent_project,
    _layer_norm,
    _rms,
    _rms_apply,
    _rope,
    kv_cache_shape,
    state_bytes_per_slot,
    state_pool_shapes,
    window_ring_blocks,
    window_store_shape,
)
from determined_tpu.ops.attention import NEG_INF, _repeat_kv, reference_attention
from determined_tpu.ops.paged_attention import (
    paged_chunk_attention,
    paged_decode_attention,
    paged_latent_attention,
)
from determined_tpu.ops.retention import retention_chunk, retention_decode


def init_kv_cache(
    cfg: TransformerConfig, num_blocks: int, block_size: int, lanes: Optional[int] = None,
    chunk_tokens: Optional[int] = None,
) -> Dict[str, jax.Array]:
    """Zeroed cache in the model's compute dtype.  The paged pool: ``k`` and
    ``v`` (keys are stored post-rope, i.e. exactly what attention consumes), or,
    under latent attention, ONE array ``kv`` whose row is ``[c_kv after its norm
    | k_r after rope | zeros]``.  A model with sliding-window layers holds a
    cache of two kinds: the pool keeps its full layers alone, addressed by block
    table, and ``wk`` / ``wv`` are the window layers' store
    (:func:`window_store_shape`: a ring a lane, for ``lanes`` decode lanes and
    prefill chunks of ``chunk_tokens``), addressed by lane and position.
    Power-retention layers make a third kind: ``rs`` / ``rz``, a float32 state
    and its normaliser a lane a layer (:func:`state_pool_shapes`), addressed
    by lane alone.  A model none of whose layers reads the pool gets none."""
    shape = kv_cache_shape(cfg, num_blocks, block_size)
    if cfg.latent:
        return {"kv": jnp.zeros(shape, cfg.dtype)}
    cache = {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)} if cfg.paged_layers else {}
    if cfg.retention_layers:
        if lanes is None:
            raise ValueError("a model with power-retention layers needs its lanes to size the state pool")
        state, norm = state_pool_shapes(cfg, lanes)
        cache.update(rs=jnp.zeros(state, transformer.STATE_DTYPE), rz=jnp.zeros(norm, transformer.STATE_DTYPE))
    if cfg.window_layers:
        if lanes is None or chunk_tokens is None or chunk_tokens % block_size:
            raise ValueError(
                "a model with sliding-window layers needs its lanes and its prefill chunk (whole blocks) "
                f"to size the window store (got lanes={lanes}, chunk_tokens={chunk_tokens})"
            )
        ring = window_store_shape(cfg, lanes, block_size, chunk_tokens)
        cache.update(wk=jnp.zeros(ring, cfg.dtype), wv=jnp.zeros(ring, cfg.dtype))
    return cache


def _block_size(cache: Dict[str, jax.Array]) -> Optional[int]:
    """Tokens a block of the paged pool; None where the cache has no pool."""
    pool = cache.get("kv", cache.get("k"))
    return None if pool is None else pool.shape[2]


def _norm_apply(cfg: TransformerConfig, x: jax.Array, scale: jax.Array) -> jax.Array:
    """A block's (or the final) norm as the configuration states it."""
    if cfg.norm == "rms":
        return _rms_apply(x, scale, cfg.norm_eps)
    with jax.named_scope("serve.norm"):
        return _layer_norm(x, scale, cfg.norm_eps)


def _attn_proj(p: Dict[str, Any], x: jax.Array, dtype: Any) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q/k/v projections as ``Attention`` computes them, to [b, heads, s, d]."""
    # under the caller's scope (``serve.attn.qkv``, with the rope that follows)
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"]["kernel"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"]["kernel"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"]["kernel"].astype(dtype))
    return q, k, v


def _retention_proj(cfg: TransformerConfig, p: Dict[str, Any], h: jax.Array, positions: jax.Array):
    """A retention layer's q, k, v ``[b, heads, s, d]`` as ``Retention`` makes
    them (a norm a head, rotary) and the gate's logarithm ``[b, kv_heads, s]``."""
    q, k, v = _attn_proj(p, h, cfg.dtype)
    log_g = _gate_log(cfg, h @ p["wg"]["kernel"].astype(cfg.dtype)).transpose(0, 2, 1)
    if cfg.qk_norm:
        q, k = _rms(q, p["q_norm"], cfg.norm_eps), _rms(k, p["k_norm"], cfg.norm_eps)
    rope = cfg.rope(RETENTION)
    return _rope(q, positions, rope), _rope(k, positions, rope), v, log_g


def _mlp_apply(p: Dict[str, Any], x: jax.Array, dtype: Any) -> jax.Array:
    gate = x @ p["w_gate"]["kernel"].astype(dtype)
    up = x @ p["w_up"]["kernel"].astype(dtype)
    return (nn.silu(gate) * up) @ p["w_down"]["kernel"].astype(dtype)


def _check_decodable(cfg: TransformerConfig) -> None:
    if cfg.moe_experts > 0 and not cfg.moe_top_k:
        raise ValueError(
            "KV-cache serving runs dropless experts (moe_top_k > 0); the top-2 capacity "
            "layer drops tokens by the batch they arrive in and is not served"
        )
    if cfg.seq_axis_name is not None or cfg.expert_axis_name is not None:
        raise ValueError("KV-cache serving runs outside pipeline stages")


def _pool_rows(x: jax.Array, lead: Tuple[int, ...]) -> jax.Array:
    """Projected k or v ``[b, kv_heads, s, head_dim]`` as the pool stores a
    token, ``[*lead, kv_heads * head_dim]``: ``lead`` is (b, s), or (b,) where s is 1."""
    return x.transpose(0, 2, 1, 3).reshape(*lead, x.shape[1] * x.shape[3])


def _gather_table(cfg: TransformerConfig, pool: jax.Array, layer: int, block_tables: jax.Array) -> jax.Array:
    """Every token of every table column of one layer, the KV heads
    repeated: ``[b, n_heads, T * block_size, head_dim]``."""
    b, t = block_tables.shape
    rows = pool[layer, block_tables].reshape(b, t * pool.shape[2], cfg.kv_heads, -1)
    return _repeat_kv(rows.transpose(0, 2, 1, 3), cfg.n_heads // cfg.kv_heads)


def _embed_rows(params: Dict[str, Any], tokens: jax.Array, dtype: Any) -> jax.Array:
    """Embedding rows of ``tokens`` in the compute dtype: the rows first, then
    their conversion, so that the table is read where ``tokens`` point and not swept."""
    with jax.named_scope("serve.embed"):
        return jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(dtype)


def _head(cfg: TransformerConfig, params: Dict[str, Any], x: jax.Array, row: Optional[int] = None) -> jax.Array:
    """Final norm and ``lm_head`` (tied: the embedding's transpose, times
    ``logit_scale``): float32 logits at every position of ``x``, or at ``row`` alone."""
    x = _norm_apply(cfg, x, params["ln_f"]["scale"])
    with jax.named_scope("serve.head"):
        x = x if row is None else x[:, row, :]
        if not cfg.tie_embeddings:
            return (x @ params["lm_head"]["kernel"].astype(cfg.dtype)).astype(jnp.float32)
        # float32 out of the product itself: the table is the head, and its logits are what a caller samples from
        logits = jnp.einsum(
            "...d,vd->...v", x, params["embed"]["embedding"].astype(cfg.dtype), preferred_element_type=jnp.float32
        )
        return logits * cfg.logit_scale


# The attention backends of the serving layer: ``attend(q, k, v, cache, i)`` with
# q [b, n_heads, s, head_dim], this call's own k, v [b, kv_heads, s, head_dim]
# and the pool that already holds them; returns [b, n_heads, s, head_dim].  An
# entry point picks one before its layer loop.


def _attend_local(q, k, v, cache, i):
    """Causal, over this call's own keys: the wide prefill's prompts start at position 0."""
    return reference_attention(q, k, v, causal=True)


def _attend_paged(cfg: TransformerConfig, block_tables: jax.Array, positions: jax.Array, window: Optional[int] = None):
    """One query a lane against the lane's live blocks, read where they lie
    in the pool (``ops/paged_attention.py``); ``positions`` [b], -1 = idle.
    ``window``: the layer slides, ``block_tables`` are the lanes' rings of the
    window store, and a lane reads its newest ``window`` tokens there."""
    pools = ("k", "v") if window is None else ("wk", "wv")

    def attend(q, k, v, cache, i):
        att = paged_decode_attention(
            q[:, :, 0, :], cache[pools[0]], cache[pools[1]], i, block_tables, positions, scale=cfg.head_dim ** -0.5,
            window=window,
        )
        return att.astype(cfg.dtype)[:, :, None, :]

    return attend


def _attend_chunk(cfg: TransformerConfig, block_tables: jax.Array, chunk: jax.Array, window: Optional[int] = None):
    """The prefill walk's read: the queries of chunk ``chunk`` (positions
    ``chunk * s ..``) against the keys up to the chunk's end, read from the
    pool a tile at a time (``ops/paged_attention.py paged_chunk_attention``);
    under ``window`` from the lanes' rings, and no key older than the window."""
    pools = ("k", "v") if window is None else ("wk", "wv")

    def attend(q, k, v, cache, i):
        b, h, s, d = q.shape
        att = paged_chunk_attention(
            q.reshape(b, cfg.kv_heads, h // cfg.kv_heads, s, d), cache[pools[0]], cache[pools[1]], i, block_tables, chunk,
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


def _attend_table(cfg: TransformerConfig, block_tables: jax.Array, mask: jax.Array):
    """Queries against every token of every table column, gathered from the
    pool, under ``mask``: decode without the paged path, the oracle that path
    is tested against."""

    def attend(q, k, v, cache, i):
        keys = _gather_table(cfg, cache["k"], i, block_tables)
        vals = _gather_table(cfg, cache["v"], i, block_tables)
        return _masked_attention(cfg, q, keys, vals, mask)

    return attend


def _attend_ring_table(cfg: TransformerConfig, positions: jax.Array):
    """A window layer's decode without the paged path: every lane's whole
    ring, gathered, each slot masked by the position it must hold.  Slot ``s``
    of a lane at position ``pos`` holds ``p = pos - (pos - s) % ring`` if it
    holds anything of this request; the query sees it if ``p >= 0`` and ``p >
    pos - window``.  What an earlier request left in the lane is never seen."""

    def attend(q, k, v, cache, i):
        b = q.shape[0]
        ring = cache["wk"].shape[1] // b * cache["wk"].shape[2]
        rows = lambda pool: _repeat_kv(  # noqa: E731
            pool[i].reshape(b, ring, cfg.kv_heads, -1).transpose(0, 2, 1, 3), cfg.n_heads // cfg.kv_heads
        )
        pos = positions[:, None]
        held = pos - (pos - jnp.arange(ring)[None, :]) % ring  # [b, ring]
        mask = (held >= 0) & (held > pos - cfg.sliding_window) & (pos >= 0)
        return _masked_attention(cfg, q, rows(cache["wk"]), rows(cache["wv"]), mask[:, None, :])

    return attend


# A retention layer's backends: ``retain(q, k, v, log_g, cache, j)`` with q [b,
# n_heads, s, head_dim], k, v [b, kv_heads, s, head_dim], the gate's logarithm
# [b, kv_heads, s] and ``j`` the layer's place in the state pool; returns ([b,
# n_heads, s, head_dim], the cache with the lanes' slots updated).  It is write
# and attend in one: the state is both.


def _retain_chunk(cfg: TransformerConfig, lanes: jax.Array, valid: jax.Array, fresh):
    """``s`` tokens a row after what the slots of ``lanes`` [b] hold (nothing,
    under ``fresh``: a sequence starts from a zeroed slot), and into them:
    the prefill walk's chunk, and the wide prefill as one chunk."""

    def retain(q, k, v, log_g, cache, j):
        state, norm = cache["rs"][j, lanes], cache["rz"][j, lanes]
        state, norm = jnp.where(fresh, 0.0, state), jnp.where(fresh, 0.0, norm)
        out, state, norm = retention_chunk(q, k, v, log_g, state, norm, valid)
        return out.astype(cfg.dtype), {**cache, "rs": cache["rs"].at[j, lanes].set(state), "rz": cache["rz"].at[j, lanes].set(norm)}

    return retain


def _retain_decode(cfg: TransformerConfig, live: jax.Array, impl: Optional[str] = None):
    """One token a lane, row ``b`` of the batch IS lane ``b``: the slot is
    decayed, takes the token and answers it (``ops/retention.py
    retention_decode``: the Pallas kernel on a TPU, in place); a lane that is
    not ``live`` [b] leaves its slot alone."""

    def retain(q, k, v, log_g, cache, j):
        out, state, norm = retention_decode(
            q[:, :, 0], k[:, :, 0], v[:, :, 0], log_g[:, :, 0], cache["rs"], cache["rz"], j, live, impl=impl
        )
        return out.astype(cfg.dtype)[:, :, None, :], {**cache, "rs": state, "rz": norm}

    return retain


# Latent attention's backends: ``attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, i)``
# with q_nope [b, n_heads, s, qk_nope], q_rope [b, n_heads, s, qk_rope] (after
# rope), this call's own latent rows c_kv [b, s, kv_lora] (after their norm) and
# k_r [b, s, qk_rope] (after rope), ``wkv_b`` [kv_lora, n_heads, qk_nope + v]
# and the pool that already holds the rows; returns [b, s, n_heads, v_head_dim].
# The first expands keys and values a head from the rows, as the equations are
# published; the other two stay in the latent space (``q_lat_h = q_nope_h
# W^K_h``, ``o_h = (sum p c_kv) W^V_h``: the same mathematics, and one row a
# token serves every head's scores and values).


def _latent_split(cfg, wkv_b, q_nope):
    """(queries in the latent space [b, h, s, kv_lora], W^V [kv_lora, h, v])."""
    w = wkv_b.astype(cfg.dtype)
    return jnp.einsum("bhsn,chn->bhsc", q_nope, w[..., : cfg.qk_nope_head_dim]), w[..., cfg.qk_nope_head_dim:]


def _latent_attend_paged(cfg: TransformerConfig, block_tables: jax.Array, positions: jax.Array):
    """One query a lane against the lane's live latent rows, read where they
    lie in the pool (``ops/paged_attention.py``); ``positions`` [b], -1 = idle."""

    def attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, i):
        q_lat, w_v = _latent_split(cfg, wkv_b, q_nope)
        q = jnp.concatenate([q_lat, q_rope], axis=-1)[:, :, 0]
        q = jnp.pad(q, ((0, 0), (0, 0), (0, cache["kv"].shape[-1] - q.shape[-1])))
        with jax.named_scope("serve.mla.attend"):  # the kernel alone: what its roofline share times
            out = paged_latent_attention(
                q, cache["kv"], i, block_tables, positions, scale=cfg.attn_scale, value_dim=cfg.kv_lora_rank
            )
        return jnp.einsum("bhc,chv->bhv", out.astype(cfg.dtype), w_v)[:, None]

    return attend


def _latent_attend_chunk(cfg: TransformerConfig, block_tables: jax.Array, chunk: jax.Array):
    """The prefill walk's read (``_attend_chunk``) in the latent space: every
    head's queries ``[q_lat | q_rope | zeros]`` against the pool's rows, whose
    first ``kv_lora_rank`` columns are the values."""

    def attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, i):
        q_lat, w_v = _latent_split(cfg, wkv_b, q_nope)
        q = jnp.concatenate([q_lat, q_rope], axis=-1)
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, cache["kv"].shape[-1] - q.shape[-1]),))
        with jax.named_scope("serve.mla.attend"):
            out = paged_chunk_attention(
                q[:, None], cache["kv"], None, i, block_tables, chunk, scale=cfg.attn_scale, value_dim=cfg.kv_lora_rank
            )
        return jnp.einsum("bhsc,chv->bshv", out[:, 0].astype(cfg.dtype), w_v)

    return attend


def _latent_attend_table(cfg: TransformerConfig, block_tables: jax.Array, mask: jax.Array):
    """Queries against every row of every table column, gathered from the
    pool, under ``mask`` (as ``_attend_table``'s) and a float32 softmax:
    decode without the paged path, the oracle that path is tested against."""

    def attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, i):
        b, t = block_tables.shape
        r = cfg.kv_lora_rank
        rows = cache["kv"][i, block_tables].reshape(b, t * cache["kv"].shape[2], -1)
        lat, rot = rows[..., :r], rows[..., r: r + cfg.qk_rope_head_dim]
        q_lat, w_v = _latent_split(cfg, wkv_b, q_nope)
        logits = jnp.einsum("bhsc,bkc->bhsk", q_lat, lat, preferred_element_type=jnp.float32)
        logits = logits + jnp.einsum("bhsr,bkr->bhsk", q_rope, rot, preferred_element_type=jnp.float32)
        seen = mask[None, None] if mask.ndim == 2 else mask[:, None]
        probs = jax.nn.softmax(jnp.where(seen, logits * cfg.attn_scale, NEG_INF), axis=-1)
        out = jnp.einsum("bhsk,bkc->bhsc", probs.astype(lat.dtype), lat)
        return jnp.einsum("bhsc,chv->bshv", out, w_v)

    return attend


#: what a decode step of a model with expert layers counts beside its logits,
#: each summed over the expert layers: picks that landed on a held expert, and
#: held experts that got at least one row (whose matrices the step had to read)
SERVE_COUNTERS = ("serve.moe.held_picks", "serve.moe.experts_hit")
#: what a decode step of a model with window layers counts first: the cached
#: tokens its attention reads, summed over the lanes, in the full layers (the
#: context a layer) and in the window layers (the context or the window a layer)
SERVE_KV_COUNTERS = ("serve.kv.full_tokens", "serve.kv.window_tokens")
#: what a decode step of a model with retention layers counts first: the lanes
#: whose state it updated, and the bytes of state those hold over the retention
#: layers (lanes x layers x ``state_bytes_per_slot``): what the step had to read
SERVE_STATE_COUNTERS = ("serve.state.live_lanes", "serve.state.bytes")


def serve_counters(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The names of what ``transformer_decode(counters=True)`` counts, in the row's order."""
    return (
        (SERVE_STATE_COUNTERS if cfg.retention_layers else ()) + (SERVE_KV_COUNTERS if cfg.window_layers else ())
        + (SERVE_COUNTERS if cfg.moe_experts else ())
    )


def _serve_layer(cfg, i, blk, x, positions, write, attend, cache, live=None, sliding=None, retain=None):
    """Layer ``i`` of the serving forward, stated once under the three entry
    points below: norm, the attention's projections (q/k/v, or latent
    attention's), rope at ``positions`` ([s], or [b, s]), this call's rows into
    the pool at ``write`` = (physical block, slot), each [b, s] (or [b] where s
    is 1), then ``attend`` against the updated pool, so that a token sees its
    own key, then the output projection, the MLP or the experts held here
    (which count the tokens ``live`` [b, s] marks) and both residuals; under
    ``parallel_block`` the MLP or experts read the one norm attention read.
    A sliding-window layer writes and attends through ``sliding`` = (write,
    attend) instead, into the window store: its blocks are a lane's ring, and
    a block id past the store's end drops the row (idle lanes, padding).
    A power-retention layer writes no row and attends to none: ``retain``
    updates its lanes' slots of the state pool and answers from them (``write``
    may be None for a model of such layers alone).
    Returns (x, cache, what an expert layer counted or None)."""
    dt = cfg.dtype
    rope = cfg.rope(cfg.layer_type(i))
    slides, retains = cfg.layer_type(i) == SLIDING, cfg.layer_type(i) == RETENTION
    if not retains:
        phys, slots = sliding[0] if slides else write
    j = cfg.cache_index(i)
    h = _norm_apply(cfg, x, blk["ln1"]["scale"])
    if cfg.latent:
        with jax.named_scope("serve.mla"):
            p = blk["attn"]
            q_nope, q_rope, c_kv, k_r = _latent_project(cfg, p, h, positions, rope)
            with jax.named_scope("serve.kv.write"):
                row = jnp.concatenate([c_kv, k_r], axis=-1)
                row = jnp.pad(row, ((0, 0), (0, 0), (0, cache["kv"].shape[-1] - row.shape[-1])))
                cache = {"kv": cache["kv"].at[i, phys, slots].set(row.reshape(*phys.shape, -1))}
            att = attend(q_nope, q_rope, c_kv, k_r, p["wkv_b"], cache, i)
            x = x + jnp.einsum("bshv,hvD->bsD", att, p["wo"].astype(dt))
    elif retains:
        with jax.named_scope("serve.retention.qkvg"):
            q, k, v, log_g = _retention_proj(cfg, blk["attn"], h, positions)
        with jax.named_scope("serve.retention.state"):  # decay, update, query, normalise
            att, cache = retain(q, k, v, log_g, cache, j)
        with jax.named_scope("serve.retention.out"):
            x = x + jnp.einsum("bshk,hkD->bsD", att.transpose(0, 2, 1, 3), blk["attn"]["wo"]["kernel"].astype(dt))
    else:
        with jax.named_scope("serve.attn.qkv"):
            q, k, v = _attn_proj(blk["attn"], h, dt)
            q, k = _rope(q, positions, rope), _rope(k, positions, rope)
        with jax.named_scope("serve.kv.write"):
            if slides:
                cache = {
                    **cache,
                    "wk": cache["wk"].at[j, phys, slots].set(_pool_rows(k, phys.shape), mode="drop"),
                    "wv": cache["wv"].at[j, phys, slots].set(_pool_rows(v, phys.shape), mode="drop"),
                }
            else:
                cache = {
                    **cache,
                    "k": cache["k"].at[j, phys, slots].set(_pool_rows(k, phys.shape)),
                    "v": cache["v"].at[j, phys, slots].set(_pool_rows(v, phys.shape)),
                }
        with jax.named_scope("serve.attn.attend"):  # whichever backend the entry point picked
            if sliding is None:
                att = attend(q, k, v, cache, j)
            else:  # a cache of two kinds: the device trace tells them apart
                with jax.named_scope("serve.attn.window" if slides else "serve.attn.full"):
                    att = (sliding[1] if slides else attend)(q, k, v, cache, j)
            att = att.transpose(0, 2, 1, 3)  # [b, s, h, hd]
        with jax.named_scope("serve.attn.out"):
            x = x + jnp.einsum("bshk,hkD->bsD", att, blk["attn"]["wo"]["kernel"].astype(dt))
    if not cfg.parallel_block:  # else the one norm: what attention read
        h = _norm_apply(cfg, x, blk["ln2"]["scale"])
    if not cfg.use_moe(i):
        with jax.named_scope("serve.mlp"):
            return x + _mlp_apply(blk["mlp"], h, dt), cache, None
    from determined_tpu.models.moe import serve_routed_experts

    y, counted = serve_routed_experts(cfg, blk["moe"], h, live)
    return x + y, cache, counted


def _serve_layers(cfg, params, x, positions, write, attend, cache, live=None, sliding=None, retain=None):
    """Every layer; the last value is SERVE_COUNTERS' sums over the expert
    layers, [2] float32, or None for a model without them."""
    counted = []
    for i in range(cfg.n_layers):
        x, cache, c = _serve_layer(cfg, i, params[f"block_{i}"], x, positions, write, attend, cache, live, sliding, retain)
        if c is not None:
            counted.append(jnp.stack(c).astype(jnp.float32))
    return x, cache, sum(counted) if counted else None


def transformer_prefill(
    cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array, prompt_lens: jax.Array,
    block_tables: jax.Array, cache: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-prompt forward that also populates the paged cache: every
    position's logits in one pass over the padded width.  The engine runs
    :func:`transformer_prefill_chunked`, whose work follows the prompt; this
    form stays as the oracle the walk and the decode step are tested against.

    ``tokens`` [B, S] is the prompt padded to a fixed S (one trace);
    ``prompt_lens`` [B] the real lengths; ``block_tables`` [B, T] each
    lane's physical block ids.  Returns (logits [B, S, vocab] f32, cache).
    Logits at positions >= prompt_len are computed over padding — callers
    sample at ``prompt_len - 1``.  Causality makes positions < prompt_len
    match the full-sequence forward exactly (padding sits strictly after
    them), which is what the parity tests in tests/test_transformer.py pin.
    A model with sliding-window layers prefills through the walk alone (the
    window store is sized by the walk's chunk), its oracle the full forward.
    """
    _check_decodable(cfg)
    if cfg.window_layers:
        raise ValueError("the wide prefill runs full layers only: sliding-window layers prefill through transformer_prefill_chunked")
    block_size = _block_size(cache)
    b, s = tokens.shape
    x = _embed_rows(params, tokens, cfg.dtype)
    positions = jnp.arange(s)
    valid = positions[None, :] < prompt_lens[:, None]
    write = None
    if block_size is not None:
        with jax.named_scope("serve.kv.write"):
            # physical destination of every (lane, position): padded tail -> scratch
            phys = jnp.where(
                valid,
                jnp.take_along_axis(
                    block_tables, jnp.broadcast_to(positions[None, :] // block_size, (b, s)), axis=1
                ),
                0,
            )
            write = (phys, jnp.broadcast_to((positions % block_size)[None, :], (b, s)))
    attend = _latent_attend_local(cfg) if cfg.latent else _attend_local
    # a retention layer takes the prompt as one chunk into lane b's zeroed slot
    retain = _retain_chunk(cfg, jnp.arange(b), valid, True) if cfg.retention_layers else None
    # the padded tail takes no expert's rows
    live = valid if cfg.moe_experts else None
    x, cache, _ = _serve_layers(cfg, params, x, positions, write, attend, cache, live, retain=retain)
    return _head(cfg, params, x), cache


def transformer_decode(
    cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array, positions: jax.Array,
    block_tables: jax.Array, cache: Dict[str, jax.Array], *, chunk_blocks: int = 0,
    counters: bool = False, retention_impl: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step over the paged cache for every lane at once.

    ``tokens`` [B] the token each lane just consumed; ``positions`` [B] its
    global position (-1 marks an empty lane: it reads/writes the scratch
    block and its logits are garbage the caller ignores); ``block_tables``
    [B, T].  Returns (logits [B, vocab] f32, cache).  Shapes are lane-count
    static, so a mixed stream of request lengths never retraces — the
    continuous batcher joins and retires sequences by editing lane state,
    not by reshaping the batch.

    ``chunk_blocks`` > 0 selects the paged path
    (:func:`determined_tpu.ops.paged_attention.paged_decode_attention`):
    each lane's live blocks are read from the pool where it lies and folded
    into a float32 online softmax, by the Pallas kernel where the shapes
    tile on a TPU and by the same mathematics in ``jax.numpy`` elsewhere.
    The value only selects: the width of a pass is chosen from the shapes
    (it still has to divide the table width, as it always had to).  0 keeps
    the full-table gather ``[b, T*block_size, kv_heads, head_dim]`` every
    step, the oracle of the parity tests.  Both paths share every
    projection and the cache-write scatter, and agree to f32 tolerance.

    ``counters`` (the engine's decode program, where the model has expert
    or window layers): the logits come back ``[B + 1, vocab]``, and the last
    row's first entries are ``serve_counters(cfg)`` of this step, so that they
    reach the host in the logits' own copy.  Idle lanes take no expert's rows.

    Sliding-window layers read and write the window store (``init_kv_cache``):
    row ``b`` of the batch IS lane ``b``, whose ring holds the lane's newest
    tokens by position; a window layer reads ``min(position + 1, window)`` of
    them and nothing older, by the paged path over the ring (the gathered ring
    under ``chunk_blocks`` 0).

    Power-retention layers read and write the state pool alone: row ``b`` of
    the batch IS lane ``b`` and updates slot ``b``; an idle lane leaves its
    slot as it is.  A model of such layers alone has no pool, and
    ``block_tables`` is read by nothing.  ``retention_impl`` picks the form
    of ``ops/retention.py retention_decode`` (tests).
    """
    _check_decodable(cfg)
    block_size = _block_size(cache)
    t = block_tables.shape[1]
    if chunk_blocks and t % chunk_blocks:
        raise ValueError(f"chunk_blocks={chunk_blocks} must divide the table width {t}")
    active = positions >= 0
    pos = jnp.maximum(positions, 0)
    x = _embed_rows(params, tokens[:, None], cfg.dtype)
    retain = _retain_decode(cfg, active, retention_impl) if cfg.retention_layers else None
    if block_size is None:  # no layer reads a pool: nothing to write, no table to attend through
        x, cache, counted = _serve_layers(cfg, params, x, pos[:, None], None, None, cache, retain=retain)
        return _decode_result(cfg, params, x, active, pos, counted, counters), cache
    with jax.named_scope("serve.kv.write"):  # where each lane's row goes: idle lanes -> scratch
        phys = jnp.where(
            active, jnp.take_along_axis(block_tables, (pos // block_size)[:, None], axis=1)[:, 0], 0
        )
    if chunk_blocks:
        attend = (_latent_attend_paged if cfg.latent else _attend_paged)(cfg, block_tables, positions)
    else:
        # every cache position up to and including the current token
        with jax.named_scope("serve.attn.attend"):
            mask = (jnp.arange(t * block_size)[None, :] <= pos[:, None]) & active[:, None]  # [B, kv_len]
        attend = (_latent_attend_table if cfg.latent else _attend_table)(cfg, block_tables, mask[:, None, :])
    live = active[:, None] if cfg.moe_experts else None
    pos_col = pos[:, None]
    with jax.named_scope("serve.kv.write"):
        slots = pos % block_size
    sliding = None
    if cfg.window_layers:
        lanes, store = tokens.shape[0], cache["wk"].shape[1]
        ring_blocks = store // lanes
        with jax.named_scope("serve.kv.write"):  # lane b's ring; an idle lane's row is dropped
            first = jnp.arange(lanes, dtype=jnp.int32) * ring_blocks
            wphys = jnp.where(active, first + (pos // block_size) % ring_blocks, store)
        if chunk_blocks:
            rings = first[:, None] + jnp.arange(ring_blocks, dtype=jnp.int32)[None, :]
            sliding = ((wphys, slots), _attend_paged(cfg, rings, positions, cfg.sliding_window))
        else:
            sliding = ((wphys, slots), _attend_ring_table(cfg, positions))
    x, cache, counted = _serve_layers(cfg, params, x, pos_col, (phys, slots), attend, cache, live, sliding, retain)
    return _decode_result(cfg, params, x, active, pos, counted, counters), cache


def _decode_result(cfg, params, x, active, pos, counted, counters: bool) -> jax.Array:
    """A decode step's logits and, under ``counters``, the row of
    ``serve_counters(cfg)`` after them (``counted``: what the expert layers counted)."""
    logits = _head(cfg, params, x, row=0)
    if counters and cfg.window_layers:
        with jax.named_scope("serve.head"):  # the cached tokens this step's attention reads, by kind of layer
            lens = jnp.where(active, pos + 1, 0).astype(jnp.float32)
            n_window = len(cfg.window_layers)
            read = jnp.stack([
                jnp.sum(lens) * (cfg.n_layers - n_window), jnp.sum(jnp.minimum(lens, cfg.sliding_window)) * n_window,
            ])
            counted = read if counted is None else jnp.concatenate([read, counted])
    if counters and cfg.retention_layers:
        with jax.named_scope("serve.head"):  # the lanes whose state this step updated, and the bytes they hold
            lanes = jnp.sum(active.astype(jnp.float32))
            held = jnp.stack([lanes, lanes * (len(cfg.retention_layers) * state_bytes_per_slot(cfg))])
            counted = held if counted is None else jnp.concatenate([held, counted])
    if counters and counted is not None:
        with jax.named_scope("serve.head"):  # the counters ride in the logits' own copy
            row = jnp.zeros((1, logits.shape[1]), jnp.float32).at[0, : counted.shape[0]].set(counted)
            logits = jnp.concatenate([logits, row], axis=0)
    return logits


#: tokens an iteration of the prefill walk aims for.  An iteration sweeps every
#: weight once, so its time is the sweep's until the chunk's own arithmetic
#: passes it: at 256 tokens the sweep still bounds it at InternLM2's and at
#: DeepSeek-V3's widths (PERF.md section 5), and a prompt pays for at most 255
#: tokens it did not ask for.
PREFILL_CHUNK_TOKENS = 256


def prefill_chunk_tokens(block_size: int, prompt_tokens: int) -> int:
    """Tokens a chunk of :func:`transformer_prefill_chunked`, from the shapes:
    ``PREFILL_CHUNK_TOKENS`` in whole blocks and whole 128-wide tiles, or the
    longest prompt in whole blocks where that is shorter.  A caller pads its
    prompts to a multiple of it."""
    unit = math.lcm(block_size, 128)
    chunk = -(-PREFILL_CHUNK_TOKENS // unit) * unit
    return min(chunk, -(-prompt_tokens // block_size) * block_size)


def transformer_prefill_chunked(
    cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array, start_lens: jax.Array,
    prompt_lens: jax.Array, block_tables: jax.Array, cache: Dict[str, jax.Array],
    lanes: Optional[jax.Array] = None, *, chunk_tokens: Optional[int] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill each prompt from ``start_lens`` on, a chunk of tokens at a time:
    the serving engine's one prefill program, for a cold prompt (``start`` 0)
    and for the un-cached suffix of one whose prefix is cached alike.

    ``tokens`` [B, S] is the FULL prompt padded to a multiple of the chunk
    (``prefill_chunk_tokens(block_size, S)``); ``start_lens`` [B] how many
    leading tokens already sit in cache blocks mapped into ``block_tables``
    (block-aligned by construction: only full blocks are shared);
    ``prompt_lens`` [B] the real lengths.  Returns (last_logits [B, vocab] f32,
    the logits at ``prompt_len - 1`` each lane samples its first token from,
    and the updated cache).

    The walk is one chunk of C tokens an iteration of a dynamic-trip-count
    ``fori_loop``, chunks at the absolute positions ``[c * C, (c + 1) * C)``
    for ``c`` in ``start // C .. ceil(len / C)``: the compute and the single
    compiled trace follow the tokens ASKED, not the padded width, and a
    70%-shared system prompt pays for its unique tail only.  An iteration
    reads every weight once for C tokens.  Queries attend against keys READ
    FROM THE CACHE up to the chunk's end (prefix blocks written by whoever
    prefilled them first, the chunk's own written just before attending),
    masked ``k_pos <= q_pos``, which makes a warm start and a cold ``start=0``
    run of the same prompt bitwise identical, wherever ``start`` falls in its
    chunk: the parity the prefix-cache admission tests pin.  Positions outside
    ``[start, len)`` write to scratch block 0 and take no expert's rows;
    since keys come from the cache rather than the local projection, garbage
    padding columns cannot leak into valid ones.  The head runs once, on the
    row of ``prompt_len - 1`` alone.

    Sliding-window layers keep their rows in the window store, in the ring of
    the decode lane each prompt will run in: ``lanes`` [B] (absent: row ``b`` is
    lane ``b``).  A chunk's rows go to the slots of their positions, and its
    queries read the ring back to ``window - 1`` positions before each of them:
    the ring is one chunk longer than the window, so no row a query of the
    chunk still sees is overwritten.  Such a prompt starts at 0 (a shared
    block holds no window state), and its walk computes every chunk.

    Power-retention layers carry a state through the walk, in the slot of the
    decode lane each prompt will run in (``lanes`` as above): the first chunk
    starts from a zeroed slot, every chunk is answered from the slot and folded
    into it, and the walk leaves it holding the prompt's state.  Such a prompt
    starts at 0 too (no block holds a state).  A model of such layers alone has
    no pool to take the block size from: ``chunk_tokens`` states the chunk.
    """
    _check_decodable(cfg)
    block_size = _block_size(cache)
    paged = block_size is not None
    b, s = tokens.shape
    if not paged:
        if chunk_tokens is None:
            raise ValueError("a cache without a pool states no block size: pass chunk_tokens")
        chunk, block_size = chunk_tokens, 1
    else:
        chunk = prefill_chunk_tokens(block_size, s)
    if s % chunk:
        raise ValueError(
            f"chunked prefill needs tokens padded to whole chunks (got S={s}, "
            f"chunk={chunk}, block_size={block_size})"
        )
    slot_lanes = jnp.arange(b, dtype=jnp.int32) if lanes is None else lanes.astype(jnp.int32)
    blocks, t = chunk // block_size, block_tables.shape[1]
    if cfg.window_layers:
        ring_blocks, store = window_ring_blocks(cfg, block_size, chunk), cache["wk"].shape[1]
        if store % ring_blocks:
            raise ValueError(
                f"the window store ({store} blocks) is not whole rings of {ring_blocks} blocks: it was sized "
                f"for another prefill chunk than {chunk} tokens"
            )
        with jax.named_scope("serve.kv.write"):
            first = (jnp.arange(b, dtype=jnp.int32) if lanes is None else lanes.astype(jnp.int32)) * ring_blocks
            rings = first[:, None] + jnp.arange(ring_blocks, dtype=jnp.int32)[None, :]
    with jax.named_scope("serve.walk"):  # the trip count; the loop below is under it too, around its layers' scopes
        c_lo = jnp.min(start_lens) // chunk
        c_hi = (jnp.max(prompt_lens) + chunk - 1) // chunk
        offsets = jnp.arange(chunk)

    def body(c, carry):
        cache, last = carry
        with jax.named_scope("serve.embed"):
            toks = jax.lax.dynamic_slice(tokens, (0, c * chunk), (b, chunk))
        p = c * chunk + offsets  # absolute positions [chunk]
        with jax.named_scope("serve.kv.write"):
            valid = (p[None, :] >= start_lens[:, None]) & (p[None, :] < prompt_lens[:, None])  # [b, chunk]
        write = attend = None
        if paged:
            with jax.named_scope("serve.kv.write"):
                # a padded prompt may be wider than the table: those columns hold no valid row
                cols = jnp.minimum(c * blocks + offsets // block_size, t - 1)
                phys = jnp.where(valid, jnp.take(block_tables, cols, axis=1), 0)
                slots = jnp.broadcast_to((offsets % block_size)[None, :], (b, chunk))
            write = (phys, slots)
            attend = (_latent_attend_chunk if cfg.latent else _attend_chunk)(cfg, block_tables, c)
        retain = _retain_chunk(cfg, slot_lanes, valid, c == c_lo) if cfg.retention_layers else None
        sliding = None
        if cfg.window_layers:
            with jax.named_scope("serve.kv.write"):  # rows outside [start, len) are dropped
                wcols = (c * blocks + offsets // block_size) % ring_blocks
                wphys = jnp.where(valid, first[:, None] + wcols[None, :], store)
            sliding = ((wphys, slots), _attend_chunk(cfg, rings, c, cfg.sliding_window))
        # a leaf stored wider than the compute dtype is read as it lies and
        # converted on its way into each product, every iteration.  The
        # conversions depend on nothing the loop changes, and XLA would move
        # them before it: a second copy of the model in the compute dtype,
        # made and held for the whole call (3.3 GiB of scratch at InternLM2's
        # float32 leaves, and 11 ms before the first chunk).  Adding a zero
        # that only the loop's counter decides keeps them where they are.
        zero = (c < 0).astype(jnp.float32)
        layers = {name: sub for name, sub in params.items() if name.startswith("block_")}
        layers = jax.tree.map(lambda w: w if w.dtype == cfg.dtype else w + zero.astype(w.dtype), layers)
        x = _embed_rows(params, toks, cfg.dtype)
        x, cache, _ = _serve_layers(
            cfg, layers, x, p, write, attend, cache, valid if cfg.moe_experts else None, sliding, retain
        )
        with jax.named_scope("serve.head"):  # the one row the head will read
            sel = prompt_lens - 1 - c * chunk  # [b]
            row = jnp.take_along_axis(x, jnp.clip(sel, 0, chunk - 1)[:, None, None], axis=1)
            return cache, jnp.where(((sel >= 0) & (sel < chunk))[:, None, None], row, last)

    with jax.named_scope("serve.walk"):
        init = (cache, jnp.zeros((b, 1, cfg.d_model), cfg.dtype))
        cache, last = jax.lax.fori_loop(c_lo, c_hi, body, init)
    return _head(cfg, params, last, row=0), cache
