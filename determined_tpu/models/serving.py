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
index to a physical block id.  What else a layer may keep of the past (a
latent row, a ring a lane, a state a lane) is stated in ``models/cache_kinds.py``,
one record a kind: the layer function below calls whichever mixer the layer's
kind built, and no function here names a leaf of the cache.  Everything below
is a pure function over the UNBOXED param tree that
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

import functools
import math
import types
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from determined_tpu.models.cache_kinds import Rows, cache_kinds, layer_kinds, pool_block_size
from determined_tpu.models.transformer import CCA, TransformerConfig, _layer_norm, _rms_apply, _times


def init_kv_cache(
    cfg: TransformerConfig, num_blocks: int, block_size: int, lanes: Optional[int] = None,
    chunk_tokens: Optional[int] = None,
) -> Dict[str, jax.Array]:
    """The zeroed cache of a model: the leaves of every kind its layers are of,
    and no other (a model none of whose layers reads the pool gets none).  Keys
    are stored post-rope, i.e. exactly what attention consumes; ``lanes`` decode
    lanes and prefill chunks of ``chunk_tokens`` size the stores a lane holds."""
    sizes = types.SimpleNamespace(num_blocks=num_blocks, block_size=block_size, max_batch=lanes, prefill_chunk=chunk_tokens)
    return {
        leaf: jnp.zeros(shape, dtype)
        for kind in cache_kinds(cfg) for leaf, shape, dtype in zip(kind.leaves, kind.shapes(cfg, sizes), kind.dtypes(cfg))
    }


def _norm_apply(cfg: TransformerConfig, x: jax.Array, scale: jax.Array) -> jax.Array:
    """A block's (or the final) norm as the configuration states it."""
    if cfg.norm == "rms":
        return _rms_apply(x, scale, cfg.norm_eps)
    with jax.named_scope("serve.norm"):
        return _layer_norm(x, scale, cfg.norm_eps)


def _mlp_apply(p: Dict[str, Any], x: jax.Array, dtype: Any, multipliers: Tuple[float, float] = (1.0, 1.0)) -> jax.Array:
    """SwiGLU; ``multipliers``: muP's scalars on the gate and on the output."""
    gate = _times(x @ p["w_gate"]["kernel"].astype(dtype), multipliers[0])
    up = x @ p["w_up"]["kernel"].astype(dtype)
    return _times((nn.silu(gate) * up) @ p["w_down"]["kernel"].astype(dtype), multipliers[1])


def _check_decodable(cfg: TransformerConfig) -> None:
    if cfg.moe_experts > 0 and not cfg.moe_top_k:
        raise ValueError(
            "KV-cache serving runs dropless experts (moe_top_k > 0); the top-2 capacity "
            "layer drops tokens by the batch they arrive in and is not served"
        )
    if cfg.seq_axis_name is not None or cfg.expert_axis_name is not None:
        raise ValueError("KV-cache serving runs outside pipeline stages")
    unserved = [
        what for what, there in (
            ("a cca layer (its cache is K and V a token AND a lane's tail: the convolutions' newest latent rows and "
             "the newest normed input, a kind models/cache_kinds.py does not have)", CCA in (cfg.layer_types or ())),
            ("moe_router mlp (its state from layer to layer)", cfg.moe_router == "mlp"),
            ("residual_scaling", cfg.residual_scaling),
        ) if there
    ]
    if unserved:
        raise ValueError("KV-cache serving does not run " + "; ".join(unserved) + ": such a model is trained, not served yet")


def _embed_rows(params: Dict[str, Any], tokens: jax.Array, dtype: Any) -> jax.Array:
    """Embedding rows of ``tokens`` in the compute dtype: the rows first, then
    their conversion, so that the table is read where ``tokens`` point and not swept."""
    with jax.named_scope("serve.embed"):
        return jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(dtype)


def _head(cfg: TransformerConfig, params: Dict[str, Any], x: jax.Array, row: Optional[int] = None) -> jax.Array:
    """Final norm and ``lm_head`` (tied: the embedding's transpose), times
    ``logit_scale``: float32 logits at every position of ``x``, or at ``row`` alone."""
    x = _norm_apply(cfg, x, params["ln_f"]["scale"])
    with jax.named_scope("serve.head"):
        x = x if row is None else x[:, row, :]
        if not cfg.tie_embeddings:
            return _times((x @ params["lm_head"]["kernel"].astype(cfg.dtype)).astype(jnp.float32), cfg.logit_scale)
        # float32 out of the product itself: the table is the head, and its logits are what a caller samples from
        logits = jnp.einsum(
            "...d,vd->...v", x, params["embed"]["embedding"].astype(cfg.dtype), preferred_element_type=jnp.float32
        )
        return logits * cfg.logit_scale


#: what a decode step of a model with expert layers counts beside its logits,
#: each summed over the expert layers: picks that landed on a held expert, and
#: held experts that got at least one row (whose matrices the step had to read)
SERVE_COUNTERS = ("serve.moe.held_picks", "serve.moe.experts_hit")
#: and, where the router has identity experts (``moe_zero_experts``), the picks
#: of live tokens that landed on one: each adds ``w x`` and costs no row
ZERO_PICKS = "serve.moe.zero_picks"


def serve_counters(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The names of what ``transformer_decode(counters=True)`` counts, in the
    row's order: each cache kind's, in the table's order, then the experts'."""
    kinds = sum((kind.counters for kind in cache_kinds(cfg)), ())
    return kinds + (SERVE_COUNTERS if cfg.moe_experts else ()) + ((ZERO_PICKS,) if cfg.moe_zero_experts else ())


def serve_gauges(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The names of what ``transformer_decode(counters=True)`` reads off the
    cache it returns, in the row's order after ``serve_counters``: each cache
    kind's gauges, in the table's order."""
    return sum((kind.gauges for kind in cache_kinds(cfg)), ())


def _serve_layer(cfg, i, blk, x, mixers: Mapping[str, Callable], cache, live=None, handed=None):
    """Layer ``i`` of the serving forward, stated once under the three entry
    points below: norm; the mixer of each of the layer's cache kinds, in the
    table's order (``mixers``: one a kind of the model, built by the entry point
    from the rows of its call), which reads the norm, projects, writes this
    call's rows, attends so that a token sees its own key, and adds its output
    projection to the stream (attention heads and Mamba-2 heads side by side are
    a layer of two kinds); then the MLP or the
    experts held here (which count the tokens ``live`` [b, s] marks) and its
    residual; under ``parallel_block`` the MLP or experts read the one norm
    attention read.  Under ``shortcut_block`` the block has two attention
    sublayers (two rows of the kind's arrays, two subtrees) and two dense MLPs;
    its experts read the first sublayer's second norm and join the stream after
    the second MLP.  Under ``mixer_block`` a layer is the norm and ONE of these:
    the mixer of its one cache kind, or the experts (a layer of no kind).
    ``handed``: what the layers of a kind that hands something on beside the
    stream take from the layer before and leave for the next, inside one call
    (``CacheKind.indexed``: a latent layer's picks where an indexer selects its keys).
    Returns (x, cache, what an expert layer counted or None, handed)."""

    def attend(sub: int, x, cache):
        """Attention sublayer ``sub``: (the stream with it, the norm its MLP or experts read, the cache)."""
        nonlocal handed
        tail = f"_{sub}" if sub else ""
        h = _norm_apply(cfg, x, blk["ln1" + tail]["scale"])
        for kind, j, subtree in layer_kinds(cfg, i, sub):
            if kind.indexed:
                x, cache, handed = mixers[kind.name](blk[subtree], x, h, cache, j, handed)
            else:
                x, cache = mixers[kind.name](blk[subtree], x, h, cache, j)
        if not cfg.parallel_block:  # else the one norm: what attention read
            h = _norm_apply(cfg, x, blk["ln2" + tail]["scale"])
        return x, h, cache

    def mlp(name: str, x, h):
        with jax.named_scope("serve.mlp"):
            return x + _mlp_apply(blk[name], h, cfg.dtype, cfg.mlp_multipliers)

    if cfg.mixer_block:
        # one norm, ONE mixer, one residual: the experts (a layer of no cache kind), or the one kind's mixer
        h = _norm_apply(cfg, x, blk["ln1"]["scale"])
        if cfg.use_moe(i):
            from determined_tpu.models.moe import serve_routed_experts

            y, counted = serve_routed_experts(cfg, blk["moe"], h, live)
            return x + y, cache, counted, handed
        for kind, j, subtree in layer_kinds(cfg, i):  # no kind of a mixer_block model hands anything on
            x, cache = mixers[kind.name](blk[subtree], x, h, cache, j)
        return x, cache, None, handed
    x, h, cache = attend(0, x, cache)
    if not cfg.use_moe(i):
        return mlp("mlp", x, h), cache, None, handed
    from determined_tpu.models.moe import serve_routed_experts

    y, counted = serve_routed_experts(cfg, blk["moe"], h, live)
    if cfg.shortcut_block:
        x, h, cache = attend(1, mlp("mlp", x, h), cache)
        x = mlp("mlp_1", x, h)
    return x + y, cache, counted, handed


def _serve_layers(cfg, params, x, mixers: Mapping[str, Callable], cache, live=None):
    """Every layer; the last value is what the expert layers counted
    (``SERVE_COUNTERS``, and ``ZERO_PICKS`` where there are identity experts),
    summed over them, float32, or None for a model without them."""
    counted, handed = [], None
    for i in range(cfg.n_layers):
        x, cache, c, handed = _serve_layer(cfg, i, params[f"block_{i}"], x, mixers, cache, live, handed)
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
    window store is sized by the walk's chunk), its oracle the full forward;
    a retention layer takes the prompt as one chunk into lane b's zeroed slot.
    """
    _check_decodable(cfg)
    kinds = cache_kinds(cfg)
    if any(kind.wide is None for kind in kinds):
        raise ValueError("the wide prefill runs full layers only: sliding-window layers prefill through transformer_prefill_chunked")
    block_size = pool_block_size(cfg, cache)
    b, s = tokens.shape
    x = _times(_embed_rows(params, tokens, cfg.dtype), cfg.embedding_multiplier)
    positions = jnp.arange(s)
    valid = positions[None, :] < prompt_lens[:, None]
    where = None
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
            where = (phys, jnp.broadcast_to((positions % block_size)[None, :], (b, s)))
    rows = Rows(positions, block_tables, valid, where, block_size)
    mixers = {kind.name: kind.wide(cfg, rows, cache) for kind in kinds}
    # the padded tail takes no expert's rows
    x, cache, _ = _serve_layers(cfg, params, x, mixers, cache, valid if cfg.moe_experts else None)
    return _head(cfg, params, x), cache


def transformer_decode(
    cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array, positions: jax.Array,
    block_tables: jax.Array, cache: Dict[str, jax.Array], *, chunk_blocks: int = 0,
    counters: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step over the cache for every lane at once.

    ``tokens`` [B] the token each lane just consumed; ``positions`` [B] its
    global position (-1 marks an empty lane: it reads/writes the scratch
    block and its logits are garbage the caller ignores); ``block_tables``
    [B, T].  Returns (logits [B, vocab] f32, cache).  Shapes are lane-count
    static, so a mixed stream of request lengths never retraces — the
    continuous batcher joins and retires sequences by editing lane state,
    not by reshaping the batch.

    ``chunk_blocks`` > 0 selects every kind's ``step`` form, the paged path
    (:func:`determined_tpu.ops.paged_attention.paged_decode_attention`):
    each lane's live blocks are read from the pool where it lies and folded
    into a float32 online softmax, by the Pallas kernel where the shapes
    tile on a TPU and by the same mathematics in ``jax.numpy`` elsewhere.
    The value only selects: the width of a pass is chosen from the shapes
    (it still has to divide the table width, as it always had to).  0 selects
    the ``table`` form, the full-table gather ``[b, T*block_size, kv_heads,
    head_dim]`` every step, the oracle of the parity tests.  Both share every
    projection and the cache-write scatter, and agree to f32 tolerance.

    ``counters`` (the engine's decode program, where the model has expert
    layers or a cache kind that counts): the logits come back ``[B + 1,
    vocab]``, and the last row's first entries are ``serve_counters(cfg)`` of
    this step and then ``serve_gauges(cfg)`` of the cache it leaves, so that
    they reach the host in the logits' own copy.  Idle lanes
    take no expert's rows.

    Row ``b`` of the batch IS lane ``b`` for the kinds a request holds by its
    lane: a window layer reads and writes lane ``b``'s ring, a retention layer
    updates slot ``b``, and an idle lane leaves both as they are.  A model none
    of whose layers reads the pool has no pool, and ``block_tables`` is read by
    nothing.
    """
    _check_decodable(cfg)
    kinds = cache_kinds(cfg)
    block_size = pool_block_size(cfg, cache)
    t = block_tables.shape[1]
    if chunk_blocks and t % chunk_blocks:
        raise ValueError(f"chunk_blocks={chunk_blocks} must divide the table width {t}")
    active = positions >= 0
    pos = jnp.maximum(positions, 0)
    x = _times(_embed_rows(params, tokens[:, None], cfg.dtype), cfg.embedding_multiplier)
    with jax.named_scope("serve.kv.write"):  # where each lane's row goes: idle lanes -> scratch
        phys = None if block_size is None else jnp.where(
            active, jnp.take_along_axis(block_tables, (pos // block_size)[:, None], axis=1)[:, 0], 0
        )
    live = active[:, None] if cfg.moe_experts else None
    pos_col = pos[:, None]
    with jax.named_scope("serve.kv.write"):
        where = None if block_size is None else (phys, pos % block_size)
    rows = Rows(pos_col, block_tables, active, where, block_size, lane_positions=positions, pos=pos)
    mixers = {kind.name: (kind.step if chunk_blocks else kind.table)(cfg, rows, cache) for kind in kinds}
    x, cache, counted = _serve_layers(cfg, params, x, mixers, cache, live)
    logits = _head(cfg, params, x, row=0)
    if not counters:
        return logits, cache
    with jax.named_scope("serve.head"):  # a kind's counts go before what the later kinds and the experts counted
        for kind in reversed(kinds):
            if kind.count is not None:
                own = kind.count(cfg, active, pos)
                counted = own if counted is None else jnp.concatenate([own, counted])
        gauged = [kind.gauge(cfg, active, cache) for kind in kinds if kind.gauge is not None]
        if gauged:  # after every count: the newest step's values, which nobody sums
            counted = jnp.concatenate(([] if counted is None else [counted]) + gauged)
        if counted is not None:  # the counters ride in the logits' own copy
            row = jnp.zeros((1, logits.shape[1]), jnp.float32).at[0, : counted.shape[0]].set(counted)
            logits = jnp.concatenate([logits, row], axis=0)
    return logits, cache


#: tokens a NARROW iteration of the prefill walk aims for, the width of a
#: prompt's tail.  An iteration sweeps every weight once, and that sweep is a
#: cost a chunk whatever its tokens: at 256 tokens it is 41-47 % of a chunk's
#: time at the widths of the models with routed experts (PERF.md section 5
#: "PR 62"), and a prompt pays for at most 255 tokens it did not ask for.
PREFILL_CHUNK_TOKENS = 256
#: tokens a WIDE iteration aims for: whole narrow chunks, the width of a long
#: prompt's body, one sweep for four times the tokens.  Chosen on a v5e from the
#: walk alone at 256 / 512 / 1,024 / 2,048 (PERF.md section 5 "PR 62"): a chunk
#: of n tokens takes 5.3 ms + 29 us a token at Nemotron-3-Super's widths (12.8 /
#: 20.4 / 35.4 / 63.7 ms) and ~7.5 ms + 31 us at Command A+'s (15.9 / 25.2 / 41.6
#: / 75.4), so 1,024 tokens is 51 -> 35 and 64 -> 42 ms of a prompt's every 1,024:
#: the part a chunk costs whatever its tokens is down to a sixth of it, four
#: fifths of all a single chunk could save.  2,048 takes 8-10 % more off the
#: longest prompts, leaves a prompt under 2,048 tokens (a third of the
#: Nemotron cell's) without a wide chunk and its tail up to seven narrow ones
#: (a 5,120-token Command A+ prompt: 199 ms at 1,024, 208 at 2,048), and holds
#: 0.26-0.31 GB more scratch.
PREFILL_WIDE_TOKENS = 1024


def prefill_chunk_tokens(block_size: int, prompt_tokens: int) -> int:
    """Tokens a narrow chunk of :func:`transformer_prefill_chunked`, from the
    shapes: ``PREFILL_CHUNK_TOKENS`` in whole blocks and whole 128-wide tiles, or
    the longest prompt in whole blocks where that is shorter.  A caller pads its
    prompts to a multiple of it."""
    unit = math.lcm(block_size, 128)
    chunk = -(-PREFILL_CHUNK_TOKENS // unit) * unit
    return min(chunk, -(-prompt_tokens // block_size) * block_size)


def prefill_wide_chunks(chunk: int, prompt_tokens: int) -> int:
    """Narrow chunks of ``chunk`` tokens a wide chunk of the walk holds
    (``PREFILL_WIDE_TOKENS`` in whole narrow chunks), from the shapes; 1 where the
    walk has no wide loop: one is built only where it can engage, for prompts
    padded to ``prompt_tokens`` that hold at least eight wide chunks.  One rule
    for every model: each cache kind takes a wide chunk (what it keeps written
    and read a narrow chunk at a time inside it, ``cache_kinds._narrow_chunks``)."""
    per_wide = max(PREFILL_WIDE_TOKENS // chunk, 1)
    return per_wide if prompt_tokens >= 8 * per_wide * chunk else 1


def prefill_walk_chunks(per_wide: int, first: int, end: int) -> Tuple[int, int]:
    """(wide, narrow) iterations the walk makes over the narrow chunks ``first ..
    end - 1`` of a prompt, on the host: from chunk 0 each whole group of
    ``per_wide`` is one wide chunk and the ``per_wide - 1`` at most after the last
    are walked one by one; from a later chunk (a warm start) all are.  The walk's
    own bounds, stated for the engine's counts."""
    wide = end // per_wide if per_wide > 1 and first == 0 else 0
    return wide, end - first - wide * per_wide


def transformer_prefill_chunked(
    cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array, start_lens: jax.Array,
    prompt_lens: jax.Array, block_tables: jax.Array, cache: Dict[str, jax.Array],
    lanes: Optional[jax.Array] = None, *, chunk_tokens: Optional[int] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill each prompt from ``start_lens`` on, a chunk of tokens at a time:
    the serving engine's one prefill program, for a cold prompt (``start`` 0)
    and for the un-cached suffix of one whose prefix is cached alike.

    ``tokens`` [B, S] is the FULL prompt padded to a multiple of the narrow chunk
    (``prefill_chunk_tokens(block_size, S)``); ``start_lens`` [B] how many
    leading tokens already sit in cache blocks mapped into ``block_tables``
    (block-aligned by construction: only full blocks are shared);
    ``prompt_lens`` [B] the real lengths.  Returns (last_logits [B, vocab] f32,
    the logits at ``prompt_len - 1`` each lane samples its first token from,
    and the updated cache).

    The walk covers the narrow chunks of C tokens at the absolute positions
    ``[c * C, (c + 1) * C)`` for ``c`` in ``start // C .. ceil(len / C)``, an
    iteration of a dynamic-trip-count ``fori_loop`` each: the compute and the
    single compiled trace follow the tokens ASKED, not the padded width, and a
    70%-shared system prompt pays for its unique tail only.  An iteration
    reads every weight once for its tokens, and at C tokens that read is what it
    costs.  So where S holds at least eight wide chunks
    (:func:`prefill_wide_chunks`) the walk has TWO widths: of a call that starts
    at 0, every whole group of ``PREFILL_WIDE_TOKENS / C`` narrow chunks is ONE
    iteration of a second loop, one read of the weights for four times the
    tokens, and only what is left (at most three narrow chunks after the last
    wide one) is walked narrow: a prompt computes the tokens it computed, in
    ``ceil(P / 1024) + 3`` sweeps at most where it took ``ceil(P / 256)``.  The
    two loops run one after the other inside this one program (wide, narrow),
    the cache their carry; a call from a warm start runs none of the first.
    Inside a wide iteration what a layer keeps of the past is still
    written and read a narrow chunk at a time (``cache_kinds._narrow_chunks``: the attention's
    tiles, a ring one narrow chunk longer than its window, the quadratic forms of
    a state's chunk, an indexer's mask), so a wide iteration computes, row for
    row, what its narrow chunks would have.  Queries attend against keys READ
    FROM THE CACHE up to the chunk's end (prefix blocks written by whoever
    prefilled them first, the chunk's own written just before attending),
    masked ``k_pos <= q_pos``, which makes a warm start and a cold ``start=0``
    run of the same prompt bitwise identical, wherever ``start`` falls in its
    chunk, as far as both compute a row under the same width: the parity the
    prefix-cache admission tests pin (one width: bit for bit).  Across widths
    a product's rows are the same numbers to the compute dtype's rounding, not to
    the bit: the compiler tiles a product of 1,024 rows otherwise than one of
    256 (on a v5e the logits of a prompt walked two-width differ from the
    narrow walk's by 0.01-0.2 in bfloat16, PERF.md section 5 "PR 62").  So a
    warm start is walked narrow throughout, every row of it under ONE width
    whatever the cached prefix, and the program holds two loops, not three
    (a third, for the narrow chunks before a warm start's first wide one, was a
    third of a program's size again and of the time a replica takes to load it).
    Positions outside
    ``[start, len)`` write to scratch block 0 and take no expert's rows;
    since keys come from the cache rather than the local projection, garbage
    padding columns cannot leak into valid ones.  The head runs once, on the
    row of ``prompt_len - 1`` alone.

    A kind a request holds by its lane (a window layer's ring, a retention
    layer's state slot: ``models/cache_kinds.py``) keeps the prompt in the store
    of the decode lane it will run in: ``lanes`` [B] (absent: row ``b`` is lane
    ``b``).  Such a prompt starts at 0 (no block holds what such a layer keeps),
    and its walk computes every chunk.  A model of such layers alone has no pool
    to take the block size from: ``chunk_tokens`` states the narrow chunk (with a
    pool it follows from the shapes, whatever is passed).
    """
    _check_decodable(cfg)
    kinds = cache_kinds(cfg)
    block_size = pool_block_size(cfg, cache)
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
    per_wide = prefill_wide_chunks(chunk, s)
    lanes = jnp.arange(b, dtype=jnp.int32) if lanes is None else lanes.astype(jnp.int32)
    t = block_tables.shape[1]
    at_chunk = {kind.name: kind.walk(cfg, cache, lanes, chunk) for kind in kinds}  # what a kind prepares once a call
    with jax.named_scope("serve.walk"):  # the trip counts; the loops below are under it too, around their layers' scopes
        c_lo = jnp.min(start_lens) // chunk
        c_hi = (jnp.max(prompt_lens) + chunk - 1) // chunk
        offsets = jnp.arange(chunk)
        if per_wide > 1:  # a call from 0: its whole groups of narrow chunks, [0, w_hi) in wide chunks; a warm start: none
            w_hi = jnp.where(c_lo == 0, c_hi // per_wide, 0)
            wide_offsets = jnp.arange(per_wide * chunk)

    def body(parts, offsets, first_chunk, c, carry):
        """One iteration over ``parts`` narrow chunks, ``c`` its index among the chunks of its own width."""
        cache, last = carry
        width = parts * chunk
        blocks = width // block_size
        with jax.named_scope("serve.embed"):
            toks = jax.lax.dynamic_slice(tokens, (0, c * width), (b, width))
        p = c * width + offsets  # absolute positions [width]
        with jax.named_scope("serve.kv.write"):
            valid = (p[None, :] >= start_lens[:, None]) & (p[None, :] < prompt_lens[:, None])  # [b, width]
        where = None
        if paged:
            with jax.named_scope("serve.kv.write"):
                # a padded prompt may be wider than the table: those columns hold no valid row
                cols = jnp.minimum(c * blocks + offsets // block_size, t - 1)
                phys = jnp.where(valid, jnp.take(block_tables, cols, axis=1), 0)
                where = (phys, jnp.broadcast_to((offsets % block_size)[None, :], (b, width)))
        rows = Rows(p, block_tables, valid, where, block_size, lanes, chunk=c, first_chunk=first_chunk, offsets=offsets, parts=parts)
        mixers = {name: at(rows) for name, at in at_chunk.items()}
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
        x = _times(_embed_rows(params, toks, cfg.dtype), cfg.embedding_multiplier)
        x, cache, _ = _serve_layers(cfg, layers, x, mixers, cache, valid if cfg.moe_experts else None)
        with jax.named_scope("serve.head"):  # the one row the head will read
            sel = prompt_lens - 1 - c * width  # [b]
            row = jnp.take_along_axis(x, jnp.clip(sel, 0, width - 1)[:, None, None], axis=1)
            return cache, jnp.where(((sel >= 0) & (sel < width))[:, None, None], row, last)

    # ``first_chunk``: the index the CALL's first chunk has among a loop's chunks (a kind that a request holds by its
    # lane starts from an empty store there, and nowhere else): wide chunk 0 where any runs, else narrow chunk ``c_lo``
    with jax.named_scope("serve.walk"):
        carry = (cache, jnp.zeros((b, 1, cfg.d_model), cfg.dtype))
        narrow_lo = c_lo
        if per_wide > 1:
            carry = jax.lax.fori_loop(0, w_hi, functools.partial(body, per_wide, wide_offsets, 0), carry)
            narrow_lo = jnp.maximum(c_lo, w_hi * per_wide)
        cache, last = jax.lax.fori_loop(narrow_lo, c_hi, functools.partial(body, 1, offsets, c_lo), carry)
    return _head(cfg, params, last, row=0), cache
