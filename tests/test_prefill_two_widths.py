"""The prefill walk has two chunk widths (``models/serving.py
transformer_prefill_chunked``): a prompt's body in wide chunks, what is left at
either end in narrow ones.  Over the benchmark's eight served architectures at
their tiny sizes (between them every kind of ``models/cache_kinds.py``), with the
two widths scaled down to 32 and 8 tokens: a prompt walked two-width leaves the
logits and the cache of the same prompt walked narrow throughout, for lengths
that give no, one and several wide chunks and a tail of 0-3 narrow ones; a
warm start, on or off a wide chunk's edge, is walked narrow throughout and
leaves the cold walk's; the host's count of the walk's iterations is the walk's
own; and a program whose prompts cannot hold eight wide chunks has no wide loop."""

import dataclasses
import functools
import inspect
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.models import serving
from determined_tpu.models.cache_kinds import BLOCKS, CACHE_KINDS, LANE, Rows, cache_kinds
from determined_tpu.models.serving import init_kv_cache, prefill_wide_chunks, prefill_walk_chunks, transformer_prefill_chunked
from determined_tpu.serve.config import ServeConfig
from tests.model_cases import SERVED_ARCHS, tiny_served, tracer  # noqa: F401  (fixture reuse)

NARROW, WIDE = 8, 32
PER_WIDE = WIDE // NARROW
PAD = 8 * WIDE          # the shortest padded width that builds the wide loop
BLOCK, LANES = 4, 3     # the tiny forms' block; the lanes the stores a lane holds are sized for


def scaled(wide_tokens=WIDE):
    """The walk's two widths as these tests trace it: the narrow chunk 8 tokens whatever the shapes, the wide one
    ``wide_tokens`` (the narrow width: no wide chunk, the walk as it was)."""
    return (
        mock.patch.object(serving, "prefill_chunk_tokens", lambda block_size, prompt_tokens: NARROW),
        mock.patch.object(serving, "PREFILL_WIDE_TOKENS", wide_tokens),
    )


@functools.lru_cache(maxsize=None)
def walks(arch_name, pad=PAD):
    """(cfg, params, an empty cache, the walk compiled two-width, the walk compiled narrow throughout) at prompts padded to ``pad``."""
    cfg, params, _, _ = tiny_served(arch_name, max_prompt_len=pad, max_new_tokens=8, num_blocks=1 + LANES * (pad // BLOCK + 2))
    cache = init_kv_cache(cfg, 1 + LANES * (pad // BLOCK + 2), BLOCK, lanes=LANES, chunk_tokens=NARROW)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    avals = (params, i32(2, pad), i32(2), i32(2), i32(2, pad // BLOCK + 2), cache, i32(2))
    compiled = []
    for wide_tokens in (WIDE, NARROW):
        narrow_chunk, wide_chunk = scaled(wide_tokens)
        with narrow_chunk, wide_chunk:
            fn = jax.jit(functools.partial(transformer_prefill_chunked, cfg, chunk_tokens=NARROW))
            compiled.append(fn.lower(*avals).compile())
    return cfg, params, cache, compiled[0], compiled[1]


def prompts(seed, lens, pad=PAD):
    rng = np.random.default_rng(seed)
    tokens = np.zeros((2, pad), np.int32)
    for row, n in enumerate(lens):
        tokens[row, :n] = rng.integers(1, 250, n)
    width = pad // BLOCK + 2
    tables = 1 + np.arange(2 * width, dtype=np.int32).reshape(2, width)  # block 0 is the scratch block
    return tokens, tables


def same(got, want, what=""):
    """Value for value, to float32's rounding: the CPU's product of 32 rows and its product of 8 differ in the last
    bits of a row (2e-6 on values of order 1, measured); a row dropped, a state not carried or a pick flipped is 1e-2 and more."""
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=1e-4, atol=3e-5, err_msg=what)


def same_cache(cfg, got, want, what, exact=False):
    """Every leaf of the cache; the pool's scratch block aside (padding lands there, in the walk's order)."""
    pooled = {leaf for kind in cache_kinds(cfg) if kind.holds == BLOCKS for leaf in kind.leaves}
    assert set(got) == set(want)
    for leaf in want:
        a, b = np.asarray(got[leaf], np.float32), np.asarray(want[leaf], np.float32)
        a, b = (a[:, 1:], b[:, 1:]) if leaf in pooled else (a, b)
        np.testing.assert_array_equal(a, b, err_msg=f"{what}: leaf {leaf}") if exact else same(a, b, f"{what}: leaf {leaf}")


#: (the longer prompt's tokens, the shorter one's): narrow chunks alone; one wide chunk and nothing after; one and a
#: tail of one; several and a tail of three, the last a token long; several whose last part is a token long (no tail);
#: the whole width
LENGTHS = [(20, 3), (32, 32), (37, 9), (3 * WIDE + 2 * NARROW + 1, 40), (2 * WIDE - NARROW + 1, 1), (PAD, PAD - 1)]


@pytest.mark.parametrize("lens", LENGTHS, ids=lambda lens: f"{lens[0]}-{lens[1]}")
@pytest.mark.parametrize("arch_name", list(SERVED_ARCHS))
def test_a_prompt_walked_two_width_is_the_prompt_walked_narrow(arch_name, lens):
    cfg, params, cache, two_width, narrow = walks(arch_name)
    tokens, tables = prompts(sum(lens), lens)
    zeros, lens, lanes = np.zeros(2, np.int32), np.asarray(lens, np.int32), np.asarray([2, 0], np.int32)
    want_logits, want = narrow(params, tokens, zeros, lens, tables, cache, lanes)
    got_logits, got = two_width(params, tokens, zeros, lens, tables, cache, lanes)
    assert np.isfinite(np.asarray(want_logits)).all()
    same(got_logits, want_logits)
    same_cache(cfg, got, want, f"{arch_name} {tuple(lens)}")


#: (first un-cached token, the prompt's tokens): on a wide chunk's edge; off one, whole wide chunks' worth of tokens
#: after it; off one inside a narrow chunk (block-aligned); inside the first wide chunk, nearly the whole width after it
WARM = [(2 * WIDE, 4 * WIDE + 5), (WIDE + NARROW, 3 * WIDE), (WIDE + NARROW + BLOCK, 2 * WIDE - 1), (NARROW + BLOCK, PAD - 3)]


@pytest.mark.parametrize("start,length", WARM, ids=lambda v: str(v))
@pytest.mark.parametrize("arch_name", [name for name, held in SERVED_ARCHS.items() if "_slot" not in "".join(held) and "window_ring" not in held])
def test_a_warm_start_on_or_off_a_wide_edge_is_the_cold_start(arch_name, start, length):
    """A prompt whose first blocks are cached is prefilled from there by the two-width program's narrow loop alone
    (every row under one width whatever the prefix; one lane of the two from a block earlier): logits and cache are
    the cold narrow walk's (what the prefix cache rests on)."""
    cfg, params, cache, two_width, narrow = walks(arch_name)
    assert not any(kind.holds == LANE for kind in cache_kinds(cfg))
    tokens, tables = prompts(start + length, (length, length - BLOCK))
    zeros, lens, lanes = np.zeros(2, np.int32), np.asarray([length, length - BLOCK], np.int32), np.asarray([0, 1], np.int32)
    cold_logits, cold = narrow(params, tokens, zeros, lens, tables, cache, lanes)
    # the cached prefix: the cold walk's blocks, as another request would have left them
    starts = np.asarray([start, start - BLOCK], np.int32)
    warm_logits, warm = two_width(params, tokens, starts, lens, tables, cold, lanes)
    np.testing.assert_array_equal(np.asarray(warm_logits), np.asarray(cold_logits))  # one width: to the bit
    same_cache(cfg, warm, cold, f"{arch_name} from {start}", exact=True)
    # and over a pool that holds the prefix ALONE (the suffix's rows are the warm walk's own)
    prefix = np.asarray([start, start - BLOCK], np.int32)
    _, held = narrow(params, tokens, zeros, prefix, tables, cache, lanes)
    warm_logits, warm = two_width(params, tokens, starts, lens, tables, held, lanes)
    np.testing.assert_array_equal(np.asarray(warm_logits), np.asarray(cold_logits))  # one width: to the bit
    same_cache(cfg, warm, cold, f"{arch_name} from {start}, the prefix alone", exact=True)


def test_a_model_of_retention_layers_alone_is_walked_two_width():
    """Brumby's block: no pool, so the walk is told its chunk (``chunk_tokens``); a cold prompt of eight wide chunks and
    a tail (one narrow chunk and 3 tokens of the next) beside a short one: the last rows' logits, the state and the
    normaliser are the narrow walk's, and the two-width program does hold a wide loop (until PR 65 the kind opted out)."""
    cfg, params, cache, two_width, narrow = walks("power_retention", PAD + WIDE)
    assert [kind.name for kind in cache_kinds(cfg)] == ["state_slot"]
    assert f"[2,{WIDE}]" in two_width.as_text() and f"[2,{WIDE}]" not in narrow.as_text()  # a wide chunk's tokens
    lens = np.asarray([PAD + NARROW + 3, 2 * WIDE + 1], np.int32)
    tokens, tables = prompts(65, lens, PAD + WIDE)
    args = (params, tokens, np.zeros(2, np.int32), lens, tables, cache, np.asarray([1, 2], np.int32))
    want_logits, want = narrow(*args)
    got_logits, got = two_width(*args)
    assert np.isfinite(np.asarray(want_logits)).all() and np.abs(np.asarray(want["rs"])[:, 1:]).max() > 0
    same(got_logits, want_logits)
    same_cache(cfg, got, want, "power_retention, eight wide chunks and a tail")
    assert not np.asarray(got["rs"])[:, 0].any() and not np.asarray(got["rn"]).any()  # a lane no row walked; no row left pending


def test_an_indexers_picks_of_a_wide_chunk_are_its_narrow_chunks_picks():
    """GLM-5.2's block: the mask a layer that holds an indexer hands on is made a narrow chunk's queries at a time,
    and side by side the parts are the masks the narrow chunks' own iterations make."""
    cfg, params, cache, _, _ = walks("glm_moe_dsa")
    kind, = cache_kinds(cfg)
    tokens, tables = prompts(5, (WIDE + 3, WIDE))
    tables = jnp.asarray(tables)
    layer = cfg.index_layers[0]
    p = params[f"block_{layer}"][kind.params]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, WIDE, cfg.d_model)), cfg.dtype)
    live = jnp.asarray(np.arange(WIDE)[None, :] < np.asarray([[WIDE], [WIDE - 3]]))

    def rows_of(chunk, width, parts):
        offsets = jnp.arange(width)
        pos = chunk * width + offsets
        cols = jnp.minimum(chunk * (width // BLOCK) + offsets // BLOCK, tables.shape[1] - 1)
        valid = jax.lax.dynamic_slice_in_dim(live, chunk * width, width, axis=1) if width < WIDE else live
        where = (jnp.where(valid, jnp.take(tables, cols, axis=1), 0), jnp.broadcast_to((offsets % BLOCK)[None, :], (2, width)))
        return Rows(pos, tables, valid, where, BLOCK, jnp.arange(2), chunk=chunk, first_chunk=0, offsets=offsets, parts=parts)

    at_chunk = kind.walk(cfg, cache, jnp.arange(2), NARROW)
    _, wide_cache, wide_mask = jax.jit(lambda c: at_chunk(rows_of(0, WIDE, PER_WIDE))(p, x, x, c, layer, None))(cache)
    masks, c = [], cache
    for part in range(PER_WIDE):
        part_x = x[:, part * NARROW: (part + 1) * NARROW]
        _, c, mask = jax.jit(lambda c, part=part, part_x=part_x: at_chunk(rows_of(part, NARROW, 1))(p, part_x, part_x, c, layer, None))(c)
        masks.append(np.asarray(mask))
    wide_mask = np.asarray(wide_mask)
    assert wide_mask.dtype == bool and wide_mask.shape == (2, WIDE, tables.shape[1] * BLOCK)
    np.testing.assert_array_equal(wide_mask, np.concatenate(masks, axis=1))
    picked = wide_mask.sum(-1)  # a query picks index_topk keys, or every key it may see where those are fewer
    assert picked.max() == cfg.index_topk and (picked[0] == np.minimum(np.arange(WIDE) + 1, cfg.index_topk)).all()
    same(np.asarray(wide_cache["ik"], np.float32)[:, 1:], np.asarray(c["ik"], np.float32)[:, 1:])


@pytest.mark.parametrize("per_wide", [1, 2, 4])
def test_the_hosts_count_of_the_walks_iterations(per_wide):
    """``prefill_walk_chunks``: whole aligned groups of narrow chunks are wide chunks, the others are walked one by one."""
    for first in range(0, 11):
        for end in range(first + 1, 24):
            wide, narrow = prefill_walk_chunks(per_wide, first, end)
            groups = [g for g in range(24) if (g + 1) * per_wide <= end] if per_wide > 1 and first == 0 else []
            assert wide == len(groups) and wide * per_wide + narrow == end - first
            assert narrow < per_wide or first > 0 or per_wide == 1
    cold = prefill_walk_chunks(4, 0, -(-4097 // 256))
    assert cold == (4, 1) and sum(cold) <= -(-4097 // 1024) + 3


def test_the_wide_loop_is_built_where_the_prompts_hold_eight_wide_chunks():
    """A rule on the shapes: the narrow chunk and the padded width decide; a short width lowers to the one loop."""
    assert serving.PREFILL_WIDE_TOKENS == 4 * serving.PREFILL_CHUNK_TOKENS == 1024
    cfg, params, cache, _, _ = walks("dense_decoder")
    assert [prefill_wide_chunks(256, pad) for pad in (512, 4096, 8192 - 256, 8192, 24576)] == [1, 1, 1, 4, 4]
    assert prefill_wide_chunks(384, 8 * 768) == 2 and prefill_wide_chunks(384, 8 * 768 - 384) == 1  # a 48-token block
    assert prefill_wide_chunks(1024, 65536) == 1  # a narrow chunk as wide as the wide one: nothing to widen
    # and the shapes ALONE decide: the rule is handed no model and no cache kind has a say (the retention layers' chunk
    # kernel is a loop since PR 65, small enough to stand in both loops of a program)
    assert list(inspect.signature(prefill_wide_chunks).parameters) == ["chunk", "prompt_tokens"]
    assert "wide_walk" not in {field.name for field in dataclasses.fields(CACHE_KINDS[0])}
    for serve, per_wide in ((dict(max_prompt_len=4096), 1), (dict(max_prompt_len=8192), 4), (dict(max_prompt_len=8000), 4)):
        assert ServeConfig(block_size=16, num_blocks=1024, max_new_tokens=64, **serve).prefill_wide == per_wide
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731

    def loops(pad):
        narrow_chunk, wide_chunk = scaled()
        with narrow_chunk, wide_chunk:
            fn = jax.jit(functools.partial(transformer_prefill_chunked, cfg))
            text = fn.lower(params, i32(1, pad), i32(1), i32(1), i32(1, PAD // BLOCK + 2), cache).as_text()
        return text.count("stablehlo.while"), f"tensor<1x{WIDE}xi32>" in text  # a wide chunk's tokens

    # a narrow loop holds the attention's loop over its tiles, one a layer (2 layers); the wide loop a loop over its
    # parts a layer, and that the tiles' loop: whatever the parts, the program grows by loops, not by copies of a chunk
    assert loops(PAD - NARROW) == (1 + 2, False)
    assert loops(PAD) == (1 + 2 + 1 + 2 * 2, True)  # the wide loop, then the narrow one


@pytest.mark.parametrize("arch_name", ["dense_decoder", "nemotron_h"])
def test_the_engine_counts_the_tokens_of_each_width_and_draws_the_same_tokens(arch_name, tracer):
    """An engine whose ``max_prompt_len`` holds eight wide chunks admits through the two-width walk: ``serve.prefill``
    and ``/stats`` say how many tokens each width computed (their sum is what was computed before), a request's tokens
    are those of an engine whose walk is narrow throughout, and an engine of short prompts counts no wide token."""
    from determined_tpu.serve.engine import DecodeKernels, ServeEngine

    rng = np.random.default_rng(11)
    lengths = [5, WIDE, WIDE + 1, 3 * WIDE + 2 * NARROW + 3, PAD]
    prompts = [[int(t) for t in rng.integers(1, 250, size=n)] for n in lengths]
    # (wide chunks, narrow chunks) a cold prompt of each length walks
    walked = [(0, 1), (1, 0), (1, 1), (3, 3), (8, 0)]
    sizes = dict(max_prompt_len=PAD, max_new_tokens=4, num_blocks=1 + 2 * (PAD // BLOCK + 2), max_batch=2, prefix_cache=False)

    def generate(wide_tokens, **serve):
        narrow_chunk, wide_chunk = scaled(wide_tokens)
        with narrow_chunk, wide_chunk:
            cfg, params, serve_cfg, _ = tiny_served(arch_name, **{**sizes, **serve})
            eng = ServeEngine(DecodeKernels(cfg, params, serve_cfg))
            assert serve_cfg.prefill_chunk == NARROW and eng.kernels.prefill_wide == (PER_WIDE if wide_tokens == WIDE and serve_cfg.max_prompt_len >= PAD else 1)
            reqs = []
            try:
                for prompt in prompts[: 5 if serve_cfg.max_prompt_len >= PAD else 4]:
                    reqs.append(eng.submit(prompt, max_new_tokens=3, temperature=0.0))
                    while not reqs[-1].done.is_set():
                        assert eng.step_once()
            finally:
                eng.stop()
            assert all(r.error is None for r in reqs)
            spans = {e["args"]["request"]: e["args"] for e in tracer.chrome_events() if e.get("ph") == "X" and e["name"] == "serve.prefill"}
            return [r.output for r in reqs], [spans[r.id] for r in reqs], eng.stats(), serve_cfg

    tokens, spans, stats, serve_cfg = generate(WIDE)
    narrow_chunk, wide_chunk = scaled()
    with narrow_chunk, wide_chunk:
        assert [serve_cfg.prefill_walk(PER_WIDE, n) for n in lengths] == walked
        assert serve_cfg.prefill_walk(PER_WIDE, 3 * WIDE, WIDE) == (0, 2 * PER_WIDE)  # a warm start: narrow throughout
    for n, (wide, narrow), said in zip(lengths, walked, spans):
        assert (said["wide_tokens"], said["narrow_tokens"], said["chunks"]) == (wide * WIDE, narrow * NARROW, wide + narrow)
        assert said["computed_tokens"] == said["wide_tokens"] + said["narrow_tokens"] == -(-n // NARROW) * NARROW
    assert stats["prefill_wide_tokens"] == sum(w for w, _ in walked) * WIDE == 13 * WIDE
    assert stats["prefill_narrow_tokens"] == sum(n for _, n in walked) * NARROW
    assert stats["prefill_tokens_computed"] == stats["prefill_wide_tokens"] + stats["prefill_narrow_tokens"]
    tracer.reset()
    narrow_tokens, narrow_spans, narrow_stats, _ = generate(NARROW)
    assert tokens == narrow_tokens
    assert [s["computed_tokens"] for s in narrow_spans] == [s["computed_tokens"] for s in spans]
    assert narrow_stats["prefill_wide_tokens"] == 0 and all(s["wide_tokens"] == 0 for s in narrow_spans)
    assert narrow_stats["prefill_tokens_computed"] == stats["prefill_tokens_computed"]
    tracer.reset()
    # prompts of up to 7 wide chunks and a narrow one: the walk has no wide loop, and nothing is counted wide
    short_tokens, short_spans, short_stats, _ = generate(WIDE, max_prompt_len=PAD - NARROW)
    assert short_tokens == tokens[:4] and short_stats["prefill_wide_tokens"] == 0
    assert [s["chunks"] for s in short_spans] == [-(-n // NARROW) for n in lengths[:4]]
