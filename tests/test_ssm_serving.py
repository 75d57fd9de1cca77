"""A layer of two kinds served from two caches at once: attention heads over
K and V rows in the paged pool and Mamba-2 heads over a float32 state and a
convolution tail a decode lane, side by side under one norm
(models/transformer.py, models/cache_kinds.py, ops/ssm.py, serve/engine.py),
against the plain reference the benchmark keeps
(benchmark/reference/falcon_h1.py: float32, the scan as its recurrence one
token at a time, no chunk, no cache, no import from the program).  CPU, tiny
sizes, seeded weights; the Pallas kernel in interpret mode."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from determined_tpu.models import cache_kinds
from determined_tpu.models.cache_kinds import BLOCKS, LANE, PAGED_KV, SSM_SLOT, Rows, layer_kinds
from determined_tpu.models.serving import (
    init_kv_cache,
    serve_counters,
    transformer_decode,
    transformer_prefill,
    transformer_prefill_chunked,
)
from determined_tpu.models.transformer import (
    HYBRID,
    TransformerConfig,
    TransformerLM,
    kv_bytes_per_token,
    ssm_bytes_per_slot,
    ssm_pool_shapes,
)
from determined_tpu.ops import paged_attention, ssm
from determined_tpu.serve.config import ServeConfig
from determined_tpu.serve.engine import DecodeKernels, ServeEngine
from tests.model_cases import reference_module

reference = reference_module("falcon_h1")

LAYERS = 2
MULTIPLIERS = dict(
    embedding_multiplier=5.656854249492381, key_multiplier=0.25, attention_in_multiplier=0.9, attention_out_multiplier=0.6,
    ssm_in_multiplier=0.5, ssm_multipliers=(0.7, 1.5, 0.8, 1.2, 0.6), ssm_out_multiplier=0.3, mlp_multipliers=(0.5, 0.25),
    logit_scale=0.5,
)


def tiny(**kw) -> TransformerConfig:
    """Two layers of 10 query heads over 2 KV heads of 16 (5 a KV head) beside 4 Mamba-2 heads of 16 over 2 groups of 8
    state values, a convolution over 4 tokens; muP's scalars all away from 1."""
    base = dict(
        vocab_size=96, d_model=48, n_layers=LAYERS, n_heads=10, n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=1024,
        dtype=jnp.float32, attention_impl="reference", partition_params=False, rope_theta=1e11, norm_eps=1e-5,
        layer_types=(HYBRID,) * LAYERS, ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_groups=2, ssm_conv=4, ssm_chunk=8,
        **MULTIPLIERS,
    )
    return TransformerConfig(**{**base, **kw})


def build(cfg, seed=1):
    params = meta.unbox(jax.jit(TransformerLM(cfg).init)(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    # norms and the skip away from one, so that one the program skipped or ran twice shows
    for i in range(cfg.n_layers):
        blk = params[f"block_{i}"]
        for j, leaf in enumerate((blk["ln1"], blk["ln2"])):
            leaf["scale"] = leaf["scale"] * (1.0 + 0.1 * jax.random.normal(jax.random.key(100 + 2 * i + j), leaf["scale"].shape))
        for j, n in enumerate(("norm", "D")):
            blk["ssm"][n] = blk["ssm"][n] * (1.0 + 0.2 * jax.random.normal(jax.random.key(200 + 2 * i + j), blk["ssm"][n].shape))
    return params


def reference_weights(params, cfg):
    layers = []
    for i in range(cfg.n_layers):
        b = params[f"block_{i}"]
        layers.append({
            "attn_norm": b["ln1"]["scale"], "mlp_norm": b["ln2"]["scale"], "ssm_norm": b["ssm"]["norm"],
            **{k: b["attn"][k]["kernel"] for k in ("wq", "wk", "wv", "wo")},
            **{k: b["ssm"][k] for k in ("w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "w_out")},
            **{k: b["mlp"][k]["kernel"] for k in ("w_gate", "w_up", "w_down")},
        })
    return {"embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"], "final_norm": params["ln_f"]["scale"], "layers": layers}


def numerics(cfg, **kw):
    said = dict(
        eps=cfg.norm_eps, rope_theta=cfg.rope_theta, heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim, d_state=cfg.ssm_state,
        groups=cfg.ssm_groups, conv=cfg.ssm_conv, lm_head_multiplier=cfg.logit_scale, query_block=64, mlp_block=24, vocab_block=40,
        **{k: getattr(cfg, k) for k in MULTIPLIERS if k != "logit_scale"},
    )
    return {**said, **kw}


def oracle(cfg, params, tokens, **kw):
    forward = jax.jit(functools.partial(reference.forward, **numerics(cfg, **kw)))
    return np.stack([np.asarray(forward(reference_weights(params, cfg), jnp.asarray(row))) for row in tokens])


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = build(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(0), (3, 600), 1, cfg.vocab_size))
    return cfg, params, tokens, oracle(cfg, params, tokens)


def _parts(seed, b=2, s=24, h=4, p=16, g=2, n=8):
    """x [b, s, h, P], B and C [b, s, g, N], dt [b, s, h] of steps that remember 1 to 1,000 tokens, A and D [h]."""
    ks = jax.random.split(jax.random.key(seed), 6)
    x, bb, cc = (jax.random.normal(k, shape, jnp.float32) for k, shape in zip(ks, ((b, s, h, p), (b, s, g, n), (b, s, g, n))))
    dt = jnp.exp(jax.random.uniform(ks[3], (b, s, h), jnp.float32, np.log(1e-3), np.log(0.1)))
    return x, bb, cc, dt, -jax.random.uniform(ks[4], (h,), jnp.float32, 1.0, 16.0), jax.random.normal(ks[5], (h,), jnp.float32)


def _recurrence(x, bb, cc, dt, a, skip):
    """The reference's scan, a row of the batch at a time: y [b, s, h, P]."""
    r = x.shape[2] // bb.shape[2]
    one = jax.jit(lambda x, b, c, d: reference._recurrence(x, jnp.repeat(b, r, axis=1), jnp.repeat(c, r, axis=1), d, a, skip))
    return np.stack([np.asarray(one(x[i], bb[i], cc[i], dt[i])) for i in range(x.shape[0])])


# ---------------------------------------------------------------------------
# the two forms of the scan against the recurrence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [1, 5, 8, 24, 32])
def test_chunks_that_carry_a_state_give_the_recurrence(chunk):
    """Token for token across chunk edges, lanes of unequal length, a last chunk that is part padding."""
    x, bb, cc, dt, a, skip = _parts(3)
    b, s, h, p = x.shape
    lens = np.asarray([s, 17])
    want = _recurrence(x, bb, cc, dt, a, skip)
    step = jax.jit(ssm.ssm_chunk)
    state, outs = jnp.zeros((b, h, p, bb.shape[-1])), []
    for lo in range(0, s, chunk):
        cut = lambda t: jnp.pad(t[:, lo:lo + chunk], ((0, 0), (0, chunk - t[:, lo:lo + chunk].shape[1])) + ((0, 0),) * (t.ndim - 2))  # noqa: E731
        live = jnp.asarray((lo + np.arange(chunk))[None, :] < lens[:, None])
        out, state = step(cut(x), cut(bb), cut(cc), cut(dt), a, skip, state, live)
        outs.append(out)
    got = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(got[0, :s], want[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[1, :17], want[1, :17], rtol=2e-4, atol=2e-5)
    # the padded end of the shorter lane advanced nothing: its state is the recurrence's after 17 tokens
    after = jax.jit(lambda: ssm.ssm_chunk(x[1:, :17], bb[1:, :17], cc[1:, :17], dt[1:, :17], a, skip, jnp.zeros_like(state[1:]), jnp.ones((1, 17), bool)))()[1]
    np.testing.assert_allclose(np.asarray(state[1]), np.asarray(after[0]), rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("chunk", [4, 8, 24])
def test_a_scan_of_chunks_is_the_recurrence(chunk):
    x, bb, cc, dt, a, skip = _parts(4)
    got = jax.jit(functools.partial(ssm.ssm_scan, chunk=chunk))(x, bb, cc, dt, a, skip)
    np.testing.assert_allclose(np.asarray(got), _recurrence(x, bb, cc, dt, a, skip), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("impl,dims", [("jnp", (4, 16, 2, 8)), ("kernel_interpret", (16, 128, 2, 128))])
def test_decode_steps_give_the_recurrence_and_leave_idle_lanes_alone(impl, dims):
    """One token a lane a step into layer 1 of a pool of three: the live lanes' answers are the recurrence's, token
    for token; an idle lane's slot, the scratch slot's neighbours and the other layers stay as they were."""
    h, p, g, n = dims
    x, bb, cc, dt, a, skip = _parts(5, b=3, s=6, h=h, p=p, g=g, n=n)
    want = _recurrence(x, bb, cc, dt, a, skip)
    pool = jax.random.normal(jax.random.key(9), ssm.state_shape(3, 3, h, p, n), jnp.float32)
    pool = pool.at[1, jnp.asarray([0, 2])].set(0.0)                                  # lanes 0 and 2 start a sequence
    start = np.asarray(pool)
    live = jnp.asarray([True, False, True])
    for t in range(x.shape[1]):
        y, pool = ssm.ssm_decode(x[:, t], bb[:, t], cc[:, t], dt[:, t], a, skip, pool, 1, live, impl=impl)
        np.testing.assert_allclose(np.asarray(y)[[0, 2]], want[[0, 2], t], rtol=3e-4, atol=3e-5)
        assert not np.asarray(y)[1].any()
    after = np.asarray(pool)
    np.testing.assert_array_equal(after[[0, 2]], start[[0, 2]])                      # the other layers
    np.testing.assert_array_equal(after[1, 1], start[1, 1])                          # the idle lane's slot
    assert np.abs(after[1, 0] - start[1, 0]).max() > 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_in_interpret_mode_agrees_with_its_jnp_form(dtype):
    """At the published head: 128 wide over a state of 256, 16 heads over 2 groups; a state held in either dtype."""
    h, p, g, n = 16, 128, 2, 256
    assert ssm.kernel_takes(h, g, p, n, dtype) and ssm.kernel_takes(32, 2, 128, 256, jnp.float32) and not ssm.kernel_takes(4, 2, 16, 8, dtype)
    x, bb, cc, dt, a, skip = _parts(6, b=4, s=1, h=h, p=p, g=g, n=n)
    pool = jax.random.normal(jax.random.key(2), ssm.state_shape(2, 4, h, p, n), jnp.float32).astype(dtype)
    live = jnp.asarray([True, True, False, True])
    args = (x[:, 0], bb[:, 0], cc[:, 0], dt[:, 0], a, skip, pool, 1, live)
    y0, s0 = ssm.ssm_decode(*args, impl="jnp")
    y1, s1 = ssm.ssm_decode(*args, impl="kernel_interpret")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=2e-4, atol=2e-4)
    lanes = slice(0, 4)                                                              # the scratch slot is nobody's
    np.testing.assert_allclose(np.asarray(s1[:, lanes], np.float32), np.asarray(s0[:, lanes], np.float32), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s1[1, 2], np.float32), np.asarray(pool[1, 2], np.float32))
    with pytest.raises(ValueError, match="whole 128-wide tiles"):
        ssm.ssm_decode(*_parts(6, b=4, s=1)[:3], dt[:, 0, :4], a[:4], skip[:4], jnp.zeros(ssm.state_shape(1, 4, 4, 16, 8)), 0, live, impl="kernel")


# ---------------------------------------------------------------------------
# the mixer with its convolution: chunks of any size, a state and a tail carried
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [3, 7, 16, 64])
def test_the_mixer_carries_a_state_and_a_tail_over_chunks_of_any_size(model, chunk):
    """The kind's walk form called a chunk at a time as the prefill walk calls it, three lanes of unequal length
    into lanes 2, 0, 3 of four: what it adds to the stream is the reference's mixer on the whole sequence."""
    cfg, params, _, _ = model
    p = params["block_1"]["ssm"]
    lens, lanes, s = np.asarray([50, 37, 9]), jnp.asarray([2, 0, 3]), 50
    u = jax.random.normal(jax.random.key(11), (3, s, cfg.d_model), jnp.float32)
    w = {k: jnp.asarray(v, jnp.float32) for k, v in reference_weights(params, cfg)["layers"][1].items()}
    told = numerics(cfg)
    want = np.stack([np.asarray(jax.jit(lambda row: reference._mamba2(
        row * cfg.ssm_in_multiplier, w, heads=4, head_dim=16, d_state=8, groups=2, conv=4, eps=cfg.norm_eps,
        ssm_multipliers=told["ssm_multipliers"], skip=True, norm_groups=2, shared_group=False,
    ) @ w["w_out"] * cfg.ssm_out_multiplier)(u[i])) for i in range(3)])
    cache = init_kv_cache(cfg, 8, 4, lanes=4)
    cache = {k: v + 7.0 for k, v in cache.items()}                                   # what earlier sequences left in the lanes
    before = {k: np.asarray(v) for k, v in cache.items()}

    @jax.jit
    def at_chunk(c, cache):
        live = jnp.asarray(lens)[:, None] > c * chunk + jnp.arange(chunk)[None, :]
        rows = Rows(c * chunk + jnp.arange(chunk), None, live, None, 4, lanes, chunk=c, first_chunk=0, offsets=jnp.arange(chunk))
        part = jax.lax.dynamic_slice_in_dim(jnp.pad(u, ((0, 0), (0, chunk), (0, 0))), c * chunk, chunk, axis=1)
        x, cache = SSM_SLOT.walk(cfg, cache, lanes, chunk)(rows)(p, jnp.zeros_like(part), part, cache, 1)
        return x, cache

    outs = []
    for c in range(-(-s // chunk)):
        out, cache = at_chunk(c, cache)
        outs.append(np.asarray(out))
    got = np.concatenate(outs, axis=1)
    for i, n in enumerate(lens):
        np.testing.assert_allclose(got[i, :n], want[i, :n], rtol=3e-4, atol=3e-5)
    # lane 1, layer 0 and the pool were nobody's: untouched
    state_leaf, tail_leaf = SSM_SLOT.leaves
    for leaf in SSM_SLOT.leaves:
        np.testing.assert_array_equal(np.asarray(cache[leaf])[0], before[leaf][0])
        np.testing.assert_array_equal(np.asarray(cache[leaf])[1, 1], before[leaf][1, 1])
    # the tail a lane keeps: the last three rows of the convolution's input before the prompt's end
    _, xbc, _ = cache_kinds._ssm_project(cfg, p, u)
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(cache[tail_leaf])[1, int(lanes[i])], np.asarray(xbc)[i, n - 3:n], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the model: the whole-sequence form, the walk and the decode step against the reference
# ---------------------------------------------------------------------------


def test_a_layer_is_of_two_kinds_and_the_cache_holds_both(model):
    cfg, _, _, _ = model
    assert cache_kinds.cache_kinds(cfg) == (PAGED_KV, SSM_SLOT) and (PAGED_KV.holds, SSM_SLOT.holds) == (BLOCKS, LANE)
    assert [layer_kinds(cfg, i) for i in range(2)] == [((PAGED_KV, 0, "attn"), (SSM_SLOT, 0, "ssm")), ((PAGED_KV, 1, "attn"), (SSM_SLOT, 1, "ssm"))]
    assert (PAGED_KV.params, SSM_SLOT.params) == ("attn", "ssm") and PAGED_KV.layer_types == ("full_attention", HYBRID)
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 40, 4, lanes=3, chunk_tokens=16))
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "k": ((2, 40, 4, 32), "float32"), "v": ((2, 40, 4, 32), "float32"),
        "ssm": ((2, 4, 4, 16, 8), "float32"), "conv": ((2, 3, 3, 96), "float32"),   # a slot a lane and one scratch; three rows a tail
    }
    assert ssm_pool_shapes(cfg, 3) == ((2, 4, 4, 16, 8), (2, 3, 3, 96)) and ssm_bytes_per_slot(cfg) == 4 * 16 * 8 * 4
    assert kv_bytes_per_token(cfg) == 2 * 2 * 2 * 16 * 4 and serve_counters(cfg) == ("serve.ssm.live_lanes", "serve.ssm.bytes")
    half = jax.eval_shape(lambda: init_kv_cache(tiny(dtype=jnp.bfloat16), 40, 4, lanes=3))
    assert (str(half["ssm"].dtype), str(half["conv"].dtype), str(half["k"].dtype)) == ("float32", "bfloat16", "bfloat16")
    with pytest.raises(ValueError, match="needs its lanes"):
        init_kv_cache(cfg, 40, 4)
    with pytest.raises(ValueError, match="an attention_mamba2 layer needs ssm_heads"):
        tiny(ssm_heads=3)
    with pytest.raises(ValueError, match="one scalar for each of z, x, B, C, dt"):
        tiny(ssm_multipliers=(1.0, 1.0))


def test_the_whole_sequence_form_and_the_wide_prefill_are_the_reference(model):
    cfg, params, tokens, want = model
    got = jax.jit(lambda p, t: TransformerLM(cfg).apply({"params": p}, t))(params, jnp.asarray(tokens[:, :100]))
    np.testing.assert_allclose(np.asarray(got), want[:, :100], rtol=2e-4, atol=2e-5)   # 100 tokens: not whole chunks of 8
    got = jax.jit(lambda p, t: TransformerLM(cfg).apply({"params": p}, t))(params, jnp.asarray(tokens[:1, :96]))
    np.testing.assert_allclose(np.asarray(got), want[:1, :96], rtol=2e-4, atol=2e-5)   # 96: a scan of twelve
    cache = init_kv_cache(cfg, 80, 4, lanes=3)
    tables = jnp.asarray(1 + np.arange(3 * 25).reshape(3, 25), jnp.int32)
    lens = jnp.asarray([100, 61, 7])
    logits, cache = jax.jit(functools.partial(transformer_prefill, cfg))(params, jnp.asarray(tokens[:, :100]), lens, tables, cache)
    for i, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(np.asarray(logits)[i, :n], want[i, :n], rtol=2e-4, atol=2e-5)


def test_the_walk_and_the_decode_step_are_the_reference_in_lanes_of_unequal_length(model):
    """Prompts of 300, 270 and 40 tokens into lanes 3, 0 and 2 of four: the walk's chunk is 256, so two of them cross
    a chunk's edge and end in a part-padded chunk; then 40 decode steps in the three lanes at once, lane 1 idle."""
    cfg, params, tokens, want = model
    block, lanes = 4, jnp.asarray([3, 0, 2])
    cache = init_kv_cache(cfg, 3 * 100 + 1, block, lanes=4)
    cache = {k: (v + 5.0 if k in SSM_SLOT.leaves else v) for k, v in cache.items()}  # a reused lane: the walk must zero it
    tables = np.zeros((4, 100), np.int32)
    tables[[3, 0, 2]] = 1 + np.arange(300).reshape(3, 100)
    lens = np.asarray([300, 270, 40])
    padded = np.zeros((3, 512), np.int32)
    for i, n in enumerate(lens):
        padded[i, :n] = tokens[i, :n]
    walk = jax.jit(functools.partial(transformer_prefill_chunked, cfg))
    last, cache = walk(params, padded, np.zeros(3, np.int32), lens, tables[[3, 0, 2]], cache, lanes)
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(last)[i], want[i, n - 1], rtol=3e-4, atol=3e-5)
    idle = {leaf: np.asarray(cache[leaf])[:, 1] for leaf in SSM_SLOT.leaves}
    step = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True))
    table_step = jax.jit(functools.partial(transformer_decode, cfg))
    for t in range(40):
        toks, pos = np.zeros(4, np.int32), np.full(4, -1, np.int32)
        for i, lane in enumerate((3, 0, 2)):
            toks[lane], pos[lane] = tokens[i, lens[i] + t], lens[i] + t
        if t == 7:                                                                       # the table form from the same cache
            other, _ = table_step(params, toks, pos, tables, cache)
        logits, cache = step(params, toks, pos, tables, cache)
        for i, lane in enumerate((3, 0, 2)):
            np.testing.assert_allclose(np.asarray(logits)[lane], want[i, lens[i] + t], rtol=3e-4, atol=3e-5)
        if t == 7:
            np.testing.assert_allclose(np.asarray(other)[[3, 0, 2]], np.asarray(logits)[[3, 0, 2]], rtol=2e-4, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(logits)[4, :2], [3.0, 3.0 * LAYERS * ssm_bytes_per_slot(cfg)])
    for leaf in SSM_SLOT.leaves:                                                        # the idle lane's slot and tail
        np.testing.assert_array_equal(np.asarray(cache[leaf])[:, 1], idle[leaf])


@pytest.mark.parametrize("layout,heads", [("block_diagonal", (20, 4)), ("per_kv_head", (32, 4))])
def test_the_paged_kernel_multiplies_five_queries_a_kv_head_in_the_block_diagonal_layout(layout, heads):
    """20 query heads over 4 KV heads: 5 a KV head, the first group in the benchmark that is no power of two."""
    n_heads, kv = heads
    assert paged_attention.attn_products(n_heads // kv) == layout
    d, block, blocks, b = 128, 16, 24, 3
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (b, n_heads, d), jnp.float32)
    k_pool, v_pool = (jax.random.normal(k, (2, blocks, block, kv * d), jnp.float32) for k in ks[1:])
    tables = jnp.asarray(1 + np.arange(b * 6).reshape(b, 6), jnp.int32)
    positions = jnp.asarray([70, -1, 33], jnp.int32)
    args = (q, k_pool, v_pool, 1, tables, positions)
    want = paged_attention.paged_decode_attention(*args, scale=d ** -0.5, impl="jnp")
    got = paged_attention.paged_decode_attention(*args, scale=d ** -0.5, impl="kernel_interpret")
    np.testing.assert_allclose(np.asarray(got)[[0, 2]], np.asarray(want)[[0, 2]], rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# the engine: blocks AND a lane
# ---------------------------------------------------------------------------


def _engine(cfg, params, **kw):
    sizes = dict(block_size=4, num_blocks=121, max_batch=3, decode_chunk_blocks=1, prefix_cache=False, queue_depth=16,
                 max_prompt_len=300, max_new_tokens=60)
    return ServeEngine(DecodeKernels(cfg, params, ServeConfig(**{**sizes, **kw})))


def _drain(engine, *reqs):
    while not all(r.done.is_set() for r in reqs):
        assert engine.step_once()


def test_generate_greedy_is_the_references_argmax_and_a_reused_lane_starts_from_nothing(model):
    cfg, params, tokens, _ = model
    engine = _engine(cfg, params)
    first = engine.submit(tokens[0, :290].tolist(), max_new_tokens=12, temperature=0.0)   # crosses a chunk's edge
    _drain(engine, first)
    again = engine.submit(tokens[1, :33].tolist(), max_new_tokens=12, temperature=0.0)    # into the lane the first left
    _drain(engine, again)
    assert first.error is None and again.error is None and engine.lanes.stats()["active"] == 0
    for req, row, n in ((first, 0, 290), (again, 1, 33)):
        seq = np.concatenate([tokens[row, :n], np.asarray(req.output[:-1], np.int64)])
        want = oracle(cfg, params, seq[None])[0, n - 1:].argmax(-1)
        assert req.output == want.tolist()
    fresh = _engine(cfg, params)
    alone = fresh.submit(tokens[1, :33].tolist(), max_new_tokens=12, temperature=0.0)
    _drain(fresh, alone)
    assert alone.output == again.output                                                  # the slot and the tail were zeroed
    stats = engine.stats()
    assert stats["ssm"] == {"slots": 3, "live": 0, "bytes_per_slot": LAYERS * ssm_bytes_per_slot(cfg)}
    assert stats["attn_products"] == "block_diagonal" and "block_ids_address_nothing" not in stats and "state" not in stats
    assert set(stats["step_counters"]) == {"serve.ssm.live_lanes", "serve.ssm.bytes"} and stats["step_counters"]["serve.ssm.live_lanes"] == 22.0


def test_admission_waits_for_blocks_or_for_a_lane(model):
    """A request holds blocks for its prompt and its answer AND the slot of its lane: either can keep the next one waiting."""
    cfg, params, tokens, _ = model
    # three lanes, blocks for two such requests: the third waits for BLOCKS while a lane is free
    small = dict(max_prompt_len=60, max_new_tokens=40)
    engine = _engine(cfg, params, num_blocks=2 * 25 + 1, **small)
    reqs = [engine.submit(tokens[i, :60].tolist(), max_new_tokens=40, temperature=0.0) for i in range(3)]
    for _ in range(3):
        assert engine.step_once()
    assert engine.lanes.stats()["active"] == 2 and engine.queue.depth() == 1 and engine.allocator.stats()["used"] == 50
    _drain(engine, *reqs)
    assert all(r.error is None and len(r.output) == 40 for r in reqs) and engine.allocator.stats()["used"] == 0
    # blocks for many, three lanes: the fourth waits for a LANE while blocks are free
    engine = _engine(cfg, params, num_blocks=241, **small)
    reqs = [engine.submit(tokens[i % 3, :20 + i].tolist(), max_new_tokens=10 + 10 * i, temperature=0.0) for i in range(4)]
    for _ in range(3):
        assert engine.step_once()
    assert engine.lanes.stats()["active"] == 3 and engine.queue.depth() == 1 and engine.allocator.stats()["used"] < 120
    _drain(engine, *reqs)
    assert all(r.error is None for r in reqs) and [len(r.output) for r in reqs] == [10, 20, 30, 40]
    # each answer is what the request gives alone: no lane read another's state or tail
    for i in (0, 3):
        alone = _engine(cfg, params, **small)
        only = alone.submit(tokens[i % 3, :20 + i].tolist(), max_new_tokens=10 + 10 * i, temperature=0.0)
        _drain(alone, only)
        assert only.output == reqs[i].output


def test_prefix_cache_is_refused_by_name_and_a_prefill_starts_at_zero(model):
    cfg, params, _, _ = model
    with pytest.raises(ValueError) as refused:
        _engine(cfg, params, prefix_cache=True)
    assert str(refused.value) == SSM_SLOT.no_prefix_cache and "a shared block holds no state" in str(refused.value)
    assert "Set prefix_cache: false" in str(refused.value) and PAGED_KV.no_prefix_cache is None
    kernels = _engine(cfg, params).kernels
    with pytest.raises(ValueError, match="is prefilled from 0, not from 8"):
        kernels.prefill_suffix(list(range(1, 20)), [0] * kernels.serve_cfg.blocks_per_seq, 8, 1)
