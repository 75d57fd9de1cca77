"""Sliding-window layers served beside full ones over a cache of two kinds, a
parallel block under one LayerNorm, a layer type without positions, a tied
head, the plain sigmoid router and averaged shared experts
(models/transformer.py, models/moe.py, ops/paged_attention.py,
serve/engine.py), against the plain reference the benchmark keeps
(benchmark/reference/cohere2_moe.py: float32, no cache, no ring, a loop over
experts, no import from the program).  The window kernel against its oracle,
the router and the shared experts alone, and the block under ``LMTrial`` are
tests/test_window_kernels.py.  CPU, tiny sizes, seeded weights."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from determined_tpu.models import moe
from determined_tpu.models.cache_kinds import BLOCKS, LANE, PAGED_KV, WINDOW_RING, layer_kinds
from determined_tpu.models.serving import (
    SERVE_COUNTERS,
    _check_decodable,
    init_kv_cache,
    prefill_chunk_tokens,
    serve_counters,
    transformer_decode,
    transformer_prefill,
    transformer_prefill_chunked,
)
from determined_tpu.models.transformer import (
    FULL,
    SLIDING,
    TransformerConfig,
    TransformerLM,
    kv_cache_shape,
    window_ring_blocks,
    window_store_shape,
)
from tests.model_cases import reference_module

reference = reference_module("cohere2_moe")

BLOCK, EXPERTS, TOP_K, SHARED = 4, 16, 4, 2
TYPES = (SLIDING, SLIDING, SLIDING, FULL)


def tiny(**kw) -> TransformerConfig:
    """Layers W W W F with a window of 8; 8 heads of 16 over 2 KV heads; 16
    experts, top-4, experts 4..7 held, two shared experts averaged."""
    base = dict(
        vocab_size=96, d_model=64, n_layers=4, n_heads=8, n_kv_heads=2, head_dim=16, d_ff=32, max_seq_len=512,
        dtype=jnp.float32, attention_impl="reference", partition_params=False,
        layer_types=TYPES, sliding_window=8, rope_theta=50000.0,
        rope_parameters={SLIDING: {"rope_type": "default", "rope_theta": 50000.0}, FULL: {"rope_type": "none"}},
        norm="layernorm", norm_eps=1e-5, parallel_block=True, tie_embeddings=True, logit_scale=0.5,
        moe_experts=EXPERTS, moe_every=1, moe_top_k=TOP_K, moe_intermediate_size=32, moe_experts_held=(4, 4),
        moe_router="sigmoid", moe_shared_experts=SHARED, moe_shared_combine="mean",
    )
    return TransformerConfig(**{**base, **kw})


def numerics(cfg, **kw):
    return {**dict(eps=cfg.norm_eps, rope_theta=50000.0, window=cfg.sliding_window, layer_types=cfg.layer_types, top_k=TOP_K,
                   shared=SHARED, first_expert=cfg.moe_experts_held[0], logit_scale=cfg.logit_scale, query_block=16), **kw}


def build(cfg, seed=1):
    params = meta.unbox(jax.jit(TransformerLM(cfg).init)(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    # norms away from one, so that a norm the program skipped or ran twice shows
    norms = [params[f"block_{i}"]["ln1"] for i in range(cfg.n_layers)] + [params["ln_f"]]
    for i, leaf in enumerate(norms):
        leaf["scale"] = leaf["scale"] * (1.0 + 0.1 * jax.random.normal(jax.random.key(100 + i), leaf["scale"].shape))
    return params


def reference_weights(params, cfg):
    layers = [
        {"norm": params[f"block_{i}"]["ln1"]["scale"], **{k: params[f"block_{i}"]["attn"][k]["kernel"] for k in ("wq", "wk", "wv", "wo")},
         **params[f"block_{i}"]["moe"]}
        for i in range(cfg.n_layers)
    ]
    return {"embed": params["embed"]["embedding"], "final_norm": params["ln_f"]["scale"], "layers": layers}


def oracle(cfg, params, tokens):
    forward = jax.jit(functools.partial(reference.forward, **numerics(cfg)))
    return np.stack([np.asarray(forward(reference_weights(params, cfg), jnp.asarray(row))) for row in tokens])


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = build(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(0), (2, 340), 1, cfg.vocab_size))
    return cfg, params, tokens, oracle(cfg, params, tokens)


# ---------------------------------------------------------------------------
# the block as published
# ---------------------------------------------------------------------------


def test_the_full_forward_builds_the_published_block_and_matches_the_reference(model):
    cfg, params, tokens, want = model
    assert set(params) == {"embed", "ln_f", *(f"block_{i}" for i in range(4))}          # tied: no lm_head
    assert set(params["block_0"]) == {"ln1", "attn", "moe"}                              # parallel: one norm
    assert set(params["block_0"]["moe"]) == {"router", "w_gate", "w_up", "w_down", "shared_w_gate", "shared_w_up", "shared_w_down"}
    assert params["block_0"]["moe"]["shared_w_gate"].shape == (64, SHARED * 32)
    assert cfg.rope(FULL) is None and cfg.rope(SLIDING).theta == 50000.0
    assert cfg.window_layers == WINDOW_RING.layers(cfg) == (0, 1, 2) and PAGED_KV.layers(cfg) == (3,)
    assert [layer_kinds(cfg, i) for i in range(4)] == [((WINDOW_RING, 0, "attn"),), ((WINDOW_RING, 1, "attn"),), ((WINDOW_RING, 2, "attn"),), ((PAGED_KV, 0, "attn"),)]
    got = _forward(cfg)(params, jnp.asarray(tokens[:, :64]))
    np.testing.assert_allclose(np.asarray(got), want[:, :64], atol=3e-5)
    # the hidden state times the table, times logit_scale, is what a fused loss contracts
    hidden = jax.jit(functools.partial(TransformerLM(cfg).apply, return_hidden=True))({"params": params}, jnp.asarray(tokens[:, :64]))
    np.testing.assert_allclose(np.asarray(hidden @ params["embed"]["embedding"].T * cfg.logit_scale), want[:, :64], atol=3e-5)
    # bfloat16 leaves are made as such
    half = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(lambda: TransformerLM(half).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))
    assert {str(x.dtype) for x in leaves} == {"bfloat16"}


@pytest.mark.parametrize("told, by", [
    ({"layer_types": (SLIDING,) * 4}, "rotary on the full layer, and its window"),
    ({"window": 12}, "a window off by one block"),
    ({"logit_scale": 1.0}, "no logit_scale"),
    ({"eps": 1e-2}, "another eps"),
])
def test_the_reference_told_otherwise_disagrees(model, told, by):
    cfg, params, tokens, want = model
    forward = jax.jit(functools.partial(reference.forward, **numerics(cfg, **told)))
    other = np.asarray(forward(reference_weights(params, cfg), jnp.asarray(tokens[0, :64])))
    assert np.abs(other - want[0, :64]).max() > 1e-3, by


def test_configurations_the_program_cannot_run_are_refused():
    with pytest.raises(ValueError, match="rope_type"):
        tiny(rope_parameters={FULL: {"rope_type": "linear"}})
    with pytest.raises(ValueError, match="moe_shared_combine|norm"):
        tiny(norm="batchnorm")
    with pytest.raises(ValueError, match="moe_shared_combine"):
        tiny(moe_shared_combine="median")
    with pytest.raises(ValueError, match="latent attention"):
        TransformerConfig(q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, parallel_block=True)
    _check_decodable(tiny())                                        # window layers are served
    with pytest.raises(ValueError, match="outside pipeline stages"):
        _check_decodable(tiny(expert_axis_name="expert"))
    cfg = tiny()
    with pytest.raises(ValueError, match="lanes and its prefill chunk"):
        init_kv_cache(cfg, 8, BLOCK)
    with pytest.raises(ValueError, match="transformer_prefill_chunked"):
        transformer_prefill(cfg, {}, jnp.zeros((1, 8), jnp.int32), jnp.ones(1, jnp.int32), jnp.zeros((1, 2), jnp.int32),
                            init_kv_cache(cfg, 8, BLOCK, 1, 8))


# ---------------------------------------------------------------------------
# a cache of two kinds
# ---------------------------------------------------------------------------


def test_the_cache_holds_a_pool_for_full_layers_and_a_ring_a_lane_for_window_layers():
    cfg = tiny()
    cache = init_kv_cache(cfg, 24, BLOCK, lanes=3, chunk_tokens=128)
    assert set(cache) == {"k", "v", "wk", "wv"}
    assert cache["k"].shape == kv_cache_shape(cfg, 24, BLOCK) == (1, 24, BLOCK, 32)              # the one full layer
    ring = window_ring_blocks(cfg, BLOCK, 128)
    assert ring * BLOCK == 8 + 128                                                               # the window and one chunk: the bound
    assert cache["wk"].shape == window_store_shape(cfg, 3, BLOCK, 128) == (3, 3 * ring, BLOCK, 32)
    assert window_ring_blocks(tiny(sliding_window=10), BLOCK, 128) * BLOCK == 12 + 128            # the window in whole blocks
    # a model without window layers: today's pool and nothing else
    plain = tiny(layer_types=None, sliding_window=None, rope_parameters=None)
    assert set(init_kv_cache(plain, 24, BLOCK)) == {"k", "v"} and kv_cache_shape(plain, 24, BLOCK)[0] == 4
    assert serve_counters(cfg) == WINDOW_RING.counters + SERVE_COUNTERS and serve_counters(plain) == SERVE_COUNTERS
    assert serve_counters(tiny(moe_experts=0, moe_top_k=0, moe_intermediate_size=None, moe_experts_held=None, moe_router="softmax",
                               moe_shared_experts=0)) == WINDOW_RING.counters


@functools.lru_cache(maxsize=None)
def _walk_step(cfg):
    return jax.jit(functools.partial(transformer_prefill_chunked, cfg))


def _walk(cfg, params, tokens, lens, lanes, cache=None, pad=None, n_lanes=3):
    """Prefill ``tokens`` rows (lengths ``lens``) into lanes ``lanes`` by the chunked walk."""
    pad = pad or -(-max(lens) // 256) * 256
    chunk = prefill_chunk_tokens(BLOCK, pad)
    toks = np.zeros((len(lens), pad), np.int32)
    for r, n in enumerate(lens):
        toks[r, :n] = tokens[r, :n]
    t = 96
    tables = np.zeros((len(lens), t), np.int32)
    for r in range(len(lens)):
        tables[r] = 1 + r * t + np.arange(t)
    if cache is None:
        cache = init_kv_cache(cfg, 1 + 3 * t, BLOCK, lanes=n_lanes, chunk_tokens=chunk)
    last, cache = _walk_step(cfg)(
        params, jnp.asarray(toks), jnp.zeros(len(lens), jnp.int32), jnp.asarray(lens, jnp.int32), jnp.asarray(tables), cache,
        jnp.asarray(lanes, jnp.int32),
    )
    return np.asarray(last), cache, tables


@functools.lru_cache(maxsize=None)
def _decode_step(cfg, form):
    return jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=form, counters=True))


@functools.lru_cache(maxsize=None)
def _forward(cfg):
    return jax.jit(lambda params, tokens: TransformerLM(cfg).apply({"params": params}, tokens))


def _decode(cfg, params, tokens, lens, lanes, tables, cache, steps, form, n_lanes=3):
    """Teacher-forced decode of the rows in their lanes; yields (step, logits [rows, V], counters)."""
    for step in range(steps):
        pos = np.full(n_lanes, -1, np.int32)
        tok = np.zeros(n_lanes, np.int32)
        tb = np.zeros((n_lanes, tables.shape[1]), np.int32)
        for r, lane in enumerate(lanes):
            pos[lane], tok[lane], tb[lane] = lens[r] + step, tokens[r, lens[r] + step], tables[r]
        out, cache = _decode_step(cfg, form)(params, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tb), cache)
        yield step, np.asarray(out)[list(lanes)], np.asarray(out)[-1], pos


@pytest.mark.parametrize("lens, window, why", [
    ((5, 3), 8, "one chunk, inside the window"),
    ((40, 120), 8, "one chunk, past the window"),
    ((300, 150), 8, "several chunks"),
    ((300, 260), 200, "a window wider than a chunk: queries reach two chunks back"),
])
@pytest.mark.parametrize("form", [1, 0])
def test_prefill_then_decode_through_both_kinds_of_cache_match_the_reference(model, lens, window, why, form):
    cfg, params, tokens, want = model
    if window != cfg.sliding_window:
        cfg = dataclasses.replace(cfg, sliding_window=window)
        want = oracle(cfg, params, tokens)
    lanes = (2, 0)                                                   # not the rows' own numbers; lane 1 idles
    last, cache, tables = _walk(cfg, params, tokens, lens, lanes)
    for r, n in enumerate(lens):
        np.testing.assert_allclose(last[r], want[r, n - 1], atol=5e-5, err_msg=why)
    steps = 3 * window + 5 if window == 8 else 6                     # contexts run past three windows
    for step, got, counted, pos in _decode(cfg, params, tokens, lens, lanes, tables, cache, steps, form):
        for r, n in enumerate(lens):
            np.testing.assert_allclose(got[r], want[r, n + step], atol=5e-5, err_msg=f"{why}, step {step}")
        ctx = pos[pos >= 0] + 1
        assert counted[0] == ctx.sum() * 1 and counted[1] == np.minimum(ctx, window).sum() * 3   # full and window tokens read
        held, hit = counted[2:4]
        assert 0 < hit <= min(held, 4 * 4) and held <= 2 * 4 * TOP_K and not np.any(counted[4:])


def test_the_full_forward_is_the_same_oracle(model):
    """``TransformerLM`` == the reference == the cache path: the three agree on one sequence."""
    cfg, params, tokens, want = model
    full = np.asarray(_forward(cfg)(params, jnp.asarray(tokens[:, :64])))[0]       # the program the first test compiled
    last, cache, tables = _walk(cfg, params, tokens[:1], (40,), (1,))
    np.testing.assert_allclose(last[0], full[39], atol=5e-5)
    for step, got, _, _ in _decode(cfg, params, tokens[:1], (40,), (1,), tables, cache, 20, 1):
        np.testing.assert_allclose(got[0], full[40 + step], atol=5e-5)
        np.testing.assert_allclose(full[40 + step], want[0, 40 + step], atol=5e-5)


def test_a_lanes_next_request_never_reads_the_last_ones_rows(model):
    """A ring slot is valid by position alone: request B in a lane that
    request A (longer, other tokens) has just left reads what B would read in
    a fresh store, bit for bit; and the store keeps its size whatever the context."""
    cfg, params, tokens, _ = model
    _, cache, tables = _walk(cfg, params, tokens[:1], (300,), (1,))
    shape = cache["wk"].shape
    for _ in _decode(cfg, params, tokens[:1], (300,), (1,), tables, cache, 0, 1):
        pass
    used, fresh = [], []
    for start, sink in ((cache, used), (None, fresh)):
        last, c, tb = _walk(cfg, params, tokens[1:], (21,), (1,), cache=start, pad=512)
        sink.append(last)
        for _, got, _, _ in _decode(cfg, params, tokens[1:], (21,), (1,), tb, c, 12, 1):
            sink.append(got)
        assert c["wk"].shape == shape
    for a, b in zip(used, fresh):
        np.testing.assert_array_equal(a, b)
    assert np.abs(np.asarray(cache["wk"])).max() > 0                 # request A did leave rows there


def test_a_window_store_sized_for_another_chunk_is_refused(model):
    cfg, params, tokens, _ = model
    cache = init_kv_cache(cfg, 8, BLOCK, lanes=2, chunk_tokens=64)    # the walk below takes chunks of 256
    with pytest.raises(ValueError, match="another prefill chunk"):
        transformer_prefill_chunked(cfg, params, jnp.zeros((1, 256), jnp.int32), jnp.zeros(1, jnp.int32), jnp.ones(1, jnp.int32),
                                    jnp.zeros((1, 4), jnp.int32), cache)


# ---------------------------------------------------------------------------
# eight shares of the expert layer (the router and the shared experts alone: tests/test_window_kernels.py)
# ---------------------------------------------------------------------------


def test_eight_shares_and_the_shared_experts_once_add_up_to_the_uncut_layer():
    """Each of eight chips holds two of sixteen experts and computes the picks
    that land there; all of them compute the shared experts alike.  The
    routed parts of all shares, and the shared experts counted ONCE, are the
    uncut reference layer: in the reference, and in the program's serving layer."""
    cfg = tiny(moe_experts_held=None)
    whole = build(cfg)["block_0"]["moe"]
    h = jax.random.normal(jax.random.key(9), (1, 24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = reference.expert_layer(h[0], whole, top_k=TOP_K, shared=SHARED, first=0)
        shared = reference.shared_part(h[0], whole, SHARED)
    by_reference, by_program = jnp.zeros_like(uncut), jnp.zeros_like(uncut)
    for share in range(8):
        held = {k: (v[2 * share:2 * share + 2] if k in ("w_gate", "w_up", "w_down") else v) for k, v in whole.items()}
        with jax.default_matmul_precision("highest"):
            by_reference += reference.expert_layer(h[0], held, top_k=TOP_K, shared=SHARED, first=2 * share, with_shared=False)
        share_cfg = dataclasses.replace(cfg, moe_experts_held=(2 * share, 2))
        y, (picks_held, hit) = moe.serve_routed_experts(share_cfg, held, h)
        by_program += y[0] - shared                                   # every share computes the shared experts alike
        assert 0 <= int(hit) <= 2 and int(picks_held) <= 24 * 2
    np.testing.assert_allclose(np.asarray(by_reference + shared), np.asarray(uncut), atol=2e-5)
    np.testing.assert_allclose(np.asarray(by_program + shared), np.asarray(uncut), atol=2e-5)


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine_parts(model):
    from determined_tpu.serve.config import ServeConfig
    from determined_tpu.serve.engine import DecodeKernels

    cfg, params, _, _ = model
    serve_cfg = ServeConfig(block_size=BLOCK, num_blocks=200, max_batch=3, max_prompt_len=300, max_new_tokens=40,
                            prefix_cache=False, queue_depth=8)
    return cfg, params, serve_cfg, DecodeKernels(cfg, params, serve_cfg)


def _greedy(cfg, params, prompt, n):
    """Greedy continuation by the full forward, on one padded width (causal: what follows a position cannot move it)."""
    seq = list(prompt)
    for _ in range(n):
        padded = np.zeros((1, 336), np.int32)
        padded[0, : len(seq)] = seq
        seq.append(int(np.argmax(np.asarray(_forward(cfg)(params, jnp.asarray(padded))[0, len(seq) - 1]))))
    return seq[len(prompt):]


def test_two_lanes_of_unequal_length_through_the_engine_match_the_full_forward(engine_parts, model):
    from determined_tpu.serve.engine import ServeEngine

    cfg, params, serve_cfg, kernels = engine_parts
    tokens = model[2]
    # a cache of two kinds: a request holds blocks of the one and its lane's ring of the other
    assert kernels.kinds == (PAGED_KV, WINDOW_RING) and [kind.holds for kind in kernels.kinds] == [BLOCKS, LANE]
    assert 8 + serve_cfg.prefill_chunk == 264                         # a ring's tokens: ``window_store`` below
    assert kernels.cache["wk"].shape == (3, 3 * 66, BLOCK, 32) and kernels.cache["k"].shape == (1, 200, BLOCK, 32)
    eng = ServeEngine(kernels)                                       # not started: the test drives step_once()
    short = eng.submit(tokens[0, :5].tolist(), max_new_tokens=6)      # inside the window when it ends
    long = eng.submit(tokens[1, :290].tolist(), max_new_tokens=30)    # two chunks, decodes past three windows
    while not (short.done.is_set() and long.done.is_set()):
        assert eng.step_once(), "scheduler stalled"
    assert short.error is None and long.error is None
    assert short.output == _greedy(cfg, params, tokens[0, :5].tolist(), 6)
    assert long.output == _greedy(cfg, params, tokens[1, :290].tolist(), 30)
    # a third request takes the lane the short one left, after it: the ring there holds older rows
    third = eng.submit(tokens[0, 40:70].tolist(), max_new_tokens=12)
    while not third.done.is_set():
        assert eng.step_once()
    assert third.output == _greedy(cfg, params, tokens[0, 40:70].tolist(), 12)
    stats = eng.stats()
    assert stats["window_store"] == {"window_store_bytes": 2 * 3 * 3 * 264 * 32 * 4, "ring_tokens": 264}
    assert set(stats["step_counters"]) == set(WINDOW_RING.counters + SERVE_COUNTERS)
    assert stats["step_counters"]["serve.kv.full_tokens"] > stats["step_counters"]["serve.kv.window_tokens"] / 3 > 0
    # the host's count of the kernels' walks (``walk_counts``: the full layer's whole context, the three window layers'
    # newest 8 tokens) reads what the device counted, and the copies brought whole blocks: more, and never a whole tile
    walked = stats["step_inputs"]
    assert [kind.walked(cfg) for kind in kernels.kinds] == [(1, None), (3, 8)]
    assert walked["paged_live_tokens"] == stats["step_counters"]["serve.kv.full_tokens"] + stats["step_counters"]["serve.kv.window_tokens"]
    assert walked["paged_live_tokens"] < walked["paged_copied_tokens"] < walked["paged_live_tokens"] + walked["decode_steps"] * 2 * (1 + 3 * 2) * BLOCK
    assert stats["tile_copies"] == "live_blocks" and stats["lane_prefetch"] is True
    assert stats["kv_cache"]["used"] == 0                            # the allocator counts the full layer's blocks, all freed




def test_the_engine_picks_the_lane_before_it_prefills(engine_parts, model):
    from determined_tpu.serve.engine import ServeEngine

    cfg, params, serve_cfg, kernels = engine_parts
    tokens = model[2]
    eng = ServeEngine(kernels)
    seen = []
    prefill = kernels.prefill_suffix
    kernels.prefill_suffix = lambda prompt, table, start, lane=0: seen.append(lane) or prefill(prompt, table, start, lane)
    try:
        reqs = [eng.submit(tokens[0, 10 * i:10 * i + 9].tolist(), max_new_tokens=3 + 4 * i) for i in range(3)]
        eng.step_once()
        assert seen == [0, 1, 2] and [eng.lanes.get(i).request.id for i in range(3)] == [r.id for r in reqs]
        while not reqs[0].done.is_set():
            eng.step_once()
        late = eng.submit(tokens[1, :9].tolist(), max_new_tokens=2)
        eng.step_once()
        assert seen[-1] == 0                                          # the lane the first one left
        while not all(r.done.is_set() for r in reqs + [late]):
            eng.step_once()
    finally:
        kernels.prefill_suffix = prefill
    with pytest.raises(RuntimeError, match="lane 1 is not free"):
        from determined_tpu.serve.scheduler import LaneTable

        table = LaneTable(2)
        table.join(object(), 1)
        table.join(object(), 1)


def test_prefix_sharing_over_window_state_is_refused_with_the_reason(model):
    from determined_tpu.serve.config import ServeConfig
    from determined_tpu.serve.engine import DecodeKernels

    cfg, params, _, _ = model
    with pytest.raises(ValueError, match="a shared block holds no window state"):
        DecodeKernels(cfg, params, ServeConfig(block_size=BLOCK, num_blocks=64, max_batch=2, max_prompt_len=64, max_new_tokens=8))


def test_a_windowed_prompt_is_prefilled_from_zero(engine_parts, model):
    cfg, params, serve_cfg, kernels = engine_parts
    with pytest.raises(ValueError, match="prefilled from 0"):
        kernels.prefill_suffix(model[2][0, :20].tolist(), [0] * serve_cfg.blocks_per_seq, 8)
