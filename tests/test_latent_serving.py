"""Latent attention, the published sigmoid group-limited router, a shared
expert and a dense prefix (models/transformer.py, models/moe.py,
ops/paged_attention.py), against the plain reference the benchmark keeps
(benchmark/reference/deepseek_mla_moe.py: float32, nothing absorbed, a loop
over experts, no import from the program).  CPU, tiny sizes, seeded weights;
Pallas kernels in interpret mode."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from determined_tpu.models import moe
from determined_tpu.models.serving import (
    SERVE_COUNTERS,
    _check_decodable,
    init_kv_cache,
    transformer_decode,
    transformer_prefill,
    transformer_prefill_chunked,
)
from determined_tpu.models.transformer import (
    FULL,
    TransformerConfig,
    TransformerLM,
    kv_bytes_per_token,
    kv_cache_shape,
)
from determined_tpu.ops import grouped_matmul as gm, paged_attention as paged
from tests.model_cases import PAGED_EDGES, check_copy_schedule, reference_module

reference = reference_module("deepseek_mla_moe")

ROPE_SCALING = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 16, "beta_fast": 32, "beta_slow": 1,
                "mscale": 1.0, "mscale_all_dim": 1.0}
N_GROUP, TOPK_GROUP, TOP_K, EXPERTS, SCALING = 4, 2, 4, 16, 2.5
NUMERICS = dict(eps=1e-6, rope_theta=10000.0, rope_scaling=ROPE_SCALING, nope=16, rope_dim=8, latent=32,
                top_k=TOP_K, n_group=N_GROUP, topk_group=TOPK_GROUP, scaling=SCALING)


def tiny(**kw) -> TransformerConfig:
    """3 layers, the first dense; 16 experts in 4 groups of which 2 stay,
    top-4, experts 4..7 held; 4 heads of [16 | 8] against a latent row of [32 | 8]."""
    on_cos_sin, scale = reference.yarn_scales(ROPE_SCALING, 24)
    base = dict(
        vocab_size=96, d_model=64, n_layers=3, n_heads=4, d_ff=96, max_seq_len=64, dtype=jnp.float32,
        attention_impl="reference", partition_params=False,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, softmax_scale=scale,
        rope_parameters={FULL: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 40, "original_max_position_embeddings": 16,
                                "beta_fast": 32, "beta_slow": 1, "attention_factor": on_cos_sin}},
        dense_prefix=1, moe_experts=EXPERTS, moe_every=1, moe_top_k=TOP_K, moe_intermediate_size=32, moe_experts_held=(4, 4),
        moe_router="sigmoid_grouped", moe_n_group=N_GROUP, moe_topk_group=TOPK_GROUP, moe_routed_scaling=SCALING,
        moe_shared_experts=1,
    )
    return TransformerConfig(**{**base, **kw})


def build(cfg, seed=1, bias_scale=20.0):
    """The program's own initialiser; the selection bias made large enough
    (0.2 against sigmoid scores near 0.5) that it changes picks."""
    params = meta.unbox(jax.jit(TransformerLM(cfg).init)(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    for name, block in params.items():
        if "moe" in block:
            block["moe"]["router_bias"] = block["moe"]["router_bias"] * bias_scale
    return params


def reference_weights(params, cfg):
    layers = []
    for i in range(cfg.n_layers):
        b = params[f"block_{i}"]
        layer = {"attn_norm": b["ln1"]["scale"], "mlp_norm": b["ln2"]["scale"], **b["attn"]}
        layer.update(b["moe"] if "moe" in b else {k: b["mlp"][k]["kernel"] for k in ("w_gate", "w_up", "w_down")})
        layers.append(layer)
    return {"embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"],
            "final_norm": params["ln_f"]["scale"], "layers": layers}


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = build(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(0), (2, 40), 1, cfg.vocab_size))
    forward = jax.jit(functools.partial(reference.forward, first_expert=4, **NUMERICS))
    want = np.stack([np.asarray(forward(reference_weights(params, cfg), jnp.asarray(row))) for row in tokens])
    return cfg, params, tokens, want


# ---------------------------------------------------------------------------
# the whole model against the reference
# ---------------------------------------------------------------------------


def test_the_full_forward_builds_the_published_block_and_matches_the_reference(model):
    cfg, params, tokens, want = model
    assert [cfg.use_moe(i) for i in range(3)] == [False, True, True]
    assert set(params["block_0"]) == {"ln1", "attn", "ln2", "mlp"} and set(params["block_1"]) == {"ln1", "attn", "ln2", "moe"}
    shapes = {k: v.shape for k, v in params["block_1"]["attn"].items()}
    assert shapes == {"wq_a": (64, 24), "q_norm": (24,), "wq_b": (24, 4, 24), "wkv_a": (64, 40), "kv_norm": (32,),
                      "wkv_b": (32, 4, 32), "wo": (4, 16, 64)}
    assert params["block_1"]["moe"]["router_bias"].shape == (16,) and params["block_1"]["moe"]["shared_w_gate"].shape == (64, 32)
    got = jax.jit(TransformerLM(cfg).apply)({"params": params}, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    # bfloat16 leaves are made as such, and the forward still runs on them
    half = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(lambda: TransformerLM(half).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))
    assert {str(x.dtype) for x in leaves} == {"bfloat16", "float32"} and sum(x.dtype == jnp.float32 for x in leaves) == 2  # the two biases


@pytest.mark.parametrize("form", ["table", "jnp"])
def test_prefill_then_decode_through_the_latent_pool_match_the_reference(model, form):
    """The wide prefill expands keys and values a head (as published); decode
    stays in the latent space (absorbed), by the full-table gather or the
    ``jax.numpy`` walk over the pool (the kernel against that walk: below)."""
    cfg, params, tokens, want = model
    cache = init_kv_cache(cfg, 24, 8)
    assert set(cache) == {"kv"} and cache["kv"].shape == kv_cache_shape(cfg, 24, 8) == (3, 24, 8, 128)
    assert kv_bytes_per_token(cfg) == 3 * 40 * 4
    tables = jnp.asarray([list(range(1, 9)), list(range(9, 17))], jnp.int32)
    lens = jnp.asarray([20, 24], jnp.int32)
    # one program a shape: called bare, the step is compiled an operation at a time
    decode = jax.jit(functools.partial(transformer_decode, cfg), static_argnames=("chunk_blocks", "counters"))
    logits, cache = jax.jit(functools.partial(transformer_prefill, cfg))(params, jnp.asarray(tokens[:, :32]), lens, tables, cache)
    np.testing.assert_allclose(np.asarray(logits[0, :20]), want[0, :20], atol=2e-4)
    np.testing.assert_allclose(np.asarray(logits[1, :24]), want[1, :24], atol=2e-4)
    chunk = 0 if form == "table" else 1
    for step in range(8):
        pos = jnp.asarray([20 + step, 24 + step], jnp.int32)
        tok = jnp.asarray([tokens[0, 20 + step], tokens[1, 24 + step]], jnp.int32)
        out, cache = decode(params, tok, pos, tables, cache, chunk_blocks=chunk, counters=True)
        assert out.shape == (3, cfg.vocab_size)
        np.testing.assert_allclose(np.asarray(out[0]), want[0, 20 + step], atol=3e-4)
        np.testing.assert_allclose(np.asarray(out[1]), want[1, 24 + step], atol=3e-4)
        held, hit = (float(v) for v in out[2, :2])
        assert len(SERVE_COUNTERS) == 2 and 0 <= hit <= min(held, 8) and held <= 2 * 2 * TOP_K and not np.any(np.asarray(out[2, 2:]))
    # an idle lane takes no expert's rows, and its logits are nobody's
    out, _ = decode(params, tok, jnp.asarray([28, -1], jnp.int32), tables, cache, chunk_blocks=1, counters=True)
    alone, _ = decode(params, tok[:1], jnp.asarray([28], jnp.int32), tables[:1], cache, chunk_blocks=1, counters=True)
    assert float(out[2, 0]) == float(alone[1, 0])
    assert decode(params, tok, pos, tables, cache, chunk_blocks=1)[0].shape == (2, cfg.vocab_size)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-6), (jnp.bfloat16, 4e-3)], ids=["f32", "bf16"])
def test_the_latent_kernel_and_the_jnp_walk_match_a_dense_softmax_over_ragged_lanes(dtype, atol):
    """``paged_latent_attention`` in the TPU interpreter and in ``jax.numpy``:
    6 heads against one 256-wide row a token whose first 128 columns are the
    values, lanes of 71 and 6 tokens and an idle one, tiles of 2 blocks (the
    last one part live).  In a bfloat16 pool the probabilities enter the
    second product rounded to bfloat16: a flash kernel's rounding."""
    layers, blocks, block, width, values, heads, lanes, cols = 2, 24, 16, 256, 128, 6, 3, 6
    keys = jax.random.split(jax.random.key(0), 2)
    pool = jax.random.normal(keys[0], (layers, blocks, block, width), jnp.float32).astype(dtype)
    q = (jax.random.normal(keys[1], (lanes, heads, width), jnp.float32) * 0.3).astype(dtype)
    tables = jnp.asarray(np.random.default_rng(0).permutation(np.arange(1, blocks))[: lanes * cols].reshape(lanes, cols), jnp.int32)
    pos = jnp.asarray([70, -1, 5], jnp.int32)
    rows = pool[1][tables].reshape(lanes, cols * block, width).astype(jnp.float32)
    s = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32), rows) * 0.1
    s = jnp.where(jnp.arange(cols * block)[None, None, :] <= pos[:, None, None], s, -1e30)
    want = np.asarray(jnp.einsum("bht,btc->bhc", jax.nn.softmax(s, -1), rows[..., :values]) * (pos >= 0)[:, None, None])
    for impl in ("jnp", "kernel_interpret"):
        got = paged.paged_latent_attention(q, pool, 1, tables, pos, scale=0.1, value_dim=values, impl=impl, tile_blocks=2)
        assert got.dtype == jnp.float32 and got.shape == (lanes, heads, values)
        np.testing.assert_allclose(np.asarray(got), want, atol=atol)
        assert not np.asarray(got)[1].any()
    assert paged.latent_kernel_takes(256, 128, 16, dtype) and not paged.latent_kernel_takes(576, 512, 16, dtype)
    with pytest.raises(ValueError, match="width % 128"):
        paged.paged_latent_attention(q[..., :200], pool[..., :200], 1, tables, pos, scale=0.1, value_dim=128, impl="kernel_interpret")


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-6), (jnp.bfloat16, 4e-3)], ids=["f32", "bf16"])
@pytest.mark.parametrize("lanes", list(PAGED_EDGES))
def test_the_latent_kernels_copy_schedule_at_a_lanes_edges(lanes, dtype, atol):
    """The latent kernel over the lanes of ``PAGED_EDGES`` (a token, a block, a
    tile, a tile and one; idle lanes first, in the middle, last, two in a row,
    all), tiles of 2 blocks: against a dense softmax and the ``jax.numpy`` walk,
    then the poison case (``check_copy_schedule``).  The kernel multiplies a
    whole tile, so a row no copy wrote must be zeros or a pool's row."""
    contexts = PAGED_EDGES[lanes]
    layers, blocks, block, width, values, heads, cols = 2, 32, 16, 256, 128, 6, 6
    keys = jax.random.split(jax.random.key(3), 2)
    pool = jax.random.normal(keys[0], (layers, blocks, block, width), jnp.float32).astype(dtype)
    q = (jax.random.normal(keys[1], (len(contexts), heads, width), jnp.float32) * 0.3).astype(dtype)
    tables = jnp.asarray(np.random.default_rng(3).permutation(np.arange(1, blocks))[: len(contexts) * cols].reshape(-1, cols), jnp.int32)
    pos = jnp.asarray(contexts, jnp.int32) - 1

    def run(pool, impl="kernel_interpret"):
        return paged.paged_latent_attention(q, pool, 1, tables, pos, scale=0.1, value_dim=values, impl=impl, tile_blocks=2)

    got = check_copy_schedule(run, (pool,), 1, tables, contexts, block)
    rows = pool[1][tables].reshape(len(contexts), cols * block, width).astype(jnp.float32)
    s = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32), rows) * 0.1
    s = jnp.where(jnp.arange(cols * block)[None, None, :] <= pos[:, None, None], s, -1e30)
    want = jnp.einsum("bht,btc->bhc", jax.nn.softmax(s, -1), rows[..., :values]) * (pos >= 0)[:, None, None]
    np.testing.assert_allclose(got, np.asarray(want), atol=atol)
    np.testing.assert_allclose(got, np.asarray(run(pool, "jnp")), atol=2e-6, rtol=2e-5)
    assert not got[[n == 0 for n in contexts]].any()


def test_suffix_prefill_from_a_shared_prefix_matches_the_reference_and_a_cold_start(model):
    """Two lanes in one walk, each from its own start (the chunk is the
    40-token width here)."""
    cfg, params, tokens, want = model
    tables = jnp.asarray([[1, 2, 3, 4, 5, 0, 0, 0], [6, 7, 8, 9, 10, 0, 0, 0]], jnp.int32)
    lens = jnp.asarray([37, 40], jnp.int32)
    walk = jax.jit(functools.partial(transformer_prefill_chunked, cfg))
    cold, cache = walk(params, jnp.asarray(tokens), jnp.zeros(2, jnp.int32), lens, tables, init_kv_cache(cfg, 16, 8))
    np.testing.assert_allclose(np.asarray(cold[0]), want[0, 36], atol=3e-4)
    np.testing.assert_allclose(np.asarray(cold[1]), want[1, 39], atol=3e-4)
    # the first 16 and 24 tokens already sit in the pool: only the rest is computed, to the same logits
    warm, _ = walk(params, jnp.asarray(tokens), jnp.asarray([16, 24], jnp.int32), lens, tables, cache)
    np.testing.assert_array_equal(np.asarray(warm), np.asarray(cold))


@pytest.fixture(scope="module")
def long_model():
    """The tiny latent + expert model over three chunks of 256, ONE jitted
    walk under the retrace sentinel, and the full forward's logits of one row."""
    from determined_tpu.lint._runtime import get_retrace_sentinel

    cfg = tiny(max_seq_len=768)
    params = build(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(4), (1, 768), 1, cfg.vocab_size), np.int32)
    full = np.asarray(jax.jit(TransformerLM(cfg).apply)({"params": params}, jnp.asarray(tokens)))[0]
    tables = jnp.arange(1, 97, dtype=jnp.int32)[None, :]
    sentinel = get_retrace_sentinel()
    walk = jax.jit(sentinel.wrap(
        "test.latent_prefill_walk", lambda t, s, n, c: transformer_prefill_chunked(cfg, params, t, s, n, tables, c), allowed=1,
    ))

    def run(n, start=0, cache=None):
        padded = tokens.copy()
        padded[0, n:] = 0
        cache = init_kv_cache(cfg, 97, 8) if cache is None else cache
        return walk(jnp.asarray(padded), jnp.asarray([start], jnp.int32), jnp.asarray([n], jnp.int32), cache)

    return run, full, sentinel


@pytest.mark.parametrize("n", [255, 256, 257, 2 * 256 + 17, 768])
def test_the_prefill_walk_matches_the_full_forward_across_chunk_edges(long_model, n):
    """Latent rows and expert layers at C - 1, C, C + 1, 2C + 17 and the padded
    width: a chunk's rows outside the prompt take no expert's rows and write
    the scratch block; a warm start inside a chunk (block-aligned, not
    chunk-aligned: part of its chunk's rows are masked, so the held picks lie
    elsewhere in the experts' buffer) is bitwise the cold run; one trace."""
    run, full, sentinel = long_model
    cold, cache = run(n)
    np.testing.assert_allclose(np.asarray(cold[0]), full[n - 1], atol=3e-4)
    start = (n // 2) // 8 * 8 + 8
    assert start % 256
    warm, _ = run(n, start, cache)
    np.testing.assert_array_equal(np.asarray(warm), np.asarray(cold))
    assert {r.label: r.traces for r in sentinel.records()}["test.latent_prefill_walk"] == 1


def test_what_serving_still_refuses_it_refuses_by_name():
    _check_decodable(tiny())
    with pytest.raises(ValueError, match="capacity"):
        _check_decodable(TransformerConfig(moe_experts=4))
    with pytest.raises(ValueError, match="pipeline stages"):
        _check_decodable(tiny(expert_axis_name="expert"))
    for kw, says in ((dict(moe_n_group=3), "moe_n_group"), (dict(moe_topk_group=1, moe_top_k=5), "moe_topk_group"),
                     (dict(qk_rope_head_dim=None), "latent attention needs"), (dict(dense_prefix=4), "dense_prefix"),
                     (dict(moe_router="softmax2"), "moe_router"), (dict(quantized_matmul="int8"), "quantized_matmul")):
        with pytest.raises(ValueError, match=says):
            tiny(**kw)


# ---------------------------------------------------------------------------
# the router alone
# ---------------------------------------------------------------------------


def test_the_router_picks_and_weighs_as_the_reference_with_the_group_limit_active():
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, EXPERTS)) * 0.4, jnp.float32)
    bias = jnp.asarray(rng.normal(size=EXPERTS) * 0.2, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want_picks, want_w = reference.route(h, router, bias, top_k=TOP_K, n_group=N_GROUP, topk_group=TOPK_GROUP, scaling=SCALING)
    weights, picks = moe._route(
        {"router": router, "router_bias": bias}, h, kind="sigmoid_grouped", top_k=TOP_K, n_group=N_GROUP,
        topk_group=TOPK_GROUP, scaling=SCALING,
    )
    order = np.argsort(np.asarray(picks), axis=1)
    want_order = np.argsort(np.asarray(want_picks), axis=1)
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(picks), order, 1), np.take_along_axis(np.asarray(want_picks), want_order, 1))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(weights), order, 1), np.take_along_axis(np.asarray(want_w), want_order, 1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(1), SCALING, rtol=1e-5)
    scores = np.asarray(jax.nn.sigmoid(h @ router))
    select = scores + np.asarray(bias)[None]
    groups = np.asarray(picks) // (EXPERTS // N_GROUP)
    assert all(len(set(g)) <= TOPK_GROUP for g in groups)
    # the limit is active: some token's best expert lies in a dropped group
    best = select.argmax(1)
    assert np.sum([b not in p for b, p in zip(best, np.asarray(picks))]) > 0
    # and the bias selects without weighing: the weights are the picks' SCORES, normalised
    top = np.take_along_axis(scores, np.asarray(picks), 1)
    np.testing.assert_allclose(np.asarray(weights), top / top.sum(1, keepdims=True) * SCALING, rtol=1e-5)
    assert np.any(np.argsort(-scores, 1)[:, :TOP_K].min(1) != np.sort(np.asarray(picks), 1)[:, 0])  # not the plain top-k of the scores


# ---------------------------------------------------------------------------
# the share test: all the shares' parts, the shared expert counted once
# ---------------------------------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips hold 4 of 16 experts each.  The routed parts the four give,
    with the shared expert (which every chip computes alike) counted once,
    are what the reference gives for the layer holding all 16."""
    cfg = tiny(n_layers=2, moe_experts_held=None)
    params = build(cfg, seed=5)
    whole = params["block_1"]["moe"]
    h = jnp.asarray(np.random.default_rng(7).normal(size=(1, 24, 64)), jnp.float32)
    f32 = {k: v.astype(jnp.float32) for k, v in whole.items()}
    router = dict(top_k=TOP_K, n_group=N_GROUP, topk_group=TOPK_GROUP, scaling=SCALING)
    with jax.default_matmul_precision("highest"):
        want = reference.expert_layer(h[0], f32, first=0, **router)
        shared = reference.swiglu(h[0], f32["shared_w_gate"], f32["shared_w_up"], f32["shared_w_down"])
    total, picks_seen, hit_seen = jnp.zeros_like(h[0]), 0.0, 0.0
    for share in range(4):
        held = dataclasses.replace(cfg, moe_experts_held=(4 * share, 4))
        mine = {k: (v[4 * share: 4 * share + 4] if k in ("w_gate", "w_up", "w_down") else v) for k, v in whole.items()}
        y, (picks, hit) = moe.serve_routed_experts(held, mine, h)
        total = total + (y[0] - shared)
        picks_seen, hit_seen = picks_seen + float(picks), hit_seen + float(hit)
        # a share is what the reference gives when told the same range
        with jax.default_matmul_precision("highest"):
            part = reference.expert_layer(h[0], {**f32, **{k: f32[k][4 * share: 4 * share + 4] for k in ("w_gate", "w_up", "w_down")}}, first=4 * share, **router)
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(part), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), atol=5e-5)
    assert picks_seen == 24 * TOP_K and 1 <= hit_seen <= 16      # every pick landed on exactly one share


# ---------------------------------------------------------------------------
# rows for serving: an expert without rows owns no tile
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sizes", [[0, 3, 0, 40], [0, 0, 0, 0], [17, 0, 0, 0], [1, 1, 1, 1]])
def test_a_serving_layout_gives_an_expert_without_rows_no_tile(sizes):
    layout = gm.tile_layout(jnp.asarray(sizes, jnp.int32), 64, 16, empty_groups_own_tile=False)
    tiles = [-(-s // 16) for s in sizes]
    live = int(layout.live_tiles[0])
    assert live == max(sum(tiles), 1) and layout.rows == gm.buffer_rows(64, 4, 16)
    groups = np.asarray(layout.tile_group)
    want = [e for e, n in enumerate(tiles) for _ in range(n)] or [0]
    assert list(groups[:live]) == want and set(groups[live:]) <= {want[-1]}   # a dead tile re-reads the last live block
    # the kernels' default still gives every group a tile (tgmm writes every block)
    assert int(gm.tile_layout(jnp.asarray(sizes, jnp.int32), 64, 16).live_tiles[0]) == sum(max(t, 1) for t in tiles)
