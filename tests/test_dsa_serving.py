"""Latent attention over the keys a learned indexer picks (``indexer_types``:
models/transformer.py, models/cache_kinds.py, ops/paged_attention.py), the picks
of a layer that holds an indexer shared by the layers after it, and the index
keys cached beside the latent rows, against the plain reference the benchmark
keeps (benchmark/reference/glm_moe_dsa.py: float32, an explicit mask from an
exact top-k, no import from the program).  CPU, a tiny size whose
``index_topk`` is SMALLER than the tests' contexts, seeded weights."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from determined_tpu.models import cache_kinds, moe
from determined_tpu.models.cache_kinds import PAGED_INDEXED, PAGED_LATENT, cache_kinds as kinds_of, layers_by_kind
from determined_tpu.models.serving import (
    _check_decodable, init_kv_cache, serve_counters, transformer_decode, transformer_prefill, transformer_prefill_chunked,
)
from determined_tpu.models.transformer import LatentAttention, TransformerConfig, TransformerLM, kv_bytes_per_token
from tests.model_cases import reference_module

reference = reference_module("glm_moe_dsa")

TOPK, TOP_K, EXPERTS, SCALING = 8, 4, 16, 2.5
NUMERICS = dict(eps=1e-5, rope_theta=8e6, nope=16, rope_dim=8, latent=32, index_topk=TOPK, top_k=TOP_K, scaling=SCALING)
INDEX_LEAVES = {"index_wq_b": (24, 2, 16), "index_wk": (64, 16), "index_k_norm": (16,), "index_k_bias": (16,), "index_w": (64, 2)}


def tiny(**kw) -> TransformerConfig:
    """4 layers, the first dense; an indexer of 2 heads of 16 (rotary on the
    first 8) in layers 0 and 2, whose 8 picks layers 1 and 3 share; 4 heads of
    [16 | 8] with values of 24; 16 experts in one group, top-4, experts 4..7 held."""
    base = dict(
        vocab_size=96, d_model=64, n_layers=4, n_heads=4, d_ff=96, max_seq_len=64, dtype=jnp.float32, norm_eps=1e-5,
        rope_theta=8e6, attention_impl="reference", partition_params=False,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
        indexer_types=("full", "shared", "full", "shared"), index_n_heads=2, index_head_dim=16, index_topk=TOPK,
        dense_prefix=1, moe_experts=EXPERTS, moe_every=1, moe_top_k=TOP_K, moe_intermediate_size=32, moe_experts_held=(4, 4),
        moe_router="sigmoid_grouped", moe_n_group=1, moe_topk_group=1, moe_routed_scaling=SCALING, moe_shared_experts=1,
    )
    return TransformerConfig(**{**base, **kw})


def build(cfg, seed=1):
    """The program's own initialiser; a key's bias that is not zero, so that leaving it out would show."""
    params = meta.unbox(jax.jit(TransformerLM(cfg).init)(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    for i, block in enumerate(params.values()):
        if "attn" in block and "index_k_bias" in block["attn"]:
            block["attn"]["index_k_bias"] = 0.3 * jax.random.normal(jax.random.key(100 + i), block["attn"]["index_k_bias"].shape)
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
    forward = jax.jit(functools.partial(reference.forward_and_masks, first_expert=4, **NUMERICS))
    out = [forward(reference_weights(params, cfg), jnp.asarray(row)) for row in tokens]
    want = np.stack([np.asarray(logits) for logits, _ in out])
    masks = np.stack([np.stack([np.asarray(m) for m in ms]) for _, ms in out])  # [rows, layers, S, S]
    return cfg, params, tokens, want, masks


def as_mask(picks, valid, keys):
    """The program's picks ``[b, s, k]`` as the reference states a selection: a mask ``[b, s, keys]``."""
    picks, valid = np.asarray(picks), np.asarray(valid)
    mask = np.zeros((*picks.shape[:2], keys), bool)
    for b, s, k in zip(*np.nonzero(valid)):
        mask[b, s, picks[b, s, k]] = True
    return mask


# ---------------------------------------------------------------------------
# the whole model against the reference, and its picks one by one
# ---------------------------------------------------------------------------


def test_the_full_forward_selects_as_the_reference_does_pick_for_pick(model):
    cfg, params, tokens, want, masks = model
    assert cfg.index_layers == (0, 2) and [cfg.index_layer(i) for i in range(4)] == [0, None, 1, None]
    for i in range(4):  # a layer that shares picks holds no indexer leaves
        extra = {k: v.shape for k, v in params[f"block_{i}"]["attn"].items() if k.startswith("index_")}
        assert extra == (INDEX_LEAVES if i in (0, 2) else {})
    assert params["block_0"]["attn"]["wkv_b"].shape == (32, 4, 16 + 24) and params["block_0"]["attn"]["wo"].shape == (4, 24, 64)
    got, state = jax.jit(functools.partial(
        TransformerLM(cfg).apply, capture_intermediates=lambda m, _: isinstance(m, LatentAttention)
    ))({"params": params}, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    # the contexts pass index_topk: the masks leave keys out, and a model that attended over every key differs
    assert masks[:, :, -1].sum(-1).tolist() == [[TOPK] * 4] * 2 and masks[0, 0, 3].sum() == 4
    every = jax.jit(TransformerLM(dataclasses.replace(cfg, index_topk=64)).apply)({"params": params}, jnp.asarray(tokens))
    assert np.abs(np.asarray(every) - want).max() > 0.05
    # each layer's picks are the reference's, one by one; a shared layer's are its full layer's
    picked = [np.asarray(state["intermediates"][f"block_{i}"]["attn"]["__call__"][0][1]) for i in range(4)]
    for i, mask in enumerate(picked):
        np.testing.assert_array_equal(mask, masks[:, i])
    for shared, full in ((1, 0), (3, 2)):
        np.testing.assert_array_equal(picked[shared], picked[full])
    assert not np.array_equal(masks[:, 0], masks[:, 2])
    # up to index_topk tokens nothing is scored and nothing is left out
    short, state = jax.jit(functools.partial(
        TransformerLM(cfg).apply, capture_intermediates=lambda m, _: isinstance(m, LatentAttention)
    ))({"params": params}, jnp.asarray(tokens[:, :TOPK]))
    assert state["intermediates"]["block_0"]["attn"]["__call__"][0][1] is None
    np.testing.assert_allclose(np.asarray(short), want[:, :TOPK], atol=2e-4)


@pytest.fixture()
def recorded(monkeypatch):
    """Every selection of the serving forward, in order, as the mask ``[b, s, 40]`` it makes: a decode step's
    ``index_topk`` (positions) and the walk's ``index_topk_mask`` alike, as the program made them."""
    seen = []

    def recording(name, to_mask):
        plain = getattr(cache_kinds, name)

        def select(scores, mask, topk):
            out = plain(scores, mask, topk)
            jax.debug.callback(lambda *a: seen.append(to_mask(*a)), *(out if isinstance(out, tuple) else (out,)), ordered=True)
            return out

        monkeypatch.setattr(cache_kinds, name, select)

    recording("index_topk", lambda picks, valid: as_mask(picks, valid, 40))
    recording("index_topk_mask", lambda mask: np.asarray(mask))
    return seen


@pytest.mark.parametrize("form", ["table", "jnp"])
def test_the_walk_and_decode_steps_in_lanes_of_unequal_length_match_the_reference(model, recorded, form):
    """A prompt of 24 tokens (past ``index_topk``) beside one of 5 (under it)
    through the walk, then 14 decode steps in both lanes: the short lane passes
    ``index_topk`` on its way, an idle lane between them leaves the cache alone,
    and every full layer's picks are the reference's, one by one."""
    cfg, params, tokens, want, masks = model
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [0] * 10, [11, 12, 13, 14, 15, 16, 17, 18, 19, 20]], jnp.int32)
    lens = np.array([24, 0, 5])
    feed = np.zeros((3, 24), np.int32)
    feed[0], feed[2, :5] = tokens[0, :24], tokens[1, :5]
    walk = jax.jit(functools.partial(transformer_prefill_chunked, cfg))
    logits, cache = walk(params, jnp.asarray(feed), jnp.zeros(3, jnp.int32), jnp.asarray(lens), tables, init_kv_cache(cfg, 24, 4))
    np.testing.assert_allclose(np.asarray(logits[0]), want[0, 23], atol=3e-4)
    np.testing.assert_allclose(np.asarray(logits[2]), want[1, 4], atol=3e-4)
    assert len(recorded) == 2  # the two layers that hold an indexer, once each
    for picked, layer in zip(recorded, (0, 2)):
        np.testing.assert_array_equal(picked[0, :24], masks[0, layer, :24])
        np.testing.assert_array_equal(picked[2, :5], masks[1, layer, :5])
    del recorded[:]
    decode = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=0 if form == "table" else 1, counters=True))
    row = tokens[[0, 0, 1]]
    for _ in range(14):
        pos = np.where(lens > 0, lens, -1)
        before = {k: np.asarray(v) for k, v in cache.items()}
        out, cache = decode(params, jnp.asarray(row[np.arange(3), np.maximum(pos, 0)]), jnp.asarray(pos, jnp.int32), tables, cache)
        np.testing.assert_allclose(np.asarray(out[0]), want[0, pos[0]], atol=3e-4)
        np.testing.assert_allclose(np.asarray(out[2]), want[1, pos[2]], atol=3e-4)
        picked0, picked2 = recorded[-2:]
        for lane, seq in ((0, 0), (2, 1)):
            np.testing.assert_array_equal(picked0[lane, 0], masks[seq, 0, pos[lane]])
            np.testing.assert_array_equal(picked2[lane, 0], masks[seq, 2, pos[lane]])
        assert not picked0[1].any()  # the idle lane picks nothing, and what it writes goes to the scratch block
        for leaf in ("kv", "ik"):
            np.testing.assert_array_equal(np.asarray(cache[leaf])[:, 1:].reshape(-1)[: 0], before[leaf][:, 1:].reshape(-1)[: 0])
            changed = np.nonzero(np.any(np.asarray(cache[leaf]) != before[leaf], axis=(0, 2, 3)))[0]
            assert set(changed) <= {0, int(tables[0, pos[0] // 4]), int(tables[2, pos[2] // 4])}
        # the step's counters: what the indexers scored, what attention read, what it would have read without them
        live = float(pos[0] + 1 + pos[2] + 1)
        np.testing.assert_allclose(np.asarray(out[3, :3]), [2 * live, 4 * (min(pos[0] + 1, TOPK) + min(pos[2] + 1, TOPK)), 4 * live])
        lens = lens + (lens > 0)
    assert lens.tolist() == [38, 0, 19]


def test_the_wide_prefill_fills_both_arrays_as_the_walk_does(model):
    cfg, params, tokens, want, _ = model
    tables = jnp.asarray([[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]], jnp.int32)
    lens = jnp.asarray([40, 33], jnp.int32)
    wide, cache_w = jax.jit(functools.partial(transformer_prefill, cfg))(params, jnp.asarray(tokens), lens, tables, init_kv_cache(cfg, 12, 8))
    np.testing.assert_allclose(np.asarray(wide[0]), want[0], atol=3e-4)
    np.testing.assert_allclose(np.asarray(wide[1, :33]), want[1, :33], atol=3e-4)
    _, cache_c = jax.jit(functools.partial(transformer_prefill_chunked, cfg))(
        params, jnp.asarray(tokens), jnp.zeros(2, jnp.int32), lens, tables, init_kv_cache(cfg, 12, 8))
    for leaf in ("kv", "ik"):  # the scratch block aside
        np.testing.assert_allclose(np.asarray(cache_w[leaf])[:, 1:], np.asarray(cache_c[leaf])[:, 1:], atol=2e-5)
    assert np.abs(np.asarray(cache_c["ik"])[:, 1:6]).min() > 0 and not np.asarray(cache_c["ik"])[:, 11].any()


def test_suffix_prefill_from_shared_blocks_serves_index_keys_as_it_serves_latent_rows(model):
    """A prefix-cache hit: the second request's table names the first's full
    blocks, whose latent rows AND index keys it reads as they lie; only the
    rest is computed, to the same logits as a cold start, bit for bit."""
    cfg, params, tokens, want, _ = model
    walk = jax.jit(functools.partial(transformer_prefill_chunked, cfg))
    first = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)
    cold, cache = walk(params, jnp.asarray(tokens[:1]), jnp.zeros(1, jnp.int32), jnp.asarray([40], jnp.int32), first, init_kv_cache(cfg, 12, 8))
    np.testing.assert_allclose(np.asarray(cold[0]), want[0, 39], atol=3e-4)
    shared = jnp.asarray([[1, 2, 3, 9, 10]], jnp.int32)  # 24 tokens in the first request's blocks, the rest in its own
    warm, cache = walk(params, jnp.asarray(tokens[:1]), jnp.asarray([24], jnp.int32), jnp.asarray([37], jnp.int32), shared, cache)
    np.testing.assert_allclose(np.asarray(warm[0]), want[0, 36], atol=3e-4)
    again, _ = walk(params, jnp.asarray(tokens[:1]), jnp.zeros(1, jnp.int32), jnp.asarray([37], jnp.int32),
                    jnp.asarray([[6, 7, 8, 9, 10]], jnp.int32), init_kv_cache(cfg, 12, 8))
    np.testing.assert_array_equal(np.asarray(warm), np.asarray(again))
    np.testing.assert_array_equal(np.asarray(cache["ik"])[:, 9, :5], np.asarray(cache["ik"])[:, 4, :5])  # positions 24..28, written twice alike


@pytest.fixture(scope="module")
def long_model():
    cfg = tiny(max_seq_len=1152)
    params = build(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(4), (1, 1152), 1, cfg.vocab_size), np.int32)
    full = np.asarray(jax.jit(TransformerLM(cfg).apply)({"params": params}, jnp.asarray(tokens)))[0]
    return cfg, params, tokens, full


@pytest.mark.parametrize("block, width, n", [(8, 768, 256), (8, 768, 2 * 256 + 17), (48, 1152, 2 * 384 + 5), (4, 40, 37)])
def test_the_prefill_walk_selects_a_chunk_at_a_time_across_chunk_edges(long_model, block, width, n):
    """Chunks of 256, of 384 (blocks of 48) and one of 40 tokens: a chunk's
    queries score every index key so far, the shared layers read the same
    ``[chunk, keys]`` selection, and a warm start inside a chunk is the cold
    run bit for bit."""
    cfg, params, tokens, full = long_model
    padded = tokens[:, :width].copy()
    padded[0, n:] = 0
    tables = jnp.arange(1, width // block + 1, dtype=jnp.int32)[None, :]
    walk = jax.jit(functools.partial(transformer_prefill_chunked, cfg))
    fresh = lambda: init_kv_cache(cfg, width // block + 1, block)  # noqa: E731
    cold, cache = walk(params, jnp.asarray(padded), jnp.zeros(1, jnp.int32), jnp.asarray([n], jnp.int32), tables, fresh())
    np.testing.assert_allclose(np.asarray(cold[0]), full[n - 1], atol=3e-4)
    start = (n // 2) // block * block
    warm, _ = walk(params, jnp.asarray(padded), jnp.asarray([start], jnp.int32), jnp.asarray([n], jnp.int32), tables, cache)
    np.testing.assert_array_equal(np.asarray(warm), np.asarray(cold))


# ---------------------------------------------------------------------------
# the cache's second array, and what is refused by name
# ---------------------------------------------------------------------------


def test_the_cache_holds_index_keys_beside_the_latent_rows_and_says_so():
    import types

    cfg = tiny()
    sizes = types.SimpleNamespace(num_blocks=24, block_size=4, max_batch=3, prefill_chunk=24)
    assert kinds_of(cfg) == (PAGED_INDEXED,) and PAGED_INDEXED.holds == "blocks" and PAGED_INDEXED.no_prefix_cache is None
    assert PAGED_INDEXED.layers(cfg) == (0, 1, 2, 3) and PAGED_LATENT.layers(cfg) == ()
    assert PAGED_INDEXED.layers(dataclasses.replace(cfg, indexer_types=None, index_n_heads=0, index_head_dim=0, index_topk=0)) == ()
    assert layers_by_kind(cfg) == {"paged_indexed": 4, "none": 0}
    cache = init_kv_cache(cfg, 24, 4, 3, 24)
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {"kv": ((4, 24, 4, 128), jnp.float32), "ik": ((2, 24, 4, 16), jnp.float32)}
    assert PAGED_INDEXED.shapes(cfg, sizes) == ((4, 24, 4, 128), (2, 24, 4, 16))
    assert kv_bytes_per_token(cfg) == 4 * 40 * 4  # the latent rows, as attention reads them; the index keys are said beside
    index = {"layers": 2, "bytes_per_token": 2 * 16 * 4, "bytes": 2 * 24 * 4 * 16 * 4}
    said = PAGED_INDEXED.report(cfg, sizes, 0)
    assert said["index_keys"] == {**index, "index_topk": TOPK} and said["tile_copies"] == "live_blocks"
    assert PAGED_INDEXED.setup(cfg, sizes) == {"index_layers": 2, "index_bytes_per_token": 128, "index_bytes": index["bytes"],
                                               "index_topk": TOPK, "tile_copies": "live_blocks", "lane_prefetch": True}
    assert PAGED_INDEXED.walked(cfg) == (2, None) and PAGED_LATENT.report(cfg, sizes, 0) == {}  # the index kernel walks two rows
    assert serve_counters(cfg)[:3] == ("serve.dsa.index_tokens", "serve.dsa.selected_tokens", "serve.dsa.live_tokens")


def test_what_an_indexer_cannot_run_with_is_refused_by_name():
    _check_decodable(tiny())
    gqa = dict(q_lora_rank=None, kv_lora_rank=None, qk_nope_head_dim=None, qk_rope_head_dim=None, v_head_dim=None)
    for kw, named in [
        (gqa, "without latent attention"), (dict(shortcut_block=True, dense_prefix=0), "under shortcut_block"),
        (dict(moe_router="mlp", router_hidden_size=8, moe_n_group=1), "with moe_router mlp"),
        (dict(expert_axis_name="expert"), "inside pipeline stages"),
        (dict(indexer_types=("shared", "full", "full", "full")), "the first `full`"),
        (dict(indexer_types=("full", "full")), "for each of the 4 layers"), (dict(indexer_types=("full", "some", "full", "full")), "`full` or `shared`"),
        (dict(index_topk=0), "index_topk >= 1"), (dict(index_head_dim=4), "index_head_dim >= qk_rope_head_dim"),
        (dict(indexer_types=None), "belong to indexer_types"),
    ]:
        with pytest.raises(ValueError, match=named):
            tiny(**kw)
    for kw in (dict(parallel_block=True), dict(mixer_block=True, layer_types=("full_attention",) * 4)):
        with pytest.raises(ValueError):  # latent attention's own refusals come first
            tiny(**kw)


# ---------------------------------------------------------------------------
# the share test: attention, the indexer, the shared expert and every residual once
# ---------------------------------------------------------------------------


def test_the_shares_of_the_expert_layers_add_up_to_the_uncut_model():
    """Four chips hold 4 of 16 experts each (sixteen hold 16 of 256 at the
    published widths).  Layer by layer on the uncut model's own stream: the
    routed parts the four give, with attention over the indexer's picks, the
    shared expert and the residuals counted once, are the uncut reference's
    output."""
    cfg = tiny(moe_experts_held=None)
    params = build(cfg, seed=5)
    weights = reference_weights(params, cfg)
    tokens = jnp.asarray(np.random.default_rng(7).integers(1, 96, size=24), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.forward_and_masks(weights, tokens, first_expert=0, **NUMERICS)
        inv = (8e6 ** (-2.0 * np.arange(4, dtype=np.float64) / 8)).astype(np.float32)
        x, mask, picks_seen = weights["embed"][tokens], None, 0.0
        for i, layer in enumerate(weights["layers"]):
            att, mask = reference.attention(reference.dsv3._rms_norm(x, layer["attn_norm"], 1e-5), layer, mask, eps=1e-5, nope=16,
                                            latent=32, inv=inv, index_topk=TOPK)
            x = x + att
            h = reference.dsv3._rms_norm(x, layer["mlp_norm"], 1e-5)
            if "router" not in layer:
                x = x + reference.dsv3.swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"])
                continue
            shared = reference.dsv3.swiglu(h, layer["shared_w_gate"], layer["shared_w_up"], layer["shared_w_down"])
            routed = jnp.zeros_like(h)
            for share in range(4):
                held = dataclasses.replace(cfg, moe_experts_held=(4 * share, 4))
                mine = {k: (v[4 * share: 4 * share + 4] if k in ("w_gate", "w_up", "w_down") else v) for k, v in params[f"block_{i}"]["moe"].items()}
                y, (picks, _) = moe.serve_routed_experts(held, mine, h[None])
                routed, picks_seen = routed + (y[0] - shared), picks_seen + float(picks)
            x = x + routed + shared
        logits = reference.dsv3._rms_norm(x, weights["final_norm"], 1e-5) @ weights["head"]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=2e-4)
    assert picks_seen == 3 * 24 * TOP_K  # every pick of the three expert layers landed on exactly one share
