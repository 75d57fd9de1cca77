"""The window in the decode attention (ops/paged_attention.py: the Pallas kernel
in interpret mode and its ``jax.numpy`` form against a plain softmax over the
window), the chunk attention under a window, the plain sigmoid router and the
averaged shared experts against the reference's (benchmark/reference/
cohere2_moe.py), what the engine says of its decode kernel's products, and
the same block under ``LMTrial``.  Cut from tests/test_window_serving.py,
which keeps the model over its cache of two kinds and the engine: nothing
here reads that file's model.  CPU, tiny sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from determined_tpu.models import moe
from determined_tpu.models.transformer import FULL, SLIDING, TransformerConfig, TransformerLM
from determined_tpu.ops import paged_attention as paged
from tests.model_cases import PAGED_EDGES, check_copy_schedule, reference_module

reference = reference_module("cohere2_moe")

BLOCK = 4


# ---------------------------------------------------------------------------
# the window in the decode attention: kernel against oracle
# ---------------------------------------------------------------------------


def _ring_case(dtype, lanes=3, window=40, ring_blocks=8, block=16, kv_heads=2, n_rep=4, head_dim=128, seed=0, contexts=None):
    """Lanes whose rings hold their newest tokens by position; contexts inside
    the window, past it, past the ring's end, and an idle lane."""
    rng = np.random.default_rng(seed)
    ring = ring_blocks * block
    contexts = [17, 90, 5 * ring + 3][:lanes] if contexts is None else list(contexts)
    lanes = len(contexts)
    store = rng.standard_normal((2, lanes * ring_blocks, block, kv_heads * head_dim)).astype(np.float32)
    k_pool, v_pool = jnp.asarray(store, dtype), jnp.asarray(rng.standard_normal(store.shape).astype(np.float32), dtype)
    q = jnp.asarray(rng.standard_normal((lanes, kv_heads * n_rep, head_dim)).astype(np.float32), dtype)
    tables = jnp.asarray(np.arange(lanes)[:, None] * ring_blocks + np.arange(ring_blocks)[None, :], jnp.int32)
    positions = jnp.asarray([c - 1 for c in contexts], jnp.int32)
    return q, k_pool, v_pool, tables, positions, window, ring


def _plain_window_attention(q, k_pool, v_pool, layer, tables, positions, window, scale):
    """Softmax over the positions ``pos - window < j <= pos``, each looked up in its slot."""
    out = []
    block = k_pool.shape[2]
    for b, pos in enumerate(np.asarray(positions)):
        if pos < 0:
            out.append(np.zeros(q.shape[1:], np.float32))
            continue
        js = np.arange(max(0, pos - window + 1), pos + 1)
        ring = tables.shape[1] * block
        blk, slot = np.asarray(tables)[b, (js % ring) // block], js % block
        keys = np.asarray(k_pool[layer], np.float32)[blk, slot].reshape(len(js), -1, q.shape[-1])
        vals = np.asarray(v_pool[layer], np.float32)[blk, slot].reshape(len(js), -1, q.shape[-1])
        n_rep = q.shape[1] // keys.shape[1]
        qb = np.asarray(q[b], np.float32).reshape(keys.shape[1], n_rep, -1)
        s = np.einsum("grd,jgd->grj", qb, keys) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out.append(np.einsum("grj,jgd->grd", p / p.sum(-1, keepdims=True), vals).reshape(q.shape[1:]))
    return np.stack(out)


@pytest.mark.parametrize(
    "dtype, tile_blocks, n_rep",
    [(jnp.float32, 2, 4), (jnp.float32, 3, 4), (jnp.bfloat16, 2, 4), (jnp.bfloat16, 3, 16)],
    ids=["f32-tile2", "f32-tile3", "bf16-tile2", "bf16-tile3-n_rep16"],
)
@pytest.mark.parametrize("impl", ["jnp", "kernel_interpret"])
def test_the_window_kernel_and_its_jnp_form_read_the_window_and_nothing_older(dtype, tile_blocks, n_rep, impl):
    """At 4 query heads a KV head the kernel's block-diagonal products, at 16 a KV head at a time."""
    q, k_pool, v_pool, tables, positions, window, _ = _ring_case(dtype, n_rep=n_rep)
    assert paged.attn_products(n_rep) == ("per_kv_head" if n_rep == 16 else "block_diagonal")
    positions = positions.at[1].set(-1) if tile_blocks == 3 else positions           # an idle lane: zeros
    want = _plain_window_attention(q, k_pool, v_pool, 1, tables, positions, window, 0.09)
    got = paged.paged_decode_attention(q, k_pool, v_pool, 1, tables, positions, scale=0.09, window=window,
                                       tile_blocks=tile_blocks, impl=impl)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), want, atol=tol, rtol=tol)
    # what lies in the ring outside the window does not matter: poison it
    ring = tables.shape[1] * k_pool.shape[2]
    poisoned_k, poisoned_v = np.asarray(k_pool, np.float32).copy(), np.asarray(v_pool, np.float32).copy()
    for b, pos in enumerate(np.asarray(positions)):
        seen = {int(j % ring) for j in range(max(0, pos - window + 1), pos + 1)} if pos >= 0 else set()
        for s in set(range(ring)) - seen:
            poisoned_k[1, np.asarray(tables)[b, s // 16], s % 16] = 1e4
            poisoned_v[1, np.asarray(tables)[b, s // 16], s % 16] = -1e4
    again = paged.paged_decode_attention(q, jnp.asarray(poisoned_k, dtype), jnp.asarray(poisoned_v, dtype), 1, tables, positions,
                                         scale=0.09, window=window, tile_blocks=tile_blocks, impl=impl)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


#: beside ``PAGED_EDGES`` (a window of 40 over rings of 128 tokens: a lane of 70 sees from position 30, inside a
#: block): rings that have wrapped, the oldest token seen inside a block, the newest at a block's and a tile's edge
RING_EDGES = {**PAGED_EDGES, "wrapped_rings_that_start_inside_a_block": (5 * 128 + 3, 128 + 16, 300, 2 * 128)}


@pytest.mark.parametrize("dtype, n_rep", [(jnp.float32, 4), (jnp.bfloat16, 16)], ids=["f32-block_diagonal", "bf16-per_kv_head"])
@pytest.mark.parametrize("lanes", list(RING_EDGES))
def test_the_window_kernels_copy_schedule_at_a_lanes_edges(lanes, dtype, n_rep):
    """The window kernel over the lanes of ``RING_EDGES``, tiles of 2 blocks:
    against the plain window attention and the ``jax.numpy`` form, then the
    poison case (``check_copy_schedule``): of a ring only the blocks from the
    oldest position the query sees to the newest are copied, none older in the
    walk's first tile and none past the newest in its last."""
    contexts = RING_EDGES[lanes]
    q, k_pool, v_pool, tables, positions, window, _ = _ring_case(dtype, n_rep=n_rep, contexts=contexts)

    def run(k_pool, v_pool, impl="kernel_interpret"):
        return paged.paged_decode_attention(q, k_pool, v_pool, 1, tables, positions, scale=0.09, window=window, tile_blocks=2, impl=impl)

    got = check_copy_schedule(run, (k_pool, v_pool), 1, tables, contexts, 16, window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, _plain_window_attention(q, k_pool, v_pool, 1, tables, positions, window, 0.09), atol=tol, rtol=tol)
    np.testing.assert_allclose(got, np.asarray(run(k_pool, v_pool, "jnp")), atol=2e-6, rtol=2e-5)
    assert not got[[n == 0 for n in contexts]].any()


def test_a_window_wider_than_its_ring_is_refused():
    q, k_pool, v_pool, tables, positions, _, ring = _ring_case(jnp.float32)
    with pytest.raises(ValueError, match="ring of at least"):
        paged.paged_decode_attention(q, k_pool, v_pool, 0, tables, positions, scale=1.0, window=ring + 1)


def test_the_chunk_attention_under_a_window_matches_plain_attention():
    """A chunk's queries against the ring: their own chunk's keys and the
    store's, under one mask; the first tile of the walk holds keys a late
    query no longer sees."""
    rng = np.random.default_rng(3)
    block, s, window, g, r, d = 4, 16, 24, 2, 2, 8
    ring_blocks = -(-window // block) + s // block
    n = 5 * s
    k = rng.standard_normal((n, g * d)).astype(np.float32)
    v = rng.standard_normal((n, g * d)).astype(np.float32)
    q = rng.standard_normal((1, g, r, n, d)).astype(np.float32)
    pool_k = np.zeros((1, 2 * ring_blocks, block, g * d), np.float32)
    pool_v = np.zeros_like(pool_k)
    table = jnp.asarray(ring_blocks + np.arange(ring_blocks)[None, :], jnp.int32)     # lane 1's ring
    for c in range(n // s):
        for p in range(c * s, (c + 1) * s):                                            # the chunk's rows first, then its read
            slot = p % (ring_blocks * block)
            pool_k[0, ring_blocks + slot // block, slot % block] = k[p]
            pool_v[0, ring_blocks + slot // block, slot % block] = v[p]
        got = paged.paged_chunk_attention(jnp.asarray(q[:, :, :, c * s:(c + 1) * s]), jnp.asarray(pool_k), jnp.asarray(pool_v), 0,
                                          table, jnp.asarray(c), scale=0.3, window=window)
        for qi in range(s):
            pos = c * s + qi
            js = np.arange(max(0, pos - window + 1), pos + 1)
            sc = np.einsum("grd,jgd->grj", q[0, :, :, pos], k[js].reshape(-1, g, d)) * 0.3
            p_ = np.exp(sc - sc.max(-1, keepdims=True))
            want = np.einsum("grj,jgd->grd", p_ / p_.sum(-1, keepdims=True), v[js].reshape(-1, g, d))
            np.testing.assert_allclose(np.asarray(got)[0, :, :, qi], want, atol=2e-5)


# ---------------------------------------------------------------------------
# the router and the shared experts
# ---------------------------------------------------------------------------


def test_the_sigmoid_router_picks_and_weighs_as_the_reference_does():
    key = jax.random.key(5)
    x = jax.random.normal(key, (64, 48), jnp.float32)
    router = jax.random.normal(jax.random.key(6), (48, 32), jnp.float32) * 0.2
    weights, picks = moe._route({"router": router}, x, kind="sigmoid", top_k=8, n_group=1, topk_group=1, scaling=1.0)
    with jax.default_matmul_precision("highest"):
        ref_picks, ref_weights = reference.route(x, router, 8)
    np.testing.assert_array_equal(np.asarray(picks), np.asarray(ref_picks))
    np.testing.assert_allclose(np.asarray(weights), np.asarray(ref_weights), atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)          # norm_topk_prob
    # it is the grouped router at one group and no bias
    again, same = moe.route_sigmoid_grouped(x @ router, jnp.zeros(32), top_k=8, n_group=1, topk_group=1, scaling=1.0)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(picks))
    np.testing.assert_allclose(np.asarray(again), np.asarray(weights), atol=1e-6)


def test_the_shared_experts_are_averaged_not_summed():
    rng = np.random.default_rng(0)
    p = {"shared_w_gate": jnp.asarray(rng.standard_normal((16, 24)), jnp.float32),
         "shared_w_up": jnp.asarray(rng.standard_normal((16, 24)), jnp.float32),
         "shared_w_down": jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)}
    x = jnp.asarray(rng.standard_normal((5, 16)), jnp.float32)
    summed, mean = moe._shared_experts(p, x, 3, "sum"), moe._shared_experts(p, x, 3, "mean")
    np.testing.assert_allclose(np.asarray(mean), np.asarray(summed) / 3, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(reference.shared_part(x, p, 3)), atol=1e-5)
    each = [reference.swiglu(x, p["shared_w_gate"][:, 8 * j:8 * j + 8], p["shared_w_up"][:, 8 * j:8 * j + 8],
                             p["shared_w_down"][8 * j:8 * j + 8]) for j in range(3)]
    np.testing.assert_allclose(np.asarray(mean), np.asarray(sum(each)) / 3, atol=1e-5)

# ---------------------------------------------------------------------------
# what the engine says of its decode kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "heads, want",
    [(dict(n_heads=16, n_kv_heads=1), "per_kv_head"), (dict(n_heads=4, n_kv_heads=2), "block_diagonal"),
     (dict(n_heads=2, q_lora_rank=8, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8), None)],
    ids=["16-a-kv-head", "2-a-kv-head", "latent"],
)
def test_the_engine_says_what_a_tile_of_its_decode_kernel_multiplies(heads, want):
    """``serve.setup.kv_pool`` and ``/stats`` name the layout of the GQA kernel's
    products, from the function the kernel's wrapper asks; a latent model has none.
    Both kinds name the copy schedule their kernels share (``COPY_SCHEDULE``)."""
    from determined_tpu.observability import get_tracer
    from determined_tpu.serve.config import ServeConfig
    from determined_tpu.serve.engine import DecodeKernels, ServeEngine

    cfg = TransformerConfig(vocab_size=32, d_model=32, n_layers=1, d_ff=32, max_seq_len=32, dtype=jnp.bfloat16,
                            attention_impl="reference", partition_params=False, **heads)
    params = meta.unbox(jax.jit(TransformerLM(cfg).init)(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    tracer = get_tracer()
    tracer.reset()
    tracer.configure(enabled=True)
    try:
        kernels = DecodeKernels(cfg, params, ServeConfig(block_size=BLOCK, num_blocks=8, max_batch=1, max_prompt_len=8,
                                                         max_new_tokens=4, queue_depth=2))
        (pool,) = [e for e in tracer.chrome_events() if e.get("ph") == "X" and e["name"] == "serve.setup.kv_pool"]
    finally:
        tracer.reset()
    stats = ServeEngine(kernels).stats()
    from determined_tpu.models.cache_kinds import PAGED_KV, PAGED_LATENT

    # the kind says it once, to the set-up span and to /stats
    assert kernels.kinds == ((PAGED_KV,) if want else (PAGED_LATENT,))
    schedule = {"tile_copies": "live_blocks", "lane_prefetch": True}
    assert paged.COPY_SCHEDULE == schedule
    assert PAGED_KV.report(cfg, None, 0) == ({"attn_products": want, **schedule} if want else {})
    assert PAGED_LATENT.report(cfg, None, 0) == ({} if want else schedule)
    assert pool["args"].get("attn_products") == want and stats.get("attn_products") == want
    assert {k: pool["args"][k] for k in schedule} == schedule == {k: stats[k] for k in schedule}
    assert stats["step_inputs"]["paged_live_tokens"] == stats["step_inputs"]["paged_copied_tokens"] == 0  # no step yet


# ---------------------------------------------------------------------------
# the same block under LMTrial
# ---------------------------------------------------------------------------


def test_lmtrial_builds_the_block_and_its_fused_loss_is_the_tied_heads(tmp_path):
    """``LMTrial`` takes the block's hparams, and the fused cross-entropy
    contracts the hidden state with the tied table times ``logit_scale``: the
    same loss as the logits path."""
    from determined_tpu import core, train
    from determined_tpu.models.transformer import LMTrial

    hparams = dict(
        lr=1e-3, global_batch_size=2, seq_len=32, dataset_size=8, vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, bf16=False, attention="reference", fused_adamw=False, layer_types=[SLIDING, FULL], sliding_window=8,
        rope_parameters={FULL: {"rope_type": "none"}}, norm="layernorm", norm_eps=1e-5, parallel_block=True, tie_embeddings=True,
        logit_scale=0.25, moe_experts=8, moe_every=1, moe_top_k=2, moe_intermediate_size=16, moe_experts_held=[0, 4],
        moe_router="sigmoid", moe_shared_experts=2, moe_shared_combine="mean",
    )
    losses = {}
    for fused in (False, True):
        ctx = train.init(
            hparams=dict(hparams, fused_ce=fused), core_context=core._dummy_init(checkpoint_dir=str(tmp_path / str(fused))), seed=3,
            devices=jax.devices()[:1],
        )
        trial = LMTrial(ctx)
        cfg = trial._cfg()
        assert (cfg.norm, cfg.parallel_block, cfg.tie_embeddings, cfg.logit_scale, cfg.moe_router, cfg.moe_shared_combine) == (
            "layernorm", True, True, 0.25, "sigmoid", "mean")
        model = trial.build_model()
        params = jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
        assert "lm_head" not in meta.unbox(params)["params"]
        batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 33), 1, 64)}
        losses[fused] = float(jax.jit(lambda p: trial.loss(model, p, batch, jax.random.key(2))[0])(params))
    assert losses[True] == pytest.approx(losses[False], rel=1e-5)
