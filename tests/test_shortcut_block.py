"""The shortcut block (LongCat-Flash's double layer: two latent-attention
sublayers and two dense MLPs round an expert branch that leaves the stream
after the first attention and joins it after the second MLP), the softmax
router with a selection bias and identity experts, and the scales on the
latents (models/transformer.py, models/moe.py, models/cache_kinds.py,
models/serving.py), against the plain reference the benchmark keeps
(benchmark/reference/longcat_scmoe.py: float32, keys and values expanded a
head, a loop over experts, no import from the program).  CPU, the tiny form
of tests/benchmark/tiny/longcat_scmoe.json, seeded weights."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from determined_tpu.models import moe
from determined_tpu.models.cache_kinds import PAGED_KV, PAGED_LATENT, layer_kinds
from determined_tpu.ops.paged_attention import COPY_SCHEDULE
from determined_tpu.models.serving import (
    SERVE_COUNTERS,
    ZERO_PICKS,
    init_kv_cache,
    serve_counters,
    transformer_decode,
    transformer_prefill,
    transformer_prefill_chunked,
)
from determined_tpu.models.transformer import TransformerConfig, TransformerLM, kv_bytes_per_token, kv_cache_shape
from tests.model_cases import reference_module

reference = reference_module("longcat_scmoe")

with open(os.path.join(os.path.dirname(__file__), "benchmark", "tiny", "longcat_scmoe.json")) as f:
    TINY = json.load(f)["config"]
REAL, ZERO, TOP_K, FIRST, HELD, SCALING = 8, 4, 3, 2, 4, 6.0
NUMERICS = dict(eps=1e-5, rope_theta=1e7, nope=16, latent=32, q_scale=(64 / 24) ** 0.5, kv_scale=2.0 ** 0.5,
                top_k=TOP_K, scaling=SCALING, real_experts=REAL)


def tiny(**kw) -> TransformerConfig:
    """2 double layers; 8 real + 4 identity experts, top-3, experts 2..5 held; 4
    heads of [16 | 8] against a latent row of [32 | 8]; both latents scaled."""
    base = dict(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=96, max_seq_len=64, dtype=jnp.float32, rope_theta=1e7,
        norm_eps=1e-5, attention_impl="reference", partition_params=False, shortcut_block=True,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        q_latent_scale=NUMERICS["q_scale"], kv_latent_scale=NUMERICS["kv_scale"],
        moe_experts=REAL, moe_zero_experts=ZERO, moe_every=1, moe_top_k=TOP_K, moe_intermediate_size=32,
        moe_experts_held=(FIRST, HELD), moe_router="softmax_bias", moe_routed_scaling=SCALING,
    )
    return TransformerConfig(**{**base, **kw})


def build(cfg, seed=1, bias_scale=2.0):
    """The program's own initialiser; the selection bias made large enough (0.04
    against softmax scores near 1 / 12) that it changes picks."""
    params = meta.unbox(jax.jit(TransformerLM(cfg).init)(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    for block in params.values():
        if "moe" in block:
            block["moe"]["router_bias"] = block["moe"]["router_bias"] * bias_scale
    return params


def reference_weights(params, cfg):
    layers = []
    for i in range(cfg.n_layers):
        b = params[f"block_{i}"]
        sub = [
            {"attn_norm": b["ln1" + t]["scale"], "ffn_norm": b["ln2" + t]["scale"], **b["attn" + t],
             **{k: b["mlp" + t][k]["kernel"] for k in ("w_gate", "w_up", "w_down")}}
            for t in ("", "_1")
        ]
        m = b["moe"]
        layers.append({"sub": sub, "router": m["router"], "router_bias": m["router_bias"],
                       "e_gate": m["w_gate"], "e_up": m["w_up"], "e_down": m["w_down"]})
    return {"embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"],
            "final_norm": params["ln_f"]["scale"], "layers": layers}


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = build(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(0), (2, 40), 1, cfg.vocab_size))
    forward = jax.jit(functools.partial(reference.forward, first_expert=FIRST, held=HELD, **NUMERICS))
    want = np.stack([np.asarray(forward(reference_weights(params, cfg), jnp.asarray(row))) for row in tokens])
    return cfg, params, tokens, want


# ---------------------------------------------------------------------------
# the whole model against the reference
# ---------------------------------------------------------------------------


def test_the_tiny_form_is_the_configuration_these_tests_build():
    assert (TINY["hidden_size"], TINY["num_layers"], TINY["n_routed_experts_published"], TINY["zero_expert_num"], TINY["moe_topk"]) == (64, 2, REAL, ZERO, TOP_K)
    assert (TINY["first_expert_held"], TINY["n_routed_experts"], TINY["routed_scaling_factor"], TINY["rms_norm_eps"]) == (FIRST, HELD, SCALING, 1e-5)


def test_the_full_forward_builds_the_double_layer_and_matches_the_reference(model):
    cfg, params, tokens, want = model
    assert all(cfg.use_moe(i) for i in range(2)) and cfg.attn_sublayers == 2 and cfg.paged_layers == 4
    assert set(params["block_0"]) == {"ln1", "attn", "ln2", "mlp", "moe", "ln1_1", "attn_1", "ln2_1", "mlp_1"}
    shapes = {k: v.shape for k, v in params["block_1"]["attn_1"].items()}
    assert shapes == {"wq_a": (64, 24), "q_norm": (24,), "wq_b": (24, 4, 24), "wkv_a": (64, 40), "kv_norm": (32,),
                      "wkv_b": (32, 4, 32), "wo": (4, 16, 64)}
    m = params["block_1"]["moe"]
    assert m["router"].shape == (64, REAL + ZERO) and m["router_bias"].shape == (REAL + ZERO,) and m["w_gate"].shape == (HELD, 64, 32)
    got = jax.jit(TransformerLM(cfg).apply)({"params": params}, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    # each of the form's parts is in the result: without the scales, or with the sublayers' weights swapped, it differs
    plain = dataclasses.replace(cfg, q_latent_scale=1.0, kv_latent_scale=1.0)
    assert np.abs(np.asarray(jax.jit(TransformerLM(plain).apply)({"params": params}, jnp.asarray(tokens))) - want).max() > 1e-2
    swapped = {**params, "block_0": {**params["block_0"], "mlp": params["block_0"]["mlp_1"], "mlp_1": params["block_0"]["mlp"]}}
    assert np.abs(np.asarray(jax.jit(TransformerLM(cfg).apply)({"params": swapped}, jnp.asarray(tokens))) - want).max() > 1e-2


@pytest.mark.parametrize("form", ["table", "paged"])
def test_prefill_then_decode_through_two_rows_a_block_match_the_reference(model, form):
    """The wide prefill expands keys and values a head; decode stays in the
    latent space, by the full-table gather or the paged walk over the pool;
    both sublayers of a block write and read their OWN row of the pool."""
    cfg, params, tokens, want = model
    cache = init_kv_cache(cfg, 24, 8)
    assert set(cache) == {"kv"} and cache["kv"].shape == kv_cache_shape(cfg, 24, 8) == (4, 24, 8, 128)
    assert kv_bytes_per_token(cfg) == 4 * 40 * 4
    tables = jnp.asarray([list(range(1, 9)), list(range(9, 17))], jnp.int32)
    lens = jnp.asarray([20, 24], jnp.int32)
    logits, cache = jax.jit(functools.partial(transformer_prefill, cfg))(params, jnp.asarray(tokens[:, :24]), lens, tables, cache)
    for b, n in enumerate((20, 24)):
        np.testing.assert_allclose(np.asarray(logits[b, :n]), want[b, :n], atol=2e-4)
    assert all(float(jnp.abs(cache["kv"][row, 1:17]).sum()) > 0 for row in range(4))   # four rows a token were written
    step = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1 if form == "paged" else 0, counters=True))
    pos = np.asarray([20, 24])
    for _ in range(6):
        out, cache = step(params, jnp.asarray(tokens[np.arange(2), pos]), jnp.asarray(pos, jnp.int32), tables, cache)
        for b in range(2):
            np.testing.assert_allclose(np.asarray(out[b]), want[b, pos[b]], atol=2e-4)
        held, hit, zero = (float(v) for v in out[2, :3])
        assert 0 <= hit <= held <= 2 * 2 * TOP_K and 0 <= zero <= 2 * 2 * TOP_K and held + zero <= 2 * 2 * TOP_K
        pos = pos + 1


def test_the_chunked_walk_cold_and_from_a_cached_prefix_matches_the_reference(model):
    """The engine's one prefill program: a cold prompt, and the suffix of one
    whose first blocks (both sublayers' rows of them) are already in the pool."""
    cfg, params, tokens, want = model
    walk = jax.jit(functools.partial(transformer_prefill_chunked, cfg))
    tables = jnp.asarray([list(range(1, 9)), list(range(9, 17))], jnp.int32)
    lens = jnp.asarray([29, 37], jnp.int32)
    padded = jnp.asarray(tokens[:, :40])
    cold, cache = walk(params, padded, jnp.zeros(2, jnp.int32), lens, tables, init_kv_cache(cfg, 24, 8))
    for b, n in enumerate((29, 37)):
        np.testing.assert_allclose(np.asarray(cold[b]), want[b, n - 1], atol=2e-4)
    # the same prompts again from their third block on: the first 16 tokens' rows are read from the pool
    warm, again = walk(params, padded, jnp.asarray([16, 16], jnp.int32), lens, tables, cache)
    np.testing.assert_array_equal(np.asarray(warm), np.asarray(cold))
    np.testing.assert_array_equal(np.asarray(again["kv"][:, 1:17]), np.asarray(cache["kv"][:, 1:17]))


# ---------------------------------------------------------------------------
# the router and the identity experts
# ---------------------------------------------------------------------------


def test_the_bias_picks_and_never_weighs_and_the_weights_are_not_renormalised():
    key = jax.random.key(3)
    logits = jax.random.normal(key, (50, REAL + ZERO)) * 2.0
    bias = jax.random.normal(jax.random.key(4), (REAL + ZERO,)) * 0.2
    weights, picks = moe.route_softmax_bias(logits, bias, top_k=TOP_K, scaling=SCALING)
    scores = np.asarray(jax.nn.softmax(logits, axis=-1))
    want_picks = np.argsort(-(scores + np.asarray(bias)[None]), axis=-1)[:, :TOP_K]
    assert (np.sort(np.asarray(picks), axis=-1) == np.sort(want_picks, axis=-1)).all()
    assert (np.sort(np.asarray(picks), axis=-1) != np.sort(np.argsort(-scores, axis=-1)[:, :TOP_K], axis=-1)).any()   # the bias changed picks
    np.testing.assert_allclose(np.asarray(weights), SCALING * np.take_along_axis(scores, np.asarray(picks), axis=1), rtol=1e-6)
    sums = np.asarray(weights).sum(-1)
    assert sums.max() < SCALING and np.ptp(sums) > 0.1                                  # no constant sum: not renormalised
    # and it is the reference's router, pick for pick
    x = jax.random.normal(jax.random.key(5), (50, 64))
    router = jax.random.normal(jax.random.key(6), (64, REAL + ZERO)) * 0.3
    got_w, got_p = moe._route({"router": router, "router_bias": bias}, x, kind="softmax_bias", top_k=TOP_K, n_group=1, topk_group=1, scaling=SCALING)
    with jax.default_matmul_precision("highest"):
        ref_p, ref_w = reference.route(x, router, bias, top_k=TOP_K, scaling=SCALING)
    assert (np.asarray(got_p) == np.asarray(ref_p)).all()
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(ref_w), rtol=1e-5)


def test_a_pick_on_an_identity_expert_adds_w_x_and_reaches_no_row_buffer(model):
    cfg, params, _, _ = model
    p = params["block_0"]["moe"]
    x = jax.random.normal(jax.random.key(7), (1, 30, 64))
    weights, picks = moe._route(p, x[0], kind="softmax_bias", top_k=TOP_K, n_group=1, topk_group=1, scaling=SCALING)
    picks, weights = np.asarray(picks), np.asarray(weights)
    assert (picks >= REAL).any() and ((picks >= FIRST) & (picks < FIRST + HELD)).any()
    # no row: the buffer's rows are owned by held picks alone, and a zero pick is never `held`
    rows = moe._sorted_rows(jnp.asarray(picks), FIRST, HELD, serving=True)
    assert not np.asarray(rows.pick_held)[picks >= REAL].any() and int(rows.load.sum()) == int(((picks >= FIRST) & (picks < FIRST + HELD)).sum())
    assert int(rows.row_live.sum()) == int(rows.load.sum())
    y, counted = jax.jit(functools.partial(moe.serve_routed_experts, cfg))(p, x, None)
    assert [int(c) for c in counted] == [int(rows.load.sum()), int((np.asarray(rows.load) > 0).sum()), int((picks >= REAL).sum())]
    # the identity part is exactly w x, and what is left is the reference's held experts
    added, zero = moe._identity_part(jnp.asarray(weights), jnp.asarray(picks), REAL, REAL + ZERO, x[0])
    np.testing.assert_array_equal(np.asarray(zero), picks >= REAL)
    np.testing.assert_allclose(np.asarray(added), np.where(picks >= REAL, weights, 0).sum(-1)[:, None] * np.asarray(x[0]), rtol=1e-6)
    w = {"router": p["router"], "router_bias": p["router_bias"], "e_gate": p["w_gate"], "e_up": p["w_up"], "e_down": p["w_down"]}
    nobody = {**w, **{k: w[k][:0] for k in ("e_gate", "e_up", "e_down")}}
    with jax.default_matmul_precision("highest"):
        told = dict(top_k=TOP_K, scaling=SCALING, real_experts=REAL)
        want = reference.experts(x[0], w, first_expert=FIRST, held=HELD, **told)
        want_identity = reference.experts(x[0], nobody, first_expert=0, held=0, **told)   # nobody's experts: the identity part alone
    np.testing.assert_allclose(np.asarray(added), np.asarray(want_identity), atol=2e-5)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), atol=2e-5)
    # idle lanes take no expert's rows and count no identity pick
    live = jnp.zeros((1, 30), bool).at[0, :10].set(True)
    _, counted = jax.jit(functools.partial(moe.serve_routed_experts, cfg))(p, x, live)
    assert int(counted[2]) == int((picks[:10] >= REAL).sum()) and int(counted[0]) == int(((picks[:10] >= FIRST) & (picks[:10] < FIRST + HELD)).sum())


def test_training_sees_the_identity_experts_and_their_gradient(model):
    cfg, params, tokens, _ = model

    def loss(p):
        return jnp.mean(TransformerLM(cfg).apply({"params": p}, jnp.asarray(tokens[:, :16])) ** 2)

    grads = jax.jit(jax.grad(loss))(params)
    g = np.asarray(grads["block_0"]["moe"]["router"])
    assert np.abs(g[:, REAL:]).max() > 0 and np.abs(g[:, FIRST: FIRST + HELD]).max() > 0   # the router learns from both kinds of pick
    assert np.abs(np.asarray(grads["block_0"]["moe"]["router_bias"])).max() == 0            # the bias picks: no gradient


# ---------------------------------------------------------------------------
# two rows a layer, and what is refused by name
# ---------------------------------------------------------------------------


def test_a_layer_owns_two_rows_of_one_kind_each_with_its_own_subtree():
    cfg = tiny()
    assert layer_kinds(cfg, 0) == ((PAGED_LATENT, 0, "attn"),) and layer_kinds(cfg, 0, 1) == ((PAGED_LATENT, 1, "attn_1"),)
    assert layer_kinds(cfg, 1) == ((PAGED_LATENT, 2, "attn"),) and layer_kinds(cfg, 1, 1) == ((PAGED_LATENT, 3, "attn_1"),)
    sizes = type("S", (), {"num_blocks": 24, "block_size": 8, "max_batch": 2, "prefill_chunk": 8})
    assert PAGED_LATENT.shapes(cfg, sizes) == ((4, 24, 8, 128),) and PAGED_LATENT.report(cfg, sizes, 0) == {"rows_per_token": 4, **COPY_SCHEDULE}
    assert PAGED_LATENT.setup(cfg, sizes) == {"rows_per_token": 4, **COPY_SCHEDULE} and PAGED_LATENT.walked(cfg) == (4, None)  # four calls of the kernel a step
    assert serve_counters(cfg) == SERVE_COUNTERS + (ZERO_PICKS,)
    # a sequential block says nothing new: one row a layer, no ``rows_per_token`` (the kernel's copy schedule alone)
    plain = tiny(shortcut_block=False)
    assert layer_kinds(plain, 1) == ((PAGED_LATENT, 1, "attn"),) and PAGED_LATENT.report(plain, sizes, 0) == COPY_SCHEDULE and plain.paged_layers == 2
    # the form over GQA: two K and two V rows a block
    gqa = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=48, shortcut_block=True,
                            moe_experts=4, moe_every=1, moe_top_k=2, moe_intermediate_size=16)
    assert kv_cache_shape(gqa, 8, 4) == (4, 8, 4, 16) and layer_kinds(gqa, 1, 1) == ((PAGED_KV, 3, "attn_1"),)
    assert PAGED_KV.report(gqa, sizes, 0)["rows_per_token"] == 4


@pytest.mark.parametrize("kw, match", [
    (dict(moe_every=2), "EVERY block"),
    (dict(dense_prefix=1), "EVERY block"),
    (dict(parallel_block=True, kv_lora_rank=None, q_lora_rank=None, q_latent_scale=1.0, kv_latent_scale=1.0), "shortcut_block"),
    (dict(expert_axis_name="expert", moe_experts_held=None), "outside pipeline"),
    (dict(moe_router="softmax"), "moe_zero_experts"),
    (dict(moe_top_k=REAL + ZERO + 1), "moe_top_k"),
    (dict(kv_lora_rank=None, q_lora_rank=None), "belong to latent attention"),
])
def test_what_the_form_cannot_run_beside_is_refused_by_name(kw, match):
    with pytest.raises(ValueError, match=match):
        tiny(**kw)


def test_the_trial_maps_the_hparams_and_refuses_pipeline_stages(tmp_path):
    from determined_tpu import core, train
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig

    hparams = dict(
        lr=1e-3, global_batch_size=8, dataset_size=32, bf16=False, attention="reference", fused_ce=False, fused_adamw=False,
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=96, seq_len=32, shortcut_block=True, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, q_latent_scale=2.0, kv_latent_scale=1.5,
        moe_experts=REAL, moe_zero_experts=ZERO, moe_every=1, moe_top_k=TOP_K, moe_intermediate_size=32,
        moe_experts_held=[FIRST, HELD], moe_router="softmax_bias", moe_routed_scaling=SCALING, norm_eps=1e-5,
    )

    def trial(name, **mesh):
        ctx = train.init(hparams=hparams, mesh_config=MeshConfig(**mesh) if mesh else None,
                         core_context=core._dummy_init(checkpoint_dir=str(tmp_path / name)), seed=7)
        return LMTrial(ctx)

    one = trial("one", data=1)
    cfg = one._cfg()
    assert (cfg.shortcut_block, cfg.moe_zero_experts, cfg.q_latent_scale, cfg.kv_latent_scale, cfg.moe_router) == (True, ZERO, 2.0, 1.5, "softmax_bias")
    # a token multiplies with two attention sublayers, two dense MLPs, the router's 12 outputs and 3 x 4 / 12 = 1 expert a layer
    attn = 64 * (24 + 32 + 8) + 4 * (24 * 24 + 32 * 32 + 16 * 64)
    per_layer = 2 * attn + 2 * 3 * 64 * 96 + 64 * (REAL + ZERO) + 1.0 * 3 * 64 * 32
    assert one.flops_per_token == pytest.approx(6 * (256 * 64 + 2 * per_layer) + 12 * (2 * 2 * 32) * (4 * (24 + 16) // 2))
    with pytest.raises(ValueError, match=r"pipe=2: shortcut_block \(its expert branch beside it\) not run inside pipeline stages"):
        trial("two", pipe=2, data=4)._cfg()
