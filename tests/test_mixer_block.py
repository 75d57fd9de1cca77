"""The block of ONE mixer a layer (Nemotron-H's: a Mamba-2 mixer, attention or
the experts alone under one norm and one residual), the two-matrix squared-ReLU
expert, the latent the routed experts work in and the state kernel at 64-wide
heads (models/transformer.py, models/moe.py, models/cache_kinds.py,
models/serving.py, ops/ssm.py, serve/engine.py), against the plain reference
the benchmark keeps (benchmark/reference/nemotron_h.py: float32, the scan as
its recurrence one token at a time, a loop over experts, no cache, no import
from the program).  CPU, tiny sizes, seeded weights; the Pallas kernels in
interpret mode."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from determined_tpu import core, train
from determined_tpu.models import cache_kinds, moe, serving
from determined_tpu.models.cache_kinds import BLOCKS, LANE, PAGED_KV, SSM_SLOT, layer_kinds, layers_by_kind
from determined_tpu.models.serving import (
    SERVE_COUNTERS,
    init_kv_cache,
    serve_counters,
    transformer_decode,
    transformer_prefill,
    transformer_prefill_chunked,
)
from determined_tpu.models.transformer import (
    EXPERTS,
    FULL,
    MAMBA2,
    LMTrial,
    TransformerConfig,
    TransformerLM,
    kv_bytes_per_token,
    kv_cache_shape,
    ssm_bytes_per_slot,
    ssm_pool_shapes,
)
from determined_tpu.ops import ssm
from determined_tpu.parallel.mesh import MeshConfig
from determined_tpu.serve.config import ServeConfig
from determined_tpu.serve.engine import DecodeKernels, ServeEngine
from tests.model_cases import reference_module
from tests.test_ssm_serving import _parts

reference = reference_module("nemotron_h")

#: the published pattern's period, as the cell cuts it: five M, five E, one *
PATTERN = "MEMEMEMEM*E"
LETTERS = {"M": MAMBA2, "*": FULL, "E": EXPERTS}
EVERY, TOP_K, FIRST, HELD, SCALING = 16, 4, 4, 8, 5.0


def tiny(pattern: str = "ME*ME", **kw) -> TransformerConfig:
    """A layer a letter: 8 Mamba-2 heads of 16 over 2 groups of 8 state values and a convolution over 4 tokens; 4
    query heads over 2 KV heads of 16, no rotary; top-4 of 16 sigmoid-routed two-matrix squared-ReLU experts of width
    24 in a 32-wide latent, experts 4..11 held, a shared expert of width 40, the weights scaled by 5."""
    base = dict(
        vocab_size=96, d_model=64, n_layers=len(pattern), n_heads=4, n_kv_heads=2, head_dim=16, max_seq_len=1024,
        dtype=jnp.float32, attention_impl="reference", partition_params=False, norm_eps=1e-5, mixer_block=True,
        layer_types=tuple(LETTERS[c] for c in pattern), rope_parameters={"full_attention": {"rope_type": "none"}},
        ssm_heads=8, ssm_head_dim=16, ssm_state=8, ssm_groups=2, ssm_conv=4, ssm_chunk=8,
        moe_experts=EVERY, moe_top_k=TOP_K, moe_intermediate_size=24, moe_experts_held=(FIRST, HELD),
        moe_router="sigmoid_grouped", moe_routed_scaling=SCALING, moe_shared_experts=1, moe_shared_intermediate_size=40,
        moe_expert_act="relu2", moe_latent_size=32,
    )
    return TransformerConfig(**{**base, **kw})


def build(cfg, seed=1):
    params = meta.unbox(jax.jit(TransformerLM(cfg).init)(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    # norms and the skip away from one, so that one the program skipped or ran twice shows; a selection bias large
    # enough (0.1 beside sigmoid scores that differ by ~0.1) to change some picks
    for i in range(cfg.n_layers):
        blk = params[f"block_{i}"]
        blk["ln1"]["scale"] = blk["ln1"]["scale"] * (1.0 + 0.1 * jax.random.normal(jax.random.key(100 + i), (cfg.d_model,)))
        if "ssm" in blk:
            for j, n in enumerate(("norm", "D")):
                blk["ssm"][n] = blk["ssm"][n] * (1.0 + 0.2 * jax.random.normal(jax.random.key(200 + 2 * i + j), blk["ssm"][n].shape))
        if "moe" in blk:
            blk["moe"]["router_bias"] = blk["moe"]["router_bias"] * 10.0
    return params


def reference_weights(params, cfg):
    layers = []
    for i in range(cfg.n_layers):
        b = params[f"block_{i}"]
        if "ssm" in b:
            mixer = {k: b["ssm"][k] for k in ("w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "w_out")}
            mixer["ssm_norm"] = b["ssm"]["norm"]
        elif "attn" in b:
            mixer = {k: b["attn"][k]["kernel"] for k in ("wq", "wk", "wv", "wo")}
        else:
            mixer = dict(b["moe"])
        layers.append({"norm": b["ln1"]["scale"], **mixer})
    return {"embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"], "final_norm": params["ln_f"]["scale"], "layers": layers}


def numerics(cfg, **kw):
    first = (cfg.moe_experts_held or (0, cfg.moe_experts))[0]
    said = dict(eps=cfg.norm_eps, heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim, d_state=cfg.ssm_state, groups=cfg.ssm_groups,
                conv=cfg.ssm_conv, top_k=cfg.moe_top_k, scaling=cfg.moe_routed_scaling, first_expert=first, query_block=64)
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


# ---------------------------------------------------------------------------
# the form: what a layer is, what it keeps, what is refused
# ---------------------------------------------------------------------------


def test_a_layer_is_one_mixer_and_the_tree_holds_nothing_else(model):
    cfg, params, _, _ = model
    assert [sorted(params[f"block_{i}"]) for i in range(5)] == [["ln1", "ssm"], ["ln1", "moe"], ["attn", "ln1"], ["ln1", "ssm"], ["ln1", "moe"]]
    experts = params["block_1"]["moe"]
    assert {k: v.shape for k, v in experts.items()} == {
        "router": (64, 16), "router_bias": (16,), "w_latent_in": (64, 32), "w_latent_out": (32, 64),
        "w_up": (8, 32, 24), "w_down": (8, 24, 32), "shared_w_up": (64, 40), "shared_w_down": (40, 64),   # two matrices: no gate
    }
    assert [cfg.use_moe(i) for i in range(5)] == [False, True, False, False, True] and cfg.ssm_layers == (0, 3)
    assert cfg.paged_layers == 1 and cfg.rowless_layers == (0, 1, 3, 4) and cfg.rope(FULL) is None


def test_a_layer_of_no_kind_and_the_caches_leaves_bytes_and_report():
    """The cell's pattern at tiny widths: five layers of ``ssm_slot`` alone, one of ``paged_kv`` alone, five of no kind."""
    cfg = tiny(PATTERN)
    assert cache_kinds.cache_kinds(cfg) == (PAGED_KV, SSM_SLOT) and (PAGED_KV.holds, SSM_SLOT.holds) == (BLOCKS, LANE)
    kinds = [layer_kinds(cfg, i) for i in range(11)]
    assert [k for k in kinds if k == ()] == [()] * 5 and kinds[9] == ((PAGED_KV, 0, "attn"),)           # the expert layers own no row
    assert [kinds[i] for i in (0, 2, 4, 6, 8)] == [((SSM_SLOT, j, "ssm"),) for j in range(5)]
    assert layers_by_kind(cfg) == {"paged_kv": 1, "ssm_slot": 5, "none": 5}
    assert SSM_SLOT.layer_types == ("attention_mamba2", MAMBA2) and PAGED_KV.layer_types == (FULL, "attention_mamba2")
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 40, 4, lanes=3, chunk_tokens=16))
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "k": ((1, 40, 4, 32), "float32"), "v": ((1, 40, 4, 32), "float32"),              # ONE layer's rows a token
        "ssm": ((5, 4, 8, 16, 8), "float32"), "conv": ((5, 3, 3, 160), "float32"),       # a slot a lane and one scratch; three rows a tail
    }
    assert kv_cache_shape(cfg, 40, 4) == (1, 40, 4, 32) and kv_bytes_per_token(cfg) == 1 * 2 * 2 * 16 * 4
    assert ssm_pool_shapes(cfg, 3) == ((5, 4, 8, 16, 8), (5, 3, 3, 160)) and ssm_bytes_per_slot(cfg) == 8 * 16 * 8 * 4
    assert serve_counters(cfg) == ("serve.ssm.live_lanes", "serve.ssm.bytes") + SERVE_COUNTERS
    sizes = ServeConfig(block_size=4, num_blocks=40, max_batch=3, prefix_cache=False, max_prompt_len=32, max_new_tokens=8)
    assert cache_kinds._ssm_report(cfg, sizes, 2) == {"ssm": {"slots": 3, "live": 2, "bytes_per_slot": 5 * 8 * 16 * 8 * 4},
                                                      "layers_by_kind": {"paged_kv": 1, "ssm_slot": 5, "none": 5}}
    assert cache_kinds.PAGED_LATENT.report(cfg, sizes, 0) == {} == cache_kinds.STATE_SLOT.report(cfg, sizes, 0)   # a kind without layers here
    assert cache_kinds._kv_report(cfg) == {"attn_products": "block_diagonal", "tile_copies": "live_blocks", "lane_prefetch": True,
                                           "layers_by_kind": {"paged_kv": 1, "ssm_slot": 5, "none": 5}}
    assert cache_kinds.PAGED_KV.walked(cfg) == (1, None) and cache_kinds.SSM_SLOT.walked is None  # ONE layer's rows are walked a step
    assert cache_kinds._ssm_setup(cfg, sizes) == {
        "ssm_slots": 3, "ssm_bytes_per_slot": 5 * 4096, "ssm_pool_bytes": 5 * 4 * 4096 + 5 * 3 * 3 * 160 * 4,
        "layers_by_kind": {"paged_kv": 1, "ssm_slot": 5, "none": 5},
    }
    # a model of the other block forms says nothing of the kind
    plain = TransformerConfig(vocab_size=96, d_model=64, n_layers=2, n_heads=4, max_seq_len=64)
    assert set(cache_kinds._kv_report(plain)) == {"attn_products", "tile_copies", "lane_prefetch"}


def test_the_engine_says_the_layers_by_kind_at_set_up_and_in_stats(model):
    from determined_tpu.observability import get_tracer

    cfg, params, _, _ = model
    tracer = get_tracer()
    tracer.reset()
    tracer.configure(enabled=True)
    try:
        engine = _engine(cfg, params)
        (pool,) = [e for e in tracer.chrome_events() if e.get("ph") == "X" and e["name"] == "serve.setup.kv_pool"]
    finally:
        tracer.reset()
    said = {"paged_kv": 1, "ssm_slot": 2, "none": 2}
    assert pool["args"]["layers_by_kind"] == said and pool["args"]["bytes_per_token"] == 2 * 2 * 16 * 4
    assert pool["args"]["ssm_bytes_per_slot"] == 2 * ssm_bytes_per_slot(cfg) and pool["args"]["ssm_slots"] == 3
    stats = engine.stats()
    assert stats["layers_by_kind"] == said and stats["ssm"]["bytes_per_slot"] == 2 * ssm_bytes_per_slot(cfg)


@pytest.mark.parametrize("what,kw", [
    ("parallel_block", dict(parallel_block=True)),
    ("shortcut_block", dict(shortcut_block=True, moe_every=1)),
    ("residual_scaling", dict(residual_scaling=True)),
    ("latent attention", dict(q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)),
    ("pipeline stages", dict(expert_axis_name="expert")),
    ("a layer type other than", dict(layer_types=(MAMBA2, EXPERTS, "sliding_attention", MAMBA2, EXPERTS), sliding_window=8)),
])
def test_the_form_refuses_by_name_what_it_cannot_run(what, kw):
    with pytest.raises(ValueError, match="mixer_block is one norm and ONE mixer a layer.*does not run with.*" + what):
        tiny(**kw)


def test_the_layer_types_and_the_experts_belong_to_the_form(tmp_path):
    with pytest.raises(ValueError, match="a mamba2 or an experts layer is a layer of ONE mixer: it belongs to mixer_block"):
        tiny(mixer_block=False)
    with pytest.raises(ValueError, match="the experts layers are those layer_types names `experts`"):
        tiny("M*M")                                                                      # experts stated, no layer holds them
    with pytest.raises(ValueError, match="the experts layers are those layer_types names `experts`"):
        tiny(moe_experts=0, moe_top_k=0, moe_experts_held=None, moe_intermediate_size=None, moe_router="softmax", moe_shared_experts=0,
             moe_shared_intermediate_size=None, moe_expert_act="swiglu", moe_latent_size=None, moe_routed_scaling=1.0)
    with pytest.raises(ValueError, match="an attention_mamba2 layer needs ssm_heads .* \\(a mamba2 layer likewise\\)"):
        tiny(ssm_heads=3)
    with pytest.raises(ValueError, match="moe_expert_act is swiglu or relu2"):
        tiny(moe_expert_act="gelu")
    with pytest.raises(ValueError, match="moe_shared_intermediate_size >= 1 to moe_shared_experts"):
        tiny(moe_shared_experts=0)
    attention_only = tiny("M*M", moe_experts=0, moe_top_k=0, moe_experts_held=None, moe_intermediate_size=None, moe_router="softmax",
                          moe_shared_experts=0, moe_shared_intermediate_size=None, moe_expert_act="swiglu", moe_latent_size=None,
                          moe_routed_scaling=1.0)
    assert not any(attention_only.use_moe(i) for i in range(3)) and layers_by_kind(attention_only) == {"paged_kv": 1, "ssm_slot": 2, "none": 0}
    # the trial hands the form on, counts what a token multiplies with, and refuses pipeline stages by name
    hparams = dict(
        vocab_size=96, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16, seq_len=32, mixer_block=True,
        layer_types=[FULL, EXPERTS], moe_experts=EVERY, moe_top_k=TOP_K, moe_intermediate_size=24, moe_experts_held=[FIRST, HELD],
        moe_router="sigmoid_grouped", moe_routed_scaling=SCALING, moe_shared_experts=1, moe_shared_intermediate_size=40,
        moe_expert_act="relu2", moe_latent_size=32,
    )

    def trial(name, **mesh):
        ctx = train.init(hparams=hparams, mesh_config=MeshConfig(**mesh) if mesh else None,
                         core_context=core._dummy_init(checkpoint_dir=str(tmp_path / name)), seed=7)
        return LMTrial(ctx)

    one = trial("one", data=1)
    cfg = one._cfg()
    assert (cfg.mixer_block, cfg.moe_expert_act, cfg.moe_latent_size, cfg.moe_shared_intermediate_size) == (True, "relu2", 32, 40)
    attn = 64 * 16 * (2 * 4 + 2 * 2)
    experts = 64 * 16 + (4 * 8 / 16) * 2 * 32 * 24 + 2 * 64 * 32 + 2 * 64 * 40       # router, 2 held picks at the latent width, W_a W_b, shared
    assert one.flops_per_token == pytest.approx(6 * (96 * 64 + attn + experts) + 12 * 32 * 4 * 16)
    with pytest.raises(ValueError, match=r"pipe=2: mixer_block \(its layers are not alike"):
        trial("two", pipe=2, data=4)._cfg()


# ---------------------------------------------------------------------------
# the router at one group, and the expert against a plain statement
# ---------------------------------------------------------------------------


def test_at_one_group_the_bias_picks_and_never_weighs_and_the_weights_sum_to_the_scaling(model):
    cfg, params, _, _ = model
    p = params["block_1"]["moe"]
    h = jax.random.normal(jax.random.key(3), (50, cfg.d_model), jnp.float32)
    weights, picks = jax.jit(lambda p, h: moe._route(p, h, kind="sigmoid_grouped", top_k=TOP_K, n_group=1, topk_group=1, scaling=SCALING))(p, h)
    ref_picks, ref_weights = jax.jit(functools.partial(reference.route, top_k=TOP_K, scaling=SCALING))(h, p["router"], p["router_bias"])
    np.testing.assert_array_equal(np.sort(np.asarray(picks), -1), np.sort(np.asarray(ref_picks), -1))
    order, ref_order = np.argsort(np.asarray(picks), -1), np.argsort(np.asarray(ref_picks), -1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(weights), order, -1), np.take_along_axis(np.asarray(ref_weights), ref_order, -1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), SCALING, rtol=1e-5)
    # the bias moved some picks (they are not the four largest scores everywhere) and no weight holds it
    scores = np.asarray(jax.nn.sigmoid(h @ p["router"]))
    unbiased = np.sort(np.argsort(-scores, -1)[:, :TOP_K], -1)
    assert (unbiased != np.sort(np.asarray(picks), -1)).any()
    chosen = np.take_along_axis(scores, np.asarray(picks), -1)
    np.testing.assert_allclose(np.asarray(weights), SCALING * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


def _plain_layer(p, x, first, count):
    """The expert layer as Tentpole 1 states it, ``jax.numpy`` and a Python loop: [T, d] -> [T, d]."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ p["router"])
        _, picks = jax.lax.top_k(scores + p["router_bias"][None, :], TOP_K)
        top = jnp.take_along_axis(scores, picks, axis=1)
        weights = SCALING * top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
        l = x @ p["w_latent_in"]
        m = jnp.zeros_like(l)
        for e in range(count):
            mine = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)
            m = m + mine[:, None] * (jnp.square(jax.nn.relu(l @ p["w_up"][e])) @ p["w_down"][e])
        return m @ p["w_latent_out"] + jnp.square(jax.nn.relu(x @ p["shared_w_up"])) @ p["shared_w_down"]


def _experts_module(cfg, held=(FIRST, HELD)):
    return moe.RoutedExperts(
        num_experts=cfg.moe_experts, top_k=cfg.moe_top_k, d_ff=cfg.moe_intermediate_size, held=held, dtype=jnp.float32, partition=False,
        router_kind="sigmoid_grouped", routed_scaling=SCALING, shared_experts=1, shared_d_ff=40, expert_act="relu2", latent_size=32,
    )


def test_the_two_matrix_expert_in_its_latent_is_the_plain_statement_forward_and_gradient(model):
    cfg, params, _, _ = model
    p = params["block_1"]["moe"]
    x = jax.random.normal(jax.random.key(5), (2, 20, cfg.d_model), jnp.float32)
    layer = _experts_module(cfg)
    got, aux = jax.jit(lambda p, x: layer.apply({"params": p}, x))(p, x)
    want = jax.jit(lambda p, x: _plain_layer(p, x.reshape(-1, cfg.d_model), FIRST, HELD))(p, x).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)
    assert float(aux) == 0.0                                                             # the bias balances such a router
    # the serving forward states the same layer through the same expert function
    served, counted = jax.jit(lambda p, x: moe.serve_routed_experts(cfg, p, x))(p, x)
    np.testing.assert_allclose(np.asarray(served), np.asarray(want), rtol=2e-5, atol=2e-6)
    picks = np.asarray(reference.route(x.reshape(-1, cfg.d_model), p["router"], p["router_bias"], top_k=TOP_K, scaling=SCALING)[0])
    held = (picks >= FIRST) & (picks < FIRST + HELD)
    assert [int(c) for c in counted] == [held.sum(), len(np.unique(picks[held]))]
    # every leaf's gradient (the backward through ``_held_experts_bwd``) and the input's, against jax.grad of the plain statement
    cot = jax.random.normal(jax.random.key(6), x.shape, jnp.float32)
    grads = jax.jit(jax.grad(lambda p, x: jnp.sum(layer.apply({"params": p}, x)[0] * cot), argnums=(0, 1)))(p, x)
    plain = jax.jit(jax.grad(lambda p, x: jnp.sum(_plain_layer(p, x.reshape(-1, cfg.d_model), FIRST, HELD).reshape(x.shape) * cot), argnums=(0, 1)))(p, x)
    for name in sorted(p):
        if name != "router_bias":                                                        # picks only: no gradient either way
            scale = float(jnp.abs(plain[0][name]).max())
            assert scale > 0, name
            np.testing.assert_allclose(np.asarray(grads[0][name]), np.asarray(plain[0][name]), rtol=2e-4, atol=2e-5 * scale, err_msg=name)
    np.testing.assert_allclose(np.asarray(grads[1]), np.asarray(plain[1]), rtol=2e-4, atol=2e-5 * float(jnp.abs(plain[1]).max()))
    assert not np.asarray(grads[0]["router_bias"]).any()


def test_the_gated_expert_and_its_gradient_are_what_they_were(model):
    """The three-matrix expert through the one expert function: ``W_down (silu(W_gate x) * W_up x)`` and ``jax.grad`` of it."""
    cfg = model[0]
    layer = moe.RoutedExperts(num_experts=8, top_k=2, d_ff=24, held=(2, 4), dtype=jnp.float32, partition=False)
    x = jax.random.normal(jax.random.key(8), (1, 30, cfg.d_model), jnp.float32)
    p = meta.unbox(jax.jit(layer.init)(jax.random.key(2), x))["params"]
    assert sorted(p) == ["router", "w_down", "w_gate", "w_up"]

    def plain(p, x):
        with jax.default_matmul_precision("highest"):
            xf = x.reshape(-1, x.shape[-1])
            top, picks = jax.lax.top_k(jax.nn.softmax(xf @ p["router"], axis=-1), 2)
            weights = top / jnp.sum(top, axis=-1, keepdims=True)
            y = jnp.zeros_like(xf)
            for e in range(4):
                mine = jnp.sum(jnp.where(picks == 2 + e, weights, 0.0), axis=-1)
                y = y + mine[:, None] * ((jax.nn.silu(xf @ p["w_gate"][e]) * (xf @ p["w_up"][e])) @ p["w_down"][e])
            return y.reshape(x.shape)

    np.testing.assert_allclose(np.asarray(jax.jit(lambda p, x: layer.apply({"params": p}, x)[0])(p, x)), np.asarray(jax.jit(plain)(p, x)), rtol=2e-5, atol=2e-6)
    got = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(layer.apply({"params": p}, x)[0]))))(p, x)
    want = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(plain(p, x)))))(p, x)
    for name in sorted(p):
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(want[name]), rtol=2e-4, atol=2e-5 * float(jnp.abs(want[name]).max()), err_msg=name)


def _stream(cfg, weights, toks, layers: int):
    """The reference's residual stream [S, d] after the first ``layers`` layers (uncut: experts from 0)."""
    told = numerics(cfg)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][toks].astype(jnp.float32)
        for layer in weights["layers"][:layers]:
            h = reference._rms_norm(x, layer["norm"], cfg.norm_eps)
            if "w_in" in layer:
                x = x + reference.mamba2(h, layer, **{k: told[k] for k in ("heads", "head_dim", "d_state", "groups", "conv", "eps")},
                                         norm_groups=cfg.ssm_groups, shared_group=False)
            elif "wq" in layer:
                x = x + reference.attention(h, layer, query_block=64)
            else:
                x = x + reference.expert_layer(h, layer, first_expert=0, top_k=TOP_K, scaling=SCALING)
        return x


def test_the_four_shares_of_a_layers_experts_add_up_to_the_uncut_layer(model):
    """The guide's section 4: the expert layer's ``m`` summed over ALL FOUR shares (experts 0-3, 4-7, 8-11, 12-15, each
    through the program's own layer told which it holds), with the shared expert, the residual and every other layer
    counted ONCE, is the uncut reference's output: of the layer alone, and of the model's logits."""
    cfg, _, tokens, _ = model
    uncut = dataclasses.replace(cfg, moe_experts_held=None)
    whole = build(uncut, seed=3)                                                         # all 16 experts of every expert layer
    weights = reference_weights(whole, uncut)
    toks = jnp.asarray(tokens[0, :48])

    def shares_of_m(p, h):
        """What each of the four shares alone computes of the layer on the normed input ``h``: its experts' part."""
        shared = jnp.square(jax.nn.relu(h @ p["shared_w_up"])) @ p["shared_w_down"]
        parts = []
        for first in range(0, EVERY, 4):
            share = dict(p, w_up=p["w_up"][first:first + 4], w_down=p["w_down"][first:first + 4])
            held = dataclasses.replace(cfg, moe_experts_held=(first, 4))
            y, _ = jax.jit(lambda q, h, held=held: moe.serve_routed_experts(held, q, h[None]))(share, h)
            parts.append(y[0] - shared)
            # the reference given the same share says the same
            ref = jax.jit(lambda q, h, first=first: reference.expert_layer(h, q, first_expert=first, top_k=TOP_K, scaling=SCALING, shared=False))(share, h)
            np.testing.assert_allclose(np.asarray(parts[-1]), np.asarray(ref), rtol=2e-4, atol=2e-5)
        assert all(np.abs(np.asarray(part)).max() > 0 for part in parts)
        return sum(parts), shared

    h = jax.random.normal(jax.random.key(12), (48, cfg.d_model), jnp.float32)
    m, shared = shares_of_m(whole["block_1"]["moe"], h)
    np.testing.assert_allclose(np.asarray(m + shared), np.asarray(jax.jit(lambda p, h: _plain_layer(p, h, 0, EVERY))(whole["block_1"]["moe"], h)),
                               rtol=2e-4, atol=2e-5)
    # the model: the stream the last layer (an expert layer) reads is every share's alike; its four shares of ``m``,
    # its shared expert and its residual once, then the final norm and the head
    x = _stream(uncut, weights, toks, 4)
    last = whole["block_4"]
    m, shared = shares_of_m(last["moe"], reference._rms_norm(x, last["ln1"]["scale"], cfg.norm_eps))
    with jax.default_matmul_precision("highest"):
        logits = reference._rms_norm(x + m + shared, whole["ln_f"]["scale"], cfg.norm_eps) @ whole["lm_head"]["kernel"]
    np.testing.assert_allclose(np.asarray(logits), oracle(uncut, whole, np.asarray(toks)[None])[0], rtol=3e-4, atol=3e-5)


# ---------------------------------------------------------------------------
# the state kernel at 64-wide heads
# ---------------------------------------------------------------------------


def test_the_kernel_takes_a_program_of_several_groups_from_the_shapes():
    assert ssm.kernel_takes(128, 8, 64, 128, jnp.float32) and ssm.kernel_takes(32, 2, 128, 256, jnp.float32)
    assert not ssm.kernel_takes(128, 8, 32, 128, jnp.float32) and not ssm.kernel_takes(128, 8, 64, 64, jnp.float32)
    assert ssm.groups_a_program(8, 16, 64, 128, jnp.float32) == 4                        # this model: 64 heads, 2 MB a program
    assert ssm.groups_a_program(2, 16, 128, 256, jnp.float32) == 1                       # Falcon-H1: its program as it was
    assert ssm.groups_a_program(8, 16, 64, 128, jnp.bfloat16) == 8 and ssm.groups_a_program(3, 8, 64, 128, jnp.float32) == 3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_at_64_wide_heads_agrees_with_its_jnp_form_and_the_recurrence(dtype):
    """32 heads of 64 over 4 groups of 128 state values (eight heads a group, all four groups in one program at float32's
    1 MB, as two of the cell's eight are): three steps into layer 1 of a pool of two, lane 2 of four idle."""
    h, p, g, n = 32, 64, 4, 128
    assert ssm.groups_a_program(g, h // g, p, n, dtype) == 4
    x, bb, cc, dt, a, skip = _parts(6, 4, 3, h, p, g, n)
    r = h // g
    want = np.stack([np.asarray(jax.jit(lambda x, b, c, d: reference._recurrence(x, jnp.repeat(b, r, axis=1), jnp.repeat(c, r, axis=1), d, a, skip))(
        x[i], bb[i], cc[i], dt[i])) for i in range(4)])
    pool = jax.random.normal(jax.random.key(2), ssm.state_shape(2, 4, h, p, n), jnp.float32).astype(dtype)
    pool = pool.at[1, jnp.asarray([0, 1, 3])].set(0.0)                                   # the live lanes start a sequence
    start = np.asarray(pool, np.float32)
    live = jnp.asarray([True, True, False, True])
    by_kernel, by_jnp = pool, pool
    for t in range(3):
        args = (x[:, t], bb[:, t], cc[:, t], dt[:, t], a, skip)
        y1, by_kernel = ssm.ssm_decode(*args, by_kernel, 1, live, impl="kernel_interpret")
        y0, by_jnp = ssm.ssm_decode(*args, by_jnp, 1, live, impl="jnp")
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=2e-4, atol=2e-4)
        if dtype == jnp.float32:
            np.testing.assert_allclose(np.asarray(y1)[[0, 1, 3]], want[[0, 1, 3], t], rtol=3e-4, atol=3e-4)
        assert not np.asarray(y1)[2].any()
    lanes = slice(0, 4)                                                                  # the scratch slot is nobody's
    np.testing.assert_allclose(np.asarray(by_kernel[:, lanes], np.float32), np.asarray(by_jnp[:, lanes], np.float32), rtol=1e-6, atol=1e-6)
    after = np.asarray(by_kernel, np.float32)
    np.testing.assert_array_equal(after[0], start[0])                                    # the other layer
    np.testing.assert_array_equal(after[1, 2], start[1, 2])                              # the idle lane's slot
    assert np.abs(after[1, 0] - start[1, 0]).max() > 0


# ---------------------------------------------------------------------------
# the model: the whole-sequence form, the wide prefill, the walk and the decode step against the reference
# ---------------------------------------------------------------------------


def test_the_whole_sequence_form_and_the_wide_prefill_are_the_reference(model):
    cfg, params, tokens, want = model
    got = jax.jit(lambda p, t: TransformerLM(cfg).apply({"params": p}, t))(params, jnp.asarray(tokens[:, :100]))
    np.testing.assert_allclose(np.asarray(got), want[:, :100], rtol=3e-4, atol=3e-5)     # 100 tokens: not whole chunks of 8
    cache = init_kv_cache(cfg, 80, 4, lanes=3)
    tables = jnp.asarray(1 + np.arange(3 * 25).reshape(3, 25), jnp.int32)
    lens = jnp.asarray([100, 61, 7])
    logits, cache = jax.jit(functools.partial(transformer_prefill, cfg))(params, jnp.asarray(tokens[:, :100]), lens, tables, cache)
    for i, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(np.asarray(logits)[i, :n], want[i, :n], rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("chunk", [128, 256])
def test_the_walk_and_the_decode_step_are_the_reference_in_lanes_of_unequal_length(model, monkeypatch, chunk):
    """Prompts of 300, 270 and 40 tokens into lanes 3, 0 and 2 of four, the walk's chunk 128 or 256 tokens (two or one
    chunk's edge crossed, the last chunk part padded); then 30 decode steps in the three lanes at once, lane 1 idle."""
    cfg, params, tokens, want = model
    monkeypatch.setattr(serving, "PREFILL_CHUNK_TOKENS", chunk)
    block, lanes = 4, jnp.asarray([3, 0, 2])
    assert serving.prefill_chunk_tokens(block, 512) == chunk
    cache = init_kv_cache(cfg, 3 * 100 + 1, block, lanes=4)
    cache = {k: (v + 5.0 if k in SSM_SLOT.leaves else v) for k, v in cache.items()}      # a reused lane: the walk must zero it
    tables = np.zeros((4, 100), np.int32)
    tables[[3, 0, 2]] = 1 + np.arange(300).reshape(3, 100)
    lens = np.asarray([300, 270, 40])
    padded = np.zeros((3, 512), np.int32)
    for i, n in enumerate(lens):
        padded[i, :n] = tokens[i, :n]
    walk = jax.jit(functools.partial(transformer_prefill_chunked, cfg))
    last, cache = walk(params, padded, np.zeros(3, np.int32), lens, tables[[3, 0, 2]], cache, lanes)
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(last)[i], want[i, n - 1], rtol=4e-4, atol=4e-5)
    idle = {leaf: np.asarray(cache[leaf])[:, 1] for leaf in SSM_SLOT.leaves}
    step = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True))
    table_step = jax.jit(functools.partial(transformer_decode, cfg))
    for t in range(30):
        toks, pos = np.zeros(4, np.int32), np.full(4, -1, np.int32)
        for i, lane in enumerate((3, 0, 2)):
            toks[lane], pos[lane] = tokens[i, lens[i] + t], lens[i] + t
        if t == 7:                                                                       # the table form from the same cache
            other, _ = table_step(params, toks, pos, tables, cache)
        logits, cache = step(params, toks, pos, tables, cache)
        for i, lane in enumerate((3, 0, 2)):
            np.testing.assert_allclose(np.asarray(logits)[lane], want[i, lens[i] + t], rtol=4e-4, atol=4e-5)
        if t == 7:
            np.testing.assert_allclose(np.asarray(other)[[3, 0, 2]], np.asarray(logits)[[3, 0, 2]], rtol=2e-4, atol=2e-5)
        counted = np.asarray(logits)[4, :4]
        np.testing.assert_array_equal(counted[:2], [3.0, 3.0 * 2 * ssm_bytes_per_slot(cfg)])     # two M layers, three live lanes
        assert 0 < counted[3] <= counted[2] <= 3 * 2 * TOP_K                                      # two E layers: experts hit, held picks
    for leaf in SSM_SLOT.leaves:                                                         # the idle lane's slot and tail
        np.testing.assert_array_equal(np.asarray(cache[leaf])[:, 1], idle[leaf])


def test_the_cells_pattern_is_the_reference_through_the_walk_and_the_decode_step():
    """All eleven layers of the cell's cut (five M, five E, one *) at tiny widths: 270 tokens prefilled, 12 decoded."""
    cfg = tiny(PATTERN)
    params = build(cfg, seed=4)
    tokens = np.asarray(jax.random.randint(jax.random.key(9), (1, 290), 1, cfg.vocab_size))
    want = oracle(cfg, params, tokens)
    cache = init_kv_cache(cfg, 101, 4, lanes=2)
    tables = np.zeros((2, 100), np.int32)
    tables[1] = 1 + np.arange(100)
    padded = np.zeros((1, 512), np.int32)
    padded[0, :270] = tokens[0, :270]
    walk = jax.jit(functools.partial(transformer_prefill_chunked, cfg))
    last, cache = walk(params, padded, np.zeros(1, np.int32), np.asarray([270]), tables[1:], cache, jnp.asarray([1]))
    np.testing.assert_allclose(np.asarray(last)[0], want[0, 269], rtol=5e-4, atol=5e-5)
    step = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1))
    for t in range(270, 282):
        logits, cache = step(params, np.asarray([0, tokens[0, t]], np.int32), np.asarray([-1, t], np.int32), tables, cache)
        np.testing.assert_allclose(np.asarray(logits)[1], want[0, t], rtol=5e-4, atol=5e-5)


# ---------------------------------------------------------------------------
# the engine: blocks for one layer, a lane for two, nothing for the expert layers
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
        assert req.output == oracle(cfg, params, seq[None])[0, n - 1:].argmax(-1).tolist()
    stats = engine.stats()
    assert stats["ssm"] == {"slots": 3, "live": 0, "bytes_per_slot": 2 * ssm_bytes_per_slot(cfg)} and "state" not in stats
    assert set(stats["step_counters"]) == {"serve.ssm.live_lanes", "serve.ssm.bytes", *SERVE_COUNTERS}
    assert stats["step_counters"]["serve.ssm.live_lanes"] == 22.0 and 0 < stats["step_counters"]["serve.moe.held_picks"] <= 22 * 2 * TOP_K
    with pytest.raises(ValueError) as refused:
        _engine(cfg, params, prefix_cache=True)
    assert str(refused.value) == SSM_SLOT.no_prefix_cache
