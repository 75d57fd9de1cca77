"""Kimi-Delta-Attention layers (the delta rule under a decay a CHANNEL) beside
latent-attention layers, served from two caches at once: a delta-rule state and
a convolution tail a decode lane for the KDA layers, ONE latent row a token in
the paged pool for the latent ones, a leading dense layer and DeepSeek-V3's
experts in the rest (models/transformer.py, models/cache_kinds.py,
ops/gated_delta.py, serve/engine.py), against the plain reference the benchmark
keeps (benchmark/reference/ling_kda_mla.py: float32, the rule as its recurrence
one token at a time, latent attention un-absorbed, no chunk, no two-sided
scaling, no cache, no import from the program).  CPU, tiny sizes, seeded
weights; the Pallas kernel in interpret mode."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from determined_tpu.models import cache_kinds
from determined_tpu.models.cache_kinds import BLOCKS, DELTA_SLOT, LANE, PAGED_LATENT, layer_kinds
from determined_tpu.models.serving import (
    init_kv_cache,
    serve_counters,
    transformer_decode,
    transformer_prefill,
    transformer_prefill_chunked,
)
from determined_tpu.models.transformer import (
    FULL,
    LINEAR,
    GatedDeltaNet,
    LatentAttention,
    TransformerConfig,
    TransformerLM,
    gdn_bytes_per_slot,
    gdn_pool_shapes,
    kv_bytes_per_token,
)
from determined_tpu.ops import gated_delta as gd
from determined_tpu.serve.config import ServeConfig
from determined_tpu.serve.engine import DecodeKernels, ServeEngine
from tests.model_cases import reference_module

reference = reference_module("ling_kda_mla")

LAYERS, FIRST, FLOOR = 7, 4, -5.0
#: what the tiny comparison holds the model to, and what a control must miss it by
LIMIT = 1e-4


def tiny(**kw) -> TransformerConfig:
    """A dense layer and one period: KDA x 5, a latent layer, KDA (4 heads of 16 x 16, a decay a channel; 4 heads of
    16 + 8 over a 24-wide latent row, no query latent, a gate a head), top-3 of 16 experts in 4 groups, 8 held from 4."""
    base = dict(
        vocab_size=96, d_model=48, n_layers=LAYERS, n_heads=4, head_dim=16, d_ff=64, max_seq_len=1024, dtype=jnp.float32,
        attention_impl="reference", partition_params=False, rope_theta=6e6, norm_eps=1e-6,
        layer_types=(LINEAR,) * 5 + (FULL, LINEAR), linear_key_heads=4, linear_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, linear_conv=4, linear_chunk=8, linear_decay_floor=FLOOR,
        attn_output_gate=True, kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        dense_prefix=1, moe_experts=16, moe_every=1, moe_top_k=3, moe_intermediate_size=24, moe_experts_held=(FIRST, 8),
        moe_router="sigmoid_grouped", moe_n_group=4, moe_topk_group=2, moe_routed_scaling=2.5, moe_shared_experts=1,
    )
    return TransformerConfig(**{**base, **kw})


def build(cfg, seed=1):
    params = meta.unbox(jax.jit(TransformerLM(cfg).init)(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    # norms away from one, so that one the program skipped or ran twice shows; channels that remember 3 to 300 tokens;
    # experts at their own fan-in under a selection bias that is not zero
    for i in range(cfg.n_layers):
        blk = params[f"block_{i}"]
        mixer = blk["gdn"] if "gdn" in blk else blk["attn"]
        leaves = [(blk["ln1"], "scale"), (blk["ln2"], "scale")] + [(mixer, n) for n in ("norm", "kv_norm") if n in mixer]
        for j, (tree, n) in enumerate(leaves):
            tree[n] = tree[n] * (1.0 + 0.2 * jax.random.normal(jax.random.key(100 + 8 * i + j), tree[n].shape))
        if "gdn" in blk:
            tau = jnp.exp(jax.random.uniform(jax.random.key(300 + i), mixer["dt_bias"].shape, minval=np.log(3.0), maxval=np.log(300.0)))
            share = 1.0 / (-FLOOR * tau)
            mixer["dt_bias"], mixer["A_log"] = jnp.log(share) - jnp.log1p(-share), 0.3 * jax.random.normal(jax.random.key(400 + i), mixer["A_log"].shape)
        if "moe" in blk:
            for n in ("w_gate", "w_up", "w_down"):
                blk["moe"][n] = blk["moe"][n] * blk["moe"][n].shape[0] ** 0.5
            blk["moe"]["router_bias"] = 0.1 * jax.random.normal(jax.random.key(200 + i), blk["moe"]["router_bias"].shape)
    return params


_MOE = ("router", "router_bias", "w_gate", "w_up", "w_down", "shared_w_gate", "shared_w_up", "shared_w_down")
_KDA = {"w_in": "w_in", "w_ba": "w_b", "w_decay": "w_decay", "conv_w": "conv_w", "dt_bias": "dt_bias", "A_log": "A_log", "norm": "kda_norm", "w_out": "w_out"}
_LATENT = {"wq": "wq", "wkv_a": "wkv_a", "kv_norm": "kv_norm", "wkv_b": "wkv_b", "w_gate": "w_head_gate", "wo": "wo"}


def reference_weights(params, cfg):
    layers = []
    for i in range(cfg.n_layers):
        b = params[f"block_{i}"]
        names, leaves = (_KDA, b["gdn"]) if "gdn" in b else (_LATENT, b["attn"])
        ffn = {k: b["moe"][k] for k in _MOE} if "moe" in b else {k: b["mlp"][k]["kernel"] for k in ("w_gate", "w_up", "w_down")}
        layers.append({"mixer_norm": b["ln1"]["scale"], "ffn_norm": b["ln2"]["scale"], **{to: leaves[of] for of, to in names.items()}, **ffn})
    return {"embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"], "final_norm": params["ln_f"]["scale"], "layers": layers}


def numerics(cfg, **kw):
    said = dict(
        eps=cfg.norm_eps, rope_theta=cfg.rope_theta, heads=cfg.linear_value_heads, key_dim=cfg.linear_key_head_dim, conv=cfg.linear_conv,
        lower_bound=cfg.linear_decay_floor, nope=cfg.qk_nope_head_dim, latent=cfg.kv_lora_rank, top_k=cfg.moe_top_k, n_group=cfg.moe_n_group,
        topk_group=cfg.moe_topk_group, scaling=cfg.moe_routed_scaling, first_expert=cfg.moe_experts_held[0], query_block=64, vocab_block=40,
    )
    return {**said, **kw}


def oracle(cfg, params, tokens, **kw):
    forward = jax.jit(functools.partial(reference.forward, **numerics(cfg, **kw)))
    return np.stack([np.asarray(forward(reference_weights(params, cfg), jnp.asarray(row))) for row in tokens])


def rel_rms(got, want):
    return float(np.sqrt(np.mean((np.asarray(got) - want) ** 2)) / np.sqrt(np.mean(want ** 2)))


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = build(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(0), (3, 400), 1, cfg.vocab_size))
    return cfg, params, tokens, oracle(cfg, params, tokens)


def _parts(seed, b=2, s=24, h=4, dk=8, dv=16, g=None):
    """q and k [b, s, h, K] at unit length (q times K ** -0.5), v [b, s, h, V], the bounded gate's decay a CHANNEL
    [b, s, h, K] in (-5, 0) (or ``g`` on every channel) and beta in (0, 1) [b, s, h]."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = gd.l2_heads(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = gd.l2_heads(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv), jnp.float32)
    decay = FLOOR * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], (b, s, h, dk), jnp.float32) - 2.0) if g is None else jnp.full((b, s, h, dk), g, jnp.float32)
    return q, k, v, decay, jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (b, s, h), jnp.float32))


def _recurrence(q, k, v, g, beta, **kw):
    """The reference's scan, a row of the batch at a time: o [b, s, h, V]."""
    one = jax.jit(functools.partial(reference._delta_rule, **kw))
    return np.stack([np.asarray(one(q[i], k[i], v[i], g[i], beta[i])) for i in range(q.shape[0])])


# ---------------------------------------------------------------------------
# the two forms of the rule against the recurrence, a decay a channel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk,sub", [(1, 1), (5, 5), (8, 4), (24, 8), (32, 16), (24, 64), (48, 48)])
def test_chunks_that_carry_a_state_give_the_recurrence_under_a_decay_a_channel(chunk, sub):
    """Token for token across chunk edges, sub-chunks of several sizes (one where the size does not divide the chunk,
    one of three blocks of the two-sided scaling), lanes of unequal length, a last chunk that is part padding."""
    q, k, v, g, beta = _parts(3, s=48)
    b, s, h, dk = q.shape
    lens = np.asarray([s, 17])
    want = _recurrence(q, k, v, g, beta)
    step = jax.jit(functools.partial(gd.gdn_chunk, chunk=sub))
    state, outs = jnp.zeros((b, h, dk, v.shape[-1])), []
    for lo in range(0, s, chunk):
        cut = lambda t: jnp.pad(t[:, lo:lo + chunk], ((0, 0), (0, chunk - t[:, lo:lo + chunk].shape[1])) + ((0, 0),) * (t.ndim - 2))  # noqa: E731
        live = jnp.asarray((lo + np.arange(chunk))[None, :] < lens[:, None])
        out, state = step(cut(q), cut(k), cut(v), cut(g), cut(beta), state, live)
        outs.append(out)
    got = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(got[0, :s], want[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[1, :17], want[1, :17], rtol=2e-4, atol=2e-5)
    after = jax.jit(gd.gdn_recurrence)(q[1:, :17], k[1:, :17], v[1:, :17], g[1:, :17], beta[1:, :17], jnp.zeros_like(state[1:]), jnp.ones((1, 17), bool))[1]
    np.testing.assert_allclose(np.asarray(state[1]), np.asarray(after[0]), rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("g,sub", [(FLOOR, 64), (FLOOR, 16), (-1e-4, 64), (gd.DECAY_FLOOR, 64)])
def test_the_two_sided_scaling_stays_inside_float32_at_the_floor_and_near_zero(g, sub):
    """``g`` on EVERY channel of every token for whole sub-chunks of 64: at the bounded gate's floor -5 a block's factors
    reach exp(+-40) and a masked pair's product exp(75), at the scaling's own floor exp(82.5); near 0 nothing decays."""
    q, k, v, decay, beta = _parts(4, b=1, s=128, g=g)
    start = jax.random.normal(jax.random.key(1), (1, 4, 8, 16))
    live = jnp.ones((1, 128), bool)
    want, s_want = jax.jit(gd.gdn_recurrence)(q, k, v, decay, beta, start, live)
    got, s_got = jax.jit(functools.partial(gd.gdn_chunk, chunk=sub))(q, k, v, decay, beta, start, live)
    assert bool(jnp.isfinite(got).all()) and bool(jnp.isfinite(s_got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-4, atol=3e-5)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want), rtol=3e-4, atol=3e-5)
    kk, qk = gd._pairwise_channel(k[0, :64].transpose(1, 0, 2), q[0, :64].transpose(1, 0, 2), jnp.cumsum(decay[0, :64].transpose(1, 0, 2), axis=-2))
    assert bool(jnp.isfinite(kk).all()) and bool(jnp.isfinite(qk).all())                  # above the diagonal too: masked, never NaN


def test_the_rule_is_the_rule_under_either_gate_and_either_rank():
    """The control the issue names: Kimi Linear's ``g = -exp(A_log) softplus(a)`` through the same two forms (its values
    inside the scaling's range here); and a decay that is the SAME on every channel of a head is the decay a head."""
    q, k, v, _, beta = _parts(7, s=32)
    a = jax.random.normal(jax.random.key(8), q.shape)
    g = -jnp.exp(jnp.asarray([-2.0, -1.0, 0.0, 1.0]))[None, None, :, None] * jax.nn.softplus(a)
    start, live = jnp.zeros((2, 4, 8, 16)), jnp.ones((2, 32), bool)
    want = _recurrence(q, k, v, g, beta)
    got, _ = jax.jit(functools.partial(gd.gdn_chunk, chunk=16))(q, k, v, g, beta, start, live)
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-4, atol=3e-5)
    head = jnp.mean(g, axis=-1)
    by_head, _ = jax.jit(functools.partial(gd.gdn_chunk, chunk=16))(q, k, v, head, beta, start, live)
    by_channel, _ = jax.jit(functools.partial(gd.gdn_chunk, chunk=16))(q, k, v, jnp.broadcast_to(head[..., None], g.shape), beta, start, live)
    np.testing.assert_allclose(np.asarray(by_channel), np.asarray(by_head), rtol=3e-4, atol=3e-5)
    assert np.abs(np.asarray(by_head) - want).max() > 1e-2                                 # and a head's mean is NOT the channels' own


@pytest.mark.parametrize("impl,dims", [("jnp", (4, 8, 16)), ("kernel_interpret", (8, 16, 128))])
def test_decode_steps_give_the_recurrence_under_a_decay_a_channel_and_leave_idle_lanes_alone(impl, dims):
    h, dk, dv = dims
    q, k, v, g, beta = _parts(5, b=3, s=6, h=h, dk=dk, dv=dv)
    want = _recurrence(q, k, v, g, beta)
    pool = jax.random.normal(jax.random.key(9), gd.state_shape(3, 3, h, dk, dv), jnp.float32)
    pool = pool.at[1, jnp.asarray([0, 2])].set(0.0)                                        # lanes 0 and 2 start a sequence
    start = np.asarray(pool)
    live = jnp.asarray([True, False, True])
    for t in range(q.shape[1]):
        o, pool = gd.gdn_decode(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], pool, 1, live, impl=impl)
        np.testing.assert_allclose(np.asarray(o)[[0, 2]], want[[0, 2], t], rtol=3e-4, atol=3e-5)
        assert not np.asarray(o)[1].any()
    after = np.asarray(pool)
    np.testing.assert_array_equal(after[[0, 2]], start[[0, 2]])                            # the other layers
    np.testing.assert_array_equal(after[1, 1], start[1, 1])                                # the idle lane's slot
    assert np.abs(after[1, 0] - start[1, 0]).max() > 0


def test_the_kernel_in_interpret_mode_agrees_with_its_jnp_form_at_the_published_head():
    """32 heads of 128 x 128 (one program's 2 MB), a decay a channel down the kernel's columns, the floor on some
    channels; dead lanes among the live ones."""
    h, dk, dv = 32, 128, 128
    q, k, v, g, beta = _parts(6, b=4, s=1, h=h, dk=dk, dv=dv)
    g = g.at[..., ::7].set(FLOOR)
    pool = jax.random.normal(jax.random.key(2), gd.state_shape(2, 4, h, dk, dv), jnp.float32)
    live = jnp.asarray([True, False, False, True])
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], pool, 1, live)
    o0, s0 = gd.gdn_decode(*args, impl="jnp")
    o1, s1 = gd.gdn_decode(*args, impl="kernel_interpret")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o0), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1[:, :4]), np.asarray(s0[:, :4]), rtol=1e-6, atol=1e-6)   # the scratch slot is nobody's
    np.testing.assert_array_equal(np.asarray(s1[1, 1:3]), np.asarray(pool[1, 1:3]))


def test_gradients_of_both_whole_sequence_modules_are_finite():
    cfg = tiny()
    u = jax.random.normal(jax.random.key(1), (2, 24, cfg.d_model), jnp.float32)
    for mixer, leaves, apply in (
        (GatedDeltaNet(cfg), {"w_in", "w_ba", "w_decay", "conv_w", "dt_bias", "A_log", "norm", "w_out"}, lambda m, p, u: m.apply(p, u)),
        (LatentAttention(cfg), {"wq", "w_gate", "wkv_a", "kv_norm", "wkv_b", "wo"}, lambda m, p, u: m.apply(p, u)[0]),
    ):
        params = mixer.init(jax.random.key(2), u)
        assert set(meta.unbox(params)["params"]) == leaves
        loss = lambda p, u: jnp.sum(jnp.square(apply(mixer, p, u)))  # noqa: E731
        grads, du = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, u)
        found = jax.tree.leaves(meta.unbox(grads)) + [du]
        assert all(bool(jnp.isfinite(leaf).all()) for leaf in found) and all(float(jnp.abs(leaf).max()) > 0 for leaf in found)
        # causal: to the bit before the token's block of the two-sided scaling, to a rounding inside it (the rows before
        # a block's middle row are scaled round it)
        moved = apply(mixer, params, u.at[:, 17].add(1.0)) - apply(mixer, params, u)
        assert float(jnp.abs(moved[:, :16]).max()) == 0.0 and float(jnp.abs(moved[:, 16]).max()) < 1e-6 and float(jnp.abs(moved[:, 17:]).max()) > 1e-3
    shapes = {k: v.shape for k, v in meta.unbox(GatedDeltaNet(cfg).init(jax.random.key(2), u))["params"].items()}
    assert (shapes["w_ba"], shapes["w_decay"], shapes["dt_bias"], shapes["A_log"]) == ((48, 4), (48, 64), (64,), (4,))


# ---------------------------------------------------------------------------
# the model: two kinds, the whole-sequence form, the walk and the decode step against the reference
# ---------------------------------------------------------------------------


def test_a_model_is_of_a_latent_row_and_a_lane_held_state_and_the_cache_holds_both(model):
    cfg, _, _, _ = model
    assert cache_kinds.cache_kinds(cfg) == (PAGED_LATENT, DELTA_SLOT) and (PAGED_LATENT.holds, DELTA_SLOT.holds) == (BLOCKS, LANE)
    assert DELTA_SLOT.latent is None and PAGED_LATENT.latent is True and cfg.latent
    kinds = [layer_kinds(cfg, i) for i in range(LAYERS)]
    assert kinds[5] == ((PAGED_LATENT, 0, "attn"),) and kinds[6] == ((DELTA_SLOT, 5, "gdn"),) and kinds[0] == ((DELTA_SLOT, 0, "gdn"),)
    assert cfg.linear_layers == (0, 1, 2, 3, 4, 6) == cfg.rowless_layers and cfg.paged_layers == 1
    assert [cfg.use_moe(i) for i in range(LAYERS)] == [False] + [True] * 6
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 40, 4, lanes=3, chunk_tokens=16))
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "kv": ((1, 40, 4, 128), "float32"),                                                # ONE layer's rows, 32 values padded to a lane tile
        "gdn": ((6, 4, 4, 16, 16), "float32"), "gconv": ((6, 3, 3, 192), "float32"),       # a slot a lane and one scratch; three rows a tail
    }
    assert gdn_pool_shapes(cfg, 3) == ((6, 4, 4, 16, 16), (6, 3, 3, 192)) and gdn_bytes_per_slot(cfg) == 4 * 16 * 16 * 4
    assert kv_bytes_per_token(cfg) == 1 * (24 + 8) * 4
    assert serve_counters(cfg) == ("serve.gdn.live_lanes", "serve.gdn.bytes", "serve.moe.held_picks", "serve.moe.experts_hit")
    # the published widths: 32 heads of 128 x 128 float32, 2 MB a lane a layer, three rows of 12,288 channels, a 640-wide stored row
    wide = tiny(d_model=2560, n_heads=32, head_dim=128, linear_key_heads=32, linear_value_heads=32, linear_key_head_dim=128,
                linear_value_head_dim=128, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, dtype=jnp.bfloat16)
    assert gdn_pool_shapes(wide, 128) == ((6, 129, 32, 128, 128), (6, 128, 3, 12288)) and gdn_bytes_per_slot(wide) == 2_097_152
    assert kv_bytes_per_token(wide) == 1152 and jax.eval_shape(lambda: init_kv_cache(wide, 17, 16, lanes=2))["kv"].shape == (1, 17, 16, 640)


@pytest.mark.parametrize("kw,says", [
    (dict(linear_decay_floor=-6.0), "holds no smaller one inside float32"),
    (dict(linear_decay_floor=0.0), "lies in \\[-5.5, 0\\)"),
    (dict(layer_types=(FULL,) * 7), "linear_decay_floor .* belongs to linear_attention layers"),
    (dict(attn_output_gate=False, layer_types=("cca",) * 7, n_kv_heads=2, kv_lora_rank=None, qk_nope_head_dim=None, qk_rope_head_dim=None, v_head_dim=None), "belongs to linear_attention layers"),
    (dict(q_latent_scale=2.0), "no query latent to scale"),
    (dict(indexer_types=("full",) * 7, index_n_heads=2, index_head_dim=8, index_topk=4), "beside an indexer"),
    (dict(parallel_block=True), "it does not run under parallel_block or shortcut_block"),
    (dict(qk_norm=True), "qk_norm runs in power_retention layers only"),
    (dict(partial_rotary_factor=0.5), "not with latent attention"),
    (dict(layer_types=("power_retention",) * 7), "a power_retention layer runs in a sequential block, without latent attention"),
    (dict(layer_types=(FULL,) * 7, linear_decay_floor=None, indexer_types=("full",) * 7, index_n_heads=2, index_head_dim=8, index_topk=4, q_lora_rank=8),
     "nor latent attention under an indexer"),
])
def test_configurations_the_program_cannot_run_are_still_refused_by_name(kw, says):
    with pytest.raises(ValueError, match=says):
        tiny(**kw)


def test_the_whole_sequence_form_and_the_wide_prefill_are_the_reference(model):
    cfg, params, tokens, want = model
    got = jax.jit(lambda p, t: TransformerLM(cfg).apply({"params": p}, t))(params, jnp.asarray(tokens[:, :100]))
    assert rel_rms(got, want[:, :100]) < LIMIT                                             # 100 tokens: not whole sub-chunks of 8
    np.testing.assert_allclose(np.asarray(got), want[:, :100], rtol=3e-4, atol=3e-5)
    cache = init_kv_cache(cfg, 80, 4, lanes=3)
    tables = jnp.asarray(1 + np.arange(3 * 25).reshape(3, 25), jnp.int32)
    lens = jnp.asarray([100, 61, 7])
    logits, cache = jax.jit(functools.partial(transformer_prefill, cfg))(params, jnp.asarray(tokens[:, :100]), lens, tables, cache)
    for i, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(np.asarray(logits)[i, :n], want[i, :n], rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("told", [
    dict(head_decay=True), dict(beta_one=True), dict(correct=False), dict(output_gate=False), dict(head_gate=False),
    dict(softplus_gate=True), dict(state_dtype=jnp.bfloat16),
])
def test_each_control_of_the_reference_misses_the_model_by_ten_limits(model, told):
    """A decay that is a head's mean, beta of 1, the update without ``- r``, either mixer's gate left out, the softplus
    gate in the bounded gate's place and a state held in bfloat16 each move the logits by at least 10 x the limit."""
    cfg, params, tokens, want = model
    other = oracle(cfg, params, tokens[:1, :200], **told)
    assert rel_rms(other, want[:1, :200]) > 10 * LIMIT, told


def test_the_walk_and_the_decode_step_are_the_reference_in_lanes_of_unequal_length(model):
    """Prompts of 300, 270 and 40 tokens into lanes 3, 0 and 2 of four: the walk's chunk is 256, so two of them cross
    a chunk's edge and end in a part-padded chunk; then 40 decode steps in the three lanes at once, lane 1 idle."""
    cfg, params, tokens, want = model
    block, lanes = 4, jnp.asarray([3, 0, 2])
    cache = init_kv_cache(cfg, 3 * 100 + 1, block, lanes=4)
    cache = {k: (v + 5.0 if k in DELTA_SLOT.leaves else v) for k, v in cache.items()}      # a reused lane: the walk must zero it
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
    idle = {leaf: np.asarray(cache[leaf])[:, 1] for leaf in DELTA_SLOT.leaves}
    step = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True))
    table_step = jax.jit(functools.partial(transformer_decode, cfg))
    rows, wanted = [], []
    for t in range(40):
        toks, pos = np.zeros(4, np.int32), np.full(4, -1, np.int32)
        for i, lane in enumerate((3, 0, 2)):
            toks[lane], pos[lane] = tokens[i, lens[i] + t], lens[i] + t
        if t == 7:                                                                         # the table form from the same cache
            other, _ = table_step(params, toks, pos, tables, cache)
        logits, cache = step(params, toks, pos, tables, cache)
        for i, lane in enumerate((3, 0, 2)):
            np.testing.assert_allclose(np.asarray(logits)[lane], want[i, lens[i] + t], rtol=4e-4, atol=4e-5)
            rows.append(np.asarray(logits)[lane]), wanted.append(want[i, lens[i] + t])
        if t == 7:
            np.testing.assert_allclose(np.asarray(other)[[3, 0, 2]], np.asarray(logits)[[3, 0, 2]], rtol=2e-4, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(logits)[4, :2], [3.0, 3.0 * 6 * gdn_bytes_per_slot(cfg)])
    assert rel_rms(np.stack(rows), np.stack(wanted)) < LIMIT
    for leaf in DELTA_SLOT.leaves:                                                         # the idle lane's slot and tail
        np.testing.assert_array_equal(np.asarray(cache[leaf])[:, 1], idle[leaf])


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Two chips' shares here (experts 0-7 and 8-15 of 16: eight shares of 64 at the published size), the shared expert
    counted once: what the program's expert layer gives each share adds up to the reference's layer over ALL experts."""
    from determined_tpu.models.moe import serve_routed_experts

    cfg = tiny(moe_experts_held=None)
    blk = build(cfg)["block_1"]["moe"]
    h = jax.random.normal(jax.random.key(3), (1, 40, cfg.d_model), jnp.float32)
    told = dict(top_k=cfg.moe_top_k, n_group=cfg.moe_n_group, topk_group=cfg.moe_topk_group, scaling=cfg.moe_routed_scaling)
    with jax.default_matmul_precision("highest"):
        whole = reference.experts(h[0], {k: blk[k] for k in _MOE}, first_expert=0, **told)
        shared_alone = reference.experts(h[0], {k: blk[k] for k in _MOE}, first_expert=1000, **told)   # no pick lands on a held expert
    total = 0.0
    for first in (0, 8):
        share = {**blk, **{n: blk[n][first:first + 8] for n in ("w_gate", "w_up", "w_down")}}
        y, _ = jax.jit(functools.partial(serve_routed_experts, tiny(moe_experts_held=(first, 8))))(share, h)
        total = total + np.asarray(y[0])
    np.testing.assert_allclose(total - np.asarray(shared_alone), np.asarray(whole), rtol=3e-4, atol=3e-5)
    assert float(jnp.abs(shared_alone).max()) > 1e-3 and float(jnp.abs(whole - shared_alone).max()) > 1e-3


# ---------------------------------------------------------------------------
# the engine: blocks of latent rows AND a lane
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
    first = engine.submit(tokens[0, :290].tolist(), max_new_tokens=12, temperature=0.0)    # crosses a chunk's edge
    _drain(engine, first)
    again = engine.submit(tokens[1, :33].tolist(), max_new_tokens=12, temperature=0.0)     # into the lane the first left
    _drain(engine, again)
    assert first.error is None and again.error is None and engine.lanes.stats()["active"] == 0
    for req, row, n in ((first, 0, 290), (again, 1, 33)):
        seq = np.concatenate([tokens[row, :n], np.asarray(req.output[:-1], np.int64)])
        want = oracle(cfg, params, seq[None])[0, n - 1:].argmax(-1)
        assert req.output == want.tolist()
    fresh = _engine(cfg, params)
    alone = fresh.submit(tokens[1, :33].tolist(), max_new_tokens=12, temperature=0.0)
    _drain(fresh, alone)
    assert alone.output == again.output                                                    # the slot and the tail were zeroed
    stats = engine.stats()
    assert stats["gdn"] == {"slots": 3, "live": 0, "bytes_per_slot": 6 * gdn_bytes_per_slot(cfg)}
    assert "block_ids_address_nothing" not in stats and "ssm" not in stats and "state" not in stats
    assert {"serve.gdn.live_lanes", "serve.gdn.bytes"} <= set(stats["step_counters"]) and stats["step_counters"]["serve.gdn.live_lanes"] == 22.0


def test_prefix_cache_is_refused_by_name_though_the_latent_rows_alone_could_be_shared(model):
    cfg, params, _, _ = model
    with pytest.raises(ValueError) as refused:
        _engine(cfg, params, prefix_cache=True)
    assert str(refused.value) == DELTA_SLOT.no_prefix_cache and "Set prefix_cache: false" in str(refused.value)
    kernels = _engine(cfg, params).kernels
    with pytest.raises(ValueError, match="is prefilled from 0, not from 8"):
        kernels.prefill_suffix(list(range(1, 20)), [0] * kernels.serve_cfg.blocks_per_seq, 8, 1)
