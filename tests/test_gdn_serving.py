"""Gated-DeltaNet layers beside gated full-attention layers, served from two
caches at once: a delta-rule state and a convolution tail a decode lane for the
linear layers, K and V rows in the paged pool for the full ones, experts with a
gated shared expert in every layer (models/transformer.py,
models/cache_kinds.py, ops/gated_delta.py, serve/engine.py), against the plain
reference the benchmark keeps (benchmark/reference/qwen3_next.py: float32, the
delta rule as its recurrence one token at a time, no chunk, no triangular
solve, no cache, no import from the program).  CPU, tiny sizes, seeded weights;
the Pallas kernel in interpret mode."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from determined_tpu.models import cache_kinds
from determined_tpu.models.cache_kinds import BLOCKS, DELTA_SLOT, LANE, PAGED_KV, Rows, layer_kinds
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

reference = reference_module("qwen3_next")

LAYERS, FIRST = 4, 4


def tiny(**kw) -> TransformerConfig:
    """One period: three linear layers (2 key heads of 8 serving 4 value heads of 16) and a gated full-attention layer
    (4 query heads over 2 KV heads of 16, rotary on the first 4), top-3 of 16 experts, 8 held from expert 4."""
    base = dict(
        vocab_size=96, d_model=48, n_layers=LAYERS, n_heads=4, n_kv_heads=2, head_dim=16, max_seq_len=1024,
        dtype=jnp.float32, attention_impl="reference", partition_params=False, rope_theta=1e7, norm_eps=1e-6,
        layer_types=(LINEAR,) * 3 + (FULL,), linear_key_heads=2, linear_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=16, linear_conv=4, linear_chunk=8, qk_norm=True, attn_output_gate=True, partial_rotary_factor=0.25,
        moe_experts=16, moe_every=1, moe_top_k=3, moe_intermediate_size=24, moe_experts_held=(FIRST, 8), moe_shared_experts=1,
        moe_shared_intermediate_size=20, moe_shared_gate=True,
    )
    return TransformerConfig(**{**base, **kw})


def build(cfg, seed=1):
    params = meta.unbox(jax.jit(TransformerLM(cfg).init)(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    # norms away from one, so that one the program skipped or ran twice shows; experts at their own fan-in
    for i in range(cfg.n_layers):
        blk = params[f"block_{i}"]
        mixer = blk["gdn"] if "gdn" in blk else blk["attn"]
        leaves = [(blk["ln1"], "scale"), (blk["ln2"], "scale")] + [(mixer, n) for n in ("norm", "q_norm", "k_norm") if n in mixer]
        for j, (tree, n) in enumerate(leaves):
            tree[n] = tree[n] * (1.0 + 0.2 * jax.random.normal(jax.random.key(100 + 8 * i + j), tree[n].shape))
        for n in ("w_gate", "w_up", "w_down"):
            blk["moe"][n] = blk["moe"][n] * blk["moe"][n].shape[0] ** 0.5
    return params


_MOE = ("router", "w_gate", "w_up", "w_down", "shared_w_gate", "shared_w_up", "shared_w_down", "shared_gate")


def reference_weights(params, cfg):
    layers = []
    for i in range(cfg.n_layers):
        b = params[f"block_{i}"]
        if "gdn" in b:
            mixer = {**{k: b["gdn"][k] for k in ("w_in", "w_ba", "conv_w", "dt_bias", "A_log", "w_out")}, "gdn_norm": b["gdn"]["norm"]}
        else:
            mixer = {**{k: b["attn"][k]["kernel"] for k in ("wq", "wk", "wv", "wo")}, "q_norm": b["attn"]["q_norm"], "k_norm": b["attn"]["k_norm"]}
        layers.append({"mixer_norm": b["ln1"]["scale"], "ffn_norm": b["ln2"]["scale"], **mixer, **{k: b["moe"][k] for k in _MOE}})
    return {"embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"], "final_norm": params["ln_f"]["scale"], "layers": layers}


def numerics(cfg, **kw):
    said = dict(
        eps=cfg.norm_eps, rope_theta=cfg.rope_theta, rotary_dim=int(cfg.head_dim * cfg.partial_rotary_factor),
        heads=cfg.linear_value_heads, key_heads=cfg.linear_key_heads, key_dim=cfg.linear_key_head_dim,
        value_dim=cfg.linear_value_head_dim, conv=cfg.linear_conv, top_k=cfg.moe_top_k, first_expert=cfg.moe_experts_held[0],
        query_block=64, vocab_block=40,
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


def _parts(seed, b=2, s=24, h=4, dk=8, dv=16):
    """q and k [b, s, h, K] at unit length (q times K ** -0.5), v [b, s, h, V], g of heads that remember 1 to 1,000
    tokens and beta in (0, 1) [b, s, h]."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = gd.l2_heads(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = gd.l2_heads(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv), jnp.float32)
    g = -jnp.exp(jax.random.uniform(ks[3], (b, s, h), jnp.float32, np.log(1e-3), np.log(1.0)))
    return q, k, v, g, jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (b, s, h), jnp.float32))


def _recurrence(q, k, v, g, beta, **kw):
    """The reference's scan, a row of the batch at a time: o [b, s, h, V]."""
    one = jax.jit(functools.partial(reference._delta_rule, correct=True, state_dtype=jnp.float32, **kw))
    return np.stack([np.asarray(one(q[i], k[i], v[i], g[i], beta[i])) for i in range(q.shape[0])])


# ---------------------------------------------------------------------------
# the two forms of the rule against the recurrence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk,sub", [(1, 1), (5, 5), (8, 4), (24, 8), (32, 16), (24, 64)])
def test_chunks_that_carry_a_state_give_the_recurrence(chunk, sub):
    """Token for token across chunk edges, sub-chunks of several sizes (one where the size does not divide the chunk),
    lanes of unequal length, a last chunk that is part padding."""
    q, k, v, g, beta = _parts(3)
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
    # the padded end of the shorter lane advanced nothing: its state is the recurrence's after 17 tokens
    after = jax.jit(gd.gdn_recurrence)(q[1:, :17], k[1:, :17], v[1:, :17], g[1:, :17], beta[1:, :17], jnp.zeros_like(state[1:]), jnp.ones((1, 17), bool))[1]
    np.testing.assert_allclose(np.asarray(state[1]), np.asarray(after[0]), rtol=2e-4, atol=2e-6)


def test_the_rule_corrects_the_state_by_what_it_holds():
    """What tells the delta rule from a decayed sum of outer products: the same key written twice at beta 1 and no
    decay leaves the SECOND value alone (the first is taken back), where a sum would hold both."""
    k = gd.l2_heads(jnp.ones((1, 2, 1, 8)))
    v = jnp.stack([jnp.full((16,), 3.0), jnp.full((16,), -1.0)])[None, :, None, :]
    zeros, ones = jnp.zeros((1, 2, 1)), jnp.ones((1, 2, 1))
    for form in (gd.gdn_recurrence, functools.partial(gd.gdn_chunk, chunk=2)):
        out, state = form(k, k, v, zeros, ones, jnp.zeros((1, 1, 8, 16)), jnp.ones((1, 2), bool))
        np.testing.assert_allclose(np.asarray(out[0, :, 0, 0]), [3.0, -1.0], atol=1e-5)
        np.testing.assert_allclose(np.asarray(jnp.einsum("kv,k->v", state[0, 0], k[0, 0, 0])), -1.0, atol=1e-5)


def test_gradients_of_the_whole_sequence_module_through_the_scan_are_finite():
    cfg = tiny()
    mixer = GatedDeltaNet(cfg)
    u = jax.random.normal(jax.random.key(1), (2, 24, cfg.d_model), jnp.float32)
    params = mixer.init(jax.random.key(2), u)
    assert set(meta.unbox(params)["params"]) == {"w_in", "w_ba", "conv_w", "dt_bias", "A_log", "norm", "w_out"}
    loss = lambda p, u: jnp.sum(jnp.square(mixer.apply(p, u)))  # noqa: E731
    grads, du = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, u)
    leaves = jax.tree.leaves(meta.unbox(grads)) + [du]
    assert all(bool(jnp.isfinite(leaf).all()) for leaf in leaves) and all(float(jnp.abs(leaf).max()) > 0 for leaf in leaves)
    # causal: a later token moves no earlier output
    moved = mixer.apply(params, u.at[:, 17].add(1.0)) - mixer.apply(params, u)
    assert float(jnp.abs(moved[:, :17]).max()) == 0.0 and float(jnp.abs(moved[:, 17:]).max()) > 0


@pytest.mark.parametrize("impl,dims", [("jnp", (4, 8, 16)), ("kernel_interpret", (8, 16, 128))])
def test_decode_steps_give_the_recurrence_and_leave_idle_lanes_alone(impl, dims):
    """One token a lane a step into layer 1 of a pool of three: the live lanes' answers are the recurrence's, token
    for token; an idle lane's slot, the scratch slot's neighbours and the other layers stay as they were."""
    h, dk, dv = dims
    q, k, v, g, beta = _parts(5, b=3, s=6, h=h, dk=dk, dv=dv)
    want = _recurrence(q, k, v, g, beta)
    pool = jax.random.normal(jax.random.key(9), gd.state_shape(3, 3, h, dk, dv), jnp.float32)
    pool = pool.at[1, jnp.asarray([0, 2])].set(0.0)                                  # lanes 0 and 2 start a sequence
    start = np.asarray(pool)
    live = jnp.asarray([True, False, True])
    for t in range(q.shape[1]):
        o, pool = gd.gdn_decode(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], pool, 1, live, impl=impl)
        np.testing.assert_allclose(np.asarray(o)[[0, 2]], want[[0, 2], t], rtol=3e-4, atol=3e-5)
        assert not np.asarray(o)[1].any()
    after = np.asarray(pool)
    np.testing.assert_array_equal(after[[0, 2]], start[[0, 2]])                      # the other layers
    np.testing.assert_array_equal(after[1, 1], start[1, 1])                          # the idle lane's slot
    assert np.abs(after[1, 0] - start[1, 0]).max() > 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_in_interpret_mode_agrees_with_its_jnp_form(dtype):
    """At the published head: keys and values of 128, 32 value heads (one program's 2 MB); a state held in either
    dtype; dead lanes among the live ones."""
    h, dk, dv = 32, 128, 128
    assert gd.kernel_takes(h, dk, dv, dtype) and not gd.kernel_takes(4, 8, 16, dtype) and not gd.kernel_takes(32, 128, 64, dtype)
    assert gd.heads_a_program(h, dk, dv, jnp.float32) == 32 and gd.heads_a_program(64, dk, dv, jnp.float32) == 32
    q, k, v, g, beta = _parts(6, b=4, s=1, h=h, dk=dk, dv=dv)
    pool = jax.random.normal(jax.random.key(2), gd.state_shape(2, 4, h, dk, dv), jnp.float32).astype(dtype)
    live = jnp.asarray([True, False, False, True])
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], pool, 1, live)
    o0, s0 = gd.gdn_decode(*args, impl="jnp")
    o1, s1 = gd.gdn_decode(*args, impl="kernel_interpret")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o0), rtol=2e-4, atol=2e-4)
    lanes = slice(0, 4)                                                              # the scratch slot is nobody's
    np.testing.assert_allclose(np.asarray(s1[:, lanes], np.float32), np.asarray(s0[:, lanes], np.float32), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s1[1, 1:3], np.float32), np.asarray(pool[1, 1:3], np.float32))
    with pytest.raises(ValueError, match="values of whole 128-wide tiles"):
        small = _parts(6, b=4, s=1)
        gd.gdn_decode(*(t[:, 0] for t in small), jnp.zeros(gd.state_shape(1, 4, 4, 8, 16)), 0, live, impl="kernel")


# ---------------------------------------------------------------------------
# the mixer with its convolution: chunks of any size, a state and a tail carried
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [3, 7, 16, 64])
def test_the_mixer_carries_a_state_and_a_tail_over_chunks_of_any_size(model, chunk):
    """The kind's walk form called a chunk at a time as the prefill walk calls it, three lanes of unequal length
    into lanes 2, 0, 3 of four: what it adds to the stream is the reference's mixer on the whole sequence."""
    cfg, params, _, _ = model
    p = params["block_1"]["gdn"]
    lens, lanes, s = np.asarray([50, 37, 9]), jnp.asarray([2, 0, 3]), 50
    u = jax.random.normal(jax.random.key(11), (3, s, cfg.d_model), jnp.float32)
    w = {k: jnp.asarray(v, jnp.float32) for k, v in reference_weights(params, cfg)["layers"][1].items()}
    told = {k: v for k, v in numerics(cfg).items() if k in ("heads", "key_heads", "key_dim", "value_dim", "conv", "eps")}
    mixer = jax.jit(lambda row: reference._gated_delta_net(row, w, correct=True, beta_one=False, gate_before_norm=False, state_dtype=jnp.float32, **told))
    want = np.stack([np.asarray(mixer(u[i])) for i in range(3)])
    cache = init_kv_cache(cfg, 8, 4, lanes=4)
    cache = {k: v + 7.0 for k, v in cache.items()}                                   # what earlier sequences left in the lanes
    before = {k: np.asarray(v) for k, v in cache.items()}

    @jax.jit
    def at_chunk(c, cache):
        live = jnp.asarray(lens)[:, None] > c * chunk + jnp.arange(chunk)[None, :]
        rows = Rows(c * chunk + jnp.arange(chunk), None, live, None, 4, lanes, chunk=c, first_chunk=0, offsets=jnp.arange(chunk))
        part = jax.lax.dynamic_slice_in_dim(jnp.pad(u, ((0, 0), (0, chunk), (0, 0))), c * chunk, chunk, axis=1)
        x, cache = DELTA_SLOT.walk(cfg, cache, lanes, chunk)(rows)(p, jnp.zeros_like(part), part, cache, 1)
        return x, cache

    outs = []
    for c in range(-(-s // chunk)):
        out, cache = at_chunk(c, cache)
        outs.append(np.asarray(out))
    got = np.concatenate(outs, axis=1)
    for i, n in enumerate(lens):
        np.testing.assert_allclose(got[i, :n], want[i, :n], rtol=3e-4, atol=3e-5)
    # lane 1, layer 0 and the pool were nobody's: untouched
    state_leaf, tail_leaf = DELTA_SLOT.leaves
    for leaf in DELTA_SLOT.leaves:
        np.testing.assert_array_equal(np.asarray(cache[leaf])[0], before[leaf][0])
        np.testing.assert_array_equal(np.asarray(cache[leaf])[1, 1], before[leaf][1, 1])
    # the tail a lane keeps: the last three rows of the convolution's input before the prompt's end
    qkv = cache_kinds._gdn_project(cfg, p, u)[0]
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(cache[tail_leaf])[1, int(lanes[i])], np.asarray(qkv)[i, n - 3:n], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the model: the whole-sequence form, the walk and the decode step against the reference
# ---------------------------------------------------------------------------


def test_a_model_is_of_two_kinds_and_the_cache_holds_both(model):
    cfg, _, _, _ = model
    assert cache_kinds.cache_kinds(cfg) == (PAGED_KV, DELTA_SLOT) and (PAGED_KV.holds, DELTA_SLOT.holds) == (BLOCKS, LANE)
    assert [layer_kinds(cfg, i) for i in range(4)] == [((DELTA_SLOT, 0, "gdn"),), ((DELTA_SLOT, 1, "gdn"),), ((DELTA_SLOT, 2, "gdn"),), ((PAGED_KV, 0, "attn"),)]
    assert cfg.linear_layers == (0, 1, 2) and cfg.rowless_layers == (0, 1, 2) and cfg.paged_layers == 1
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 40, 4, lanes=3, chunk_tokens=16))
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "k": ((1, 40, 4, 32), "float32"), "v": ((1, 40, 4, 32), "float32"),
        "gdn": ((3, 4, 4, 8, 16), "float32"), "gconv": ((3, 3, 3, 96), "float32"),   # a slot a lane and one scratch; three rows a tail
    }
    assert gdn_pool_shapes(cfg, 3) == ((3, 4, 4, 8, 16), (3, 3, 3, 96)) and gdn_bytes_per_slot(cfg) == 4 * 8 * 16 * 4
    assert kv_bytes_per_token(cfg) == 1 * 2 * 2 * 16 * 4
    assert serve_counters(cfg) == ("serve.gdn.live_lanes", "serve.gdn.bytes", "serve.moe.held_picks", "serve.moe.experts_hit")
    half = jax.eval_shape(lambda: init_kv_cache(tiny(dtype=jnp.bfloat16), 40, 4, lanes=3))
    assert (str(half["gdn"].dtype), str(half["gconv"].dtype), str(half["k"].dtype)) == ("float32", "bfloat16", "bfloat16")
    # the published widths: 32 value heads of 128 x 128 float32, 2 MB a lane a layer, and three rows of 8,192 channels
    wide = tiny(linear_key_heads=16, linear_value_heads=32, linear_key_head_dim=128, linear_value_head_dim=128, dtype=jnp.bfloat16)
    assert gdn_pool_shapes(wide, 64) == ((3, 65, 32, 128, 128), (3, 64, 3, 8192)) and gdn_bytes_per_slot(wide) == 2_097_152
    with pytest.raises(ValueError, match="needs its lanes"):
        init_kv_cache(cfg, 40, 4)


@pytest.mark.parametrize("kw,says", [
    (dict(linear_value_heads=3), "a linear_attention layer needs linear_value_heads"),
    (dict(parallel_block=True), "it does not run under parallel_block or shortcut_block"),
    (dict(kv_lora_rank=8, q_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, qk_norm=False, attn_output_gate=False, partial_rotary_factor=1.0,
          layer_types=(FULL,) * 4, indexer_types=("full",) * 4, index_n_heads=2, index_head_dim=8, index_topk=4, linear_decay_floor=-5.0),
     "linear_decay_floor .* belongs to linear_attention layers"),
    (dict(kv_lora_rank=8, q_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, qk_norm=False, attn_output_gate=False, partial_rotary_factor=1.0,
          indexer_types=("full",) * 4, index_n_heads=2, index_head_dim=8, index_topk=4), "beside an indexer"),
    (dict(seq_axis_name="seq"), "under a `seq` axis"),
    (dict(mixer_block=True), "a linear_attention layer sits in a sequential block"),
    (dict(layer_types=("power_retention",) * 4, qk_norm=False, partial_rotary_factor=1.0), "attn_output_gate gates GQA's attention layers"),
    (dict(moe_shared_experts=0, moe_shared_intermediate_size=None), "moe_shared_gate gates the shared experts"),
])
def test_configurations_the_program_cannot_run_are_refused_by_name(kw, says):
    with pytest.raises(ValueError, match=says):
        tiny(**kw)


def test_the_whole_sequence_form_and_the_wide_prefill_are_the_reference(model):
    cfg, params, tokens, want = model
    got = jax.jit(lambda p, t: TransformerLM(cfg).apply({"params": p}, t))(params, jnp.asarray(tokens[:, :100]))
    np.testing.assert_allclose(np.asarray(got), want[:, :100], rtol=3e-4, atol=3e-5)   # 100 tokens: not whole sub-chunks of 8
    got = jax.jit(lambda p, t: TransformerLM(cfg).apply({"params": p}, t))(params, jnp.asarray(tokens[:1, :96]))
    np.testing.assert_allclose(np.asarray(got), want[:1, :96], rtol=3e-4, atol=3e-5)   # 96: a scan of twelve
    cache = init_kv_cache(cfg, 80, 4, lanes=3)
    tables = jnp.asarray(1 + np.arange(3 * 25).reshape(3, 25), jnp.int32)
    lens = jnp.asarray([100, 61, 7])
    logits, cache = jax.jit(functools.partial(transformer_prefill, cfg))(params, jnp.asarray(tokens[:, :100]), lens, tables, cache)
    for i, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(np.asarray(logits)[i, :n], want[i, :n], rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("told", [
    dict(correct=False), dict(beta_one=True), dict(output_gate=False), dict(shared_gate=False), dict(gate_before_norm=True),
    dict(rotary_all=True), dict(state_dtype=jnp.bfloat16),
])
def test_each_control_of_the_reference_is_told_from_the_model(model, told):
    """What the serving check's controls leave out or do otherwise moves the logits by far more than the tolerance."""
    cfg, params, tokens, want = model
    other = oracle(cfg, params, tokens[:1, :200], **told)
    scale = np.sqrt(np.mean(want[:1, :200] ** 2))
    assert np.sqrt(np.mean((other - want[:1, :200]) ** 2)) / scale > 1e-3, told


def test_the_walk_and_the_decode_step_are_the_reference_in_lanes_of_unequal_length(model):
    """Prompts of 300, 270 and 40 tokens into lanes 3, 0 and 2 of four: the walk's chunk is 256, so two of them cross
    a chunk's edge and end in a part-padded chunk; then 40 decode steps in the three lanes at once, lane 1 idle."""
    cfg, params, tokens, want = model
    block, lanes = 4, jnp.asarray([3, 0, 2])
    cache = init_kv_cache(cfg, 3 * 100 + 1, block, lanes=4)
    cache = {k: (v + 5.0 if k in DELTA_SLOT.leaves else v) for k, v in cache.items()}  # a reused lane: the walk must zero it
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
    for t in range(40):
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
        np.testing.assert_array_equal(np.asarray(logits)[4, :2], [3.0, 3.0 * 3 * gdn_bytes_per_slot(cfg)])
    for leaf in DELTA_SLOT.leaves:                                                      # the idle lane's slot and tail
        np.testing.assert_array_equal(np.asarray(cache[leaf])[:, 1], idle[leaf])


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Two chips' shares here (experts 0-7 and 8-15 of 16), the shared expert and its gate counted once: what the
    program's expert layer gives each share adds up to the reference's layer over ALL experts."""
    from determined_tpu.models.moe import serve_routed_experts

    cfg = tiny(moe_experts_held=None)
    blk = build(cfg)["block_0"]["moe"]
    h = jax.random.normal(jax.random.key(3), (1, 40, cfg.d_model), jnp.float32)
    told = dict(top_k=cfg.moe_top_k, first_expert=0, shared_gate=True)
    with jax.default_matmul_precision("highest"):
        whole = reference._experts(h[0], {k: blk[k] for k in _MOE}, **told)
        shared_alone = reference._experts(h[0], {k: blk[k] for k in _MOE}, **{**told, "first_expert": 1000})  # no pick lands on a held expert
    total = 0.0
    for first in (0, 8):
        share = {**blk, **{n: blk[n][first:first + 8] for n in ("w_gate", "w_up", "w_down")}}
        y, _ = jax.jit(functools.partial(serve_routed_experts, tiny(moe_experts_held=(first, 8))))(share, h)
        total = total + np.asarray(y[0])
    np.testing.assert_allclose(total - np.asarray(shared_alone), np.asarray(whole), rtol=3e-4, atol=3e-5)
    assert float(jnp.abs(shared_alone).max()) > 1e-3 and float(jnp.abs(whole - shared_alone).max()) > 1e-3


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
    assert stats["gdn"] == {"slots": 3, "live": 0, "bytes_per_slot": 3 * gdn_bytes_per_slot(cfg)}
    assert "ssm" not in stats and "state" not in stats and "block_ids_address_nothing" not in stats
    assert {"serve.gdn.live_lanes", "serve.gdn.bytes"} <= set(stats["step_counters"]) and stats["step_counters"]["serve.gdn.live_lanes"] == 22.0


def test_prefix_cache_is_refused_by_name_and_a_prefill_starts_at_zero(model):
    cfg, params, _, _ = model
    with pytest.raises(ValueError) as refused:
        _engine(cfg, params, prefix_cache=True)
    assert str(refused.value) == DELTA_SLOT.no_prefix_cache and "Gated-DeltaNet (linear_attention) layer" in str(refused.value)
    assert "Set prefix_cache: false" in str(refused.value)
    kernels = _engine(cfg, params).kernels
    with pytest.raises(ValueError, match="is prefilled from 0, not from 8"):
        kernels.prefill_suffix(list(range(1, 20)), [0] * kernels.serve_cfg.blocks_per_seq, 8, 1)
