"""Power-retention layers served from a cache of a third kind: a fixed-size
state a decode lane, no keys or values a token (models/transformer.py,
ops/retention.py, serve/engine.py), against the plain reference the benchmark
keeps (benchmark/reference/power_retention.py: float32, the quadratic form
only, no state, no feature map, no import from the program).  CPU, tiny
sizes, seeded weights; the Pallas kernel in interpret mode."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from determined_tpu.models import cache_kinds
from determined_tpu.models.cache_kinds import LANE, STATE_SLOT, layer_kinds
from determined_tpu.models.serving import (
    init_kv_cache,
    serve_counters,
    transformer_decode,
    transformer_prefill,
    transformer_prefill_chunked,
)
from determined_tpu.models.transformer import (
    RETENTION,
    TransformerConfig,
    TransformerLM,
    kv_bytes_per_token,
    state_bytes_per_slot,
    state_pool_shapes,
)
from determined_tpu.ops import retention
from determined_tpu.serve.config import ServeConfig
from determined_tpu.serve.engine import DecodeKernels, ServeEngine
from tests.model_cases import reference_module, retention_chunk_step as _chunk_step, retention_heads as _heads

reference = reference_module("power_retention")

LAYERS, GATE_BIAS = 2, 3.0


def tiny(**kw) -> TransformerConfig:
    """Two retention layers; 4 heads of 16 over 2 KV heads; q/k norm; a gate that remembers ~20 tokens."""
    base = dict(
        vocab_size=96, d_model=48, n_layers=LAYERS, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=512,
        dtype=jnp.float32, attention_impl="reference", partition_params=False, rope_theta=1e6,
        layer_types=(RETENTION,) * LAYERS, qk_norm=True, retention_gate_bias=GATE_BIAS,
    )
    return TransformerConfig(**{**base, **kw})


def build(cfg, seed=1):
    params = meta.unbox(jax.jit(TransformerLM(cfg).init)(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    # norms away from one, so that a norm the program skipped or ran twice shows
    leaves = [params["ln_f"]] + [params[f"block_{i}"][n] for i in range(cfg.n_layers) for n in ("ln1", "ln2")]
    for i, leaf in enumerate(leaves):
        leaf["scale"] = leaf["scale"] * (1.0 + 0.1 * jax.random.normal(jax.random.key(100 + i), leaf["scale"].shape))
    for i in range(cfg.n_layers):
        attn = params[f"block_{i}"]["attn"]
        for j, n in enumerate(("q_norm", "k_norm") if cfg.qk_norm else ()):
            attn[n] = attn[n] * (1.0 + 0.1 * jax.random.normal(jax.random.key(200 + 2 * i + j), attn[n].shape))
    return params


def reference_weights(params, cfg):
    layers = []
    for i in range(cfg.n_layers):
        b = params[f"block_{i}"]
        layers.append({
            "attn_norm": b["ln1"]["scale"], "mlp_norm": b["ln2"]["scale"], "q_norm": b["attn"]["q_norm"], "k_norm": b["attn"]["k_norm"],
            **{k: b["attn"][k]["kernel"] for k in ("wq", "wk", "wv", "wg", "wo")},
            **{k: b["mlp"][k]["kernel"] for k in ("w_gate", "w_up", "w_down")},
        })
    return {"embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"], "final_norm": params["ln_f"]["scale"], "layers": layers}


def numerics(cfg, **kw):
    return {**dict(eps=cfg.norm_eps, rope_theta=cfg.rope_theta, gate_bias=cfg.retention_gate_bias, query_block=16, mlp_block=24, vocab_block=40), **kw}


def oracle(cfg, params, tokens, **kw):
    forward = jax.jit(functools.partial(reference.forward, **numerics(cfg, **kw)))
    return np.stack([np.asarray(forward(reference_weights(params, cfg), jnp.asarray(row))) for row in tokens])


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = build(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(0), (3, 100), 1, cfg.vocab_size))
    return cfg, params, tokens, oracle(cfg, params, tokens)


# ---------------------------------------------------------------------------
# the feature map and the three forms
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d", [2, 8, 16, 128])
def test_phi_of_x_dot_phi_of_y_is_x_dot_y_squared(d):
    x, y = jax.random.normal(jax.random.key(d), (2, 5, d), jnp.float32)
    assert retention.phi(x).shape == (5, d // 2 + 1, d)
    got = jnp.sum(retention.phi(x) * retention.phi(y), axis=(-1, -2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.sum(x * y, -1) ** 2), rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("chunk", [1, 5, 8, 24])
def test_chunks_that_carry_a_state_give_the_quadratic_form(chunk):
    q, k, v, log_g = _heads(3)
    b, g, s, d = k.shape
    want = retention.retention_quadratic(q, k, v, log_g)
    shapes = retention.state_shapes(1, b, g, d)
    state, norm, outs = jnp.zeros(shapes[0][1:]), jnp.zeros(shapes[1][1:]), []
    for lo in range(0, s, chunk):
        hi = min(lo + chunk, s)
        pad = lambda t: jnp.pad(t[:, :, lo:hi], ((0, 0), (0, 0), (0, chunk - (hi - lo))) + ((0, 0),) * (t.ndim - 3))  # noqa: E731
        valid = jnp.broadcast_to(jnp.arange(chunk) < hi - lo, (b, chunk))
        out, state, norm = _chunk_step("jnp")(pad(q), pad(k), pad(v), pad(log_g), state, norm, valid)
        outs.append(out[:, :, : hi - lo])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=2)), np.asarray(want), atol=2e-5)


def test_a_token_at_a_time_gives_the_quadratic_form_and_the_chunks_state():
    q, k, v, log_g = _heads(4)
    b, g, s, d = k.shape
    want = retention.retention_quadratic(q, k, v, log_g)
    shapes = retention.state_shapes(2, b, g, d)
    state, norm, outs = jnp.zeros(shapes[0]), jnp.zeros(shapes[1]), []
    for t in range(s):
        out, state, norm = retention.retention_decode(q[:, :, t], k[:, :, t], v[:, :, t], log_g[:, :, t], state, norm, 1, jnp.ones(b, bool))
        outs.append(out)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, axis=2)), np.asarray(want), atol=2e-4, rtol=2e-3)
    _, whole, whole_norm = _chunk_step("jnp")(q, k, v, log_g, jnp.zeros(shapes[0][1:]), jnp.zeros(shapes[1][1:]), jnp.ones((b, s), bool))
    np.testing.assert_allclose(np.asarray(state[1]), np.asarray(whole), atol=2e-5)
    np.testing.assert_allclose(np.asarray(norm[1]), np.asarray(whole_norm), atol=2e-5)
    assert not np.asarray(state[0]).any() and not np.asarray(norm[0]).any()          # the other layer's slots: untouched


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n_rep", [1, 5])
def test_the_kernel_in_interpret_mode_is_its_jnp_form(n_rep, state_dtype):
    lanes, g, d = 3, 2, 128
    ks = jax.random.split(jax.random.key(n_rep), 6)
    q = jax.random.normal(ks[0], (lanes, g * n_rep, d), jnp.bfloat16)
    k, v = (jax.random.normal(ks[i], (lanes, g, d), jnp.bfloat16) for i in (1, 2))
    log_g = jax.nn.log_sigmoid(4.0 + jax.random.normal(ks[3], (lanes, g)))
    shapes = retention.state_shapes(2, lanes, g, d)
    state = jax.random.normal(ks[4], shapes[0]).astype(state_dtype)
    norm = (1.0 + jnp.abs(jax.random.normal(ks[5], shapes[1]))).astype(state_dtype)
    live = jnp.asarray([True, False, True])
    want = retention.retention_decode(q, k, v, log_g, state, norm, 1, live, impl="jnp")
    got = retention.retention_decode(q, k, v, log_g, state, norm, 1, live, impl="kernel_interpret")
    tol = 2e-3 if state_dtype == jnp.float32 else 0.15
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=tol, rtol=1e-2 if state_dtype != jnp.float32 else 1e-5)
    # the idle lane's slot and the other layer: bit for bit what they were
    assert np.array_equal(np.asarray(got[1][1, 1], np.float32), np.asarray(state[1, 1], np.float32))
    assert np.array_equal(np.asarray(got[1][0], np.float32), np.asarray(state[0], np.float32))
    assert not np.asarray(got[0][1]).any()
    with pytest.raises(ValueError, match="head_dim 128"):
        retention.retention_decode(q[..., :64], k[..., :64], v[..., :64], log_g, state, norm, 0, live, impl="kernel")


# ---------------------------------------------------------------------------
# the block as published, and the program against the reference
# ---------------------------------------------------------------------------


def test_the_full_forward_builds_the_published_block_and_matches_the_reference(model):
    cfg, params, tokens, want = model
    assert set(params["block_0"]) == {"ln1", "attn", "ln2", "mlp"}
    assert {n: tuple(v.shape) if hasattr(v, "shape") else tuple(v["kernel"].shape) for n, v in params["block_0"]["attn"].items()} == {
        "wq": (48, 4, 16), "wk": (48, 2, 16), "wv": (48, 2, 16), "wg": (48, 2), "q_norm": (16,), "k_norm": (16,), "wo": (4, 16, 48),
    }
    assert cfg.retention_layers == STATE_SLOT.layers(cfg) == (0, 1) and cfg.paged_layers == 0
    assert [layer_kinds(cfg, i) for i in range(2)] == [((STATE_SLOT, 0),), ((STATE_SLOT, 1),)]
    got = jax.jit(TransformerLM(cfg).apply)({"params": params}, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)
    half = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(lambda: TransformerLM(half).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))
    assert {str(x.dtype) for x in leaves} == {"bfloat16"}


@pytest.mark.parametrize("told, by", [
    ({"degree": 1}, "degree 1"), ({"gated": False}, "g = 1"), ({"normalised": False}, "no normaliser"),
    ({"rotary": False}, "no rotary"), ({"kv_int8": True}, "k and v in int8"), ({"gate_bias": 0.0}, "another gate bias"),
    ({"eps": 1e-2}, "another eps"),
])
def test_the_reference_told_otherwise_disagrees(model, told, by):
    cfg, params, tokens, want = model
    other = oracle(cfg, params, tokens[:1], **told)
    assert np.abs(other - want[:1]).max() > 1e-3, by


def test_configurations_the_program_cannot_run_are_refused():
    with pytest.raises(ValueError, match="power_retention layer runs in a sequential block"):
        tiny(parallel_block=True)
    with pytest.raises(ValueError, match="power_retention layer runs in a sequential block"):
        tiny(head_dim=15)
    with pytest.raises(ValueError, match="qk_norm runs in power_retention layers only"):
        tiny(layer_types=(RETENTION, "full_attention"))
    with pytest.raises(ValueError, match="needs its lanes"):
        init_kv_cache(tiny(), 8, 4)


def test_the_cache_is_a_state_pool_and_no_token_owns_a_byte_of_it(model):
    cfg = model[0]
    cache = init_kv_cache(cfg, 64, 4, lanes=3)
    assert set(cache) == {"rs", "rz"}                                                  # no paged K, V for a model no layer of which reads one
    assert cache["rs"].shape == (2, 3, 2, 9 * 16, 16) and cache["rz"].shape == (2, 3, 2, 9, 16)
    assert {str(a.dtype) for a in cache.values()} == {"float32"}
    assert state_pool_shapes(cfg, 3) == (cache["rs"].shape, cache["rz"].shape)
    assert kv_bytes_per_token(cfg) == 0
    assert state_bytes_per_slot(cfg) == 2 * (9 * 16 * 16 + 9 * 16) * 4
    assert serve_counters(cfg) == STATE_SLOT.counters
    mixed = dataclasses.replace(cfg, layer_types=(RETENTION, "full_attention"), qk_norm=False)
    assert set(init_kv_cache(mixed, 8, 4, lanes=2)) == {"k", "v", "rs", "rz"} and kv_bytes_per_token(mixed) == 2 * 2 * 16 * 4
    # the published widths: 8,320 features of a head of 128, 34.3 MB a lane a layer
    wide = dataclasses.replace(cfg, n_heads=40, n_kv_heads=8, head_dim=128, d_model=5120)
    assert state_pool_shapes(wide, 32)[0] == (2, 32, 8, 8320, 128) and state_bytes_per_slot(wide) == 8 * (8320 * 128 + 8320) * 4 == 34_344_960


def _walk(cfg, params, tokens, lens, lanes, n_lanes, chunk, cache=None):
    """The prefill walk over prompts padded to whole chunks, into ``lanes``."""
    cache = init_kv_cache(cfg, 8, 4, lanes=n_lanes) if cache is None else cache
    width = -(-int(max(lens)) // chunk) * chunk
    padded = np.zeros((len(lens), width), np.int32)
    for i, n in enumerate(lens):
        padded[i, :n] = tokens[i, :n]
    fn = jax.jit(functools.partial(transformer_prefill_chunked, cfg, chunk_tokens=chunk))
    return fn(params, jnp.asarray(padded), jnp.zeros(len(lens), jnp.int32), jnp.asarray(lens, jnp.int32),
              jnp.zeros((len(lens), 1), jnp.int32), cache, jnp.asarray(lanes, jnp.int32))


@pytest.mark.parametrize("chunk", [4, 16, 64])
def test_the_walk_and_the_decode_step_follow_the_reference_token_for_token(model, chunk):
    """Three prompts of unequal length walked into lanes 4, 0 and 2 of five,
    then decoded together (lane 1 and 3 idle) to the sequences' ends: every
    logit against the reference's full forward, across chunk edges."""
    cfg, params, tokens, want = model
    lens, lanes, n_lanes = [37, 9, 64], [4, 0, 2], 5
    last, cache = _walk(cfg, params, tokens, lens, lanes, n_lanes, chunk)
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(last[i]), want[i, n - 1], atol=1e-4)
    decode = jax.jit(functools.partial(transformer_decode, cfg, counters=True))
    before = {k: np.asarray(v) for k, v in cache.items()}
    for step in range(30):
        toks, pos = np.zeros(n_lanes, np.int32), np.full(n_lanes, -1, np.int32)
        for i, lane in enumerate(lanes):
            toks[lane], pos[lane] = tokens[i, lens[i] + step], lens[i] + step
        logits, cache = decode(params, jnp.asarray(toks), jnp.asarray(pos), jnp.zeros((n_lanes, 1), jnp.int32), cache)
        for i, lane in enumerate(lanes):
            np.testing.assert_allclose(np.asarray(logits[lane]), want[i, lens[i] + step], atol=2e-4)
        counted = dict(zip(serve_counters(cfg), np.asarray(logits[-1])))
        assert counted == {"serve.state.live_lanes": 3.0, "serve.state.bytes": 3.0 * LAYERS * state_bytes_per_slot(cfg)}
    for name in ("rs", "rz"):                                                          # an idle lane's slot is left alone
        assert np.array_equal(np.asarray(cache[name])[:, [1, 3]], before[name][:, [1, 3]])
        assert not np.array_equal(np.asarray(cache[name])[:, 4], before[name][:, 4])


def test_the_wide_prefill_is_the_walk(model):
    cfg, params, tokens, want = model
    lens = [37, 9, 64]
    padded = np.where(np.arange(64)[None, :] < np.asarray(lens)[:, None], tokens[:, :64], 0)
    logits, cache = jax.jit(functools.partial(transformer_prefill, cfg))(
        params, jnp.asarray(padded), jnp.asarray(lens, jnp.int32), jnp.zeros((3, 1), jnp.int32), init_kv_cache(cfg, 8, 4, lanes=3)
    )
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(logits[i, :n]), want[i, :n], atol=1e-4)
    _, walked = _walk(cfg, params, tokens, lens, [0, 1, 2], 3, 16)
    for name in ("rs", "rz"):
        np.testing.assert_allclose(np.asarray(cache[name]), np.asarray(walked[name]), atol=1e-4)


def test_a_model_that_mixes_retention_with_full_layers_serves_from_a_cache_of_both_kinds():
    """A retention layer under a full-attention layer: the walk (two chunks of
    256: the state carried once, keys read back from the pool) into lane 1 of
    two and eight decode steps, against the program's own full forward."""
    cfg = tiny(layer_types=(RETENTION, "full_attention"), qk_norm=False)
    params = build(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(7), (1, 308), 1, cfg.vocab_size))
    want = np.asarray(jax.jit(TransformerLM(cfg).apply)({"params": params}, jnp.asarray(tokens)))[0]
    block, n = 4, 300
    table = jnp.arange(1, 512 // block + 1, dtype=jnp.int32)[None, :]              # block 0 is the scratch block
    cache = init_kv_cache(cfg, 512 // block + 1, block, lanes=2)
    assert set(cache) == {"k", "v", "rs", "rz"} and cache["k"].shape[0] == cache["rs"].shape[0] == 1
    padded = np.zeros((1, 512), np.int32)
    padded[0, :n] = tokens[0, :n]
    last, cache = jax.jit(functools.partial(transformer_prefill_chunked, cfg))(
        params, jnp.asarray(padded), jnp.zeros(1, jnp.int32), jnp.asarray([n], jnp.int32), table, cache, jnp.asarray([1], jnp.int32)
    )
    np.testing.assert_allclose(np.asarray(last[0]), want[n - 1], atol=2e-4)
    assert not np.asarray(cache["rs"])[:, 0].any() and np.asarray(cache["rs"])[:, 1].any()
    decode = jax.jit(functools.partial(transformer_decode, cfg))
    tables = jnp.concatenate([jnp.zeros_like(table), table])                         # lane 0 idles
    for step in range(8):
        toks, pos = np.asarray([0, tokens[0, n + step]], np.int32), np.asarray([-1, n + step], np.int32)
        logits, cache = decode(params, jnp.asarray(toks), jnp.asarray(pos), tables, cache)
        np.testing.assert_allclose(np.asarray(logits[1]), want[n + step], atol=2e-4)


def test_a_sequence_that_starts_in_a_used_lane_starts_from_a_zeroed_slot(model):
    cfg, params, tokens, want = model
    _, used = _walk(cfg, params, tokens[2:], [64], [1], 2, 16)
    last, again = _walk(cfg, params, tokens[:1], [37], [1], 2, 16, cache=used)
    _, fresh = _walk(cfg, params, tokens[:1], [37], [1], 2, 16)
    np.testing.assert_allclose(np.asarray(last[0]), want[0, 36], atol=1e-4)
    for name in ("rs", "rz"):
        assert np.array_equal(np.asarray(again[name]), np.asarray(fresh[name]))


def test_the_decode_step_through_the_kernel_is_the_step_through_jnp(monkeypatch):
    """At a head of 128 (the kernel's shape), two layers, three lanes of which one idles."""
    cfg = tiny(d_model=64, n_heads=2, n_kv_heads=1, head_dim=128, d_ff=32, vocab_size=64)
    params = build(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(5), (2, 12), 1, cfg.vocab_size))
    _, cache = _walk(cfg, params, tokens, [8, 5], [2, 0], 3, 4)
    toks, pos = jnp.asarray([tokens[1, 5], 0, tokens[0, 8]], jnp.int32), jnp.asarray([5, -1, 8], jnp.int32)
    outs = {}
    for impl in ("jnp", "kernel_interpret"):
        monkeypatch.setattr(cache_kinds, "retention_decode", functools.partial(retention.retention_decode, impl=impl))
        step = jax.jit(functools.partial(transformer_decode, cfg))
        outs[impl] = step(params, toks, pos, jnp.zeros((3, 1), jnp.int32), cache)
    np.testing.assert_allclose(np.asarray(outs["jnp"][0])[[0, 2]], np.asarray(outs["kernel_interpret"][0])[[0, 2]], atol=2e-4)
    for name in ("rs", "rz"):
        np.testing.assert_allclose(np.asarray(outs["jnp"][1][name]), np.asarray(outs["kernel_interpret"][1][name]), atol=1e-4, rtol=1e-5)
    want = oracle(cfg, params, tokens)
    np.testing.assert_allclose(np.asarray(outs["kernel_interpret"][0][2]), want[0, 8], atol=3e-4)


# ---------------------------------------------------------------------------
# the engine: a request holds a state slot
# ---------------------------------------------------------------------------


def serve_cfg(**kw) -> ServeConfig:
    base = dict(block_size=4, num_blocks=17, max_batch=2, max_prompt_len=48, max_new_tokens=16, queue_depth=8, prefix_cache=False)
    return ServeConfig(**{**base, **kw})


@pytest.fixture(scope="module")
def engine(model):
    cfg, params, *_ = model
    # 16 usable block ids for two lanes of 16 blocks each: the ids address nothing and never bind
    return ServeEngine(DecodeKernels(cfg, params, serve_cfg(num_blocks=17)))


def test_generate_is_the_references_argmax_and_a_reused_lane_starts_afresh(model, engine):
    cfg, params, tokens, _ = model
    kernels = engine.kernels
    # one kind, held by the lane: no layer reads a pool, and a request holds no block
    assert kernels.kinds == (STATE_SLOT,) and STATE_SLOT.holds == LANE and set(kernels.cache) == set(STATE_SLOT.leaves) == {"rs", "rz"}

    def greedy(prompt, new):
        seq = list(prompt)
        for _ in range(new):
            seq.append(int(oracle(cfg, params, np.asarray([seq]))[0, -1].argmax()))
        return seq[len(prompt):]

    for prompt, new in ((tokens[0, :21].tolist(), 6), (tokens[1, :5].tolist(), 4), (tokens[2, :40].tolist(), 3)):
        req = engine.submit(prompt, max_new_tokens=new)
        while not req.done.is_set():
            assert engine.step_once()
        assert req.error is None and req.output == greedy(prompt, new)                  # each ran in lane 0, after another
    stats = engine.stats()
    assert stats["state"] == {"slots": 2, "live": 0, "bytes_per_slot": LAYERS * state_bytes_per_slot(cfg)}
    assert stats["block_ids_address_nothing"] is True and stats["kv_cache"]["used"] == 0
    assert stats["step_counters"]["serve.state.live_lanes"] == 6 + 4 + 3 - 3
    assert "attn_products" not in stats and stats["window_store"] == {}


def test_admission_is_by_slots_and_never_by_blocks(model):
    cfg, params, tokens, _ = model
    # 16 usable block ids for two lanes that would need 16 each if a block held anything
    small = ServeEngine(DecodeKernels(cfg, params, serve_cfg(num_blocks=17)))
    small.allocator.alloc(small.allocator.capacity)                                     # every id taken: it must not matter
    reqs = [small.submit(tokens[i, :30].tolist(), max_new_tokens=12) for i in range(3)]
    assert small.step_once()
    assert small.lanes.stats()["active"] == 2 and small.queue.depth() == 1             # both lanes busy: the third waits
    assert small.stats()["state"]["live"] == 2
    while not all(r.done.is_set() for r in reqs):
        assert small.step_once()
    assert [r.error for r in reqs] == [None] * 3 and all(len(r.output) == 12 for r in reqs)
    assert small.stats()["rejected"] == 0


def test_the_prefix_cache_is_refused_by_name(model):
    cfg, params, *_ = model
    with pytest.raises(ValueError, match="prefix_cache.*power-retention.*Set prefix_cache: false"):
        DecodeKernels(cfg, params, serve_cfg(prefix_cache=True))


def test_the_decode_span_carries_the_state_counters(model):
    from determined_tpu.observability import get_tracer

    cfg, params, tokens, _ = model
    tracer = get_tracer()
    tracer.reset()  # spans of other tests' engines in this process
    tracer.configure(enabled=True)
    try:
        eng = ServeEngine(DecodeKernels(cfg, params, serve_cfg()))
        req = eng.submit(tokens[0, :10].tolist(), max_new_tokens=3)
        while not req.done.is_set():
            eng.step_once()
        spans = [e for e in tracer.chrome_events() if e.get("name") == "serve.decode"]
        setup = [e for e in tracer.chrome_events() if e.get("name") == "serve.setup.kv_pool"]
    finally:
        tracer.reset()  # and left on, as a process starts: later tests of this worker read their own spans
    assert spans and all(e["args"]["serve.state.live_lanes"] == 1.0 for e in spans)
    assert spans[0]["args"]["serve.state.bytes"] == LAYERS * state_bytes_per_slot(cfg)
    assert setup[-1]["args"]["slots"] == 2 and setup[-1]["args"]["bytes_per_token"] == 0
    assert setup[-1]["args"]["state_pool_bytes"] == 2 * LAYERS * state_bytes_per_slot(cfg)
