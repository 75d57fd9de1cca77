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
    recent_rows_shapes,
    state_bytes_per_slot,
    state_pool_shapes,
)
from determined_tpu.ops import retention
from determined_tpu.serve.config import ServeConfig
from determined_tpu.serve.engine import DecodeKernels, ServeEngine
from tests.model_cases import reference_module, retention_chunk_step as _chunk_step, retention_heads as _heads

reference = reference_module("power_retention")

LAYERS, GATE_BIAS = 2, 3.0
EVERY = retention.FOLD_EVERY  # tokens a lane's recent rows hold before a decode step folds them into its slot
RECENT = ("rk", "rv", "rg", "rn")


def no_recent_rows(layers, lanes, g, d, dtype=jnp.float32):
    """A lane's recent rows as a zeroed cache holds them: none pending."""
    shapes = retention.recent_shapes(layers, lanes, g, d)
    return tuple(jnp.zeros(shape, dt) for shape, dt in zip(shapes, (dtype, dtype, jnp.float32, jnp.int32)))


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


@pytest.mark.parametrize("pending", [0, 1, EVERY - 1], ids=["just-folded", "one-row", "rows-all-but-one"])
def test_a_token_at_a_time_gives_the_quadratic_form_and_the_chunks_state(pending):
    """Across two fold edges and ``pending`` tokens more: every token's answer is
    the quadratic form's (from the state as it lies and the rows not yet in
    it), and the state holds exactly the tokens folded, as the chunks leave it."""
    s = 2 * EVERY + pending
    q, k, v, log_g = _heads(4, s=s)
    b, g, _, d = k.shape
    want = retention.retention_quadratic(q, k, v, log_g)
    shapes = retention.state_shapes(2, b, g, d)
    state, norm, recent, outs = jnp.zeros(shapes[0]), jnp.zeros(shapes[1]), no_recent_rows(2, b, g, d), []
    step = jax.jit(functools.partial(retention.retention_decode, impl="jnp"))
    for t in range(s):
        before = state
        out, state, norm, recent = step(q[:, :, t], k[:, :, t], v[:, :, t], log_g[:, :, t], state, norm, recent, 1, jnp.ones(b, bool))
        outs.append(out)
        assert np.array_equal(np.asarray(recent[3]), [[0] * b, [(t + 1) % EVERY] * b])      # the rows pending: the lane's own count
        assert np.array_equal(np.asarray(before), np.asarray(state)) == ((t + 1) % EVERY != 0)  # written at a fold and at no other token
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, axis=2)), np.asarray(want), atol=2e-4, rtol=2e-3)
    folded = s - pending
    _, whole, whole_norm = _chunk_step("jnp")(
        q[:, :, :folded], k[:, :, :folded], v[:, :, :folded], log_g[:, :, :folded], jnp.zeros(shapes[0][1:]), jnp.zeros(shapes[1][1:]), jnp.ones((b, folded), bool)
    )
    np.testing.assert_allclose(np.asarray(state[1]), np.asarray(whole), atol=2e-5)
    np.testing.assert_allclose(np.asarray(norm[1]), np.asarray(whole_norm), atol=2e-5)
    assert not np.asarray(state[0]).any() and not np.asarray(norm[0]).any()          # the other layer's slots: untouched
    assert not any(np.asarray(leaf[0]).any() for leaf in recent)                       # and its rows


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
    assert [layer_kinds(cfg, i) for i in range(2)] == [((STATE_SLOT, 0, "attn"),), ((STATE_SLOT, 1, "attn"),)]
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
    assert set(cache) == {"rs", "rz", *RECENT}                                         # no paged K, V for a model no layer of which reads one
    assert cache["rs"].shape == (2, 3, 2, 9 * 16, 16) and cache["rz"].shape == (2, 3, 2, 9, 16)
    assert state_pool_shapes(cfg, 3) == (cache["rs"].shape, cache["rz"].shape)
    # a lane's recent rows: the keys and values of the tokens a decode step has not folded yet (as they reach the
    # layer), the gate's running logarithm at each (float32), and how many are pending
    assert cache["rk"].shape == cache["rv"].shape == (2, 3, 2, EVERY, 16) and cache["rg"].shape == (2, 3, 2, EVERY) and cache["rn"].shape == (2, 3)
    assert recent_rows_shapes(cfg, 3) == tuple(cache[leaf].shape for leaf in RECENT)
    assert {leaf: str(a.dtype) for leaf, a in cache.items()} == {"rs": "float32", "rz": "float32", "rk": "float32", "rv": "float32", "rg": "float32", "rn": "int32"}
    assert str(init_kv_cache(dataclasses.replace(cfg, dtype=jnp.bfloat16), 64, 4, lanes=3)["rk"].dtype) == "bfloat16"
    assert kv_bytes_per_token(cfg) == 0
    assert state_bytes_per_slot(cfg) == 2 * (9 * 16 * 16 + 9 * 16) * 4
    assert serve_counters(cfg) == STATE_SLOT.counters
    mixed = dataclasses.replace(cfg, layer_types=(RETENTION, "full_attention"), qk_norm=False)
    assert set(init_kv_cache(mixed, 8, 4, lanes=2)) == {"k", "v", "rs", "rz", *RECENT} and kv_bytes_per_token(mixed) == 2 * 2 * 16 * 4
    # the published widths: 8,320 features of a head of 128, 34.3 MB a lane a layer
    wide = dataclasses.replace(cfg, n_heads=40, n_kv_heads=8, head_dim=128, d_model=5120)
    assert state_pool_shapes(wide, 32)[0] == (2, 32, 8, 8320, 128) and state_bytes_per_slot(wide) == 8 * (8320 * 128 + 8320) * 4 == 34_344_960
    assert recent_rows_shapes(wide, 32)[0] == (2, 32, 8, EVERY, 128)


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
    for step in range(EVERY + 4):                                                      # across a fold
        toks, pos = np.zeros(n_lanes, np.int32), np.full(n_lanes, -1, np.int32)
        for i, lane in enumerate(lanes):
            toks[lane], pos[lane] = tokens[i, lens[i] + step], lens[i] + step
        logits, cache = decode(params, jnp.asarray(toks), jnp.asarray(pos), jnp.zeros((n_lanes, 1), jnp.int32), cache)
        for i, lane in enumerate(lanes):
            np.testing.assert_allclose(np.asarray(logits[lane]), want[i, lens[i] + step], atol=2e-4)
        counted = dict(zip(serve_counters(cfg), np.asarray(logits[-1])))
        assert counted == {"serve.state.live_lanes": 3.0, "serve.state.bytes": 3.0 * LAYERS * state_bytes_per_slot(cfg)}
    for name in cache:                                                                 # an idle lane's slot and rows are left alone
        assert np.array_equal(np.asarray(cache[name])[:, [1, 3]], before[name][:, [1, 3]])
        assert not np.array_equal(np.asarray(cache[name])[:, 4], before[name][:, 4])   # one fold, 4 rows pending
    assert np.array_equal(np.asarray(cache["rn"]), [[4, 0, 4, 0, 4]] * LAYERS)


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
    assert set(cache) == {"k", "v", "rs", "rz", *RECENT} and cache["k"].shape[0] == cache["rs"].shape[0] == cache["rk"].shape[0] == 1
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


def _decode_lane(cfg, params, cache, n_lanes, lane, row, start, steps):
    """``steps`` decode steps of one lane (the others idle) over ``row``'s tokens from position ``start``."""
    decode = jax.jit(functools.partial(transformer_decode, cfg))
    logits = []
    for t in range(start, start + steps):
        toks, pos = np.zeros(n_lanes, np.int32), np.full(n_lanes, -1, np.int32)
        toks[lane], pos[lane] = row[t], t
        out, cache = decode(params, jnp.asarray(toks), jnp.asarray(pos), jnp.zeros((n_lanes, 1), jnp.int32), cache)
        logits.append(np.asarray(out[lane]))
    return np.stack(logits), cache


def test_a_sequence_that_starts_in_a_used_lane_starts_from_a_zeroed_slot(model):
    cfg, params, tokens, want = model
    _, used = _walk(cfg, params, tokens[2:], [64], [1], 2, 16)
    _, used = _decode_lane(cfg, params, used, 2, 1, tokens[2], 64, 5)                  # and left with rows pending
    assert np.array_equal(np.asarray(used["rn"]), [[0, 5]] * LAYERS)
    last, again = _walk(cfg, params, tokens[:1], [37], [1], 2, 16, cache=used)
    _, fresh = _walk(cfg, params, tokens[:1], [37], [1], 2, 16)
    np.testing.assert_allclose(np.asarray(last[0]), want[0, 36], atol=1e-4)
    for name in ("rs", "rz", "rn"):                                                    # no row pending: what the rows hold is read by nothing
        assert np.array_equal(np.asarray(again[name]), np.asarray(fresh[name]))
    after_used, _ = _decode_lane(cfg, params, again, 2, 1, tokens[0], 37, EVERY + 2)
    after_fresh, _ = _decode_lane(cfg, params, fresh, 2, 1, tokens[0], 37, EVERY + 2)
    assert np.array_equal(after_used, after_fresh)
    np.testing.assert_allclose(after_used, want[0, 37:37 + EVERY + 2], atol=2e-4)


def test_a_requests_logits_are_the_same_alone_and_among_neighbours_admitted_at_other_steps(model):
    """A lane folds by its OWN count of tokens: its logits, its pending rows and
    the steps at which its slot is written are the same with the other lanes
    idle as with neighbours that were admitted 3 and 7 steps later."""
    cfg, params, tokens, want = model
    n_lanes, steps, start = 3, 2 * EVERY + 3, {1: 0, 0: 3, 2: 7}                       # lane -> the step it is admitted at
    lens, row = {1: 20, 0: 9, 2: 33}, {1: 0, 0: 1, 2: 2}
    decode = jax.jit(functools.partial(transformer_decode, cfg))

    def run(lanes):
        cache, logits, pending, written = init_kv_cache(cfg, 8, 4, lanes=n_lanes), [], [], []
        for step in range(steps):
            for lane in lanes:
                if start[lane] == step:
                    _, cache = _walk(cfg, params, tokens[row[lane]:row[lane] + 1], [lens[lane]], [lane], n_lanes, 16, cache=cache)
            toks, pos = np.zeros(n_lanes, np.int32), np.full(n_lanes, -1, np.int32)
            for lane in lanes:
                if start[lane] <= step:
                    at = lens[lane] + step - start[lane]
                    toks[lane], pos[lane] = tokens[row[lane], at], at
            before = np.asarray(cache["rs"][:, 1])
            out, cache = decode(params, jnp.asarray(toks), jnp.asarray(pos), jnp.zeros((n_lanes, 1), jnp.int32), cache)
            logits.append(np.asarray(out[1]))
            pending.append(np.asarray(cache["rn"][0]).tolist())
            written.append(not np.array_equal(before, np.asarray(cache["rs"][:, 1])))
        return np.stack(logits), pending, written

    alone, alone_pending, alone_written = run([1])
    among, among_pending, among_written = run([1, 0, 2])
    np.testing.assert_allclose(alone, want[0, 20:20 + steps], atol=2e-4)
    np.testing.assert_allclose(among, alone, atol=1e-6, rtol=0)
    assert [p[1] for p in among_pending] == [p[1] for p in alone_pending] == [(t + 1) % EVERY for t in range(steps)]
    assert among_written == alone_written == [(t + 1) % EVERY == 0 for t in range(steps)]
    assert among_pending[-1] == [(steps - 3) % EVERY, steps % EVERY, (steps - 7) % EVERY]   # three lanes, three phases


def test_the_decode_step_through_the_kernel_is_the_step_through_jnp(monkeypatch):
    """At a head of 128 (the kernel's shape), two layers, three lanes: one due at
    the compared step (its rows whole), one idle, one not due."""
    cfg = tiny(d_model=64, n_heads=2, n_kv_heads=1, head_dim=128, d_ff=32, vocab_size=64)
    params = build(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(5), (2, 8 + EVERY), 1, cfg.vocab_size))
    _, cache = _walk(cfg, params, tokens, [8, 5], [2, 0], 3, 4)
    _, cache = _decode_lane(cfg, params, cache, 3, 2, tokens[0], 8, EVERY - 1)
    assert np.array_equal(np.asarray(cache["rn"]), [[0, 0, EVERY - 1]] * LAYERS)
    at = 8 + EVERY - 1
    toks, pos = jnp.asarray([tokens[1, 5], 0, tokens[0, at]], jnp.int32), jnp.asarray([5, -1, at], jnp.int32)
    outs = {}
    for impl in ("jnp", "kernel_interpret"):
        monkeypatch.setattr(cache_kinds, "retention_decode", functools.partial(retention.retention_decode, impl=impl))
        step = jax.jit(functools.partial(transformer_decode, cfg))
        outs[impl] = step(params, toks, pos, jnp.zeros((3, 1), jnp.int32), cache)
    np.testing.assert_allclose(np.asarray(outs["jnp"][0])[[0, 2]], np.asarray(outs["kernel_interpret"][0])[[0, 2]], atol=2e-4)
    for name in cache:
        np.testing.assert_allclose(np.asarray(outs["jnp"][1][name]), np.asarray(outs["kernel_interpret"][1][name]), atol=1e-4, rtol=1e-5)
        assert np.array_equal(np.asarray(outs["kernel_interpret"][1][name][:, 1]), np.asarray(cache[name][:, 1]))     # the idle lane's
    for name in ("rs", "rz"):                                                          # written where due, and nowhere else
        assert np.array_equal(np.asarray(outs["kernel_interpret"][1][name][:, 0]), np.asarray(cache[name][:, 0]))
        assert not np.array_equal(np.asarray(outs["kernel_interpret"][1][name][:, 2]), np.asarray(cache[name][:, 2]))
    assert np.array_equal(np.asarray(outs["kernel_interpret"][1]["rn"]), [[1, 0, 0]] * LAYERS)
    want = oracle(cfg, params, tokens)
    np.testing.assert_allclose(np.asarray(outs["kernel_interpret"][0][2]), want[0, at], atol=3e-4)


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
    assert kernels.kinds == (STATE_SLOT,) and STATE_SLOT.holds == LANE and set(kernels.cache) == set(STATE_SLOT.leaves) == {"rs", "rz", *RECENT}

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
    assert stats["state"] == {"slots": 2, "live": 0, "bytes_per_slot": LAYERS * state_bytes_per_slot(cfg), "fold_every": EVERY, "pending_rows": 0}
    assert stats["block_ids_address_nothing"] is True and stats["kv_cache"]["used"] == 0
    assert stats["step_counters"]["serve.state.live_lanes"] == 6 + 4 + 3 - 3
    assert "attn_products" not in stats and "tile_copies" not in stats and stats["window_store"] == {}
    assert stats["step_inputs"]["paged_copied_tokens"] == 0 and STATE_SLOT.walked is None  # no layer holds rows: no paged kernel walks


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


def test_the_stats_say_how_many_recent_rows_the_live_lanes_hold(model):
    """``pending_rows`` is read off the cache a decode step leaves: it climbs a
    row a live lane a step, falls by ``fold_every`` when a lane folds, and never
    passes ``live x (fold_every - 1)``."""
    cfg, params, tokens, _ = model
    most = 2 * EVERY + 8
    eng = ServeEngine(DecodeKernels(cfg, params, serve_cfg(max_prompt_len=16, max_new_tokens=most, num_blocks=(16 + most) // 4 + 2)))
    assert eng.kernels.gauges == STATE_SLOT.gauges == ("serve.state.pending_rows",)
    first = eng.submit(tokens[0, :11].tolist(), max_new_tokens=2 * EVERY + 6)
    seen = []
    for step in range(5):
        assert eng.step_once()
        seen.append(eng.stats()["state"])
    second = eng.submit(tokens[1, :7].tolist(), max_new_tokens=EVERY + 4)             # joins five decode steps after the first
    while not (first.done.is_set() and second.done.is_set()):
        assert eng.step_once()
        seen.append(eng.stats()["state"])
    assert all(s["fold_every"] == EVERY and 0 <= s["pending_rows"] <= s["live"] * (EVERY - 1) for s in seen)
    rows = [s["pending_rows"] for s in seen if s["live"]]
    assert rows[:4] == [1, 2, 3, 4]                                                    # a prefill leaves no row; then a row a step
    assert max(rows) > EVERY and any(b == a + 2 - EVERY for a, b in zip(rows, rows[1:]))   # two lanes' rows, and a fold beside a lane that climbs
    assert seen[-1]["live"] == 0 and seen[-1]["pending_rows"] == 0
    assert set(eng.stats()["step_counters"]) == set(STATE_SLOT.counters)               # a gauge is no step counter


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
    assert setup[-1]["args"]["recent_rows_bytes"] == LAYERS * 2 * (2 * 2 * EVERY * 16 * 4 + 2 * EVERY * 4 + 4)
    assert "serve.state.pending_rows" not in spans[0]["args"]
