"""The serving forward is stated once (``models/serving.py``, "KV-cache
decode path"): one layer function under the three entry points (the chunked
prefill walk and the decode step the engine runs, the wide prefill the tests
keep as their oracle), one ``_rope`` for shared and per-lane positions, one
masked softmax over gathered table rows, one embedding lookup that takes its
rows before it converts them.  The behaviour's guard is the parity tests of ``test_transformer.py``;
these pin the structure, so that a fourth hand-written loop, a second rope or
a second copy of the table read cannot come back unseen."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.models import cache_kinds, serving as tx
from determined_tpu.models.transformer import Rope, TransformerConfig, TransformerLM, _rope, kv_cache_shape, yarn_inv_freq
from determined_tpu.ops.attention import reference_attention

BLOCK = 4
TABLE_W = 4  # blocks a lane: 16 tokens


def _params(cfg):
    from flax.core import meta as flax_meta

    variables = TransformerLM(cfg).init(jax.random.key(3), jnp.zeros((1, 8), jnp.int32))
    return flax_meta.unbox(variables)["params"]


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, max_seq_len=32,
        dtype=jnp.float32,
    )
    return cfg, _params(cfg)


ENTRY_POINTS = ["prefill", "decode", "prefill_chunked"]


def _trace(which, cfg, params):
    """The jaxpr of one serving program, the parameters and the cache its arguments
    (an operation on a closed-over array would run at trace time and leave no equation)."""
    cache = tx.init_kv_cache(cfg, num_blocks=16, block_size=BLOCK)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    tokens = jnp.zeros((2, 8), jnp.int32)
    lens = jnp.asarray([7, 5], jnp.int32)
    if which == "prefill":
        return jax.make_jaxpr(lambda p, c: tx.transformer_prefill(cfg, p, tokens, lens, tables, c))(params, cache)
    if which == "decode":
        return jax.make_jaxpr(
            lambda p, c: tx.transformer_decode(
                cfg, p, tokens[:, 0], jnp.asarray([3, -1], jnp.int32), tables, c, chunk_blocks=1
            )
        )(params, cache)
    return jax.make_jaxpr(
        lambda p, c: tx.transformer_prefill_chunked(
            cfg, p, tokens, jnp.asarray([4, 0], jnp.int32), lens, tables, c
        )
    )(params, cache)


@pytest.mark.parametrize("which", ENTRY_POINTS)
def test_every_entry_point_runs_the_one_layer_function(tiny, monkeypatch, which):
    """A trace of each serving program calls ``_serve_layer`` once a layer
    (the chunked walk's body is traced once), and the q/k/v projection nowhere
    else."""
    cfg, params = tiny
    calls = {"layer": [], "proj": 0}
    layer, proj = tx._serve_layer, cache_kinds._attn_proj

    def counted_layer(cfg_, i, *a, **kw):
        calls["layer"].append(i)
        before = calls["proj"]
        out = layer(cfg_, i, *a, **kw)
        assert calls["proj"] == before + 1
        return out

    def counted_proj(*a, **kw):
        calls["proj"] += 1
        return proj(*a, **kw)

    monkeypatch.setattr(tx, "_serve_layer", counted_layer)
    monkeypatch.setattr(cache_kinds, "_attn_proj", counted_proj)
    _trace(which, cfg, params)
    assert calls["layer"] == list(range(cfg.n_layers))
    assert calls["proj"] == cfg.n_layers


@pytest.mark.parametrize("which", ENTRY_POINTS)
def test_every_entry_point_takes_its_embedding_through_embed_rows(tiny, monkeypatch, which):
    """One statement of the lookup: a trace of each serving program calls
    ``_embed_rows`` once (the chunked walk's body is traced once)."""
    cfg, params = tiny
    calls = []
    embed = tx._embed_rows

    def counted(params_, tokens, dtype):
        calls.append(tuple(tokens.shape))
        return embed(params_, tokens, dtype)

    monkeypatch.setattr(tx, "_embed_rows", counted)
    _trace(which, cfg, params)
    assert len(calls) == 1, calls


@pytest.mark.parametrize(
    "table_dtype,dtype",
    [(jnp.float32, jnp.bfloat16), (jnp.float32, jnp.float32), (jnp.bfloat16, jnp.bfloat16)],
    ids=["f32-bf16", "f32-f32", "bf16-bf16"],
)
@pytest.mark.parametrize("shape", [(5,), (5, 1), (3, 7)], ids=["b", "b1", "bs"])
def test_embed_rows_equals_the_rows_of_the_converted_table(table_dtype, dtype, shape):
    """A conversion is elementwise: the rows taken and then converted are, bit
    for bit, the rows of the table converted whole (the statement ``_embed_rows``
    had, which swept ``vocab`` rows a step to read ``len(tokens)`` of them)."""
    table = (jax.random.normal(jax.random.key(0), (48, 32), jnp.float32) * 3.0).astype(table_dtype)
    tokens = jax.random.randint(jax.random.key(1), shape, 0, 48)
    tokens = tokens.reshape(-1).at[:2].set(jnp.asarray([0, 47])).reshape(shape)  # both ends of the table
    got = tx._embed_rows({"embed": {"embedding": table}}, tokens, dtype)
    want = jnp.take(table.astype(dtype), tokens, axis=0)
    assert got.dtype == jnp.dtype(dtype) and got.shape == (*shape, 32)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32)))


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its nested jaxprs too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("which", ENTRY_POINTS)
def test_no_entry_point_converts_the_whole_embedding_table(which):
    """float32 leaves under bfloat16 compute: no ``convert_element_type`` of
    any serving program has an operand of the table's shape.  48 rows of 32, a
    shape no other leaf has, so that what the test finds is the table."""
    cfg = TransformerConfig(
        vocab_size=48, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, max_seq_len=32, dtype=jnp.bfloat16,
    )
    params = _params(cfg)
    table = (cfg.vocab_size, cfg.d_model)
    leaves = jax.tree.leaves(params)
    assert all(w.dtype == jnp.float32 for w in leaves)
    assert [tuple(w.shape) for w in leaves].count(table) == 1
    converts = [e for e in _eqns(_trace(which, cfg, params).jaxpr) if e.primitive.name == "convert_element_type"]
    assert any(e.outvars[0].aval.dtype == jnp.bfloat16 for e in converts)  # the walk reaches the conversions
    swept = [e for e in converts if tuple(e.invars[0].aval.shape) == table]
    assert not swept, swept


YARN = Rope(
    500000.0,
    tuple(yarn_inv_freq(16, 500000.0, factor=8.0, original_max_position_embeddings=64, beta_fast=32.0, beta_slow=1.0)),
    1.2,
)


@pytest.mark.parametrize("rope", [Rope(10000.0), YARN], ids=["theta", "yarn"])
def test_rope_per_lane_positions_equal_shared_positions_row_by_row(rope):
    """``_rope`` at ``[b, 1]`` positions (decode: every lane at its own
    offset) is, lane by lane and bit for bit, ``_rope`` at the ``[s]``
    positions training and the prefills pass."""
    b, h, d = 5, 4, 16
    x = jax.random.normal(jax.random.key(0), (b, h, 1, d), jnp.float32)
    pos = jnp.asarray([0, 3, 17, 4096, 31], jnp.int32)
    per_lane = _rope(x, pos[:, None], rope)
    assert per_lane.shape == x.shape
    for lane in range(b):
        shared = _rope(x[lane : lane + 1], pos[lane : lane + 1], rope)
        np.testing.assert_array_equal(np.asarray(per_lane[lane]), np.asarray(shared[0]))
    # and [b, s] positions that happen to be alike equal the [s] form
    xs = jax.random.normal(jax.random.key(1), (2, h, 6, d), jnp.float32)
    p = jnp.arange(6) + 9
    np.testing.assert_array_equal(
        np.asarray(_rope(xs, jnp.broadcast_to(p, (2, 6)), rope)), np.asarray(_rope(xs, p, rope))
    )


def _filled_pool(cfg, seed):
    shape = kv_cache_shape(cfg, 16, BLOCK)
    k, v = jax.random.split(jax.random.key(seed))
    return {"k": jax.random.normal(k, shape, cfg.dtype), "v": jax.random.normal(v, shape, cfg.dtype)}


@pytest.mark.parametrize("mask_of", ["decode", "suffix"])
def test_table_backend_equals_reference_attention_on_the_gathered_rows(tiny, mask_of):
    """The gathered table's read (``cache_kinds._attend_table``, under ``paged_kv``'s
    reference mixer) under decode's mask (one query a lane, keys up to its own position) and under the suffix walk's mask (a block of queries, keys up to each) is
    ``reference_attention`` over the rows the table names."""
    cfg, _ = tiny
    cache = _filled_pool(cfg, 5)
    layer = 1
    tables = jnp.asarray([[3, 9, 1, 12], [7, 2, 14, 5]], jnp.int32)
    kv_len = TABLE_W * BLOCK
    k_pos = jnp.arange(kv_len)
    if mask_of == "decode":
        pos = jnp.asarray([9, 2], jnp.int32)
        q = jax.random.normal(jax.random.key(6), (2, cfg.n_heads, 1, cfg.head_dim), cfg.dtype)
        mask = (k_pos[None, :] <= pos[:, None])[:, None, :]  # [b, 1, kv_len]
        lens = [int(p) + 1 for p in pos]
    else:
        p = 2 * BLOCK + jnp.arange(BLOCK)  # the walk's third block
        q = jax.random.normal(jax.random.key(6), (2, cfg.n_heads, BLOCK, cfg.head_dim), cfg.dtype)
        mask = k_pos[None, :] <= p[:, None]  # [s, kv_len]
        lens = [3 * BLOCK, 3 * BLOCK]
    got = cache_kinds._attend_table(cfg, tables, mask)(q, None, None, cache, layer)
    assert got.shape == q.shape
    for lane in range(2):
        n = lens[lane]
        gathered = {
            name: np.asarray(cache[name])[layer][np.asarray(tables[lane])]
            .reshape(kv_len, cfg.kv_heads, cfg.head_dim)[:n]
            .transpose(1, 0, 2)[None]
            for name in ("k", "v")
        }
        # causal over the last q rows of n keys: query j of the block sees keys <= its position
        full_q = jnp.zeros((1, cfg.n_heads, n, cfg.head_dim), cfg.dtype).at[:, :, n - q.shape[2] :].set(q[lane : lane + 1])
        want = reference_attention(full_q, jnp.asarray(gathered["k"]), jnp.asarray(gathered["v"]), causal=True)
        np.testing.assert_allclose(
            np.asarray(got[lane]), np.asarray(want[0, :, n - q.shape[2] :]), atol=2e-6, rtol=2e-5
        )
