"""Windows in attention (ops/flash_attention.py, ops/attention.py), layer
types, a stated head_dim and YaRN in the transformer, what the program refuses
by name, the optimizer's sweep over stacks of expert matrices, the Trainer's
counters of the expert layers' load, and layer types under pipeline stages.
Cut from tests/test_routed_experts.py, which keeps the expert layer itself.
CPU, small sizes, seeded weights; Pallas kernels in interpret mode."""

import importlib
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from determined_tpu.models.serving import _check_decodable
from determined_tpu.models.transformer import FULL, SLIDING, TransformerConfig, TransformerLM, yarn_inv_freq
from determined_tpu.ops import grouped_matmul as gm
from determined_tpu.ops.attention import dot_product_attention, reference_attention
from determined_tpu.ops.flash_attention import flash_attention

flash_mod = importlib.import_module("determined_tpu.ops.flash_attention")  # ``determined_tpu.ops.flash_attention`` is the function

YARN = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192,
    "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.2772588722239782,
}


# ---------------------------------------------------------------------------
# windows in attention
# ---------------------------------------------------------------------------


def _qkv(seq, heads=4, kv=2, d=16):
    keys = jax.random.split(jax.random.key(3), 3)
    return (jax.random.normal(keys[0], (1, heads, seq, d)), jax.random.normal(keys[1], (1, kv, seq, d)),
            jax.random.normal(keys[2], (1, kv, seq, d)))


@pytest.fixture
def sub_tile(monkeypatch):
    """Sets the kernels' sub-tile (``SUB_TILE``, read as a call is traced), so
    that blocks small enough for the interpreter are worked in sub-tiles."""
    return lambda tile: monkeypatch.setattr(flash_mod, "SUB_TILE", tile)


@pytest.mark.parametrize("seq,block_q,block_k,window,tile", [
    (256, 64, 64, 64, None),     # the window is a block
    (256, 64, 32, 100, None),    # divides neither block
    (256, 32, 64, 37, None),
    (128, 128, 128, 50, None),   # one block: the single-pass kernel
    (256, 64, 64, 1, None),      # a query sees itself alone
    (256, 64, 64, 255, None),    # all but one key of the last query
    # blocks worked in sub-tiles: the window
    (256, 64, 64, 32, 16),       # a multiple of the sub-tile
    (256, 64, 64, 40, 16),       # not a multiple of it
    (256, 64, 64, 5, 16),        # smaller than a sub-tile
    (256, 64, 64, 64, 16),       # the block: every live block has an edge in it
    (256, 64, 64, 128, 16),      # two blocks: an interior block between the edges
    (256, 64, 32, 100, 16),      # block_q != block_k, either way round
    (256, 32, 64, 37, 16),
    (128, 128, 128, 50, 32),     # the single-pass kernel, both edges in its one block
    (128, 32, 128, 20, 16),      # one key block under several query blocks
    (2048, 1024, 1024, 1024, None),  # the shipped sub-tile in the cells' blocks
    # a block that the sub-tile does not divide is worked whole on that side
    (192, 192, 192, 50, None),   # a sequence under 1,024 is one block: the single-pass kernel, 192 % 128
    (320, 320, 320, 100, None),
    (384, 192, 128, 100, None),  # the query side whole, the key side in sub-tiles; and the other way round
    (384, 128, 192, 100, None),
    (256, 64, 64, 40, 48),       # several blocks, none divided
])
def test_flash_with_a_window_matches_the_reference_forward_and_backward(seq, block_q, block_k, window, tile, sub_tile):
    if tile:
        sub_tile(tile)
    q, k, v = _qkv(seq)
    flash = lambda q, k, v: flash_attention(q, k, v, block_q=block_q, block_k=block_k, window=window)  # noqa: E731
    ref = lambda q, k, v: reference_attention(q, k, v, window=window)  # noqa: E731
    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(ref(*a))), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_a_row_whose_first_computed_sub_tile_hides_every_key_is_wiped_by_its_first_visible_key(sub_tile):
    """Window = block = 64, sub-tiles of 16: the last query of a block (127)
    sees nothing of the block before it, yet its band's one rectangle there
    (keys 48..63, an edge in it) is computed: the row reads m = NEG_INF and
    p = 1, and sums those keys' v.  The values there are huge, so anything
    left of them after alpha = 0 would show."""
    sub_tile(16)
    assert flash_mod._pieces(64, 64, 64, True, 64, 16)[-1] == (48, 16, 48, 16, True)
    assert not any(0 <= 127 - j < 64 for j in range(64))
    q, k, v = _qkv(128)
    flash = lambda q, k, v: flash_attention(q, k, v, block_q=64, block_k=64, window=64)  # noqa: E731
    ref = lambda q, k, v: reference_attention(q, k, v, window=64)  # noqa: E731
    huge = v.at[:, :, 48:64].set(1e4)
    np.testing.assert_allclose(flash(q, k, huge)[:, :, 127], ref(q, k, huge)[:, :, 127], atol=2e-6)
    np.testing.assert_allclose(flash(q, k, huge), ref(q, k, huge), rtol=2e-6, atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(ref(*a))), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def _mask(offset, q_n, k_n, window):
    """The mask itself: pair (i, j) of a rectangle whose first query is ``offset`` after its first key."""
    d = offset + np.arange(q_n)[:, None] - np.arange(k_n)[None, :]
    return (d >= 0) & (d < (window or 10**9))


@pytest.mark.parametrize("window", [None, 1, 2, 3, 5, 8, 13])
def test_the_rule_of_what_is_visible_is_the_mask_itself_for_blocks_and_sub_tiles(window):
    """``_visible`` (none / some / every pair) against the mask, every shape
    up to 5 x 5 at every offset that matters; then ``_pieces``: of a block,
    exactly the sub-tiles that hold a visible pair, each once, unmasked
    only where every pair is visible; and ``_crossed_offsets``: the offsets
    of the grid's blocks that hold both kinds of pair."""
    for q_n, k_n, offset in itertools.product(range(1, 6), range(1, 6), range(-8, 24)):
        seen = _mask(offset, q_n, k_n, window)
        assert flash_mod._visible(offset, q_n, k_n, True, window) == (seen.any(), seen.all()), (q_n, k_n, offset)
    assert flash_mod._visible(-3, 4, 4, False, None) == (True, True)
    blocks = [(8, 8), (8, 4), (4, 8), (4, 4), (6, 6), (6, 8), (9, 6)]
    for (block_q, block_k), tile, tall in itertools.product(blocks, (2, 3, 4, 5, 8), (False, True)):
        # a side that the sub-tile does not divide is one sub-tile
        tq, tk = (tile if block % tile == 0 else block for block in (block_q, block_k))
        for offset in range(-12, 28):
            seen = _mask(offset, block_q, block_k, window)
            covered = np.zeros_like(seen, dtype=int)
            for a, rows, c, cols, masked in flash_mod._pieces(offset, block_q, block_k, True, window, tile, tall):
                assert 0 <= a < a + rows <= block_q and 0 <= c < c + cols <= block_k, (block_q, block_k, tile, tall, offset)
                covered[a:a + rows, c:c + cols] += 1
                assert masked == (not seen[a:a + rows, c:c + cols].all())
                assert (cols if tall else rows) == (tk if tall else tq) or seen.all()
            want = np.zeros_like(covered)
            if seen.all():
                want[:] = 1
            else:
                for a, c in itertools.product(range(0, block_q, tq), range(0, block_k, tk)):
                    want[a:a + tq, c:c + tk] = seen[a:a + tq, c:c + tk].any()
            assert (covered == want).all(), (block_q, block_k, tile, tall, offset)
    for nq, nk, block_q, block_k in [(4, 4, 8, 8), (4, 8, 8, 4), (8, 4, 4, 8)]:
        blocks = {qi * block_q - ki * block_k for qi in range(nq) for ki in range(nk)}
        crossed = {d for d in blocks if _mask(d, block_q, block_k, window).any() and not _mask(d, block_q, block_k, window).all()}
        assert set(flash_mod._crossed_offsets(nq, nk, block_q, block_k, True, window)) == crossed
    assert flash_mod._crossed_offsets(4, 4, 8, 8, False, None) == ()


@pytest.mark.parametrize("seq,block_q,block_k,window,tile", [
    (64, 16, 16, None, 4), (64, 16, 16, 16, 4), (64, 16, 8, 10, 4), (64, 8, 16, 23, 2), (64, 16, 16, 5, 16), (32, 32, 32, 7, 8),
    (60, 20, 20, 7, 8), (60, 20, 12, None, 4),   # a side of the block that the sub-tile does not divide is whole
])
def test_block_work_counts_what_the_mask_and_the_sub_tiles_say(seq, block_q, block_k, window, tile):
    seen = _mask(0, seq, seq, window)
    computed = 0
    for a, c in itertools.product(range(0, seq, block_q), range(0, seq, block_k)):
        block = seen[a:a + block_q, c:c + block_k]
        tq, tk = (block_q, block_k) if block.all() else (tile if block_q % tile == 0 else block_q, tile if block_k % tile == 0 else block_k)
        computed += sum(
            tq * tk for i, j in itertools.product(range(0, block_q, tq), range(0, block_k, tk)) if block[i:i + tq, j:j + tk].any()
        )
    assert flash_mod.block_work(seq, block_q, block_k, window, tile) == (computed, seen.sum())


@pytest.mark.parametrize("seq,window,tile,blocks", [
    (8192, 1024, 128, 8.4375), (8192, None, 128, 32.5), (4096, None, 128, 8.25),   # a Mellum2 window layer; its full layer and ZAYA1's; Mistral's
    (8192, 1024, 256, 9.375), (8192, None, 256, 33.0), (4096, None, 256, 8.5),
    (8192, 1024, 1024, 15.0), (8192, None, 1024, 36.0), (4096, None, 1024, 10.0),  # whole blocks, as before PR 52
])
def test_block_work_at_the_training_cells_shapes(seq, window, tile, blocks):
    """docs/training.md's table: blocks' worth of 1,024 x 1,024 computed a head a layer."""
    computed, visible = flash_mod.block_work(seq, 1024, 1024, window, tile)
    assert computed == blocks * 1024 * 1024 and visible == sum(min(i + 1, window or seq) for i in range(seq))
    if tile == 128:
        assert flash_mod.SUB_TILE == 128 and flash_mod.DEFAULT_BLOCK == 1024


def test_the_reference_window_is_the_stated_mask():
    q, k, v = _qkv(32)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1)) / 4.0
    i, j = jnp.arange(32)[:, None], jnp.arange(32)[None, :]
    probs = jax.nn.softmax(jnp.where((j <= i) & (i - j < 8), scores, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bhkd->bhqd", probs, jnp.repeat(v, 2, axis=1))
    np.testing.assert_allclose(reference_attention(q, k, v, window=8), want, atol=1e-6)
    np.testing.assert_allclose(dot_product_attention(q, k, v, impl="reference", window=8), want, atol=1e-6)


def test_no_window_is_bit_equal_to_the_kernel_without_the_argument():
    q, k, v = _qkv(256)
    plain = flash_attention(q, k, v, block_q=64, block_k=64)
    assert (flash_attention(q, k, v, block_q=64, block_k=64, window=None) == plain).all()
    assert (flash_attention(q, k, v, block_q=64, block_k=64, window=256) == plain).all()  # covers the sequence: no window
    grads = [
        jax.grad(lambda *a: jnp.sum(jnp.sin(flash_attention(*a, block_q=64, block_k=64, **kw))), (0, 1, 2))(q, k, v)
        for kw in ({}, {"window": None})
    ]
    assert all((a == b).all() for a, b in zip(*grads))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="causal"):
        reference_attention(q, k, v, causal=False, window=8)


def test_sharded_flash_attention_takes_the_window():
    from determined_tpu.ops.attention import sharded_flash_attention

    q, k, v = (jnp.concatenate([t, t + 1.0]) for t in _qkv(128))
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("data", "tensor"))
    got = jax.jit(lambda q, k, v: sharded_flash_attention(q, k, v, mesh, window=40))(q, k, v)
    np.testing.assert_allclose(got, reference_attention(q, k, v, window=40), atol=2e-6)


# ---------------------------------------------------------------------------
# layer types, head_dim, YaRN
# ---------------------------------------------------------------------------


def test_yarn_frequencies_match_numbers_worked_by_hand():
    """theta 500,000, head_dim 128, factor 16, original 8,192, beta 32 / 1:
    corr(32) = 128 ln(8192 / (64 pi)) / (2 ln 500000) = 18.08 -> low 18;
    corr(1) = 34.98 -> high 35."""
    inv = yarn_inv_freq(128, 500000.0, factor=16, original_max_position_embeddings=8192, beta_fast=32, beta_slow=1)
    ln = math.log(500000.0)
    assert math.floor(128 * math.log(8192 / (64 * math.pi)) / (2 * ln)) == 18
    assert math.ceil(128 * math.log(8192 / (2 * math.pi)) / (2 * ln)) == 35
    assert inv.shape == (64,) and inv.dtype == np.float32
    assert inv[0] == 1.0
    assert inv[17] == pytest.approx(0.0306345, rel=1e-5)      # i < low: plain, 500000^(-34/128)
    assert inv[25] == pytest.approx(0.00364743, rel=1e-5)     # ramp 7/17: plain x (10/17 + 7/17 / 16)
    assert inv[36] == pytest.approx(3.89233e-05, rel=1e-5)    # i > high: plain / 16
    assert inv[63] == pytest.approx(500000.0 ** (-126 / 128) / 16, rel=1e-5)


def _tiny(**kw):
    base = dict(
        vocab_size=64, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=12, d_ff=48, max_seq_len=32,
        dtype=jnp.float32, attention_impl="reference", partition_params=False,
        layer_types=(SLIDING, SLIDING, FULL), sliding_window=8,
        rope_parameters={FULL: YARN, SLIDING: {"rope_type": "default", "rope_theta": 500000}},
    )
    return TransformerConfig(**{**base, **kw})


def test_head_dim_is_a_stated_field_and_layer_types_reach_every_block():
    cfg = _tiny()
    assert cfg.head_dim == 12 and TransformerConfig(d_model=64, n_heads=4).head_dim == 16
    assert hash(cfg) == hash(_tiny())  # rope_parameters are frozen: the config stays hashable
    assert cfg.rope(SLIDING).inv_freq is None and cfg.rope(SLIDING).theta == 500000.0
    assert cfg.rope(FULL).attention_factor == YARN["attention_factor"] and len(cfg.rope(FULL).inv_freq) == 6
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 64)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(1), tokens)
    assert params["params"]["block_0"]["attn"]["wq"]["kernel"].shape == (32, 4, 12)
    assert params["params"]["block_0"]["attn"]["wo"]["kernel"].shape == (4, 12, 32)
    out = model.apply(params, tokens)
    # each departure changes the output: a layer's window, the full layer's rotary section, its factor
    for other in (
        _tiny(layer_types=(SLIDING, FULL, FULL)), _tiny(sliding_window=9),
        _tiny(rope_parameters={SLIDING: {"rope_type": "default", "rope_theta": 500000}}, rope_theta=500000.0),
        _tiny(rope_parameters={FULL: {**YARN, "attention_factor": 1.0}, SLIDING: {"rope_type": "default", "rope_theta": 500000}}),
    ):
        assert float(jnp.max(jnp.abs(TransformerLM(other).apply(params, tokens) - out))) > 1e-4
    # and flash (interpret mode) runs the same layers
    flash = TransformerLM(_tiny(attention_impl="flash")).apply(params, tokens)
    np.testing.assert_allclose(flash, out, atol=2e-5)


@pytest.mark.parametrize("kw,says", [
    (dict(layer_types=(SLIDING, FULL)), "layer_types needs one"),
    (dict(layer_types=(SLIDING, FULL, "chunked")), "layer_types needs one"),
    (dict(sliding_window=None), "sliding_window"),
    (dict(moe_experts=4, moe_top_k=5), "moe_top_k"),
    (dict(moe_experts=8, moe_top_k=2, moe_experts_held=(6, 3)), "moe_experts_held"),
    (dict(moe_experts=8, moe_top_k=2, moe_experts_held=(-1, 2)), "moe_experts_held"),
    (dict(moe_experts=8, moe_experts_held=(0, 2)), "belong to moe_top_k"),
    (dict(rope_parameters={FULL: {"rope_type": "llama3"}}), "rope_type"),
])
def test_the_config_refuses_what_does_not_fit_together(kw, says):
    with pytest.raises(ValueError, match=says):
        _tiny(**kw)


def test_what_cannot_honour_a_window_refuses_by_name():
    tokens = jnp.zeros((1, 32), jnp.int32)
    # ring attention knows no window
    with pytest.raises(ValueError, match="ring attention.*sliding_attention"):
        TransformerLM(_tiny(attention_impl="ring")).init(jax.random.key(0), tokens)
    # the serving forward does (since PR 38: a ring a lane, tests/test_window_serving.py), its wide prefill aside
    _check_decodable(_tiny())
    with pytest.raises(ValueError, match="the wide prefill runs full layers only"):
        from determined_tpu.models.serving import transformer_prefill

        transformer_prefill(_tiny(), {}, tokens, jnp.ones(1, jnp.int32), jnp.zeros((1, 8), jnp.int32), {"k": jnp.zeros((1, 2, 4, 8))})
    _check_decodable(_tiny(layer_types=None, sliding_window=None))
    _check_decodable(_tiny(layer_types=(FULL,) * 3, sliding_window=None, rope_parameters=None))


# ---------------------------------------------------------------------------
# the optimizer's sweep over stacks of expert matrices, and the Trainer's counters
# ---------------------------------------------------------------------------


def test_fused_adamw_sweeps_a_stack_of_matrices_a_matrix_at_a_time(monkeypatch):
    import importlib

    adamw = importlib.import_module("determined_tpu.ops.fused_adamw")
    # the expert leaves of the Mellum2 cell: 896 halves to no multiple of 128 and one matrix is past the budget
    assert adamw._plan_blocks((16, 2304, 896)) == ((16, 12), (1, 192, 896), 1)
    assert adamw._plan_blocks((16, 896, 2304)) == ((16, 14), (1, 64, 2304), 1)
    # plans that were there stay what they were
    assert adamw._plan_blocks((32, 128, 4096)) == ((32, 4), (1, 128, 1024), 2)
    assert adamw._plan_blocks((4096, 14336)) == ((512, 1), (8, 14336), 1)
    # the same plan at a size the interpreter sweeps, against the jnp update
    monkeypatch.setattr(adamw, "_plan_blocks", lambda shape: adamw._plan_matrix_rows(shape, 8 * 24))
    assert adamw._plan_blocks((3, 16, 24)) == ((3, 2), (1, 8, 24), 1)
    keys = jax.random.split(jax.random.key(0), 4)
    p, m, g = (jax.random.normal(k, (3, 16, 24)) for k in keys[:3])
    v = jnp.abs(jax.random.normal(keys[3], (3, 16, 24)))
    scalars = jnp.array([[1e-3, 0.5, 0.1, 0.001]], jnp.float32)
    kw = dict(b1=0.9, b2=0.999, eps=1e-8, wd=0.01)
    got = adamw._leaf_pallas(p, m, v, g, scalars, **kw)
    want = adamw._leaf_jnp(p, m, v, g, scalars, **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_the_trainer_pushes_the_expert_layers_load_as_counters(tmp_path):
    from determined_tpu import core, train
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.observability import get_tracer

    hparams = dict(
        lr=1e-3, global_batch_size=2, seq_len=32, dataset_size=8, vocab_size=64, d_model=32, n_layers=2, n_heads=4,
        head_dim=12, d_ff=48, bf16=False, attention="reference", fused_ce=False, fused_adamw=False,
        layer_types=[SLIDING, FULL], sliding_window=8, moe_experts=8, moe_every=1, moe_top_k=3,
        moe_intermediate_size=16, moe_experts_held=[2, 4], moe_aux_weight=0.001,
    )
    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)
    try:
        ctx = train.init(
            hparams=hparams, core_context=core._dummy_init(checkpoint_dir=str(tmp_path)), seed=1,
            devices=jax.devices()[:1],  # dropless experts: one device (or a pipeline stage)
        )
        trial = LMTrial(ctx)
        # what the goodput ledger divides by: head_dim 12, a window of 8 and a full layer, 3 x 4 / 8 experts a token
        params = 64 * 32 + 2 * (32 * 12 * (2 * 4 + 2 * 4) + 32 * 8 + 1.5 * 3 * 32 * 16)
        assert trial.flops_per_token == 6 * params + 12 * (8 + 32) * 4 * 12
        train.Trainer(trial).fit({"batches": 4}, report_period={"batches": 2}, checkpoint_policy="none")
        events = [e for e in tracer.chrome_events() if e.get("ph") == "C"]
    finally:
        tracer.configure(enabled=was)  # other tests of this worker read the tracer as they found it
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e["args"]["value"])
    assert by_name["train.steps"][-2:] == [2.0, 2.0]
    assert by_name["moe.picks"][-2:] == [2 * 2 * 64 * 3.0] * 2         # 2 steps x 2 layers x 64 tokens x 3 picks
    held, top, mean = (by_name[k][-1] for k in ("moe.held_picks", "moe.expert_load_max", "moe.expert_load_mean"))
    assert 0 < held < by_name["moe.picks"][-1] and held == pytest.approx(mean * 2 * 4)  # mean over 2 layers x 4 held experts
    assert top >= mean and "moe_aux_loss" in by_name
    # rows the kernels touch: whole tiles of 64 (192 rows at most over 4 held experts), never fewer than the held picks
    live = by_name["moe.live_rows"][-1]
    assert live % 64 == 0 and held <= live <= 2 * 2 * gm.buffer_rows(192, 4, 64)
    assert by_name["moe.buffer_rows"][-1] == 2 * 2 * gm.buffer_rows(192, 4, 64)   # all its rows: the worst case


# ---------------------------------------------------------------------------
# pipeline stages: the period of layer_types, and the expert axis
# ---------------------------------------------------------------------------

PIPE_HPARAMS = dict(
    lr=1e-3, global_batch_size=8, seq_len=32, vocab_size=128, d_model=32, n_layers=4, n_heads=4, head_dim=12,
    dataset_size=32, bf16=False, attention="reference", warmup_steps=1, fused_ce=False, fused_adamw=False,
    sliding_window=8, moe_experts=4, moe_every=1, moe_top_k=2, moe_intermediate_size=16,
    # the auxiliary term is a microbatch's under the pipeline and the batch's without: parity is the main loss's
    moe_aux_weight=0.0,
)


def _pipe_context(tmp_path, mesh_config, layer_types, tag="", devices=None):
    from determined_tpu import core, train

    return train.init(
        hparams=dict(PIPE_HPARAMS, layer_types=layer_types), mesh_config=mesh_config,
        core_context=core._dummy_init(checkpoint_dir=str(tmp_path / f"ckpt{tag}")), seed=7, devices=devices,
    )


def test_pipe_needs_the_period_of_layer_types_to_divide_a_chunk(tmp_path):
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig

    mesh = MeshConfig(pipe=2, data=4)
    with pytest.raises(ValueError, match="period of layer_types"):
        LMTrial(_pipe_context(tmp_path, mesh, [SLIDING, SLIDING, SLIDING, FULL]))._cfg()
    cfg = LMTrial(_pipe_context(tmp_path, mesh, [SLIDING, FULL, SLIDING, FULL], tag="b"))._cfg()
    assert cfg.layer_types == (SLIDING, FULL, SLIDING, FULL) and cfg.moe_top_k == 2
    # outside pipeline stages dropless experts are GSPMD's to partition, and a Mosaic kernel cannot be
    with pytest.raises(ValueError, match="one device or inside"):
        LMTrial(_pipe_context(tmp_path, MeshConfig(data=2), [SLIDING, FULL, SLIDING, FULL], tag="c", devices=jax.devices()[:2]))._cfg()


@pytest.mark.slow
def test_pipe_stages_run_layer_types_and_dropless_experts_over_the_expert_axis(tmp_path):
    """pipe2 x expert2 x data2 against one device: layer j of every chunk is
    one stacked leaf with one layer type, each device holds its half of the
    experts and the psum is the combine; no token is dropped on either side,
    so the main loss agrees step for step."""
    from determined_tpu import train
    from determined_tpu.config import Length
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig

    def losses(ctx):
        seen = []
        report = ctx.core.train.report_training_metrics
        ctx.core.train.report_training_metrics = lambda s, m: (seen.append(m["loss"]), report(s, m))
        train.Trainer(LMTrial(ctx)).fit(Length.batches(3), report_period=Length.batches(1), checkpoint_policy="none")
        return seen

    types = [SLIDING, FULL, SLIDING, FULL]
    one = losses(_pipe_context(tmp_path, MeshConfig(data=1), types, tag="a", devices=jax.devices()[:1]))
    staged = losses(_pipe_context(tmp_path, MeshConfig(pipe=2, expert=2, data=2), types, tag="b"))
    assert len(one) == 3 and all(np.isfinite(staged))
    np.testing.assert_allclose(one, staged, rtol=2e-4, atol=2e-5)
