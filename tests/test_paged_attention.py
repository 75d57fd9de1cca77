"""Paged decode attention (``ops/paged_attention.py``): both forms against a
plain numpy attention over ragged lanes, and ``transformer_decode``'s paged
path against the full-table gather and the full-sequence forward.

The Pallas kernel runs in the TPU interpreter here (``kernel_interpret``):
that checks its mathematics, its table walk and its double buffering at
small sizes; what the chip's compiler accepts is ``tests/test_tpu_compile.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta as flax_meta

from determined_tpu.models import cache_kinds
from determined_tpu.models.serving import init_kv_cache, transformer_decode, transformer_prefill
from determined_tpu.models.transformer import TransformerConfig, TransformerLM
from determined_tpu.ops import kernel_form, paged_attention as pa
from tests.model_cases import PAGED_EDGES, causal_forward, check_copy_schedule

# lanes of the ragged batch, by what each one pins (block_size 16, table 6):
# empty; position 0; last slot of a block (the walk ends exactly on a block
# boundary); first slot of the next block; mid-block; the table's full width
RAGGED = {"empty": -1, "pos0": 0, "block_end": 31, "block_start": 32, "mid": 40, "full": 95}


def _numpy_attention(q, k_pool, v_pool, layer, tables, positions, scale):
    b, h, d = q.shape
    kv_heads = k_pool.shape[3] // d
    n_rep = h // kv_heads
    out = np.zeros((b, h, d), np.float32)
    for i in range(b):
        length = int(positions[i]) + 1
        if length <= 0:
            continue
        k = np.asarray(k_pool[layer][tables[i]], np.float32).reshape(-1, kv_heads, d)
        v = np.asarray(v_pool[layer][tables[i]], np.float32).reshape(-1, kv_heads, d)
        for head in range(h):
            g = head // n_rep
            s = (k[:length, g] @ np.asarray(q[i, head], np.float32)) * scale
            p = np.exp(s - s.max())
            out[i, head] = (p / p.sum()) @ v[:length, g]
    return out


def _pool_case(dtype, n_rep, head_dim, block_size, positions, seed=0):
    rng = np.random.default_rng(seed)
    kv_heads, layers, num_blocks = 2, 2, 40
    table_width = 6
    b = len(positions)
    shape = (layers, num_blocks, block_size, kv_heads * head_dim)
    k_pool = jnp.asarray(rng.normal(size=shape), dtype)
    v_pool = jnp.asarray(rng.normal(size=shape), dtype)
    q = jnp.asarray(rng.normal(size=(b, kv_heads * n_rep, head_dim)), dtype)
    ids = rng.permutation(np.arange(1, num_blocks))[: b * table_width]
    tables = ids.reshape(b, table_width).astype(np.int32)
    return q, k_pool, v_pool, tables, np.asarray(positions, np.int32)


@pytest.mark.parametrize("tile_blocks", [1, 4, None], ids=["tile1", "tile4", "tile_auto"])
@pytest.mark.parametrize("n_rep", [1, 2, 4, 8, 16], ids=lambda r: f"n_rep{r}")
@pytest.mark.parametrize(
    "impl,dtype",
    [("jnp", jnp.float32), ("jnp", jnp.bfloat16),
     ("kernel_interpret", jnp.float32), ("kernel_interpret", jnp.bfloat16)],
    ids=["jnp-f32", "jnp-bf16", "kernel-f32", "kernel-bf16"],
)
def test_paged_attention_matches_numpy_over_ragged_lanes(impl, dtype, n_rep, tile_blocks):
    """Every lane of RAGGED in one call, at 1 / 4 blocks a tile (several
    trips, the last one part live; 4 does not divide the table's 6 columns)
    and the width the shapes choose (one trip).  K, V and q are exact in the
    pool's dtype on both sides, so what is left is float32 reassociation:
    a bf16 pool must NOT cost bf16 precision in the probabilities.  ``n_rep``
    1 to 4 run the kernel's block-diagonal products, 8 and 16 a KV head at a
    time."""
    q, k_pool, v_pool, tables, positions = _pool_case(
        dtype, n_rep, 128, 16, list(RAGGED.values())
    )
    scale = 128 ** -0.5
    got = pa.paged_decode_attention(
        q, k_pool, v_pool, 1, jnp.asarray(tables), jnp.asarray(positions),
        scale=scale, tile_blocks=tile_blocks, impl=impl,
    )
    want = _numpy_attention(q, k_pool, v_pool, 1, tables, positions, scale)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6, rtol=2e-5)
    assert not np.asarray(got)[list(RAGGED).index("empty")].any()


@pytest.mark.parametrize("dtype, n_rep", [(jnp.float32, 2), (jnp.bfloat16, 8)], ids=["f32-block_diagonal", "bf16-per_kv_head"])
@pytest.mark.parametrize("lanes", list(PAGED_EDGES))
def test_the_kernels_copy_schedule_at_a_lanes_edges(lanes, dtype, n_rep):
    """Tiles of 2 blocks of 16: lanes of a token, a block, a tile, a tile and
    one; an idle lane first (the next starts its own first tile), in the
    middle, last (nothing is started for it), two in a row, every lane idle.
    The kernel against the plain attention and its ``jax.numpy`` form, then the
    poison case (``check_copy_schedule``): only the blocks that hold a token a
    query sees are copied, and a row no copy wrote is zeros."""
    contexts = PAGED_EDGES[lanes]
    q, k_pool, v_pool, tables, positions = _pool_case(dtype, n_rep, 128, 16, [n - 1 for n in contexts])
    scale = 128 ** -0.5

    def run(k_pool, v_pool, impl="kernel_interpret"):
        return pa.paged_decode_attention(
            q, k_pool, v_pool, 1, jnp.asarray(tables), jnp.asarray(positions), scale=scale, tile_blocks=2, impl=impl)

    got = check_copy_schedule(run, (k_pool, v_pool), 1, tables, contexts, 16)
    np.testing.assert_allclose(got, _numpy_attention(q, k_pool, v_pool, 1, tables, positions, scale), atol=2e-6, rtol=2e-5)
    np.testing.assert_allclose(got, np.asarray(run(k_pool, v_pool, "jnp")), atol=2e-6, rtol=2e-5)
    assert not got[[n == 0 for n in contexts]].any()


@pytest.mark.parametrize("seed", range(6))
def test_walk_counts_is_the_walk_tile_by_tile_and_block_by_block(seed):
    """The schedule in integers (``walk_counts``, whose block formula the kernels
    share) against a walk made the slow way over random lanes, windows, block
    sizes and tile widths: a lane walks the tiles from the one that holds the
    oldest token its query sees to the one that holds the newest, a tile copies
    its blocks that hold such a token, and a lane's first tile was in flight
    where the lane before it walked one.  The tile's width moves none of it."""
    rng = np.random.default_rng(seed)
    block, tile_blocks = int(rng.choice([4, 16, 32])), int(rng.integers(1, 9))
    window = [None, 5, 40, 200][seed % 4]
    positions = rng.integers(0, 700, size=int(rng.integers(1, 12)))
    positions[rng.random(positions.shape) < 0.3] = -1
    live = copied = lanes = in_flight = 0
    walked_before = False
    for pos in positions:
        n = int(pos) + 1
        sees = set(range(max(0, n - window) if window else 0, n))
        tiles = sorted({j // (block * tile_blocks) for j in sees})
        assert tiles == list(range(tiles[0], tiles[-1] + 1)) if tiles else n == 0
        for tile in tiles:
            for blk in range(tile * tile_blocks, (tile + 1) * tile_blocks):
                copied += block * bool(sees & set(range(blk * block, (blk + 1) * block)))
        live += len(sees)
        lanes += bool(tiles)
        in_flight += bool(tiles) and walked_before
        walked_before = bool(tiles)
    assert pa.walk_counts(positions, block, window) == (live, copied, lanes, in_flight)
    assert pa.walk_counts(np.full(3, -1), block, window) == (0, 0, 0, 0)


@pytest.mark.parametrize("head_dim,block_size", [(8, 4), (64, 16), (128, 4)])
def test_shapes_the_kernel_does_not_take_run_the_jnp_form(head_dim, block_size, monkeypatch):
    """Small head_dim or a block that does not fill a sublane tile: the
    choice is made from shapes, even on a TPU, and asking for the kernel
    by name says why it cannot be had."""
    assert not pa.kernel_takes(head_dim, block_size, jnp.bfloat16)
    monkeypatch.setattr(kernel_form, "on_tpu", lambda: True)
    positions = [-1, 0, block_size - 1, 6 * block_size - 1]
    q, k_pool, v_pool, tables, positions = _pool_case(
        jnp.bfloat16, 2, head_dim, block_size, positions
    )
    args = (q, k_pool, v_pool, 0, jnp.asarray(tables), jnp.asarray(positions))
    got = pa.paged_decode_attention(*args, scale=0.25, tile_blocks=2)
    want = _numpy_attention(q, k_pool, v_pool, 0, tables, positions, 0.25)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6, rtol=2e-5)
    with pytest.raises(ValueError, match="head_dim"):
        pa.paged_decode_attention(*args, scale=0.25, impl="kernel")


def test_kernel_is_the_choice_on_a_tpu_when_the_shapes_tile(monkeypatch):
    assert pa.kernel_takes(128, 16, jnp.bfloat16) and pa.kernel_takes(256, 8, jnp.float32)
    assert not pa.kernel_takes(128, 8, jnp.bfloat16)  # half a packed sublane tile
    taken = []
    monkeypatch.setattr(
        pa, "_paged_attention_pallas",
        lambda *a, interpret, window: taken.append(interpret) or jnp.zeros(a[0].shape, jnp.float32),
    )
    q, k_pool, v_pool, tables, positions = _pool_case(jnp.bfloat16, 2, 128, 16, [5, 20])
    args = (q, k_pool, v_pool, 0, jnp.asarray(tables), jnp.asarray(positions))
    pa.paged_decode_attention(*args, scale=1.0)  # CPU: the jnp form
    assert taken == []
    monkeypatch.setattr(kernel_form, "on_tpu", lambda: True)
    pa.paged_decode_attention(*args, scale=1.0)
    assert taken == [False]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_products_layout_comes_from_the_query_heads_a_kv_head(dtype, monkeypatch):
    """A KV head at a time where its query heads fill whole sublane tiles of
    the float32 scores, the block-diagonal query below that: InternLM2 (2) and
    Mistral (4) stay, Command A+ (16) turns, whatever the query's dtype; and
    the layout the function names is the one the wrapper hands the kernel."""
    assert [pa.attn_products(n_rep) for n_rep in (1, 2, 4, 8, 16)] == 3 * ["block_diagonal"] + 2 * ["per_kv_head"]
    seen = {}  # n_rep -> (the kernel's layout, the dimensions of the query it was handed)
    call = pa.pl.pallas_call

    def spy(kernel, **kw):
        run = call(kernel, **kw)

        def called(*args):
            seen[kernel.keywords["n_rep"]] = (kernel.keywords["per_kv_head"], args[3].ndim)
            return run(*args)

        return called

    monkeypatch.setattr(pa.pl, "pallas_call", spy)
    for n_rep in (1, 2, 4, 16):
        q, k_pool, v_pool, tables, positions = _pool_case(dtype, n_rep, 128, 16, [5, 40])
        pa._paged_attention_pallas(q, k_pool, v_pool, jnp.asarray(0), jnp.asarray(tables), jnp.asarray(positions) + 1,
                                   128 ** -0.5, 2, interpret=True)
    # [b, kv_heads, n_rep, head_dim] a KV head at a time, [b, rows, kv_heads * head_dim] block-diagonal
    assert seen == {1: (False, 3), 2: (False, 3), 4: (False, 3), 16: (True, 4)}


def test_tile_width_comes_from_the_shapes():
    assert pa._tile_blocks(16, 128, 2048) * 16 == pa.TILE_TOKENS  # InternLM2, Mistral
    assert pa._tile_blocks(16, 6, 2048) == 6            # never wider than the table
    assert pa._tile_blocks(4 * pa.TILE_TOKENS, 8, 2048) == 1
    # 32 KV heads x 128 in float32: four 256-token tiles would be 16 MiB of VMEM
    assert 4 * pa._tile_blocks(16, 128, 16384) * 16 * 16384 <= pa.TILE_BUFFER_BYTES


# ---------------------------------------------------------------------------
# the model's decode step over the paged path
# ---------------------------------------------------------------------------


def _lm(n_heads, n_kv_heads, head_dim, dtype, seed=0):
    cfg = TransformerConfig(
        vocab_size=61, d_model=n_heads * head_dim, n_layers=2, n_heads=n_heads,
        n_kv_heads=n_kv_heads, d_ff=64, max_seq_len=96, dtype=dtype,
        attention_impl="reference",
    )
    model = TransformerLM(cfg)
    variables = flax_meta.unbox(
        jax.jit(model.init)(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32))
    )
    return cfg, model, variables


# prompt lengths whose first decode step sits at: the last slot of a block,
# the first slot of a new block, mid-block; lane 0 stays empty.  With
# block_size 16 and 3 table columns the 47-token lane decodes at the table's
# full width.
_PROMPT_LENS = [15, 16, 21, 47]


def _decode_steps(cfg, params, prompts, block_size, chunk_blocks, steps=2):
    """Prefill each prompt into its lane's blocks, then ``steps`` greedy
    decode steps of the whole ragged batch (lane 0 empty).  Returns the
    per-step logits ``[steps, lanes, vocab]`` and the tokens fed."""
    t = 3
    lanes = len(prompts) + 1
    # one program each: called bare, the forward is compiled an operation at a time, anew at every case's shapes
    prefill = jax.jit(functools.partial(transformer_prefill, cfg))
    decode = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=chunk_blocks))
    cache = init_kv_cache(cfg, num_blocks=1 + lanes * t, block_size=block_size)
    tables = np.zeros((lanes, t), np.int32)
    toks = np.zeros(lanes, np.int32)
    poss = np.full(lanes, -1, np.int32)
    for lane, prompt in enumerate(prompts, start=1):
        tables[lane] = 1 + lane * t + np.arange(t) - t
        padded = np.zeros((1, t * block_size), np.int32)
        padded[0, : len(prompt)] = prompt
        logits, cache = prefill(params, padded, jnp.asarray([len(prompt)]), tables[lane][None], cache)
        toks[lane] = int(np.argmax(np.asarray(logits[0, len(prompt) - 1])))
        poss[lane] = len(prompt)
    out, fed = [], []
    for _ in range(steps):
        # a lane at the table's full width has no slot left: it retires
        poss = np.where(poss >= t * block_size, -1, poss)
        fed.append((toks.copy(), poss.copy()))
        logits, cache = decode(params, jnp.asarray(toks), jnp.asarray(poss), jnp.asarray(tables), cache)
        out.append(np.asarray(logits))
        toks = np.where(poss >= 0, np.argmax(out[-1], axis=-1), 0).astype(np.int32)
        poss = np.where(poss >= 0, poss + 1, -1).astype(np.int32)
    return np.stack(out), fed


@pytest.mark.parametrize(
    "impl,head_dim,block_size",
    [("jnp", 8, 4), ("kernel_interpret", 128, 16)],
    ids=["jnp-hd8", "kernel-hd128"],
)
@pytest.mark.parametrize(
    "n_heads,n_kv_heads", [(2, 2), (2, 1), (4, 1)], ids=["n_rep1", "n_rep2", "n_rep4"]
)
def test_paged_decode_matches_full_gather_and_full_forward(
    impl, head_dim, block_size, n_heads, n_kv_heads, monkeypatch
):
    """``chunk_blocks=1`` (the paged path, kernel or jnp) against
    ``chunk_blocks=0`` (the full-table gather) step for step on a ragged
    batch, and the last step against the full-sequence forward of each
    lane's own tokens."""
    monkeypatch.setattr(
        cache_kinds, "paged_decode_attention",
        functools.partial(pa.paged_decode_attention, impl=impl),
    )
    cfg, model, variables = _lm(n_heads, n_kv_heads, head_dim, jnp.float32, seed=4)
    params = variables["params"]
    rng = np.random.default_rng(2)
    scale = block_size / 16  # the same lanes at either block size
    prompts = [
        [int(x) for x in rng.integers(0, cfg.vocab_size, size=int(n * scale))]
        for n in _PROMPT_LENS
    ]
    paged, fed = _decode_steps(cfg, params, prompts, block_size, chunk_blocks=1)
    full, _ = _decode_steps(cfg, params, prompts, block_size, chunk_blocks=0)
    assert fed[0][1].tolist()[0] == -1 and (fed[0][1][1:] >= 0).all()
    for step, (_, poss) in enumerate(fed):
        live = poss >= 0
        np.testing.assert_allclose(paged[step][live], full[step][live], atol=3e-5, rtol=3e-4)
    # each lane's last live step against the full-sequence forward of its
    # prompt and the tokens the decode fed it (on one padded width: the
    # forward is causal, what follows a position cannot move it)
    forward = causal_forward(model, 3 * block_size + len(fed))
    for lane, prompt in enumerate(prompts, start=1):
        live_steps = [i for i, (_, poss) in enumerate(fed) if poss[lane] >= 0]
        seq = list(prompt) + [int(fed[i][0][lane]) for i in live_steps]
        want = forward(variables, seq)[-1]
        np.testing.assert_allclose(
            paged[live_steps[-1]][lane], np.asarray(want), atol=3e-5, rtol=3e-4
        )
