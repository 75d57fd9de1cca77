"""What the TPU's own compiler says of the serving cells' programs — no chip
(the why and the how: tests/test_tpu_compile.py, which keeps the training
kernels, ``dtpu serve``'s default programs and the decode step's pool).  Each
case is one compile at a cell's published widths, its lanes, pool and table,
depth alone cut: DeepSeek-V3's walk, decode program and latent kernel,
InternLM2's walk and decode kernel, Command A+'s programs and window kernel,
Brumby's programs and retention kernel.  Cut from that file because the
heaviest compiles of the suite are here, and ``--dist loadfile`` balances by
the file (until PR 65 the Brumby walk's chunk kernel was the heaviest, 65
unrolled feature rows a copy: it is a loop now, and the walk holds it twice)."""

import functools
import importlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.model_cases import (  # noqa: F401  (fixture reuse)
    compile_text as _compile,
    mosaic_calls as _kernels,
    paged_mod,
    real_kernels_no_cache,
    rows_mod,
    tpu_devices,
)


def _arrays_with_dims(text: str, dims) -> list:
    """Array shapes of an optimized HLO module (results and operands alike,
    inside fusions too) that have every one of ``dims`` among their dimensions."""
    found = set()
    for m in re.finditer(r"\b\w+\[([\d,]+)\]", text):
        shape = [int(d) for d in m.group(1).split(",")]
        if all(shape.count(d) >= list(dims).count(d) for d in dims):
            found.add(m.group(0))
    return sorted(found)


def _kernel_scratch(fn, *avals) -> dict:
    """Bytes of scratch the ONE Pallas call of ``fn`` asks for, by memory space
    (``vmem``, ``smem``; semaphores aside), off the call's own equation."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(inner)

    (call,) = calls(jax.make_jaxpr(fn)(*avals).jaxpr)
    scratch = call.params["jaxpr"].invars[-call.params["grid_mapping"].num_scratch_operands:]
    sizes = {}
    for ref in (v.aval for v in scratch):
        if str(ref.memory_space) != "semaphore_mem":
            sizes[str(ref.memory_space)] = sizes.get(str(ref.memory_space), 0) + math.prod(ref.shape) * ref.dtype.itemsize
    return sizes


def _walk_compiled(one, cfg, *, num_blocks, max_prompt_len, table_width):
    from flax.core import meta as flax_meta

    from determined_tpu.models.serving import prefill_chunk_tokens, transformer_prefill_chunked
    from determined_tpu.models.transformer import TransformerLM, kv_cache_shape

    boxed = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    shape = kv_cache_shape(cfg, num_blocks, 16)
    cache = {"kv": aval(shape, cfg.dtype)} if cfg.latent else {"k": aval(shape, cfg.dtype), "v": aval(shape, cfg.dtype)}
    assert prefill_chunk_tokens(16, max_prompt_len) == 256 and max_prompt_len % 256 == 0
    fn = jax.jit(functools.partial(transformer_prefill_chunked, cfg), donate_argnums=(5,))
    return fn.lower(params, aval((1, max_prompt_len)), aval((1,)), aval((1,)), aval((1, table_width)), cache).compile()


def test_the_prefill_walk_at_the_dsv3_cells_widths_holds_a_chunk_not_the_prompt(tpu_devices):
    """The walk at DeepSeek-V3's published widths, the cell's pool, table and
    ``max_prompt_len`` 4,096, depth cut to the dense layer and one expert
    layer: its scratch is a fraction of the wide pass's (1.32 GiB at this
    depth, 1.35 at the cell's five layers: PERF.md section 4), and no array
    anywhere in it has heads x chunk x ``max_seq_len`` (a chunk's scores
    against the whole table: 0.94 GB a layer); a tile's [128, 256, 256] is
    the largest the attention builds."""
    from determined_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=16160, d_model=7168, n_layers=2, n_heads=128, d_ff=18432, max_seq_len=7168,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        softmax_scale=0.135234, dense_prefix=1, moe_experts=256, moe_every=1, moe_top_k=8, moe_intermediate_size=2048,
        moe_experts_held=(0, 16), moe_router="sigmoid_grouped", moe_n_group=8, moe_topk_group=4,
        moe_routed_scaling=2.5, moe_shared_experts=1, param_dtype=jnp.bfloat16,
        rope_parameters={"full_attention": {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 40, "beta_fast": 32,
                                            "original_max_position_embeddings": 4096, "beta_slow": 1, "attention_factor": 1.0}},
    )
    compiled = _walk_compiled(SingleDeviceSharding(tpu_devices[0]), cfg, num_blocks=24576, max_prompt_len=4096, table_width=448)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.5 * 1024**3
    assert mem.alias_size_in_bytes >= 2 * 24576 * 16 * 640 * 2  # the pool is donated
    assert _arrays_with_dims(text, (128, 256, 7168)) == [] and _arrays_with_dims(text, (128, 256, 4096)) == []
    assert _arrays_with_dims(text, (128, 256, 256)) != []
    assert _kernels(text) == 6  # the expert layer's two row movements, three grouped products and hidden over live tiles


def test_the_prefill_walk_at_internlm2s_widths_keeps_no_second_copy_of_the_model(tpu_devices):
    """InternLM2-1.8B's widths and float32 leaves, the decode cell's pool,
    table and ``max_prompt_len`` 1,280, 6 of 24 layers: the leaves'
    conversions stay inside the loop (moved before it they are a bfloat16
    copy of every layer, 126 MB a layer: 1.29 GiB of scratch here, 3.3 at
    full depth, against 0.56 and 1.20), and no array has heads x chunk x
    ``max_seq_len``."""
    from determined_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=92544, d_model=2048, n_layers=6, n_heads=16, n_kv_heads=8, d_ff=8192, max_seq_len=2048, rope_theta=1e6,
    )
    compiled = _walk_compiled(SingleDeviceSharding(tpu_devices[0]), cfg, num_blocks=3500, max_prompt_len=1280, table_width=128)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.8 * 1024**3
    assert _arrays_with_dims(text, (16, 256, 2048)) == [] and _arrays_with_dims(text, (2, 256, 2048)) == []
    entry = text[text.index("ENTRY "):]
    assert not re.search(r"= bf16\[(2048,8192|8192,2048)\]\S* (convert|fusion)\(", entry)


def test_the_internlm2_decode_program_finds_its_kernel_in_its_scope(tpu_devices):
    """The decode program of the InternLM2 cells at their widths, lanes, pool
    and table, float32 leaves, 2 of 24 layers: one Mosaic call a layer, under
    the name and in the scope a trace's reader asks for, whatever the copy
    schedule inside it."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.serving import transformer_decode
    from determined_tpu.models.transformer import TransformerConfig, TransformerLM, kv_cache_shape
    from determined_tpu.utils.compilation_cache import program_scopes

    one = SingleDeviceSharding(tpu_devices[0])
    cfg = TransformerConfig(
        vocab_size=92544, d_model=2048, n_layers=2, n_heads=16, n_kv_heads=8, d_ff=8192, max_seq_len=2048, rope_theta=1e6,
    )
    boxed = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    shape = kv_cache_shape(cfg, 3500, 16)
    cache = {"k": aval(shape, cfg.dtype), "v": aval(shape, cfg.dtype)}
    fn = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1), donate_argnums=(4,))
    text = fn.lower(params, aval((32,)), aval((32,)), aval((32, 128)), cache).compile().as_text()
    assert _kernels(text) == cfg.n_layers
    assert sum("paged_decode_attention" in n for n in program_scopes(text)["serve.attn.attend"]) == cfg.n_layers


# -- latent attention and served experts: the DeepSeek-V3 cell's shapes --------


def test_the_latent_decode_kernel_compiles_at_the_dsv3_cells_shape(tpu_devices):
    """64 lanes x 128 heads against ONE 640-wide row a token (576 values
    and 64 zeros), blocks of 16, a table of 448 columns (7,168 positions), a
    pool of 24,576 blocks x 5 layers: tiles of 512 tokens, two buffers."""
    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731

    def fn(q, pool, tables, positions):
        return paged_mod.paged_latent_attention(q, pool, 3, tables, positions, scale=0.135, value_dim=512)

    text = _compile(
        fn, aval((64, 128, 640), jnp.bfloat16), aval((5, 24576, 16, 640), jnp.bfloat16),
        aval((64, 448), jnp.int32), aval((64,), jnp.int32),
    )
    assert _kernels(text) == 1 and "paged_latent_attention" in text
    assert paged_mod.latent_kernel_takes(640, 512, 16, jnp.bfloat16) and not paged_mod.latent_kernel_takes(576, 512, 16, jnp.bfloat16)
    # the tile's two slots and the accumulator, as before the copies crossed lanes; the schedule's two words are SMEM
    scratch = _kernel_scratch(
        fn, aval((64, 128, 640), jnp.bfloat16), aval((5, 24576, 16, 640), jnp.bfloat16), aval((64, 448), jnp.int32), aval((64,), jnp.int32))
    assert scratch == {"vmem": 2 * 512 * 640 * 2 + 128 * 512 * 4, "smem": 8} and scratch["vmem"] <= paged_mod.TILE_BUFFER_BYTES


def test_the_dsv3_decode_program_compiles_with_its_kernels_named(tpu_devices):
    """The decode program of the cell at its widths, lanes and pool, bfloat16
    leaves, depth cut to the dense layer and one expert layer: the latent
    kernel a layer, and in the expert layer the two row movements and three
    grouped products over [7168, 2048] blocks (58.7 MB of VMEM for a block's
    two buffers).  Each Mosaic call keeps its name in the optimized program,
    so the scopes a trace's reader asks for list it."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.serving import transformer_decode
    from determined_tpu.models.transformer import TransformerConfig, TransformerLM, kv_cache_shape
    from determined_tpu.utils.compilation_cache import program_scopes

    one = SingleDeviceSharding(tpu_devices[0])
    cfg = TransformerConfig(
        vocab_size=16160, d_model=7168, n_layers=2, n_heads=128, d_ff=18432, max_seq_len=7168,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        softmax_scale=0.135234, dense_prefix=1, moe_experts=256, moe_every=1, moe_top_k=8, moe_intermediate_size=2048,
        moe_experts_held=(0, 16), moe_router="sigmoid_grouped", moe_n_group=8, moe_topk_group=4,
        moe_routed_scaling=2.5, moe_shared_experts=1, param_dtype=jnp.bfloat16,
        rope_parameters={"full_attention": {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 40, "beta_fast": 32,
                                            "original_max_position_embeddings": 4096, "beta_slow": 1, "attention_factor": 1.0}},
    )
    boxed = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    cache = {"kv": aval(kv_cache_shape(cfg, 24576, 16), cfg.dtype)}
    fn = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True), donate_argnums=(4,))
    compiled = fn.lower(params, aval((64,)), aval((64,)), aval((64, 448)), cache).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == 2 + 6
    assert mem.temp_size_in_bytes < 256 * 1024**2 and mem.alias_size_in_bytes >= 2 * 24576 * 16 * 640 * 2   # the pool is donated
    scopes = program_scopes(text)
    assert {"serve.mla", "serve.mla.attend", "serve.moe.route", "serve.moe.experts", "serve.moe.shared"} <= set(scopes)
    assert sum("paged_latent_attention" in n for n in scopes["serve.mla.attend"]) == 2
    named = [n for n in scopes["serve.moe.experts"] if re.match(r"(moe_gmm|moe_rows_of_tokens|moe_tokens_of_rows)", n)]
    assert len(named) == 5, scopes["serve.moe.experts"]


# -- two latent rows a token a block, identity experts: the LongCat-Flash-Omni cell's shapes --


def _longcat_cfg(n_layers: int):
    from determined_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=16384, d_model=6144, n_layers=n_layers, n_heads=64, d_ff=12288, max_seq_len=5120, rope_theta=1e7,
        norm_eps=1e-5, shortcut_block=True, q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, q_latent_scale=2.0, kv_latent_scale=12.0 ** 0.5, moe_experts=512, moe_zero_experts=256, moe_every=1,
        moe_top_k=12, moe_intermediate_size=2048, moe_experts_held=(0, 16), moe_router="softmax_bias",
        moe_routed_scaling=6.0, param_dtype=jnp.bfloat16,
    )


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_longcat_cells_programs_compile_over_two_rows_a_block(tpu_devices, which):
    """The LongCat-Flash-Omni cell's programs at its widths, lanes, pool (10,240
    blocks) and table (320 columns: 5,120 positions), bfloat16 leaves, depth cut
    to ONE double layer: two rows of the latent pool and two latent kernels a
    block at 64 heads, the expert layer's two row movements and three grouped
    products, and an identity part that is no kernel and no row; every Mosaic
    call keeps its name under its scope."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.serving import transformer_decode
    from determined_tpu.models.transformer import TransformerLM, kv_cache_shape
    from determined_tpu.utils.compilation_cache import program_scopes

    one = SingleDeviceSharding(tpu_devices[0])
    cfg = _longcat_cfg(1)
    assert kv_cache_shape(cfg, 10240, 16) == (2, 10240, 16, 640)
    pool_bytes = 2 * 10240 * 16 * 640 * 2
    if which == "prefill":
        compiled = _walk_compiled(one, cfg, num_blocks=10240, max_prompt_len=3072, table_width=320)
        text, mem = compiled.as_text(), compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 0.5 * 1024**3 and mem.alias_size_in_bytes >= pool_bytes
        assert _arrays_with_dims(text, (64, 256, 5120)) == [] and _kernels(text) == 6
        return
    boxed = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    cache = {"kv": aval(kv_cache_shape(cfg, 10240, 16), cfg.dtype)}
    fn = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True), donate_argnums=(4,))
    compiled = fn.lower(params, aval((64,)), aval((64,)), aval((64, 320)), cache).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == 2 + 6
    assert mem.temp_size_in_bytes < 256 * 1024**2 and mem.alias_size_in_bytes >= pool_bytes   # the pool is donated
    scopes = program_scopes(text)
    assert {"serve.mla", "serve.mla.attend", "serve.mlp", "serve.moe.route", "serve.moe.experts", "serve.moe.identity"} <= set(scopes)
    assert sum("paged_latent_attention" in n for n in scopes["serve.mla.attend"]) == 2
    named = [n for n in scopes["serve.moe.experts"] if re.match(r"(moe_gmm|moe_rows_of_tokens|moe_tokens_of_rows)", n)]
    assert len(named) == 5, scopes["serve.moe.experts"]
    assert not any(re.match(r"(moe_|paged_)", n) for n in scopes["serve.moe.identity"])  # a weighted add, no kernel


# -- sliding-window layers served from a ring a lane: the Command A+ cell's shapes --


def test_the_window_decode_kernel_compiles_at_the_command_cells_shape(tpu_devices):
    """32 lanes x 128 query heads over 8 KV heads of 128, blocks of 16, a ring
    of 272 blocks (4,352 tokens) a lane in a store of three window layers:
    tiles of 256 tokens through the ring, the window's 4,096 newest alone."""
    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731

    def fn(q, k_pool, v_pool, tables, positions):
        return paged_mod.paged_decode_attention(q, k_pool, v_pool, 2, tables, positions, scale=128 ** -0.5, window=4096)

    store = aval((3, 32 * 272, 16, 1024), jnp.bfloat16)
    text = _compile(fn, aval((32, 128, 128), jnp.bfloat16), store, store, aval((32, 272), jnp.int32), aval((32,), jnp.int32))
    assert _kernels(text) == 1 and "paged_window_attention" in text
    scratch = _kernel_scratch(fn, aval((32, 128, 128), jnp.bfloat16), store, store, aval((32, 272), jnp.int32), aval((32,), jnp.int32))
    assert scratch == {"vmem": 2 * 2 * 256 * 1024 * 2, "smem": 8} and scratch["vmem"] <= paged_mod.TILE_BUFFER_BYTES
    # 16 query heads a KV head: the kernel multiplies a KV head's own queries, and no block-diagonal query is built
    assert paged_mod.attn_products(16) == "per_kv_head" and _arrays_with_dims(text, (32, 128, 1024)) == []


@pytest.mark.parametrize(
    "heads, pool_shape, products",
    [(16, (24, 3500, 16, 1024), "block_diagonal"), (64, (1, 24576, 16, 1024), "per_kv_head")],
    ids=["internlm2-cell", "8-a-kv-head"],
)
def test_the_decode_kernel_compiles_in_both_layouts_of_its_products(tpu_devices, heads, pool_shape, products):
    """The InternLM2 cells' shape: 32 lanes x 16 query heads over 8 KV heads of
    128 against a bfloat16 pool of 3,500 blocks x 24 layers, where 2 query heads
    a KV head keep the block-diagonal query (16 rows against the tile's 1,024
    columns); and 8 query heads a KV head, half a packed tile of the bfloat16
    query each, where the rule turns (1.60 -> 1.44 us a tile: PERF.md, PR 42)."""
    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731

    def fn(q, k_pool, v_pool, tables, positions):
        return paged_mod.paged_decode_attention(q, k_pool, v_pool, 0, tables, positions, scale=128 ** -0.5)

    pool = aval(pool_shape, jnp.bfloat16)
    text = _compile(fn, aval((32, heads, 128), jnp.bfloat16), pool, pool, aval((32, 128), jnp.int32), aval((32,), jnp.int32))
    assert _kernels(text) == 1 and "paged_decode_attention" in text
    assert paged_mod.attn_products(heads // 8) == products
    # K and V, two slots of a 256-token tile each: what the kernel took before its copies crossed lanes
    scratch = _kernel_scratch(fn, aval((32, heads, 128), jnp.bfloat16), pool, pool, aval((32, 128), jnp.int32), aval((32,), jnp.int32))
    assert scratch == {"vmem": 2 * 2 * 256 * 1024 * 2, "smem": 8} and scratch["vmem"] <= paged_mod.TILE_BUFFER_BYTES
    assert bool(_arrays_with_dims(text, (32, heads, 1024))) == (products == "block_diagonal")


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_command_cells_programs_compile_over_a_cache_of_two_kinds(tpu_devices, which):
    """The cell's decode step and prefill walk at its widths, lanes, pool and
    window store, bfloat16 leaves, depth cut to one window layer and the full
    layer: weights, both kinds of cache and the program's scratch fit the chip;
    the window layer's kernel keeps its name under its own scope, the full
    layer's under the other; nothing the size of a lane's context is gathered."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.serving import (
        prefill_chunk_tokens,
        transformer_decode,
        transformer_prefill_chunked,
    )
    from determined_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
        kv_cache_shape,
        window_store_shape,
    )
    from determined_tpu.utils.compilation_cache import program_scopes

    one = SingleDeviceSharding(tpu_devices[0])
    cfg = TransformerConfig(
        vocab_size=32768, d_model=4096, n_layers=2, n_heads=128, n_kv_heads=8, head_dim=128, max_seq_len=20480,
        layer_types=("sliding_attention", "full_attention"), sliding_window=4096,
        rope_parameters={"full_attention": {"rope_type": "none"}, "sliding_attention": {"rope_type": "default", "rope_theta": 50000.0}},
        moe_experts=128, moe_every=1, moe_top_k=8, moe_intermediate_size=4096, moe_experts_held=(0, 16), moe_router="sigmoid",
        moe_shared_experts=4, moe_shared_combine="mean", norm="layernorm", norm_eps=1e-5, parallel_block=True,
        tie_embeddings=True, param_dtype=jnp.bfloat16,
    )
    boxed = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    assert prefill_chunk_tokens(16, 14336) == 256
    pool, ring = kv_cache_shape(cfg, 24576, 16), window_store_shape(cfg, 32, 16, 256)
    assert pool == (1, 24576, 16, 1024) and ring == (1, 32 * 272, 16, 1024)
    cache = {"k": aval(pool, cfg.dtype), "v": aval(pool, cfg.dtype), "wk": aval(ring, cfg.dtype), "wv": aval(ring, cfg.dtype)}
    if which == "decode":
        fn = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True), donate_argnums=(4,))
        args = (params, aval((32,)), aval((32,)), aval((32, 1280)), cache)
    else:
        fn = jax.jit(functools.partial(transformer_prefill_chunked, cfg), donate_argnums=(5,))
        args = (params, aval((1, 14336)), aval((1,)), aval((1,)), aval((1, 1280)), cache, aval((1,)))
    compiled = fn.lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    cache_bytes = 2 * 2 * (24576 + 32 * 272) * 16 * 1024
    assert mem.alias_size_in_bytes >= cache_bytes                                    # both kinds are donated
    assert mem.temp_size_in_bytes < (64 if which == "decode" else 1024) * 1024**2
    scopes = program_scopes(text)
    assert {"serve.attn.window", "serve.attn.full", "serve.attn.attend", "serve.kv.write", "serve.moe.route", "serve.moe.experts",
            "serve.moe.shared"} <= set(scopes)
    if which == "decode":
        assert _kernels(text) == 2 + 2 * 6                                          # an attention kernel and six of the experts a layer
        assert sum("paged_window_attention" in n for n in scopes["serve.attn.window"]) == 1
        assert sum("paged_decode_attention" in n for n in scopes["serve.attn.full"]) == 1
        assert not any("paged_" in n for n in set(scopes["serve.attn.window"]) & set(scopes["serve.attn.full"]))
        assert _arrays_with_dims(text, (32, 20480)) == [] and _arrays_with_dims(text, (32, 4352, 1024)) == []
    else:
        # 14,336 tokens hold eight wide chunks: the walk has its wide loop and its narrow one (prompts start at 0: no
        # third), each with the experts' six kernels a layer; a wide chunk's attention still goes a narrow chunk's
        # tile at a time (no [heads, 1024, 1024] scores, no ring written 1,024 rows at once), and no pool or ring is copied
        assert _kernels(text) == 2 * 2 * 6
        assert _arrays_with_dims(text, (128, 256, 20480)) == [] and _arrays_with_dims(text, (256, 4352)) == []
        assert _arrays_with_dims(text, (128, 1024, 1024)) == [] and _arrays_with_dims(text, (1024, 4352)) == []
        assert not re.search(r"bf16\[(1,)?(24576|8704),16,1024\]\S* copy\(", text)


# -- power-retention layers served from a state a lane: the Brumby cell's shapes --


def test_the_retention_decode_kernel_compiles_at_the_brumby_cells_shape(tpu_devices):
    """32 lanes x 40 query heads over 8 KV heads of 128 against a float32 state
    pool of five layers (8,320 x 128 a head) and the lanes' recent rows: ONE
    kernel (the conditional write is its own: a block index held where it was,
    no second call for the fold), the pools and the rows updated where they lie
    (aliased, no scratch the size of a layer's state)."""
    retention_mod = importlib.import_module("determined_tpu.ops.retention")
    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    state, norm = retention_mod.state_shapes(5, 32, 8, 128)
    assert state == (5, 32, 8, 8320, 128) and norm == (5, 32, 8, 65, 128)
    rows = retention_mod.recent_shapes(5, 32, 8, 128)
    every = retention_mod.FOLD_EVERY
    assert rows == ((5, 32, 8, every, 128),) * 2 + ((5, 32, 8, every), (5, 32))
    row_dtypes = (jnp.bfloat16, jnp.bfloat16, jnp.float32, jnp.int32)

    def fn(q, k, v, log_g, rs, rz, recent, live):
        return retention_mod.retention_decode(q, k, v, log_g, rs, rz, recent, 3, live, impl="kernel")

    compiled = jax.jit(fn, donate_argnums=(4, 5, 6)).lower(
        aval((32, 40, 128), jnp.bfloat16), aval((32, 8, 128), jnp.bfloat16), aval((32, 8, 128), jnp.bfloat16),
        aval((32, 8), jnp.float32), aval(state, jnp.float32), aval(norm, jnp.float32),
        tuple(aval(shape, dt) for shape, dt in zip(rows, row_dtypes)), aval((32,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == 1 and "retention_decode" in text
    pool_bytes = 4 * (math.prod(state) + math.prod(norm))
    row_bytes = sum(math.prod(shape) * jnp.dtype(dt).itemsize for shape, dt in zip(rows, row_dtypes))
    assert row_bytes == 5 * 32 * (2 * 8 * every * 128 * 2 + 8 * every * 4 + 4) < pool_bytes // 200       # 21 MB beside 5.5 GB
    assert mem.alias_size_in_bytes >= pool_bytes + row_bytes and mem.temp_size_in_bytes < 16 * 1024**2


@pytest.mark.parametrize("heads,tokens,state_dtype", [(5, 256, jnp.float32), (5, 256, jnp.bfloat16), (8, 512, jnp.float32)], ids=["cell", "bfloat16_state", "most_rows"])
def test_the_retention_chunk_kernel_compiles_as_a_loop_over_its_feature_rows(tpu_devices, heads, tokens, state_dtype):
    """The walk's chunk at the cell's shape (8 KV heads x 5 query heads x 256 tokens: 1,280 query rows a program), with
    the state the check's control sets (a bfloat16 row cannot be read at a dynamic index: the normaliser is held in a
    float32 scratch), and at the most rows ``chunk_kernel_takes`` admits (4,096: they and their answers fit VMEM beside
    the state's blocks): the feature rows as a loop, a dynamic lane rotation and a dynamic row of the state are what
    Mosaic has to take, and interpret mode shows none of it."""
    retention_mod = importlib.import_module("determined_tpu.ops.retention")
    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    b, g, d = 1, 8 if tokens == 256 else 2, 128
    assert retention_mod.chunk_kernel_takes(heads * tokens, tokens, d, state_dtype)
    assert tokens == 256 or not retention_mod.chunk_kernel_takes(heads * tokens + 8, tokens, d, state_dtype)  # the bound itself
    rows = retention_mod.phi_rows(d)
    fn = jax.jit(functools.partial(retention_mod._chunk_state_pallas, interpret=False), donate_argnums=(5, 6))
    lowered = fn.lower(
        aval((b, g, heads, tokens, d)), aval((b, g, tokens, d)), aval((b, g, tokens, d)), aval((b, g, tokens)), aval((b, g)),
        aval((b, g, rows * d, d), state_dtype), aval((b, g, rows, d), state_dtype),
    )
    (body,) = re.findall(r'backend_config = "([^"]*)"', lowered.as_text())
    assert len(body) < (32 if heads == 5 else 48) * 1024                             # five rows a trip; the 65 rows written out were 293 KB at the cell's shape
    compiled = lowered.compile()
    assert _kernels(compiled.as_text()) == 1 and "retention_chunk" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1024**2                   # the state is updated where it lies


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_brumby_cells_programs_compile_over_a_state_pool_alone(tpu_devices, which):
    """The cell's decode step and prefill walk at its widths, lanes and state
    pool, bfloat16 leaves, depth cut to two layers: weights, the pool and the
    program's scratch fit the chip; the pool is donated and no second copy of
    it is held; the kernel keeps its name under its own scope; no array is
    made for the allocator's block ids."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.serving import transformer_decode, transformer_prefill_chunked
    from determined_tpu.models.cache_kinds import STATE_SLOT
    from determined_tpu.models.transformer import TransformerConfig, TransformerLM, recent_rows_shapes, state_pool_shapes
    from determined_tpu.utils.compilation_cache import program_scopes

    one = SingleDeviceSharding(tpu_devices[0])
    cfg = TransformerConfig(
        vocab_size=151936, d_model=5120, n_layers=2, n_heads=40, n_kv_heads=8, head_dim=128, d_ff=17408, max_seq_len=28672,
        layer_types=("power_retention",) * 2, qk_norm=True, retention_gate_bias=6.0, rope_theta=1e6, param_dtype=jnp.bfloat16,
    )
    boxed = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    state, norm = state_pool_shapes(cfg, 32)
    shapes = (state, norm) + recent_rows_shapes(cfg, 32)
    cache = {leaf: aval(shape, dt) for leaf, shape, dt in zip(STATE_SLOT.leaves, shapes, STATE_SLOT.dtypes(cfg))}
    assert tuple(cache) == ("rs", "rz", "rk", "rv", "rg", "rn") and cache["rk"].dtype == jnp.bfloat16 and cache["rs"].dtype == jnp.float32
    if which == "decode":
        fn = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True), donate_argnums=(4,))
        args = (params, aval((32,)), aval((32,)), aval((32, 1792)), cache)
    else:
        fn = jax.jit(functools.partial(transformer_prefill_chunked, cfg, chunk_tokens=256), donate_argnums=(5,))
        args = (params, aval((1, 22528)), aval((1,)), aval((1,)), aval((1, 1792)), cache, aval((1,)))
    compiled = fn.lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    pool_bytes = 4 * (math.prod(state) + math.prod(norm))
    assert pool_bytes == 2 * 32 * 34_344_960
    assert mem.alias_size_in_bytes >= pool_bytes                                     # the pool is donated
    # the decode step holds nothing the size of a layer's pool; a chunk of the walk holds its scores and products, and phi of nothing (the kernel builds it in VMEM)
    assert mem.temp_size_in_bytes < (64 if which == "decode" else 512) * 1024**2
    scopes = program_scopes(text)
    assert {"serve.retention.qkvg", "serve.retention.state", "serve.retention.out", "serve.mlp", "serve.embed", "serve.head"} <= set(scopes)
    assert "serve.attn.qkv" not in scopes
    if which == "decode":
        assert "serve.kv.write" not in scopes
        # a layer's ONE kernel answers, and folds the lanes that are due: no second call, no branch of the program
        assert _kernels(text) == 2 and len({n for n in scopes["serve.retention.state"] if n.startswith("retention_decode")}) == 2
        assert "conditional(" not in text
        assert _arrays_with_dims(text, (32, 1792)) == []                             # the block tables are read by nothing
    else:
        # 22,528 tokens hold eight wide chunks: the walk has its wide loop and its narrow one, ONE chunk kernel a layer in
        # each (a wide chunk's narrow chunks pass through it one after the other under a scan: it sees 256 tokens a call
        # either way); the kernel's feature rows are a loop, so its serialized Mosaic body is ~30 KB where the 65 unrolled
        # copies were 293 KB a call (and 45 s of this compile); the walk gathers its lane's slots and holds no second pool
        assert _kernels(text) == 2 * 2 and len({n for n in scopes["serve.retention.state"] if n.startswith("retention_chunk")}) == 4
        assert text.count(" while(") == 2 + 2                                         # the two loops of the walk; a scan a layer inside the wide one
        bodies = re.findall(r'stablehlo.custom_call @tpu_custom_call.*?backend_config = "([^"]*)"', fn.lower(*args).as_text())
        assert bodies and max(len(body) for body in bodies) < 32 * 1024             # one jitted function, called by every layer of both loops
        assert mem.temp_size_in_bytes < 32 * 34_344_960 // 2                          # no scratch the size of a layer's state: a wide chunk's slot stays in hand through its scan


def test_the_ssm_decode_kernel_compiles_at_the_falcon_cells_shape(tpu_devices):
    """64 lanes x 32 Mamba-2 heads of 128 over 2 groups of 256 state values
    against a float32 state pool of six layers: one kernel, the pool updated
    where it lies (aliased, no scratch the size of a layer's state)."""
    ssm_mod = importlib.import_module("determined_tpu.ops.ssm")
    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    state = ssm_mod.state_shape(6, 64, 32, 128, 256)
    assert state == (6, 65, 32, 128, 256) and ssm_mod.kernel_takes(32, 2, 128, 256, jnp.float32)

    def fn(x, b, c, dt, a, skip, pool, live):
        return ssm_mod.ssm_decode(x, b, c, dt, a, skip, pool, 4, live)

    compiled = jax.jit(fn, donate_argnums=(6,)).lower(
        aval((64, 32, 128), jnp.bfloat16), aval((64, 2, 256), jnp.bfloat16), aval((64, 2, 256), jnp.bfloat16),
        aval((64, 32), jnp.float32), aval((32,), jnp.float32), aval((32,), jnp.float32), aval(state, jnp.float32),
        aval((64,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == 1 and "ssm_decode" in text
    assert mem.alias_size_in_bytes >= 4 * math.prod(state) and mem.temp_size_in_bytes < 16 * 1024**2


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_falcon_cells_programs_compile_over_a_layer_of_two_kinds(tpu_devices, which):
    """The cell's decode step and prefill walk at its widths, lanes, pool and
    state pool, bfloat16 leaves, all six layers: the weights, both pools and
    the program's scratch fit the chip's 15.75 GiB; the cache is donated and no
    second copy of a pool is held; each branch keeps its scopes, the state
    kernel its name under its own; the paged kernel multiplies 5 queries a KV
    head in the block-diagonal layout."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.cache_kinds import PAGED_KV, SSM_SLOT, cache_kinds
    from determined_tpu.models.serving import transformer_decode, transformer_prefill_chunked
    from determined_tpu.models.transformer import TransformerConfig, TransformerLM, kv_cache_shape, ssm_pool_shapes
    from determined_tpu.utils.compilation_cache import program_scopes

    one = SingleDeviceSharding(tpu_devices[0])
    cfg = TransformerConfig(
        vocab_size=261120, d_model=5120, n_layers=6, n_heads=20, n_kv_heads=4, head_dim=128, d_ff=21504, max_seq_len=2560,
        layer_types=("attention_mamba2",) * 6, rope_theta=1e11, norm_eps=1e-5, param_dtype=jnp.bfloat16,
        ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_groups=2, ssm_conv=4, ssm_chunk=128,
        embedding_multiplier=5.656854249492381, key_multiplier=0.011048543456039804, attention_out_multiplier=0.0375,
        ssm_in_multiplier=0.25, ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738),
        ssm_out_multiplier=0.08838834764831845, mlp_multipliers=(0.1767766952966369, 0.011160714285714284), logit_scale=0.0078125,
    )
    assert cache_kinds(cfg) == (PAGED_KV, SSM_SLOT)
    boxed = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    pool, (state, tail) = kv_cache_shape(cfg, 8193, 16), ssm_pool_shapes(cfg, 64)
    assert pool == (6, 8193, 16, 512) and state == (6, 65, 32, 128, 256) and tail == (6, 64, 3, 5120)
    cache = {"k": aval(pool, jnp.bfloat16), "v": aval(pool, jnp.bfloat16), "ssm": aval(state, jnp.float32), "conv": aval(tail, jnp.bfloat16)}
    if which == "decode":
        fn = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True), donate_argnums=(4,))
        args = (params, aval((64,)), aval((64,)), aval((64, 160)), cache)
    else:
        fn = jax.jit(functools.partial(transformer_prefill_chunked, cfg, chunk_tokens=256), donate_argnums=(5,))
        args = (params, aval((1, 2048)), aval((1,)), aval((1,)), aval((1, 160)), cache, aval((1,)))
    compiled = fn.lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    cache_bytes = 2 * 2 * math.prod(pool) + 4 * math.prod(state) + 2 * math.prod(tail)
    assert mem.alias_size_in_bytes >= cache_bytes                                    # the cache is donated: no second copy of a pool
    assert mem.argument_size_in_bytes >= 2 * 5_254_594_112 + cache_bytes
    # what the chip must hold at once: the arguments (the weights, both pools), what is not aliased of the output, the scratch
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.75 * 1024**3
    assert mem.temp_size_in_bytes < (256 if which == "decode" else 768) * 1024**2
    scopes = program_scopes(text)
    assert {"serve.attn.qkv", "serve.kv.write", "serve.attn.attend", "serve.attn.out", "serve.ssm.in", "serve.ssm.state",
            "serve.ssm.out", "serve.mlp", "serve.embed", "serve.head"} <= set(scopes)
    if which == "decode":                                                            # a layer: the paged kernel and the state kernel
        assert _kernels(text) == 12 and len({n for n in scopes["serve.ssm.state"] if n.startswith("ssm_decode")}) == 6
        assert paged_mod.attn_products(5) == "block_diagonal"
    print(which, "args", mem.argument_size_in_bytes, "out", mem.output_size_in_bytes, "alias", mem.alias_size_in_bytes, "temp", mem.temp_size_in_bytes)


def test_the_ssm_decode_kernel_compiles_at_the_nemotron_cells_shape(tpu_devices):
    """64 lanes x 128 Mamba-2 heads of 64 over 8 groups of 128 state values
    against a float32 state pool of five layers and 65 slots: one kernel, four
    groups' 64 heads a program (2 MB of state each way), the pool updated where
    it lies (aliased, no scratch the size of a layer's state)."""
    ssm_mod = importlib.import_module("determined_tpu.ops.ssm")
    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    state = ssm_mod.state_shape(5, 64, 128, 64, 128)
    assert state == (5, 65, 128, 64, 128) and ssm_mod.kernel_takes(128, 8, 64, 128, jnp.float32)
    assert ssm_mod.groups_a_program(8, 16, 64, 128, jnp.float32) == 4

    def fn(x, b, c, dt, a, skip, pool, live):
        return ssm_mod.ssm_decode(x, b, c, dt, a, skip, pool, 3, live)

    compiled = jax.jit(fn, donate_argnums=(6,)).lower(
        aval((64, 128, 64), jnp.bfloat16), aval((64, 8, 128), jnp.bfloat16), aval((64, 8, 128), jnp.bfloat16),
        aval((64, 128), jnp.float32), aval((128,), jnp.float32), aval((128,), jnp.float32), aval(state, jnp.float32),
        aval((64,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == 1 and "ssm_decode" in text
    assert mem.alias_size_in_bytes >= 4 * math.prod(state) and mem.temp_size_in_bytes < 16 * 1024**2


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_nemotron_cells_programs_compile_over_layers_of_one_mixer_each(tpu_devices, which):
    """The cell's decode step and prefill walk at its widths, lanes, pool and
    state pool, bfloat16 leaves, all eleven layers (five Mamba-2, five expert,
    one attention): the weights, both pools and the program's scratch fit the
    chip's 15.75 GiB; the cache is donated and no second copy of a pool is
    held; each mixer keeps its scopes, the state kernel its name under its own
    (five of them), the expert layers their grouped products under theirs and
    the two latent projections under ``serve.moe.latent``; the paged kernel
    multiplies 16 queries a KV head."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.cache_kinds import PAGED_KV, SSM_SLOT, cache_kinds, layers_by_kind
    from determined_tpu.models.serving import transformer_decode, transformer_prefill_chunked
    from determined_tpu.models.transformer import TransformerConfig, TransformerLM, kv_cache_shape, ssm_pool_shapes
    from determined_tpu.utils.compilation_cache import program_scopes

    one = SingleDeviceSharding(tpu_devices[0])
    letters = {"M": "mamba2", "*": "full_attention", "E": "experts"}
    cfg = TransformerConfig(
        vocab_size=32768, d_model=4096, n_layers=11, n_heads=32, n_kv_heads=2, head_dim=128, max_seq_len=12288, norm_eps=1e-5,
        mixer_block=True, layer_types=tuple(letters[c] for c in "MEMEMEMEM*E"), rope_parameters={"full_attention": {"rope_type": "none"}},
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=8, ssm_conv=4, ssm_chunk=128, param_dtype=jnp.bfloat16,
        moe_experts=512, moe_top_k=22, moe_intermediate_size=2688, moe_experts_held=(0, 128), moe_router="sigmoid_grouped",
        moe_routed_scaling=5.0, moe_shared_experts=1, moe_shared_intermediate_size=5376, moe_expert_act="relu2", moe_latent_size=1024,
    )
    assert cache_kinds(cfg) == (PAGED_KV, SSM_SLOT) and layers_by_kind(cfg) == {"paged_kv": 1, "ssm_slot": 5, "none": 5}
    boxed = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    pool, (state, tail) = kv_cache_shape(cfg, 32769, 16), ssm_pool_shapes(cfg, 64)
    assert pool == (1, 32769, 16, 256) and state == (5, 65, 128, 64, 128) and tail == (5, 64, 3, 10240)
    cache = {"k": aval(pool, jnp.bfloat16), "v": aval(pool, jnp.bfloat16), "ssm": aval(state, jnp.float32), "conv": aval(tail, jnp.bfloat16)}
    if which == "decode":
        fn = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True), donate_argnums=(4,))
        args = (params, aval((64,)), aval((64,)), aval((64, 768)), cache)
    else:
        fn = jax.jit(functools.partial(transformer_prefill_chunked, cfg, chunk_tokens=256), donate_argnums=(5,))
        args = (params, aval((1, 8192)), aval((1,)), aval((1,)), aval((1, 768)), cache, aval((1,)))
    compiled = fn.lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    cache_bytes = 2 * 2 * math.prod(pool) + 4 * math.prod(state) + 2 * math.prod(tail)
    assert mem.alias_size_in_bytes >= cache_bytes                                    # the cache is donated: no second copy of a pool
    assert mem.argument_size_in_bytes >= 2 * 4_648_163_712 + cache_bytes
    # what the chip must hold at once: the arguments (the weights, both pools), what is not aliased of the output, the scratch
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.75 * 1024**3
    assert mem.temp_size_in_bytes < (256 if which == "decode" else 1024) * 1024**2
    scopes = program_scopes(text)
    assert {"serve.attn.qkv", "serve.kv.write", "serve.attn.attend", "serve.attn.out", "serve.mamba2.in", "serve.mamba2.state",
            "serve.mamba2.out", "serve.moe.route", "serve.moe.latent", "serve.moe.experts", "serve.moe.shared", "serve.embed",
            "serve.head"} <= set(scopes) and not {"serve.mlp", "serve.ssm.state"} & set(scopes)
    if which == "decode":   # a Mamba-2 layer: the state kernel; the attention layer: the paged kernel; an expert layer: rows in, two products with hidden between them, rows out
        assert _kernels(text) == 5 + 1 + 5 * 5 and len({n for n in scopes["serve.mamba2.state"] if n.startswith("ssm_decode")}) == 5
        assert len({n for n in scopes["serve.moe.experts"] if n.startswith("moe_gmm")}) == 10
        assert len({n for n in scopes["serve.moe.experts"] if n.startswith("moe_hidden_rows")}) == 5   # timed with the experts
        assert paged_mod.attn_products(16) == "per_kv_head"
    print(which, "args", mem.argument_size_in_bytes, "out", mem.output_size_in_bytes, "alias", mem.alias_size_in_bytes, "temp", mem.temp_size_in_bytes)


# -- the decode step's sampler: one call over all lanes, at the serving cells' logits ------


_ARRAY = re.compile(r"\b(pred|s8|s16|s32|u8|u16|u32|bf16|f16|f32|f64)\[([\d,]*)\]\{([^}]*)\}")
_WIDTH = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "f64": 8}


# -- latent rows an indexer picks, an index key a token beside them: the GLM-5.2 cell's shapes --


def _glm_cfg(n_layers: int = 3):
    """The cell's widths, depth cut to the dense layer (its own indexer), one
    expert layer that shares its picks and one with an indexer of its own."""
    from determined_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=19360, d_model=6144, n_layers=n_layers, n_heads=64, d_ff=12288, max_seq_len=24576, rope_theta=8e6, norm_eps=1e-5,
        q_lora_rank=2048, kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        indexer_types=("full", "shared", "full")[:n_layers], index_n_heads=32, index_head_dim=128, index_topk=2048,
        dense_prefix=1, moe_experts=256, moe_every=1, moe_top_k=8, moe_intermediate_size=2048, moe_experts_held=(0, 16),
        moe_router="sigmoid_grouped", moe_n_group=1, moe_topk_group=1, moe_routed_scaling=2.5, moe_shared_experts=1,
        param_dtype=jnp.bfloat16,
    )


def test_the_index_score_kernel_compiles_at_the_glm_cells_shape(tpu_devices):
    """The indexers' score pass at the cell's lanes, table (1,536 columns:
    24,576 positions), pool and 32 heads of 128: one Mosaic call under its name."""
    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    text = _compile(
        lambda q, w, pool, tables, pos: paged_mod.paged_index_scores(q, w, pool, 1, tables, pos),
        aval((16, 32, 128), jnp.bfloat16), aval((16, 32), jnp.float32), aval((2, 28673, 16, 128), jnp.bfloat16),
        aval((16, 1536)), aval((16,)),
    )
    assert _kernels(text) == 1 and "paged_index_scores" in text


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_glm_cells_programs_compile_over_rows_an_indexer_picks(tpu_devices, which):
    """The GLM-5.2 cell's programs at its widths, lanes (16), pool (28,673
    blocks) and table (1,536 columns), bfloat16 leaves, 3 of 5 layers: both
    arrays of the cache are donated; the decode step scores with the index
    kernel in the two layers that hold an indexer, picks an exact top-2,048
    and attends over gathered rows in all three (no latent kernel walks a
    lane); the walk builds a chunk's
    scores against the table and no [heads, chunk, table] array."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.serving import transformer_decode
    from determined_tpu.utils.compilation_cache import program_scopes

    one = SingleDeviceSharding(tpu_devices[0])
    cfg = _glm_cfg()
    pool_bytes = 28673 * 16 * (3 * 640 + 2 * 128) * 2
    if which == "prefill":
        from determined_tpu.models.serving import transformer_prefill_chunked

        boxed = jax.eval_shape(lambda: TransformerLM_init(cfg))
        params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), flax_meta.unbox(boxed)["params"])
        aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
        cache = {"kv": aval((3, 28673, 16, 640), cfg.dtype), "ik": aval((2, 28673, 16, 128), cfg.dtype)}
        fn = jax.jit(functools.partial(transformer_prefill_chunked, cfg), donate_argnums=(5,))
        compiled = fn.lower(params, aval((1, 24576)), aval((1,)), aval((1,)), aval((1, 1536)), cache).compile()
        text, mem = compiled.as_text(), compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= pool_bytes and mem.temp_size_in_bytes < 1.5 * 1024**3
        assert _arrays_with_dims(text, (64, 256, 24576)) == [] and _arrays_with_dims(text, (32, 256, 24576)) == []
        scopes = program_scopes(text)
        assert {"serve.dsa.project", "serve.dsa.write", "serve.dsa.index", "serve.dsa.topk", "serve.mla.attend"} <= set(scopes)
        return
    boxed = jax.eval_shape(lambda: TransformerLM_init(cfg))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    cache = {"kv": aval((3, 28673, 16, 640), cfg.dtype), "ik": aval((2, 28673, 16, 128), cfg.dtype)}
    fn = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True), donate_argnums=(4,))
    compiled = fn.lower(params, aval((16,)), aval((16,)), aval((16, 1536)), cache).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes and mem.temp_size_in_bytes < 512 * 1024**2
    assert _kernels(text) == 2 + 2 * 6  # the index kernel in two layers; two expert layers' row movements, grouped products and hidden
    scopes = program_scopes(text)
    assert {"serve.dsa", "serve.dsa.project", "serve.dsa.write", "serve.dsa.index", "serve.dsa.topk", "serve.mla.gather",
            "serve.mla.attend", "serve.moe.experts"} <= set(scopes)
    assert sum("paged_index_scores" in n for n in scopes["serve.dsa.index"]) == 2
    assert not any("paged_latent_attention" in n for n in scopes["serve.mla.attend"])


def TransformerLM_init(cfg):
    from determined_tpu.models.transformer import TransformerLM

    return TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


def _hbm_bytes(text: str) -> int:
    """Bytes the program's top-level instructions move to and from HBM, by
    the optimized module's own layouts: an array whose layout names a memory
    space (``S(1)``: the chip's vector memory, where XLA keeps what fits) is
    not in HBM.  Each instruction is charged its HBM operands whole and its
    HBM results; an asynchronous copy or slice, what lands at its end.
    (``cost_analysis()["bytes accessed"]`` charges every pass alike, the ones
    that stay in vector memory too.)"""
    def arrays(part):
        return [(_WIDTH[t] * math.prod(int(d) for d in dims.split(",") if d), "S(" not in layout) for t, dims, layout in _ARRAY.findall(part)]

    made, moved = {}, 0
    for line in text[text.index("ENTRY "):].splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][a-z\-]+)\((.*?)\)", line)
        if not m:
            continue
        name, kind, op, operands = m.groups()
        made[name] = arrays(kind)
        if op in ("parameter", "constant", "tuple", "get-tuple-element", "bitcast", "iota", "copy-done", "slice-done"):
            continue
        if op.endswith("-start"):
            moved += max(size for size, in_hbm in made[name] if not in_hbm)
            continue
        moved += sum(size for size, in_hbm in made[name] if in_hbm)
        moved += sum(size for o in re.findall(r"%([\w.\-]+)", operands) for size, in_hbm in made.get(o, ()) if in_hbm)
    return moved


@pytest.mark.parametrize(
    "lanes, vocab, counters",
    [(32, 92544, 0), (32, 151936, 2), (64, 16160, 2)],
    ids=["internlm2-32x92544", "brumby-32x151936", "dsv3-64x16160"],
)
def test_the_steps_sampler_compiles_at_the_cells_logits_and_sweeps_them_a_few_times(tpu_devices, lanes, vocab, counters):
    """``sample_lanes`` as the engine calls it, at the decode cells' lanes and
    vocabularies (DeepSeek-V3's share is no multiple of 128; the models that
    count their steps hand one more row): no sort, nothing in float64, and
    what it moves to and from HBM is under four times the logits (they are
    read twice to thrice: the maximum, the exponentials, the copy the rest
    works on; nothing of their size is written back).  By XLA's own count,
    which charges the passes over that copy too, under 16 times."""
    from determined_tpu.serve.engine import lane_sampler

    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)  # noqa: E731
    compiled = lane_sampler(counters).lower(aval((lanes + bool(counters), vocab)), aval((2, lanes))).compile()
    text = compiled.as_text()
    assert " sort(" not in text and "f64[" not in text
    logits = 4 * lanes * vocab
    assert logits < _hbm_bytes(text) <= 4 * logits
    assert compiled.cost_analysis()["bytes accessed"] <= 16 * logits
    assert compiled.memory_analysis().temp_size_in_bytes == 0 and _kernels(text) == 0


# -- a training cell's whole step: the loss, its gradient, the clip and fused AdamW ------


def _train_step_compiled(one, cell_name: str, batch=None):
    """A training cell's step as ``Trainer``'s ``train_step`` puts it together
    (``LMTrial.loss`` under the cell's hparams, its gradient, the optimizer's
    ``apply_step``, the state donated), compiled for one described chip at the
    configuration's widths and its ``train_batch`` (or ``batch`` sequences):
    shapes only, nothing is built."""
    from flax.core import meta as flax_meta

    from tests.benchmark import bench_testlib  # noqa: F401  (puts the harness on sys.path)
    from benchlib import model, spec, train_run
    from determined_tpu.models.transformer import LMTrial

    cell = spec.Spec().cell(cell_name)
    arch = model.adapter(cell)
    arch.check_as_run(cell.config)
    hparams = train_run._hparams(cell.config, cell.traffic, arch)

    class Context:
        mesh = exp_config = None
        batch_axis_size = 1

        def get_hparam(self, name, default=None):
            return hparams.get(name, default)

        def get_global_batch_size(self):
            return hparams["global_batch_size"]

    trial = LMTrial.__new__(LMTrial)
    trial.context = Context()
    lm, tx = trial.build_model(), trial.build_optimizer()
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = flax_meta.unbox(jax.eval_shape(lambda: lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))
    opt_state = jax.eval_shape(tx.init, params)

    def step(params, opt_state, tokens):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: trial.loss(lm, p, {"tokens": tokens}, jax.random.key(0)), has_aux=True
        )(params)
        with jax.named_scope("optim.update"):
            params, opt_state = tx.apply_step(grads, opt_state, params)
        return params, opt_state, loss, metrics

    tokens = jax.ShapeDtypeStruct((batch or hparams["global_batch_size"], hparams["seq_len"] + 1), jnp.int32, sharding=one)
    return jax.jit(step, donate_argnums=(0, 1)).lower(jax.tree.map(on_chip, params), jax.tree.map(on_chip, opt_state), tokens).compile()


def _loss_products(text: str) -> list:
    """The products (XLA's ``convolution``) a compiled step holds under the
    scope ``loss.ce``, by their results' shapes."""
    return re.findall(r"= (\w+\[[\d,]+\])\S* convolution\(.*op_name=\"[^\"]*loss\.ce[^\"]*\"", text)


def _calls_a_reader_takes(text: str) -> dict:
    """How many of a compiled step's Mosaic calls each of the benchmark's
    readers that tell calls apart by RESULT SHAPE would take for its own
    (the patterns are the metric files'; an instruction is named as a trace
    names it), and how many none of them takes."""
    results = re.findall(r"= (\(?\w+\[[\d,]*\]\{[^=]*) custom-call\(.*custom_call_target=\"tpu_custom_call\"", text)
    taken = {}
    for metric in ("moe_grouped_matmul_roofline", "adamw_hbm_roofline", "mixed_attn_roofline"):
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "metrics", metric + ".json")) as f:
            pattern = re.compile(json.load(f)["args"]["pattern"])
        taken[metric] = sum(bool(pattern.search(f"%tpu_custom_call.1 = {r}")) for r in results)
    return dict(taken, none=len(results) - sum(taken.values()))


def test_the_mellum_cells_step_compiles_and_each_reader_finds_the_calls_it_found(tpu_devices):
    """Mellum2's cell: four layers, 16 held experts of 2,304 x 896 under a
    worst-case buffer of 69,632 rows, one sequence of 8,192.  A layer's nine
    grouped products are nine calls with the results they had (2-D bf16, 3-D
    float32), the attention kernels' first result is 4-D bf16 and AdamW's a
    tuple led by float32: what PR 33 counted on the chip (36, 22 and 12
    instructions).  The other calls are a layer's five row movements and,
    since PR 63, hidden (forward and backward) and its derivative over live
    tiles: 3-D results in the compute dtype (a tuple led by one), which no
    reader takes."""
    compiled = _train_step_compiled(SingleDeviceSharding(tpu_devices[0]), "train-mellum2-l4-ep4-seq8k")
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes < 15.75 * 2**30
    taken = _calls_a_reader_takes(text)
    assert taken == {"moe_grouped_matmul_roofline": 4 * 9, "adamw_hbm_roofline": 22, "mixed_attn_roofline": 4 * 3, "none": 4 * (5 + 3)}, taken
    assert sum(taken.values()) == _kernels(text)
    for name in ("moe_hidden_rows", "moe_hidden_grads", "moe_rows_of_tokens", "moe_tokens_of_rows"):
        assert name in text, name
    # nothing elementwise sweeps the whole buffer under the experts' scope any more
    swept = re.findall(r"= \w+\[69632,(?:896|2304)\]\S* fusion\(.*op_name=\"[^\"]*moe\.experts", text)
    assert not swept, swept
    print("temp", mem.temp_size_in_bytes, "kernels", _kernels(text), taken)


def test_the_zaya_cells_step_compiles_at_the_published_widths_and_its_batch(tpu_devices):
    """ZAYA1-8B's cell: five CCA layers at 8 over 2 heads of 128 and 8,192
    keys, the MLP router, top-1 into 8 held experts of 2048 x 2048, a tied head
    of 32,784 rows (16 x 2,049: no multiple of 128) through fused CE, fused
    AdamW over 601,744,730 parameters, at the configuration's batch: the
    flash kernels forward and backward a layer, the grouped products, the
    sweeps; state and scratch inside the chip's 15.75 GiB.  Since PR 54 the
    fused CE's scan makes dx and dk beside a chunk's logits and keeps them
    (96 and 256 MiB) where a remat'd scan kept the hidden rows: 6.73 GiB of
    state + 8.57 of scratch = 15.29 GiB at three sequences (15.31 before),
    with three products under ``loss.ce`` where four ran."""
    compiled = _train_step_compiled(SingleDeviceSharding(tpu_devices[0]), "train-zaya1-8b-l5-ep2-seq8k")
    mem, text = compiled.memory_analysis(), compiled.as_text()
    state = mem.argument_size_in_bytes
    assert 12 * 601_744_730 <= state < 12 * 601_744_730 + (1 << 20)         # parameters and both moments (the gradient is scratch)
    assert state + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes < 15.75 * 2**30
    # a layer: flash forward + its two backward kernels, 3 + 6 grouped products and the rows' movements; the sweeps on top
    assert _kernels(text) >= 5 * (3 + 9)
    # by result shape, as the benchmark's readers tell them apart: the products, attention, the sweeps; and the row
    # movements with the passes over live tiles (PR 63), which none of them takes
    taken = _calls_a_reader_takes(text)
    assert (taken["moe_grouped_matmul_roofline"], taken["mixed_attn_roofline"], taken["none"]) == (5 * 9, 5 * 3, 5 * (5 + 3)), taken
    assert len(_loss_products(text)) == 3, _loss_products(text)             # logits, dk, dx (a remat'd scan: the logits twice)
    print("args", state, "out", mem.output_size_in_bytes, "alias", mem.alias_size_in_bytes, "temp", mem.temp_size_in_bytes, "kernels", _kernels(text))


def test_the_mistral_cells_step_compiles_at_the_published_widths_and_its_batch(tpu_devices):
    """Mistral-7B-v0.3's cell: two layers at 32 over 8 heads of 128, an
    untied head of 32,768 rows through the fused CE's scan (a tile of 4 x
    4,096 x 32,768 float32 is 2 GiB a step: over the 1.6 GB at which it takes
    the scan), fused AdamW, at the configuration's four sequences.  The scan
    makes dx and dk beside a chunk's logits and keeps them (128 and 512 MiB)
    from the forward pass's end to the backward pass's start: 7.88 GiB of
    state + 6.21 of scratch = 14.08 GiB of the chip's 15.75 (14.10 with the
    remat'd scan; PR 23's compile read 14.45), and the compiled text holds
    three products under ``loss.ce`` where four ran."""
    compiled = _train_step_compiled(SingleDeviceSharding(tpu_devices[0]), "train-mistral7b-l2-seq4k")
    mem, text = compiled.memory_analysis(), compiled.as_text()
    state = mem.argument_size_in_bytes
    total = state + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    print("args", state, "out", mem.output_size_in_bytes, "alias", mem.alias_size_in_bytes, "temp", mem.temp_size_in_bytes, "total GiB", total / 2**30, "kernels", _kernels(text))
    assert total < 15.75 * 2**30
    assert _kernels(text) >= 2 * 3                                          # a layer: flash forward + its two backward kernels
    assert len(_loss_products(text)) == 3, _loss_products(text)             # logits, dk, dx
