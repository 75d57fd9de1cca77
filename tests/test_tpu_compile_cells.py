"""What the TPU's own compiler says of the serving cells' programs — no chip
(the why and the how: tests/test_tpu_compile.py, which keeps the training
kernels, ``dtpu serve``'s default programs and the decode step's pool).  Each
case is one compile at a cell's published widths, its lanes, pool and table,
depth alone cut: DeepSeek-V3's walk, decode program and latent kernel,
InternLM2's walk and decode kernel, LongCat's and Command A+'s programs and
window kernel, GLM-5.2's programs and score kernel, the step's sampler.  Cut
from that file because the
heaviest compiles of the suite are here, and ``--dist loadfile`` balances by
the file (until PR 65 the Brumby walk's chunk kernel was the heaviest, 65
unrolled feature rows a copy: it is a loop now, and the walk holds it twice).
Cut again at PR 67, each test under its name: the cells whose layers keep a
state a decode lane (Brumby, Falcon-H1, Nemotron-3-Super) are
tests/test_tpu_compile_lane_cells.py, the training cells' whole steps
tests/test_tpu_compile_train_cells.py: this file alone was the run's last
400 s with one worker at work."""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.model_cases import (  # noqa: F401  (fixture reuse)
    arrays_with_dims as _arrays_with_dims,
    compile_text as _compile,
    mosaic_calls as _kernels,
    paged_mod,
    real_kernels_no_cache,
    tpu_devices,
)


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
    # the router sorts once an expert layer, for its picks: a group's score and the kept groups come without (PR 67:
    # the chip's compiler makes a whole sort of a ``top_k``, and of one over a third axis a slow one)
    assert len({n for n in scopes["serve.moe.route"] if n.startswith("sort")}) == 1, scopes["serve.moe.route"]


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
