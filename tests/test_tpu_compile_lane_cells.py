"""What the TPU's own compiler says of the serving cells whose layers keep a
state a decode lane — no chip (the why and the how: tests/test_tpu_compile.py):
Brumby's retention kernels and programs, Falcon-H1's and Nemotron-3-Super's
state kernel and programs, each one compile at the cell's published widths.
Cut from tests/test_tpu_compile_cells.py at PR 67, every test under its name:
``--dist loadfile`` balances by the file, and that one was the run's tail."""

import functools
import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.model_cases import (  # noqa: F401  (fixture reuse)
    arrays_with_dims as _arrays_with_dims,
    mosaic_calls as _kernels,
    paged_mod,
    real_kernels_no_cache,
    tpu_devices,
)


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
