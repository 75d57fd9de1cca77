"""What the TPU's own compiler says of the Ling-3.0 cell's kernel, chunk form and
programs — no chip (the why and the how: tests/test_tpu_compile.py).  A file of
its own because ``--dist loadfile`` balances by the file."""

import functools
import importlib
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.model_cases import (  # noqa: F401  (fixture reuse)
    mosaic_calls as _kernels,
    real_kernels_no_cache,
    tpu_devices,
)

LANES, BLOCKS, PARAMS = 128, 131_073, 2_866_268_096


def test_the_decode_kernel_compiles_under_a_decay_a_channel_at_the_ling_cells_shape(tpu_devices):
    """128 lanes x 32 heads of 128 x 128 against a float32 state pool of six layers
    and 129 slots, the decay [lanes, heads, K] down the kernel's columns: one
    kernel, a lane's 32 heads a program, the pool updated where it lies."""
    gd = importlib.import_module("determined_tpu.ops.gated_delta")
    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    state = gd.state_shape(6, LANES, 32, 128, 128)
    assert state == (6, 129, 32, 128, 128) and gd.kernel_takes(32, 128, 128, jnp.float32)

    def fn(q, k, v, g, beta, pool, live):
        return gd.gdn_decode(q, k, v, g, beta, pool, 4, live)

    f32 = jnp.float32
    compiled = jax.jit(fn, donate_argnums=(5,)).lower(
        aval((LANES, 32, 128), f32), aval((LANES, 32, 128), f32), aval((LANES, 32, 128), f32), aval((LANES, 32, 128), f32), aval((LANES, 32), f32),
        aval(state, f32), aval((LANES,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == 1 and "gdn_decode" in text
    assert mem.alias_size_in_bytes >= 4 * math.prod(state) and mem.temp_size_in_bytes < 32 * 1024**2


@pytest.mark.parametrize("tokens", [256, 1024])
def test_the_chunk_form_compiles_under_a_decay_a_channel_at_both_widths_of_the_walk(tpu_devices, tokens):
    """A narrow and a wide chunk of the walk's tokens, 32 heads of 128 x 128,
    sub-chunks of 64 in blocks of 16: plain XLA (no kernel), its scratch a small
    part of what the cell leaves free beside 10.1 GB of weights and caches."""
    gd = importlib.import_module("determined_tpu.ops.gated_delta")
    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    rows = (1, tokens, 32, 128)
    compiled = jax.jit(functools.partial(gd.gdn_chunk, chunk=64)).lower(
        aval(rows), aval(rows), aval(rows), aval(rows), aval(rows[:3]), aval((1, 32, 128, 128)), aval(rows[:2], jnp.bool_)
    ).compile()
    mem = compiled.memory_analysis()
    assert _kernels(compiled.as_text()) == 0 and mem.temp_size_in_bytes < 2 * 1024**3
    print("chunk", tokens, "temp", mem.temp_size_in_bytes)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_ling_cells_programs_compile_over_kda_and_latent_layers(tpu_devices, which):
    """The cell's decode step and prefill walk (two widths: 16,384 tokens hold
    sixteen wide chunks) at its widths, 128 lanes, the latent pool and the state
    pool, bfloat16 leaves, all seven layers: the weights, both pools and the
    program's scratch fit the chip's 15.75 GiB; the cache is donated and no
    second copy of a pool is held; the KDA mixer keeps its own scopes, the state
    kernel its name under them (six of them), the latent layer its gate."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.cache_kinds import DELTA_SLOT, PAGED_LATENT, cache_kinds
    from determined_tpu.models.serving import prefill_wide_chunks, transformer_decode, transformer_prefill_chunked
    from determined_tpu.models.transformer import TransformerConfig, TransformerLM, gdn_pool_shapes, kv_cache_shape
    from determined_tpu.utils.compilation_cache import program_scopes

    one = SingleDeviceSharding(tpu_devices[0])
    cfg = TransformerConfig(
        vocab_size=19_648, d_model=2560, n_layers=7, n_heads=32, head_dim=128, d_ff=6144, max_seq_len=28_672, norm_eps=1e-6,
        layer_types=("linear_attention",) * 5 + ("full_attention", "linear_attention"), rope_theta=6e6, attn_output_gate=True,
        linear_key_heads=32, linear_value_heads=32, linear_key_head_dim=128, linear_value_head_dim=128, linear_decay_floor=-5.0, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, dense_prefix=1,
        param_dtype=jnp.bfloat16, moe_experts=512, moe_every=1, moe_top_k=8, moe_intermediate_size=768, moe_experts_held=(0, 64),
        moe_router="sigmoid_grouped", moe_n_group=8, moe_topk_group=4, moe_routed_scaling=2.5, moe_shared_experts=1,
    )
    assert cache_kinds(cfg) == (PAGED_LATENT, DELTA_SLOT) and prefill_wide_chunks(256, 16_384) == 4
    boxed = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    pool, (state, tail) = kv_cache_shape(cfg, BLOCKS, 16), gdn_pool_shapes(cfg, LANES)
    assert pool == (1, BLOCKS, 16, 640) and state == (6, 129, 32, 128, 128) and tail == (6, LANES, 3, 12_288)
    cache = {"kv": aval(pool, jnp.bfloat16), "gdn": aval(state, jnp.float32), "gconv": aval(tail, jnp.bfloat16)}
    if which == "decode":
        fn = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True), donate_argnums=(4,))
        args = (params, aval((LANES,)), aval((LANES,)), aval((LANES, 1792)), cache)
    else:
        fn = jax.jit(functools.partial(transformer_prefill_chunked, cfg, chunk_tokens=256), donate_argnums=(5,))
        args = (params, aval((1, 16_384)), aval((1,)), aval((1,)), aval((1, 1792)), cache, aval((1,)))
    compiled = fn.lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    cache_bytes = 2 * math.prod(pool) + 4 * math.prod(state) + 2 * math.prod(tail)
    assert mem.alias_size_in_bytes >= cache_bytes                                    # the cache is donated: no second copy of a pool
    assert mem.argument_size_in_bytes >= 2 * PARAMS + cache_bytes
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.75 * 1024**3
    assert mem.temp_size_in_bytes < (512 if which == "decode" else 3072) * 1024**2
    scopes = program_scopes(text)
    assert {"serve.mla", "serve.mla.attend", "serve.mla.gate", "serve.kv.write", "serve.kda.proj", "serve.kda.conv", "serve.kda.state",
            "serve.kda.out", "serve.mlp", "serve.moe.route", "serve.moe.experts", "serve.moe.shared", "serve.embed", "serve.head"} <= set(scopes)
    assert not {"serve.gdn.state", "serve.attn.qkv", "serve.ssm.state"} & set(scopes)
    # no pool is laid out anew round a loop: a copy of a whole pool would be 1.6 GB (the state's) or 2.7 GB (the rows')
    copies = [line for line in text.splitlines() if " copy(" in line and ("[6,129,32,128,128]" in line or f"[1,{BLOCKS},16,640]" in line)]
    assert not copies, copies[:2]
    # the router sorts once an expert layer (and a loop of the walk), for its picks: a group's score and the kept groups
    # come without (PR 67: ``sort f32[128,8,64]`` was 3.4 % of this cell's decode step)
    sorts = {n for n in scopes["serve.moe.route"] if n.startswith("sort")}
    assert len(sorts) == (6 if which == "decode" else 12), sorted(sorts)
    if which == "decode":   # a KDA layer: the state kernel; the latent layer: the paged latent kernel; six layers' experts
        assert len({n for n in scopes["serve.kda.state"] if n.startswith("gdn_decode")}) == 6
        assert len({n for n in scopes["serve.moe.experts"] if n.startswith("moe_gmm")}) == 3 * 6
    else:
        assert "serve.gdn.chunk" in scopes
    print(which, "args", mem.argument_size_in_bytes, "out", mem.output_size_in_bytes, "alias", mem.alias_size_in_bytes, "temp", mem.temp_size_in_bytes, "kernels", _kernels(text))
