"""What the TPU's own compiler says of the Qwen3-Next cell's kernel and programs
— no chip (the why and the how: tests/test_tpu_compile.py).  A file of its own
because ``--dist loadfile`` balances by the file."""

import functools
import importlib
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.model_cases import (  # noqa: F401  (fixture reuse)
    mosaic_calls as _kernels,
    paged_mod,
    real_kernels_no_cache,
    tpu_devices,
)


def test_the_gdn_decode_kernel_compiles_at_the_qwen3_next_cells_shape(tpu_devices):
    """64 lanes x 32 value heads of 128 x 128 against a float32 state pool of
    nine layers and 65 slots: one kernel, a lane's 32 heads a program (2 MB of
    state each way), the pool updated where it lies (aliased, no scratch the
    size of a layer's state)."""
    gd = importlib.import_module("determined_tpu.ops.gated_delta")
    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    state = gd.state_shape(9, 64, 32, 128, 128)
    assert state == (9, 65, 32, 128, 128) and gd.kernel_takes(32, 128, 128, jnp.float32) and gd.heads_a_program(32, 128, 128, jnp.float32) == 32

    def fn(q, k, v, g, beta, pool, live):
        return gd.gdn_decode(q, k, v, g, beta, pool, 3, live)

    f32 = jnp.float32
    compiled = jax.jit(fn, donate_argnums=(5,)).lower(
        aval((64, 32, 128), f32), aval((64, 32, 128), f32), aval((64, 32, 128), jnp.bfloat16), aval((64, 32), f32), aval((64, 32), f32),
        aval(state, f32), aval((64,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == 1 and "gdn_decode" in text
    assert mem.alias_size_in_bytes >= 4 * math.prod(state) and mem.temp_size_in_bytes < 16 * 1024**2


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_qwen3_next_cells_programs_compile_over_linear_and_full_layers(tpu_devices, which):
    """The cell's decode step and prefill walk (two widths: 12,288 tokens hold
    eight wide chunks) at its widths, lanes, pool and state pool, bfloat16
    leaves, all twelve layers (nine Gated-DeltaNet, three gated attention,
    experts in each): the weights, both pools and the program's scratch fit the
    chip's 15.75 GiB; the cache is donated and no second copy of a pool is held;
    each mixer keeps its scopes, the state kernel its name under its own (nine
    of them), the paged kernel multiplies 8 queries a KV head of 256."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.cache_kinds import DELTA_SLOT, PAGED_KV, cache_kinds
    from determined_tpu.models.serving import prefill_wide_chunks, transformer_decode, transformer_prefill_chunked
    from determined_tpu.models.transformer import TransformerConfig, TransformerLM, gdn_pool_shapes, kv_cache_shape
    from determined_tpu.utils.compilation_cache import program_scopes

    one = SingleDeviceSharding(tpu_devices[0])
    cfg = TransformerConfig(
        vocab_size=18992, d_model=2048, n_layers=12, n_heads=16, n_kv_heads=2, head_dim=256, max_seq_len=16384, norm_eps=1e-6,
        layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 3, rope_theta=1e7, partial_rotary_factor=0.25, qk_norm=True,
        attn_output_gate=True, linear_key_heads=16, linear_value_heads=32, linear_key_head_dim=128, linear_value_head_dim=128,
        param_dtype=jnp.bfloat16, moe_experts=512, moe_every=1, moe_top_k=10, moe_intermediate_size=512, moe_experts_held=(0, 64),
        moe_shared_experts=1, moe_shared_intermediate_size=512, moe_shared_gate=True,
    )
    assert cache_kinds(cfg) == (PAGED_KV, DELTA_SLOT) and prefill_wide_chunks(256, 12288) == 4
    boxed = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    pool, (state, tail) = kv_cache_shape(cfg, 49153, 16), gdn_pool_shapes(cfg, 64)
    assert pool == (3, 49153, 16, 512) and state == (9, 65, 32, 128, 128) and tail == (9, 64, 3, 8192)
    cache = {"k": aval(pool, jnp.bfloat16), "v": aval(pool, jnp.bfloat16), "gdn": aval(state, jnp.float32), "gconv": aval(tail, jnp.bfloat16)}
    if which == "decode":
        fn = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True), donate_argnums=(4,))
        args = (params, aval((64,)), aval((64,)), aval((64, 1024)), cache)
    else:
        fn = jax.jit(functools.partial(transformer_prefill_chunked, cfg, chunk_tokens=256), donate_argnums=(5,))
        args = (params, aval((1, 12288)), aval((1,)), aval((1,)), aval((1, 1024)), cache, aval((1,)))
    compiled = fn.lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    cache_bytes = 2 * 2 * math.prod(pool) + 4 * math.prod(state) + 2 * math.prod(tail)
    assert mem.alias_size_in_bytes >= cache_bytes                                    # the cache is donated: no second copy of a pool
    assert mem.argument_size_in_bytes >= 2 * 2_929_374_400 + cache_bytes
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.75 * 1024**3
    assert mem.temp_size_in_bytes < (256 if which == "decode" else 1536) * 1024**2
    scopes = program_scopes(text)
    assert {"serve.attn.qkv", "serve.kv.write", "serve.attn.attend", "serve.attn.out", "serve.gdn.proj", "serve.gdn.conv",
            "serve.gdn.state", "serve.gdn.out", "serve.moe.route", "serve.moe.experts", "serve.moe.shared", "serve.embed",
            "serve.head"} <= set(scopes) and not {"serve.mlp", "serve.ssm.state", "serve.mamba2.state"} & set(scopes)
    # no pool is laid out anew round a loop: a copy of a whole pool would be 1.2 GB (the state's) or 2.4 GB (K's, V's)
    copies = [line for line in text.splitlines() if " copy(" in line and ("[9,65,32,128,128]" in line or "[3,49153,16,512]" in line)]
    assert not copies, copies[:2]
    if which == "decode":   # a linear layer: the state kernel; a full layer: the paged kernel; every layer's experts
        assert "serve.attn.gate" in scopes
        assert len({n for n in scopes["serve.gdn.state"] if n.startswith("gdn_decode")}) == 9
        assert len({n for n in scopes["serve.moe.experts"] if n.startswith("moe_gmm")}) == 3 * 12
        assert paged_mod.attn_products(8) is not None
    else:
        assert "serve.gdn.chunk" in scopes
    print(which, "args", mem.argument_size_in_bytes, "out", mem.output_size_in_bytes, "alias", mem.alias_size_in_bytes, "temp", mem.temp_size_in_bytes, "kernels", _kernels(text))
