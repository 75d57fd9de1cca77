"""What the TPU's own compiler says of the main path's kernels — no chip.

The sandbox has no accelerator, but libtpu compiles for a chip that is
described and not attached (``jax.experimental.topologies``; the
``on-chip-measurement`` guide, section 2).  Interpret mode cannot show what
this does: a Mosaic kernel the compiler refuses (tiling, VMEM), a program
XLA cannot partition, a step that does not fit the device.  Every case is
a compile at the flagship's real widths (``examples/transformer_lm/
const.yaml``: d2048, 16 heads x 128, vocab 32768, bf16); nothing runs, so
nothing here is a result or a time.

The two 4-device-mesh cases are the ones that would have caught the fault
this file was added with: both Pallas kernels were called bare under GSPMD
and every multi-chip LM trial failed to compile with "Mosaic kernels cannot
be automatically partitioned", while every virtual-CPU-mesh test passed
(the interpreter lowers the kernels to plain ops, which partition).

Code that asks ``jax.default_backend()`` still sees the CPU here, so the
kernels' ``_interpret`` is steered from the test.
"""

import functools
import importlib
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from determined_tpu.ops.attention import dot_product_attention
from determined_tpu.parallel.mesh import MeshConfig, make_mesh

# the package re-exports functions under the modules' names
flash_mod = importlib.import_module("determined_tpu.ops.flash_attention")
adamw_mod = importlib.import_module("determined_tpu.ops.fused_adamw")
paged_mod = importlib.import_module("determined_tpu.ops.paged_attention")
grouped_mod = importlib.import_module("determined_tpu.ops.grouped_matmul")
rows_mod = importlib.import_module("determined_tpu.ops.expert_rows")

TOPOLOGY = "v5e:2x2"


@pytest.fixture(scope="module")
def tpu_devices():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)
    except Exception as e:  # noqa: BLE001 - no libtpu, or it cannot describe the chip
        pytest.skip(f"cannot describe a {TOPOLOGY} topology here: {e}")
    return list(topo.devices)


@pytest.fixture(autouse=True)
def _real_kernels_no_cache(monkeypatch):
    """Mosaic, not the interpreter; and no persistent cache around the
    compiles (an entry compiled for a described chip cannot be read back
    without one, and the next run would warn)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(flash_mod, "_interpret", lambda: False)
    monkeypatch.setattr(adamw_mod, "_interpret", lambda: False)
    monkeypatch.setattr(grouped_mod, "_interpret", lambda: False)
    monkeypatch.setattr(paged_mod, "_on_tpu", lambda: True)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # as a fresh process has it: ``setup_compilation_cache`` turns it off for
    # the process (any Trainer or DecodeKernels an earlier test file built),
    # and a compile made HERE for a described chip then names a Mosaic call
    # ``custom-call.N`` whatever its ``name=``
    tracebacks = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    yield
    jax.config.update("jax_include_full_tracebacks_in_locations", tracebacks)
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *avals) -> str:
    """The optimized program's text; raises what the chip's compiler would."""
    return jax.jit(fn).lower(*avals).compile().as_text()


def _kernels(text: str) -> int:
    return text.count('custom_call_target="tpu_custom_call"')


def _qkv(shape, sharding, kv_heads=None):
    b, h, s, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, kv_heads or h, s, d), jnp.bfloat16, sharding=sharding)
    return q, kv, kv


def _attn_loss(mesh, q, k, v):
    out = dot_product_attention(q, k, v, causal=True, impl="flash", mesh=mesh)
    return jnp.sum(out.astype(jnp.float32) ** 2)


# -- flash attention ---------------------------------------------------------


@pytest.mark.parametrize(
    "shape",
    [(8, 16, 1024, 128), (1, 16, 4096, 128)],
    ids=["const_yaml_b8_s1024", "long_b1_s4096"],
)
def test_flash_fwd_bwd_compiles_on_one_chip(tpu_devices, shape):
    one = SingleDeviceSharding(tpu_devices[0])
    grad = jax.grad(functools.partial(_attn_loss, None), argnums=(0, 1, 2))
    text = _compile(grad, *_qkv(shape, one))
    assert _kernels(text) == 3  # fwd, dq, dkv


def test_flash_with_a_window_compiles_at_the_mellum_cells_shape(tpu_devices):
    """One 8,192-token sequence, 32 heads of 128 over 4 KV heads, window
    1,024 (benchmark/configs/mellum2-12b-a2.5b-l4-ep4.json): three kernels
    with names of their own, and the kernels without a window keep theirs."""
    one = SingleDeviceSharding(tpu_devices[0])

    def loss(window, q, k, v):
        out = dot_product_attention(q, k, v, causal=True, impl="flash", window=window)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    avals = _qkv((1, 32, 8192, 128), one, kv_heads=4)
    text = _compile(jax.grad(functools.partial(loss, 1024), argnums=(0, 1, 2)), *avals)
    assert _kernels(text) == 3 and all(f"flash_window_{k}" in text for k in ("fwd", "dq", "dkv"))
    plain = _compile(jax.grad(functools.partial(loss, None), argnums=(0, 1, 2)), *avals)
    assert _kernels(plain) == 3 and "flash_window" not in plain


@pytest.mark.parametrize("tile", [256, 512], ids=["tile256", "tile512"])
def test_grouped_expert_products_compile_at_the_mellum_cells_shape(tpu_devices, tile):
    """16 held experts of 2304 x 896, the worst-case buffer of one 8,192-token
    sequence's picks (8 a token): the product, its transpose and the gradient
    to the matrices (a [2304, 896] float32 block resident in VMEM)."""
    one = SingleDeviceSharding(tpu_devices[0])
    rows = grouped_mod.buffer_rows(8192 * 8, 16, tile)

    def fn(x, g, w, sizes):
        layout = grouped_mod.tile_layout(sizes, 8192 * 8, tile)
        wb = w.astype(x.dtype)
        return (grouped_mod.gmm(x, wb, layout), grouped_mod.gmm(g, wb, layout, transpose_rhs=True),
                grouped_mod.tgmm(x, g, layout, 16))

    text = _compile(
        fn, jax.ShapeDtypeStruct((rows, 2304), jnp.bfloat16, sharding=one),
        jax.ShapeDtypeStruct((rows, 896), jnp.bfloat16, sharding=one),
        jax.ShapeDtypeStruct((16, 2304, 896), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one),
    )
    assert _kernels(text) == 3 and "moe_gmm" in text and "moe_tgmm" in text


def test_the_expert_layers_row_movements_compile_at_the_mellum_cells_shape(tpu_devices):
    """8,192 tokens of 2,304 into and out of the worst-case buffer of 69,632
    rows: two kernels whose resident float32 block is a third of the columns,
    and whose results no reader that tells Mosaic calls apart by result shape
    takes for a grouped product's (2-D bf16, 3-D float32), AdamW's (a tuple)
    or attention's (4-D bf16)."""
    one = SingleDeviceSharding(tpu_devices[0])
    rows = grouped_mod.buffer_rows(8192 * 8, 16, 256)
    assert rows_mod._columns(8192, 2304) == 768 and rows_mod._columns(48, 16) == 16

    def fn(x, out, row_token, sizes):
        layout = grouped_mod.tile_layout(sizes, 8192 * 8, 256)
        tile_rows = jnp.minimum(jnp.take(sizes, layout.tile_group), 256)   # any counts: shapes alone matter here
        return (rows_mod.rows_of_tokens(x, row_token, tile_rows, layout),
                rows_mod.tokens_of_rows(out, row_token, tile_rows, layout, 8192))

    text = _compile(
        fn, jax.ShapeDtypeStruct((8192, 2304), jnp.bfloat16, sharding=one),
        jax.ShapeDtypeStruct((rows, 2304), jnp.bfloat16, sharding=one),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one),
    )
    assert _kernels(text) == 2 and "moe_rows_of_tokens" in text and "moe_tokens_of_rows" in text
    results = sorted(re.findall(r"= (\S+?)\{[^}]*\} custom-call\(.*custom_call_target=\"tpu_custom_call\"", text))
    assert results == ["bf16[272,256,2304]", "f32[8192,2304]"], results


@pytest.mark.parametrize(
    "mesh_cfg,kv_heads",
    [(MeshConfig(fsdp=4), 16), (MeshConfig(fsdp=2, tensor=2), 16),
     (MeshConfig(fsdp=2, tensor=2), 1)],
    ids=["fsdp4", "fsdp2_tensor2", "fsdp2_tensor2_mqa"],
)
def test_flash_fwd_bwd_compiles_over_a_four_chip_mesh(tpu_devices, mesh_cfg, kv_heads):
    """Global batch 8 sharded over fsdp, heads over tensor: each device
    compiles the kernel on its own block inside shard_map."""
    mesh = make_mesh(mesh_cfg, tpu_devices)
    sh = NamedSharding(mesh, P("fsdp", None, None, None))
    grad = jax.grad(functools.partial(_attn_loss, mesh), argnums=(0, 1, 2))
    text = _compile(grad, *_qkv((8, 16, 1024, 128), sh, kv_heads))
    assert _kernels(text) == 3


def test_bare_flash_under_gspmd_is_what_the_compiler_refuses(tpu_devices):
    """The fault itself, pinned: without the mesh (so without shard_map)
    sharded operands reach a bare pallas_call and XLA cannot partition it."""
    mesh = make_mesh(MeshConfig(fsdp=4), tpu_devices)
    sh = NamedSharding(mesh, P("fsdp", None, None, None))
    grad = jax.grad(functools.partial(_attn_loss, None), argnums=(0, 1, 2))
    with pytest.raises(Exception, match="cannot be automatically partitioned"):
        _compile(grad, *_qkv((8, 16, 1024, 128), sh))


def test_a_kernel_compiles_to_the_same_program_whoever_calls_it(tpu_devices):
    """Mosaic serializes a kernel with its debug locations, and XLA's
    compile-cache key hashes that payload.  With jax's default ten frames
    of Python call stack in every location, the same train step had one key
    under ``dtpu experiment run``, another under ``run_trial``, and a new
    one at every restart of a cluster trial (code unpacked to a fresh temp
    directory) — on the chip: 28 s compiled again each time.
    ``setup_compilation_cache`` turns the call stack off; then the lowered
    program is the same text from any caller."""
    one = SingleDeviceSharding(tpu_devices[0])

    def lowered_from(filename: str) -> str:
        ns: dict = {}
        exec(compile("def call(fn, *a):\n    return fn(*a)\n", filename, "exec"), ns)
        loss = functools.partial(_attn_loss, None)
        return jax.jit(lambda q, k, v: ns["call"](loss, q, k, v)).lower(
            *_qkv((1, 16, 1024, 128), one)
        ).as_text()

    prev = jax.config.jax_include_full_tracebacks_in_locations
    try:
        jax.config.update("jax_include_full_tracebacks_in_locations", True)
        assert lowered_from("/tmp/ctx-aaaa/model_def.py") != lowered_from(
            "/tmp/ctx-bbbb/model_def.py"
        )
        jax.config.update("jax_include_full_tracebacks_in_locations", False)
        assert lowered_from("/tmp/ctx-aaaa/model_def.py") == lowered_from(
            "/tmp/ctx-bbbb/model_def.py"
        )
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", prev)


# -- fused AdamW -------------------------------------------------------------

# every >=2-d leaf shape of the const.yaml model (embed, lm_head, swiglu
# up/gate + down, q/k/v and out projections)
LEAF_SHAPES = [
    (32768, 2048), (2048, 32768), (2048, 8192), (8192, 2048),
    (2048, 16, 128), (16, 128, 2048),
    # stacks of expert matrices: one matrix is past a block's budget, and 896 halves to no multiple of 128
    (16, 2304, 896), (16, 896, 2304),
]


@pytest.mark.parametrize("shape", LEAF_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_fused_adamw_leaf_compiles_on_one_chip(tpu_devices, shape):
    one = SingleDeviceSharding(tpu_devices[0])
    assert adamw_mod._plan_blocks(shape) is not None
    kw = dict(b1=0.9, b2=0.999, eps=1e-8, wd=0.01)
    fn = lambda p, m, v, g, s: adamw_mod._leaf_pallas(p, m, v, g, s, **kw)  # noqa: E731
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
    scalars = jax.ShapeDtypeStruct((1, 4), jnp.float32, sharding=one)
    text = _compile(fn, leaf, leaf, leaf, leaf, scalars)
    assert _kernels(text) == 1


def test_fused_adamw_step_compiles_over_a_four_chip_mesh(tpu_devices, monkeypatch):
    """The whole optimizer step on a tensor-sharded param tree (what
    ``fsdp: 2, tensor: 2`` gives the const.yaml model's leaves): every big
    leaf is planned on its LOCAL shard and swept per device."""
    monkeypatch.setenv("DTPU_FUSED_MIN_SIZE", str(1024 * 1024))
    mesh = make_mesh(MeshConfig(fsdp=2, tensor=2), tpu_devices)
    specs = {
        "embed": ((32768, 2048), P("tensor", None)),
        "w_up": ((2048, 8192), P(None, "tensor")),
        "w_down": ((8192, 2048), P("tensor", None)),
        "wq": ((2048, 16, 128), P(None, "tensor", None)),
        "wk": ((2048, 16, 128), P()),            # replicated over the mesh
        "ln": ((2048,), P()),                    # small: the jnp path
    }
    shardings = {k: NamedSharding(mesh, s) for k, (_, s) in specs.items()}
    params = {
        k: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=shardings[k])
        for k, (shape, _) in specs.items()
    }
    opt = adamw_mod.fused_adamw(3e-4, clip_norm=1.0)
    state = jax.eval_shape(opt.init, params)
    state = adamw_mod.FusedAdamWState(
        jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
        *(
            {k: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=shardings[k])
             for k, a in tree.items()}
            for tree in (state.mu, state.nu)
        ),
    )
    step = lambda g, s, p: opt.apply_step(g, s, p, shardings=shardings)  # noqa: E731
    text = _compile(step, params, state, params)
    assert _kernels(text) == 5  # all but the norm scale

    # the same step with no shardings named is the bare call GSPMD refuses
    with pytest.raises(Exception, match="cannot be automatically partitioned"):
        _compile(lambda g, s, p: opt.apply_step(g, s, p), params, state, params)


# -- the serving programs ----------------------------------------------------


@pytest.mark.parametrize("which", ["prefill", "prefill_wide", "decode"])
def test_serve_programs_compile_on_one_chip(tpu_devices, which):
    """``dtpu serve``'s two jitted programs (the chunked prefill walk, the
    decode step) and the wide prefill the tests keep as their oracle, with
    the default ServeConfig at d2048 / 16 heads / vocab 32768 — paged
    scatters and gathers, the donated cache — depth cut to 2 layers (depth
    repeats, it does not change what the compiler must accept)."""
    from determined_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
        kv_cache_shape,
        transformer_decode,
        transformer_prefill,
        transformer_prefill_chunked,
    )
    from determined_tpu.serve.config import ServeConfig

    one = SingleDeviceSharding(tpu_devices[0])
    cfg = TransformerConfig(
        vocab_size=32768, d_model=2048, n_layers=2, n_heads=16, max_seq_len=1024,
        attention_impl="flash",
    )
    sc = ServeConfig()
    boxed = jax.eval_shape(
        lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )
    from flax.core import meta as flax_meta

    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    cshape = kv_cache_shape(cfg, sc.num_blocks, sc.block_size)
    cache = {"k": aval(cshape, cfg.dtype), "v": aval(cshape, cfg.dtype)}
    table = aval((1, sc.blocks_per_seq))
    if which == "prefill_wide":
        fn = jax.jit(functools.partial(transformer_prefill, cfg), donate_argnums=(4,))
        args = (params, aval((1, sc.max_prompt_len)), aval((1,)), table, cache)
    elif which == "prefill":
        fn = jax.jit(
            functools.partial(transformer_prefill_chunked, cfg), donate_argnums=(5,)
        )
        pad = sc.prefill_chunks(sc.max_prompt_len) * sc.prefill_chunk
        args = (params, aval((1, pad)), aval((1,)), aval((1,)), table, cache)
    else:
        fn = jax.jit(
            functools.partial(
                transformer_decode, cfg, chunk_blocks=sc.decode_chunk_blocks
            ),
            donate_argnums=(4,),
        )
        b = sc.max_batch
        args = (params, aval((b,)), aval((b,)), aval((b, sc.blocks_per_seq)), cache)
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 1024**3
    if which == "decode":  # the paged-attention kernel, once a layer
        assert _kernels(compiled.as_text()) == cfg.n_layers


def _arrays_with_dims(text: str, dims) -> list:
    """Array shapes of an optimized HLO module (results and operands alike,
    inside fusions too) that have every one of ``dims`` among their dimensions."""
    found = set()
    for m in re.finditer(r"\b\w+\[([\d,]+)\]", text):
        shape = [int(d) for d in m.group(1).split(",")]
        if all(shape.count(d) >= list(dims).count(d) for d in dims):
            found.add(m.group(0))
    return sorted(found)


def _walk_compiled(one, cfg, *, num_blocks, max_prompt_len, table_width):
    from flax.core import meta as flax_meta

    from determined_tpu.models.transformer import TransformerLM, kv_cache_shape, prefill_chunk_tokens, transformer_prefill_chunked

    boxed = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    shape = kv_cache_shape(cfg, num_blocks, 16)
    cache = {"kv": aval(shape, cfg.dtype)} if cfg.latent else {"k": aval(shape, cfg.dtype), "v": aval(shape, cfg.dtype)}
    assert prefill_chunk_tokens(16, max_prompt_len) == 256 and max_prompt_len % 256 == 0
    fn = jax.jit(functools.partial(transformer_prefill_chunked, cfg), donate_argnums=(5,))
    return fn.lower(params, aval((1, max_prompt_len)), aval((1,)), aval((1,)), aval((1, table_width)), cache).compile()


def test_the_prefill_walk_at_the_dsv3_cells_widths_holds_a_chunk_not_the_prompt(tpu_devices, monkeypatch):
    """The walk at DeepSeek-V3's published widths, the cell's pool, table and
    ``max_prompt_len`` 4,096, depth cut to the dense layer and one expert
    layer: its scratch is a fraction of the wide pass's (1.32 GiB at this
    depth, 1.35 at the cell's five layers: PERF.md section 4), and no array
    anywhere in it has heads x chunk x ``max_seq_len`` (a chunk's scores
    against the whole table: 0.94 GB a layer); a tile's [128, 256, 256] is
    the largest the attention builds."""
    from determined_tpu.models.transformer import TransformerConfig

    monkeypatch.setattr(rows_mod.gm, "_interpret", lambda: False)
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
    assert _kernels(text) == 5  # the expert layer's two row movements and three grouped products


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


def _pool_sized_results(text: str, pool_shape) -> list:
    """Instructions of an optimized HLO module whose result is at least one
    layer's K pool in size and is not the pool itself on its way through the
    program: a parameter, the donated pool's scatter (bare or as the root of
    a fusion), or plumbing that copies nothing (tuples and their elements,
    bitcasts)."""
    layer_elems = math.prod(pool_shape[1:])
    roots = {}  # computation -> opcode of its ROOT
    name = None
    for line in text.splitlines():
        head = re.match(r"\s*(?:ENTRY\s+)?%(\S+)\s+\(.*\{\s*$", line)
        if head:
            name = head.group(1)
        root = re.match(r"\s*ROOT\s+%\S+ = \S+ ([\w-]+)\(", line)
        if root and name:
            roots[name] = root.group(1)
    passes = {"parameter", "scatter", "tuple", "get-tuple-element", "bitcast"}
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%\S+ = \w+\[([\d,]+)\]\S* ([\w-]+)\(", line)
        if not m or math.prod(int(d) for d in m.group(1).split(",")) < layer_elems:
            continue
        op = m.group(2)
        if op == "fusion":
            called = re.search(r"calls=%(\S+?)[,\s]", line)
            op = roots.get(called.group(1), op) if called else op
        if op not in passes:
            found.append(line.strip()[:200])
    return found


@pytest.mark.parametrize("form", ["kernel", "jnp"])
def test_decode_step_holds_no_copy_of_a_layers_pool(tpu_devices, monkeypatch, form):
    """The copy that took a third of a decode step cannot come back
    unseen: ``jit_serve_decode`` for a small ServeConfig, paged path, holds
    no instruction as large as one layer's K pool
    (``[num_blocks, block_size, kv_heads * head_dim]``) except the pools
    themselves: parameters, their in-place scatters, the result.  (The
    parent read ``k_cache[i]`` into a ``while`` loop, and XLA materialized
    48 such slices a step.)  Both forms, since either may serve a shape."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
        kv_cache_shape,
        transformer_decode,
    )
    from determined_tpu.serve.config import ServeConfig

    monkeypatch.setattr(paged_mod, "_on_tpu", lambda: form == "kernel")
    one = SingleDeviceSharding(tpu_devices[0])
    # a pool much larger than anything else in the program (weights,
    # logits), so that size alone tells a copy of it from other work
    cfg = TransformerConfig(
        vocab_size=512, d_model=256, n_layers=3, n_heads=2, n_kv_heads=1,
        d_ff=512, max_seq_len=512,
    )
    sc = ServeConfig(
        num_blocks=1024, block_size=16, max_batch=4, max_prompt_len=128,
        max_new_tokens=128, decode_chunk_blocks=1,
    )
    boxed = jax.eval_shape(
        lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, flax_meta.unbox(boxed)["params"])
    aval = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    cshape = kv_cache_shape(cfg, sc.num_blocks, sc.block_size)
    cache = {"k": aval(cshape, cfg.dtype), "v": aval(cshape, cfg.dtype)}
    b = sc.max_batch
    fn = jax.jit(
        functools.partial(transformer_decode, cfg, chunk_blocks=sc.decode_chunk_blocks),
        donate_argnums=(4,),
    )
    compiled = fn.lower(
        params, aval((b,)), aval((b,)), aval((b, sc.blocks_per_seq)), cache
    ).compile()
    text = compiled.as_text()
    assert _kernels(text) == (cfg.n_layers if form == "kernel" else 0)
    assert _pool_sized_results(text, cshape) == []


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


def test_the_dsv3_decode_program_compiles_with_its_kernels_named(tpu_devices, monkeypatch):
    """The decode program of the cell at its widths, lanes and pool, bfloat16
    leaves, depth cut to the dense layer and one expert layer: the latent
    kernel a layer, and in the expert layer the two row movements and three
    grouped products over [7168, 2048] blocks (58.7 MB of VMEM for a block's
    two buffers).  Each Mosaic call keeps its name in the optimized program,
    so the scopes a trace's reader asks for list it."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.transformer import TransformerConfig, TransformerLM, kv_cache_shape, transformer_decode
    from determined_tpu.utils.compilation_cache import program_scopes

    monkeypatch.setattr(rows_mod.gm, "_interpret", lambda: False)
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
    assert _kernels(text) == 2 + 5
    assert mem.temp_size_in_bytes < 256 * 1024**2 and mem.alias_size_in_bytes >= 2 * 24576 * 16 * 640 * 2   # the pool is donated
    scopes = program_scopes(text)
    assert {"serve.mla", "serve.mla.attend", "serve.moe.route", "serve.moe.experts", "serve.moe.shared"} <= set(scopes)
    assert sum("paged_latent_attention" in n for n in scopes["serve.mla.attend"]) == 2
    named = [n for n in scopes["serve.moe.experts"] if re.match(r"(moe_gmm|moe_rows_of_tokens|moe_tokens_of_rows)", n)]
    assert len(named) == 5, scopes["serve.moe.experts"]


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
    assert bool(_arrays_with_dims(text, (32, heads, 1024))) == (products == "block_diagonal")


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_command_cells_programs_compile_over_a_cache_of_two_kinds(tpu_devices, monkeypatch, which):
    """The cell's decode step and prefill walk at its widths, lanes, pool and
    window store, bfloat16 leaves, depth cut to one window layer and the full
    layer: weights, both kinds of cache and the program's scratch fit the chip;
    the window layer's kernel keeps its name under its own scope, the full
    layer's under the other; nothing the size of a lane's context is gathered."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.transformer import (
        TransformerConfig, TransformerLM, kv_cache_shape, prefill_chunk_tokens, transformer_decode,
        transformer_prefill_chunked, window_store_shape,
    )
    from determined_tpu.utils.compilation_cache import program_scopes

    monkeypatch.setattr(rows_mod.gm, "_interpret", lambda: False)
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
        assert _kernels(text) == 2 + 2 * 5                                          # an attention kernel and five of the experts a layer
        assert sum("paged_window_attention" in n for n in scopes["serve.attn.window"]) == 1
        assert sum("paged_decode_attention" in n for n in scopes["serve.attn.full"]) == 1
        assert not any("paged_" in n for n in set(scopes["serve.attn.window"]) & set(scopes["serve.attn.full"]))
        assert _arrays_with_dims(text, (32, 20480)) == [] and _arrays_with_dims(text, (32, 4352, 1024)) == []
    else:
        assert _kernels(text) == 2 * 5
        assert _arrays_with_dims(text, (128, 256, 20480)) == [] and _arrays_with_dims(text, (256, 4352)) == []


# -- power-retention layers served from a state a lane: the Brumby cell's shapes --


def test_the_retention_decode_kernel_compiles_at_the_brumby_cells_shape(tpu_devices):
    """32 lanes x 40 query heads over 8 KV heads of 128 against a float32 state
    pool of five layers (8,320 x 128 a head): one kernel, the pools updated
    where they lie (aliased, no scratch the size of a layer's state)."""
    retention_mod = importlib.import_module("determined_tpu.ops.retention")
    one = SingleDeviceSharding(tpu_devices[0])
    aval = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    state, norm = retention_mod.state_shapes(5, 32, 8, 128)
    assert state == (5, 32, 8, 8320, 128) and norm == (5, 32, 8, 65, 128)

    def fn(q, k, v, log_g, rs, rz, live):
        return retention_mod.retention_decode(q, k, v, log_g, rs, rz, 3, live)

    compiled = jax.jit(fn, donate_argnums=(4, 5)).lower(
        aval((32, 40, 128), jnp.bfloat16), aval((32, 8, 128), jnp.bfloat16), aval((32, 8, 128), jnp.bfloat16),
        aval((32, 8), jnp.float32), aval(state, jnp.float32), aval(norm, jnp.float32), aval((32,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == 1 and "retention_decode" in text
    pool_bytes = 4 * (math.prod(state) + math.prod(norm))
    assert mem.alias_size_in_bytes >= pool_bytes and mem.temp_size_in_bytes < 16 * 1024**2


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_brumby_cells_programs_compile_over_a_state_pool_alone(tpu_devices, which):
    """The cell's decode step and prefill walk at its widths, lanes and state
    pool, bfloat16 leaves, depth cut to two layers: weights, the pool and the
    program's scratch fit the chip; the pool is donated and no second copy of
    it is held; the kernel keeps its name under its own scope; no array is
    made for the allocator's block ids."""
    from flax.core import meta as flax_meta

    from determined_tpu.models.transformer import (
        TransformerConfig, TransformerLM, state_pool_shapes, transformer_decode, transformer_prefill_chunked,
    )
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
    cache = {"rs": aval(state, jnp.float32), "rz": aval(norm, jnp.float32)}
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
        assert _kernels(text) == 2 and len({n for n in scopes["serve.retention.state"] if n.startswith("retention_decode")}) == 2
        assert _arrays_with_dims(text, (32, 1792)) == []                             # the block tables are read by nothing
    else:                                                                            # the chunk's pass over the state, a layer
        assert _kernels(text) == 2 and len({n for n in scopes["serve.retention.state"] if n.startswith("retention_chunk")}) == 2
