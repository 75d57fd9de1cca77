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
kernels' one switch (``ops/kernel_form.py on_tpu``) is steered from the test (``real_kernels_no_cache``,
with the described devices in tests/model_cases.py).  The serving cells'
programs at their published widths are tests/test_tpu_compile_cells.py and
tests/test_tpu_compile_lane_cells.py, the training cells' whole steps
tests/test_tpu_compile_train_cells.py.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from determined_tpu.ops.attention import dot_product_attention
from determined_tpu.parallel.mesh import MeshConfig, make_mesh
from tests.model_cases import (  # noqa: F401  (fixture reuse)
    adamw_mod,
    compile_text as _compile,
    flash_mod,
    form_mod,
    grouped_mod,
    mosaic_calls as _kernels,
    paged_mod,
    real_kernels_no_cache,
    rows_mod,
    tpu_devices,
)


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
    "shape,kv_heads",
    [((8, 16, 1024, 128), None), ((1, 16, 4096, 128), None), ((4, 32, 4096, 128), 8), ((3, 8, 8192, 128), 2),
     ((2, 4, 320, 128), None)],
    ids=["const_yaml_b8_s1024", "long_b1_s4096", "mistral_cell_b4_s4096", "zaya1_cell_b3_s8192", "one_block_of_320"],
)
def test_flash_fwd_bwd_compiles_on_one_chip(tpu_devices, shape, kv_heads):
    """A sequence of one block (the single-pass forward in bands) and the two
    dense training cells' shapes (benchmark/configs/mistral-7b-v0.3-l2.json,
    zaya1-8b-l5-ep2.json): blocks of 1,024 worked in sub-tiles of 128 where
    the diagonal crosses them.  A block that 128 does not divide (a sequence
    of 320: one block) is worked whole."""
    one = SingleDeviceSharding(tpu_devices[0])
    grad = jax.grad(functools.partial(_attn_loss, None), argnums=(0, 1, 2))
    text = _compile(grad, *_qkv(shape, one, kv_heads))
    assert _kernels(text) == 3  # fwd, dq, dkv
    assert len(text) < FLASH_TEXT_LIMIT


# The compiled text of one layer's three kernels with what stands round them
# (builder's compiles, PR 52): 92,000 characters under a window and 61,000
# without, a crossed block's bands one product each (43,000 and 37,000 with
# whole blocks, before).  Sub-tile by sub-tile under a ``pl.when`` each, the
# forward's and the backward's programs came to 489,000 at 128 and compiled
# in 16 s, not 6: a body unrolled that far lengthens every training cell's
# set-up, and should fail here and not on the chip.
FLASH_TEXT_LIMIT = 200_000


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
    assert len(text) < FLASH_TEXT_LIMIT  # two crossed offsets (the diagonal's, the trailing edge's) a kernel
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
        # a process keeps the flash kernels' first trace a configuration
        # (``_fwd_program``, ``_bwd_program``): each caller here stands for a
        # process of its own
        flash_mod._fwd_program.clear_cache()
        flash_mod._bwd_program.clear_cache()
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
    from determined_tpu.models.serving import (
        transformer_decode,
        transformer_prefill,
        transformer_prefill_chunked,
    )
    from determined_tpu.models.transformer import TransformerConfig, TransformerLM, kv_cache_shape
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

    from determined_tpu.models.serving import transformer_decode
    from determined_tpu.models.transformer import TransformerConfig, TransformerLM, kv_cache_shape
    from determined_tpu.serve.config import ServeConfig

    monkeypatch.setattr(form_mod, "on_tpu", lambda: form == "kernel")
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
