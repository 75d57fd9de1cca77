"""What more than one test file of the models and their kernels takes: inputs,
plain forms to compare with and jitted steps, beside ``tests/faults.py`` and
``tests/parallel_utils.py``.  A file cut along a seam (``--dist loadfile``
balances by the file: tests/conftest.py) keeps its helpers here, once."""

import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.models.moe import RoutedExperts
from determined_tpu.ops import retention



def reference_module(name: str):
    """A plain reference the benchmark keeps (``benchmark/reference/<name>.py``: float32, no cache, no import from the program)."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def causal_forward(model, width: int):
    """``(variables, seq) -> logits [len(seq), vocab]`` of the full forward, as
    ONE program on a padded width: the forward is causal, so what follows a
    position cannot move it; called bare it is compiled an operation at a
    time, anew at every length it is asked for."""
    forward = jax.jit(model.apply)

    def logits(variables, seq):
        padded = np.zeros((1, width), np.int32)
        padded[0, : len(seq)] = seq
        return forward(variables, jnp.asarray(padded))[0, : len(seq)]

    return logits


# -- the dropless expert layer (tests/test_routed_experts.py, tests/test_expert_rows.py) --

def dense_experts(x, p, k, first, count):
    """Every held expert on every token, then the picks: plain."""
    probs = jax.nn.softmax(x @ p["router"], -1)
    top, idx = jax.lax.top_k(probs, k)
    w = top / top.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(first, first + count):
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        hidden = jax.nn.silu(x @ p["w_gate"][e - first]) * (x @ p["w_up"][e - first])
        y = y + mine[:, None] * (hidden @ p["w_down"][e - first])
    experts = probs.shape[-1]
    share = jnp.mean(jax.nn.one_hot(idx, experts).sum(1), 0) / k
    return y, experts * jnp.sum(share * probs.mean(0))


def routed_layer(held=None, experts=8, k=3, **kw):
    return RoutedExperts(num_experts=experts, top_k=k, d_ff=12, held=held, dtype=jnp.float32, partition=False, **kw)


def sorted_rows_by_argsort(picks, first, count, *, serving=False):
    """The layout ``models/moe.py _sorted_rows`` computed until PR 60, kept as the plain reference of the one it
    computes by counting: two stable ``argsort``s over the ``T * k`` picks, ``searchsorted`` for a tile's group and
    one scalar gather a row of the worst-case buffer.  Rows that no pick owns hold whatever the clipped gathers
    find: compare ``row_pick`` where ``row_live``."""
    from determined_tpu.models.moe import SortedRows, _round_up_pow2
    from determined_tpu.ops import grouped_matmul as gm

    tokens, k = picks.shape
    local = picks - first
    pick_held = (local >= 0) & (local < count)
    key = jnp.where(pick_held, local, count).reshape(-1)               # [T*k]
    order = jnp.argsort(key, stable=True)                              # sorted place -> pick
    place = jnp.argsort(order)                                         # pick -> sorted place
    load = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0, dtype=jnp.int32)
    max_rows = tokens * min(k, count)
    tile = min(gm.DEFAULT_TILE, max(16 if serving else 8, _round_up_pow2(max_rows // count)))
    groups = load.shape[0]
    rows = gm.buffer_rows(max_rows, groups, tile)
    if serving:  # an expert without rows owns no tile; one tile stays live whatever the sizes
        tiles = -(-load // tile)
        tiles = jnp.where((jnp.arange(groups) == 0) & (jnp.sum(tiles) == 0), 1, tiles)
    else:
        tiles = jnp.maximum(-(-load // tile), 1)
    ends = jnp.cumsum(tiles)
    live = ends[-1]
    tile_group = jnp.searchsorted(ends, jnp.arange(rows // tile), side="right")
    if serving:
        tile_group = jnp.where(jnp.arange(rows // tile) < live, tile_group, tile_group[live - 1])
    layout = gm.TileLayout(
        group_start=((ends - tiles) * tile).astype(jnp.int32),
        tile_group=jnp.minimum(tile_group, groups - 1).astype(jnp.int32),
        live_tiles=live.astype(jnp.int32)[None],
        rows=rows,
        tile=tile,
    )
    sorted_start = jnp.cumsum(load) - load                             # [count]
    group = jnp.minimum(key, count - 1)
    pick_row = jnp.where(
        key < count,
        jnp.take(layout.group_start, group) + place - jnp.take(sorted_start, group),
        layout.rows - 1,                                               # not held: never read
    ).astype(jnp.int32).reshape(tokens, k)
    row = jnp.arange(layout.rows)
    group = jnp.take(layout.tile_group, row // tile)
    offset = row - jnp.take(layout.group_start, group)
    row_live = gm.live_rows_mask(layout) & (offset < jnp.take(load, group))
    row_pick = jnp.take(
        order, jnp.clip(jnp.take(sorted_start, group) + offset, 0, tokens * k - 1)
    ).astype(jnp.int32)
    tile_rows = jnp.sum(row_live.reshape(-1, tile), axis=1, dtype=jnp.int32)
    return SortedRows(pick_held, pick_row, row_live, row_pick, tile_rows, load, layout)


def row_weights_by_gather(weights, row_pick, row_live):
    """``models/moe.py _row_weights`` as one scalar gather a row: its plain reference."""
    return jnp.where(row_live, jnp.take(weights.reshape(-1), row_pick), 0.0)


# -- power retention (tests/test_retention_serving.py, tests/test_retention_chunk_kernel.py) --


def retention_heads(seed, b=2, h=4, g=2, s=24, d=16, bias=2.0):
    """q [b, h, s, d], k and v [b, g, s, d], log_g [b, g, s] of a gate that remembers."""
    ks = jax.random.split(jax.random.key(seed), 4)
    q, k, v = (jax.random.normal(ks[i], (b, n, s, d), jnp.float32) for i, n in enumerate((h, g, g)))
    return q, k, v, jax.nn.log_sigmoid(bias + jax.random.normal(ks[3], (b, g, s)))


@functools.lru_cache(maxsize=None)
def retention_chunk_step(impl):
    """``retention_chunk`` as ONE program a shape (called bare, its ``jax.numpy`` half is compiled an operation at a time)."""
    return jax.jit(functools.partial(retention.retention_chunk, impl=impl))


# -- the paged decode kernels' copy schedule at a lane's edges (tests/test_paged_attention.py,
# -- tests/test_window_kernels.py, tests/test_latent_serving.py) --

#: a call's lanes by the tokens each one's query sees (0: an idle lane), at blocks of
#: 16 tokens and tiles of 2 blocks, by what the list pins for the walk's two edges
PAGED_EDGES = {
    "a_token_a_block_a_tile_a_tile_and_one": (1, 16, 32, 33),
    "idle_first": (0, 40, 7),
    "idle_in_the_middle": (40, 0, 7),
    "idle_last": (40, 7, 0),
    "two_idle_in_a_row": (70, 0, 0, 40),
    "every_lane_idle": (0, 0, 0),
}


def blocks_seen(tables, contexts, block, window=None):
    """The pool's blocks that hold a token some lane's query sees, token by token:
    a lane of ``n`` tokens sees the positions ``max(0, n - window) .. n - 1``, and
    position ``j`` lies in column ``(j // block) % T`` of its row of the table
    (a ring's column; a table's own where the lane fits it)."""
    tables = np.asarray(tables)
    return {
        int(tables[b, (j // block) % tables.shape[1]])
        for b, n in enumerate(contexts) for j in range(max(0, n - window) if window else 0, n)
    }


def check_copy_schedule(run, pools, layer, tables, contexts, block, window=None):
    """``run(*pools)``: one of the paged decode kernels in the interpreter over
    lanes of ``contexts`` tokens.  Returns its result, after the poison case:
    with NaN in every block of every layer of the pools but ``layer``'s blocks
    that hold a token some query sees, the result is finite and the same bit for
    bit (a tile copied whole brings NaN rows to ``p @ V``, ``0 x NaN``; the
    interpreter's scratch starts as NaN, so a row no copy wrote must not reach
    it either).  The blocks are found token by token here, and
    ``walk_counts``, the schedule's own count, must count the same."""
    got = np.asarray(run(*pools))
    seen = sorted(blocks_seen(tables, contexts, block, window))
    counts = paged_mod.walk_counts(np.asarray(contexts) - 1, block, window)
    assert counts.copied_tokens == block * len(seen)
    assert counts.live_tokens == sum(min(n, window or n) for n in contexts) and counts.lanes == sum(n > 0 for n in contexts)

    def poisoned(pool):
        out = np.full(pool.shape, np.nan, np.float32)
        out[layer, seen] = np.asarray(pool, np.float32)[layer, seen]
        return jnp.asarray(out, pool.dtype)

    again = np.asarray(run(*(poisoned(pool) for pool in pools)))
    assert np.isfinite(again).all()
    np.testing.assert_array_equal(again, got)
    return got


# -- compiling for a described chip (tests/test_tpu_compile*.py) --

flash_mod = importlib.import_module("determined_tpu.ops.flash_attention")
#: the benchmark's served architectures at their tiny sizes (``tests/benchmark/tiny/<arch>.json``) -> the cache
#: kinds their layers are of (``models/cache_kinds.py``): between them every kind of the table
SERVED_ARCHS = {
    "dense_decoder": ("paged_kv",),
    "deepseek_mla_moe": ("paged_latent",),
    "cohere2_moe": ("paged_kv", "window_ring"),
    "power_retention": ("state_slot",),
    "falcon_h1": ("paged_kv", "ssm_slot"),
    "longcat_scmoe": ("paged_latent",),
    "nemotron_h": ("paged_kv", "ssm_slot"),
    "glm_moe_dsa": ("paged_indexed",),
    "qwen3_next": ("paged_kv", "delta_slot"),
    "ling_kda_mla": ("paged_latent", "delta_slot"),
}


def tiny_form(arch_name: str, **serve_engine):
    """(the adapter, model config, ServeConfig, the form) of a served architecture's tiny form, as the benchmark
    builds it; ``serve_engine`` replaces sizes of the form's engine.  No parameter is made."""
    import json
    import sys

    from determined_tpu.serve.config import ServeConfig

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(repo, "benchmark")
    if bench not in sys.path:  # an architecture's adapter imports the harness beside it
        sys.path.insert(0, bench)
    from benchlib import model as bench_model

    with open(os.path.join(repo, "tests", "benchmark", "tiny", arch_name + ".json")) as f:
        form = json.load(f)
    arch = bench_model.load_file(os.path.join(bench, "archs", arch_name + ".py"), arch_name)
    serve_cfg = ServeConfig(**{**form["serve_engine"], **serve_engine})
    return arch, arch.model_config(form["config"], serve_cfg.max_seq_len), serve_cfg, form


def tiny_served(arch_name: str, **serve_engine):
    """(model config, parameters, ServeConfig, the form) of a served architecture's tiny form, through its
    adapter as the benchmark builds it; ``serve_engine`` replaces sizes of the form's engine."""
    arch, cfg, serve_cfg, form = tiny_form(arch_name, **serve_engine)
    return cfg, arch.init_params(cfg, 0), serve_cfg, form


@pytest.fixture()
def tracer():
    """The process tracer, empty and on; left as a fresh process has it."""
    from determined_tpu.observability import _tracer as tracer_mod, get_tracer

    t = get_tracer()
    t.reset()
    t.configure(enabled=True)
    yield t
    t.close()  # stops a shipper a test left running, closes an export
    t.configure(enabled=True, flush_interval=tracer_mod.DEFAULT_FLUSH_INTERVAL)
    t.reset()


adamw_mod = importlib.import_module("determined_tpu.ops.fused_adamw")
paged_mod = importlib.import_module("determined_tpu.ops.paged_attention")
grouped_mod = importlib.import_module("determined_tpu.ops.grouped_matmul")
rows_mod = importlib.import_module("determined_tpu.ops.expert_rows")
form_mod = importlib.import_module("determined_tpu.ops.kernel_form")

TOPOLOGY = "v5e:2x2"


@pytest.fixture(scope="module")
def tpu_devices():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)
    except Exception as e:  # noqa: BLE001 - no libtpu, or it cannot describe the chip
        pytest.skip(f"cannot describe a {TOPOLOGY} topology here: {e}")
    return list(topo.devices)


@pytest.fixture(autouse=True)
def real_kernels_no_cache(monkeypatch):
    """Mosaic, not the interpreter; and no persistent cache around the
    compiles (an entry compiled for a described chip cannot be read back
    without one, and the next run would warn)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(form_mod, "on_tpu", lambda: True)
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


def compile_text(fn, *avals) -> str:
    """The optimized program's text; raises what the chip's compiler would."""
    return jax.jit(fn).lower(*avals).compile().as_text()


def mosaic_calls(text: str) -> int:
    return text.count('custom_call_target="tpu_custom_call"')


def arrays_with_dims(text: str, dims) -> list:
    """Array shapes of an optimized HLO module (results and operands alike,
    inside fusions too) that have every one of ``dims`` among their dimensions."""
    found = set()
    for m in re.finditer(r"\b\w+\[([\d,]+)\]", text):
        shape = [int(d) for d in m.group(1).split(",")]
        if all(shape.count(d) >= list(dims).count(d) for d in dims):
            found.add(m.group(0))
    return sorted(found)
