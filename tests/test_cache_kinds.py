"""The table of cache kinds (``models/cache_kinds.py``) is where the forward,
the kernels and the engine take what they know of a model's cache: over the
benchmark's eight serving architectures at their tiny sizes, the cache is the
union of the kinds' leaves, what the engine does at admission follows from what
a request holds of each kind, and the decode step's counters are the kinds'."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.models.cache_kinds import BLOCKS, CACHE_KINDS, LANE, cache_kinds, layer_kinds
from determined_tpu.models.serving import SERVE_COUNTERS, ZERO_PICKS, init_kv_cache, serve_counters
from determined_tpu.serve.config import ServeConfig
from determined_tpu.serve.engine import DecodeKernels, ServeEngine
from tests.model_cases import SERVED_ARCHS, tiny_form, tiny_served

ARCHS, _tiny = SERVED_ARCHS, tiny_served


@pytest.mark.parametrize("arch_name", list(ARCHS))
def test_the_cache_the_engine_and_the_counters_follow_from_the_kinds(arch_name):
    cfg, params, serve_cfg, form = _tiny(arch_name)
    kinds = cache_kinds(cfg)
    assert tuple(kind.name for kind in kinds) == ARCHS[arch_name] and set(kinds) <= set(CACHE_KINDS)
    # every attention sublayer of a layer (one, or under shortcut_block two) is of one kind or of several in the
    # table's order, at the next row of each kind's arrays, and reads its own subtree of the block; under mixer_block
    # a layer is of ONE kind, or (its expert layers) of none
    rows = {kind.name: 0 for kind in kinds}
    assert cfg.attn_sublayers == (2 if arch_name == "longcat_scmoe" else 1)
    for i in range(cfg.n_layers):
        for sub in range(cfg.attn_sublayers):
            mine = layer_kinds(cfg, i, sub)
            assert (len(mine) == (0 if cfg.use_moe(i) else 1)) if cfg.mixer_block else mine
            assert [kind for kind, _, _ in mine] == [kind for kind in CACHE_KINDS if i in kind.layers(cfg)]
            for kind, j, subtree in mine:
                assert kind in kinds and j == rows[kind.name] and kind.layers(cfg)[j // cfg.attn_sublayers] == i
                assert subtree == kind.params + ("_1" if sub else "") and subtree in params[f"block_{i}"]
                rows[kind.name] += 1
    assert (len(layer_kinds(cfg, 0)) == 2) == (arch_name == "falcon_h1")

    # the cache: the union of the kinds' leaves, each of the kind's shape and dtype, a row a layer of the kind
    sizes = serve_cfg  # a kind reads num_blocks, block_size, max_batch and prefill_chunk of it
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, sizes.num_blocks, sizes.block_size, sizes.max_batch, sizes.prefill_chunk))
    want = {leaf: (shape, jnp.dtype(dtype)) for kind in kinds for leaf, shape, dtype in zip(kind.leaves, kind.shapes(cfg, sizes), kind.dtypes(cfg))}
    assert {leaf: (a.shape, a.dtype) for leaf, a in cache.items()} == want
    # (the index keys aside: a row a layer that holds an indexer, not a row a layer of the kind)
    owners = lambda kind, leaf: len(cfg.index_layers) if leaf == "ik" else len(kind.layers(cfg)) * cfg.attn_sublayers  # noqa: E731
    assert all(want[leaf][0][0] == owners(kind, leaf) for kind in kinds for leaf in kind.leaves)

    # the counters: the kinds' in the table's order, then the experts'
    experts = (SERVE_COUNTERS if cfg.moe_experts else ()) + ((ZERO_PICKS,) if cfg.moe_zero_experts else ())
    assert serve_counters(cfg) == sum((kind.counters for kind in kinds), ()) + experts

    # the engine: prefix_cache is refused iff some kind is held by the lane, with that kind's sentence ...
    held = {BLOCKS: [k for k in kinds if k.holds == BLOCKS], LANE: [k for k in kinds if k.holds == LANE]}
    assert all((kind.no_prefix_cache is not None) == (kind.holds == LANE) for kind in CACHE_KINDS)
    assert form["serve_engine"]["prefix_cache"] is not bool(held[LANE])          # the tiny form states what it may
    if held[LANE]:
        with pytest.raises(ValueError) as refused:
            DecodeKernels(cfg, params, ServeConfig(**{**form["serve_engine"], "prefix_cache": True}))
        assert str(refused.value) == held[LANE][0].no_prefix_cache and "Set prefix_cache: false" in str(refused.value)
    kernels = DecodeKernels(cfg, params, serve_cfg)
    assert kernels.kinds == kinds and kernels.counters == serve_counters(cfg) and set(kernels.cache) == set(want)
    engine = ServeEngine(kernels)
    # ... the prefill takes its lane and refuses a start past 0 iff some kind is held by the lane ...
    prompt = list(range(1, 2 * serve_cfg.block_size + 1))
    if held[LANE]:
        with pytest.raises(ValueError, match="is prefilled from 0, not from %d" % serve_cfg.block_size):
            kernels.prefill_suffix(prompt, [0] * serve_cfg.blocks_per_seq, serve_cfg.block_size, 1)
    else:
        blocks = engine.allocator.alloc(2)
        table = blocks + [0] * (serve_cfg.blocks_per_seq - 2)
        kernels.prefill(prompt, table)
        warm = kernels.prefill_suffix(prompt, table, serve_cfg.block_size, 1)
        assert np.isfinite(warm).all() and warm.shape == (cfg.vocab_size,)
        engine.allocator.free(blocks)
    # ... and the allocator is asked iff some kind is held in blocks
    req = engine.submit(prompt, max_new_tokens=3)
    while not req.done.is_set():
        assert engine.step_once()
    stats = engine.stats()
    assert req.error is None and len(req.output) == 3
    asked = engine.allocator.blocks_for(len(prompt) + 3) if held[BLOCKS] else 0
    assert stats["kv_cache"]["peak"] == asked and stats["kv_cache"]["used"] == 0
    assert stats.get("block_ids_address_nothing", False) == (not held[BLOCKS])
    assert set(stats["step_counters"]) == set(serve_counters(cfg))
    # /stats carries what each kind of the table reports, the model's or not
    for kind in CACHE_KINDS:
        said = kind.report(cfg, sizes, 0)
        assert {key: stats[key] for key in said} == said
        assert kind in kinds or said in ({}, {"window_store": {}})
    assert stats.get("rows_per_token") == (cfg.paged_layers if cfg.attn_sublayers > 1 else None)


def _equations(jaxpr, outer=""):
    """(the scopes an equation stands under, the equation) of a jaxpr and of every jaxpr nested in it."""
    for eqn in jaxpr.eqns:
        scopes = outer + "/" + str(eqn.source_info.name_stack)
        yield scopes, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, scopes)


@pytest.mark.parametrize("arch_name", list(ARCHS))
def test_a_decode_step_sorts_no_group_to_route_and_only_a_grouped_router_enters_the_grouped_function(arch_name, monkeypatch):
    """The decode step of every served tiny form, traced: under ``serve.moe.route`` a model of the grouped sigmoid
    router (DeepSeek-V3, Nemotron-H, GLM-5.2, Ling-3.0) holds ONE ``top_k`` an expert layer, the picks' over ``[lanes,
    experts]``: a group's score and the kept groups are made without one (on the chip a ``top_k`` over a third axis is a
    whole sort of every group), nothing there sorts, and no gather reads the scores (one index an element: a third of
    what the sort left of a route); a model of another router never enters ``route_sigmoid_grouped``, so what is done
    to that function leaves its programs the text they were."""
    from determined_tpu.models import moe
    from determined_tpu.models.serving import transformer_decode

    arch, cfg, serve_cfg, _ = tiny_form(arch_name)
    grouped = bool(cfg.moe_experts) and cfg.moe_router == "sigmoid_grouped"
    assert grouped == (arch_name in ("deepseek_mla_moe", "nemotron_h", "glm_moe_dsa", "ling_kda_mla"))
    if not grouped:
        monkeypatch.setattr(moe, "route_sigmoid_grouped", lambda *a, **kw: pytest.fail("a router of another kind entered it"))
    lanes = serve_cfg.max_batch
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    params = jax.eval_shape(lambda: arch.init_params(cfg, 0))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, serve_cfg.num_blocks, serve_cfg.block_size, lanes, serve_cfg.prefill_chunk))
    step = functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=True)
    jaxpr = jax.make_jaxpr(step)(params, i32(lanes), i32(lanes), i32(lanes, serve_cfg.blocks_per_seq), cache).jaxpr
    routed = [eqn for scopes, eqn in _equations(jaxpr) if "serve.moe.route" in scopes.split("/")]
    expert_layers = sum(cfg.use_moe(i) for i in range(cfg.n_layers)) if cfg.moe_experts else 0
    assert bool(routed) == bool(expert_layers)
    if grouped:
        assert not [eqn for eqn in routed if eqn.primitive.name in ("sort", "argsort")]
        picks = [tuple(eqn.invars[0].aval.shape) for eqn in routed if eqn.primitive.name == "top_k"]
        assert picks == [(lanes, cfg.moe_experts)] * expert_layers
        assert (lanes, cfg.moe_experts) not in [tuple(eqn.invars[0].aval.shape) for eqn in routed if eqn.primitive.name == "gather"]
