"""``program_scopes`` (utils/compilation_cache.py): which instructions of an
optimized program fall under which ``jax.named_scope``.

Three things are pinned here, all on the CPU:

- a scope is found through every transformation jax wraps it in, the first
  one entered under the transformation too, forward and backward;
- a fusion is listed by its body (a hand-written module text), and only what
  runs is listed;
- the guard that keeps the programs scoped: a tiny ``LMTrial`` step and tiny
  decode and chunked-prefill programs list at least 95 % of their
  instructions under a scope, so a code path added without one fails here
  and not on the chip.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from determined_tpu.utils import compilation_cache as cc

# ---------------------------------------------------------------------------
# through transformations
# ---------------------------------------------------------------------------


def _plain(x, w):
    with jax.named_scope("a.one"):
        h = jnp.tanh(x @ w)
    with jax.named_scope("b.two"):
        h = jnp.sin(h @ w.T)
    with jax.named_scope("c.three"):
        return jnp.sum(h * h)


class _Layer(nn.Module):
    @nn.compact
    def __call__(self, x):
        with jax.named_scope("layer.proj"):
            return jnp.tanh(nn.Dense(16, use_bias=False)(x))


class _Model(nn.Module):
    @nn.compact
    def __call__(self, x):
        return _Layer(name="second")(_Layer(name="first")(x))


def _flax_loss(params, x):
    with jax.named_scope("loss.sq"):  # entered in the loss function, round the model: the first under grad
        y = _Model().apply(params, x)
        return jnp.mean(y * y)


@jax.custom_vjp
def _cv(x, w):
    return jnp.tanh(x @ w)


def _cv_fwd(x, w):
    with jax.named_scope("in.fwd"):
        y = jnp.tanh(x @ w)
    return y, (x, w, y)


def _cv_bwd(res, g):
    x, w, y = res
    with jax.named_scope("in.bwd"):
        d = g * (1 - y * y)
        return d @ w.T, x.T @ d


_cv.defvjp(_cv_fwd, _cv_bwd)


def _around_custom_vjp(x, w):
    with jax.named_scope("out.side"):
        return jnp.sum(_cv(x, w) ** 2)


X, W = jnp.ones((8, 16)), jnp.full((16, 16), 0.1)
_PLAIN = ("a.one", "b.two", "c.three")


def _flax_args():
    x = jnp.ones((4, 16))
    return _Model().init(jax.random.key(0), x), x


def _cases():
    vg = functools.partial(jax.value_and_grad, argnums=1)
    yield "plain.jit", _plain, (X, W), _PLAIN, ()
    yield "plain.grad", vg(_plain), (X, W), _PLAIN, _PLAIN
    yield "plain.vmap_grad", jax.vmap(vg(_plain), in_axes=(0, None)), (jnp.ones((3, 8, 16)), W), _PLAIN, _PLAIN
    yield "plain.checkpoint", vg(jax.checkpoint(_plain)), (X, W), _PLAIN, _PLAIN
    yield "custom_vjp", vg(_around_custom_vjp), (X, W), ("out.side", "in.fwd", "in.bwd"), ("out.side", "in.bwd")
    flax = ("loss.sq", "layer.proj")
    yield "flax.jit", _flax_loss, None, flax, ()
    yield "flax.grad", jax.value_and_grad(_flax_loss), None, flax, flax
    yield "flax.vmap_grad", jax.vmap(jax.value_and_grad(_flax_loss), in_axes=(None, 0)), "batched", flax, flax
    yield "flax.checkpoint", jax.value_and_grad(jax.checkpoint(_flax_loss)), None, flax, flax


@pytest.mark.parametrize("case", list(_cases()), ids=lambda c: c[0])
def test_every_scope_is_listed_through_the_transformation(case):
    _, fn, args, scopes, backward = case
    if args is None or args == "batched":
        params, x = _flax_args()
        args = (params, jnp.ones((3, 4, 16)) if args == "batched" else x)
    text = jax.jit(fn).lower(*args).compile().as_text()
    listed = cc.program_scopes(text)
    assert set(scopes) <= set(listed), (sorted(listed), re.findall(r'op_name="([^"]*)"', text)[:40])
    # the backward instructions are listed under the scope of the forward code they differentiate
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in backward:
        assert any("transpose(" in n and scope in cc.scopes_of(n) for n in op_names), (scope, op_names)
    for scope in scopes:
        if not scope.endswith(".bwd"):
            assert any("transpose(" not in n and scope in cc.scopes_of(n) for n in op_names), (scope, op_names)


@pytest.mark.parametrize(
    "op_name, scopes",
    [
        ("jit(step)/jvp(loss.ce)/dot_general", ["loss.ce"]),
        ("jit(step)/transpose(jvp(loss.ce))/dot_general", ["loss.ce"]),
        ("jit(step)/vmap(transpose(jvp(loss.ce)))/mul", ["loss.ce"]),
        ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/a.one/tanh", ["a.one"]),
        ("jit(step)/jvp(LM)/block_0/attn/attn.qkv/wq/dot_general", ["attn.qkv"]),
        ("jit(serve_decode)/serve.mla/serve.mla.attend/jit(_paged)/pallas_call", ["serve.mla", "serve.mla.attend"]),
        ("jit(step)/jvp(LM)/block_3/moe/moe.route", ["moe.route"]),   # XLA names some fusions by the common prefix
        ("jit(step)/transpose(jvp())/broadcast_in_dim", []),
        ("state.step", []),                                            # an argument's own name, not a scope
        ("dot_general", []),
    ],
)
def test_a_part_is_a_scope_after_its_wrappers_are_peeled(op_name, scopes):
    assert cc.scopes_of(op_name) == scopes


def test_name_stacks_survive_lowering_with_tracebacks_out_of_the_locations():
    """``setup_compilation_cache`` takes tracebacks out of the locations (the
    cache key must not depend on who called) and repairs what that costs: the
    inliner would leave every scope entered directly in a jitted function out
    of ``op_name``.  The lowered text without locations does not change."""
    cc._keep_name_stacks()
    lowered = {}
    for full in (True, False):
        prev = jax.config.jax_include_full_tracebacks_in_locations
        jax.config.update("jax_include_full_tracebacks_in_locations", full)
        try:
            lowered[full] = jax.jit(jax.value_and_grad(_plain, argnums=1)).lower(X, W)
        finally:
            jax.config.update("jax_include_full_tracebacks_in_locations", prev)
    assert lowered[True].as_text() == lowered[False].as_text()
    assert set(_PLAIN) <= set(cc.program_scopes(lowered[False].compile().as_text()))


# ---------------------------------------------------------------------------
# a fusion by its body, and only what runs
# ---------------------------------------------------------------------------

_MODULE = """
HloModule jit_step, entry_computation_layout={(f32[8,16]{1,0})->f32[]}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(step)/optim.clip/reduce_sum"}
}

%fused_computation.1 (p0: f32[8,16], p1: f32[16,16]) -> f32[] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %p1 = f32[16,16]{1,0} parameter(1)
  %dot.1 = f32[8,16]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp(mlp.dense))/dot_general"}
  %square.1 = f32[8,16]{1,0} multiply(%dot.1, %dot.1), metadata={op_name="jit(step)/optim.update/optim.clip/mul"}
  %zero.1 = f32[] constant(0)
  ROOT %reduce.1 = f32[] reduce(%square.1, %zero.1), dimensions={0,1}, to_apply=%region_0.1, metadata={op_name="jit(step)/optim.update/optim.clip/reduce_sum"}
}

%fused_computation.2 (p0: f32[8,16], p1: f32[16,16], p2: f32[16,16]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %p1 = f32[16,16]{1,0} parameter(1)
  %p2 = f32[16,16]{1,0} parameter(2)
  %dot.2 = f32[8,16]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(attn.out)/dot_general"}
  %dot.3 = f32[8,16]{1,0} dot(%dot.2, %p2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(mlp.dense)/dot_general"}
  ROOT %only_in_a_body.1 = f32[8,16]{1,0} tanh(%dot.3), metadata={op_name="jit(step)/jvp(block.norm)/tanh"}
}

%fused_computation.3 (p0: f32[8,16]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  ROOT %neg.1 = f32[8,16]{1,0} negate(%p0), metadata={op_name="jit(step)/jvp(block.norm)/neg"}
}

%body.1 (carry: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %carry = (s32[], f32[8,16]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%carry), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%carry), index=1
  %in_the_loop.1 = f32[8,16]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/jvp(block.norm)/neg"}
  ROOT %next = (s32[], f32[8,16]{1,0}) tuple(%i, %in_the_loop.1)
}

%cond.1 (carry: (s32[], f32[8,16])) -> pred[] {
  %carry = (s32[], f32[8,16]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%carry), index=0
  %three = s32[] constant(3)
  ROOT %lt.1 = pred[] compare(%i, %three), direction=LT, metadata={op_name="jit(step)/jvp(loss.ce)/while/cond/lt"}
}

%never_called.1 (p0: f32[8,16]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  ROOT %dead.1 = f32[8,16]{1,0} exponential(%p0), metadata={op_name="jit(step)/jvp(loss.ce)/exp"}
}

ENTRY %main.1 (x: f32[8,16], w: f32[16,16], v: f32[16,16]) -> f32[] {
  %x = f32[8,16]{1,0} parameter(0), metadata={op_name="x"}
  %w = f32[16,16]{1,0} parameter(1), metadata={op_name="state.params.w"}
  %v = f32[16,16]{1,0} parameter(2)
  %slice-start.1 = ((f32[16,16]{1,0}), f32[16,16]{1,0}, s32[]) slice-start(%w), slice={[0:16], [0:16]}
  %slice-done.1 = f32[16,16]{1,0} slice-done(%slice-start.1)
  %concat_bitcast.1 = f32[16,16]{1,0} custom-call(%slice-done.1), custom_call_target="ConcatBitcast"
  %one_product.1 = f32[] fusion(%x, %concat_bitcast.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/optim.update/optim.clip/reduce_sum"}
  %two_products.1 = f32[8,16]{1,0} fusion(%x, %w, %v), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(step)/jvp(block.norm)/tanh"}
  %init = (s32[], f32[8,16]{1,0}) tuple(%zero, %two_products.1)
  %while.1 = (s32[], f32[8,16]{1,0}) while(%init), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/jvp(loss.ce)/while"}
  %tpu_custom_call.7 = f32[8,16]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="pallas_call"}
  ROOT %out = f32[] add(%one_product.1, %one_product.1), metadata={op_name="jit(step)/train.metrics/add"}
}
"""

_RULES = {
    "one product's scope wins over the root's": lambda p: "one_product.1" in p.scopes["mlp.dense"]
    and "one_product.1" not in p.scopes.get("optim.clip", []) and "one_product.1" not in p.scopes.get("optim.update", []),
    "a fusion that mixes scopes says which": lambda p: p.mixed["one_product.1"] == ["mlp.dense", "optim.clip"],
    "two products of two scopes fall back to the root": lambda p: "two_products.1" in p.scopes["block.norm"]
    and all("two_products.1" not in p.scopes.get(s, []) for s in ("attn.out", "mlp.dense")),
    "and appear under mixed": lambda p: p.mixed["two_products.1"] == ["attn.out", "block.norm", "mlp.dense"],
    "a fusion of one scope is not mixed": lambda p: "in_the_loop.1" not in p.mixed,
    "a body's instruction is never listed": lambda p: not any(
        n in names for names in p.scopes.values() for n in ("only_in_a_body.1", "dot.1", "dot.2", "dot.3", "reduce.1", "neg.1", "add.9")
    ),
    "a while body's instruction is listed": lambda p: "in_the_loop.1" in p.scopes["block.norm"],
    "and its condition's": lambda p: "lt.1" in p.scopes["loss.ce"] and "while.1" in p.scopes["loss.ce"],
    "a computation nothing calls is not": lambda p: "dead.1" not in p.scopes["loss.ce"],
    "the compiler's prefetch is listed where its user is": lambda p: {"slice-start.1", "slice-done.1", "concat_bitcast.1"}
    <= set(p.scopes["mlp.dense"]),
    "an argument's name is no scope": lambda p: "state.params.w" not in p.scopes and "w" not in p.scopes.get("state.params", []),
    "a bare kernel runs under none": lambda p: p.unnamed == ["tpu_custom_call.7"],
    "what does work is counted": lambda p: p.listable == 10,
    "program_scopes is the scopes alone": lambda p: cc.program_scopes(_MODULE) == p.scopes,
}


@pytest.mark.parametrize("rule", list(_RULES), ids=lambda r: r.replace(" ", "_"))
def test_a_fusion_is_listed_by_its_body_and_only_what_runs_is_listed(rule):
    found = cc.read_program_scopes(_MODULE)
    assert _RULES[rule](found), found


# ---------------------------------------------------------------------------
# the guard: the programs stay scoped
# ---------------------------------------------------------------------------

LEAST_NAMED = 0.95

_TRAIN_HP = {
    "lr": 1e-3, "global_batch_size": 2, "seq_len": 32, "vocab_size": 128, "d_model": 32, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "d_ff": 64, "dataset_size": 8, "bf16": True, "attention": "reference", "warmup_steps": 1,
    "fused_ce": True, "fused_adamw": True,
}
_EXPERTS_HP = {"moe_experts": 4, "moe_top_k": 2, "moe_every": 1, "moe_intermediate_size": 32, "moe_shared_experts": 1}


def _step_text(tmp_path, hparams):
    from determined_tpu import core, train
    from determined_tpu.data import to_global
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig
    from determined_tpu.train import _jit_cache

    _jit_cache.clear_step_cache()
    ctx = train.init(
        hparams=hparams, mesh_config=MeshConfig(data=1), devices=jax.devices()[:1], seed=5,
        core_context=core._dummy_init(checkpoint_dir=str(tmp_path / "ck")),
    )
    trainer = train.Trainer(LMTrial(ctx))
    trainer._setup()
    batch = to_global(next(trainer.train_loader.iter_epoch(0)), trainer.mesh)
    with trainer.mesh:
        return trainer._train_step_jit.lower(trainer.state, batch).compile().as_text()


def _serve_texts(cfg):
    from flax.core import meta

    from determined_tpu.models.serving import (
        init_kv_cache,
        prefill_chunk_tokens,
        transformer_decode,
        transformer_prefill_chunked,
    )
    from determined_tpu.models.transformer import TransformerLM

    # shapes alone: the programs are lowered and compiled, never run
    params = meta.unbox(jax.eval_shape(TransformerLM(cfg).init, jax.random.key(1), jnp.zeros((1, 8), jnp.int32)))["params"]
    cache = init_kv_cache(cfg, 16, 8)
    lanes, width = 4, 8
    tables = jnp.asarray(np.arange(1, 1 + lanes * width).reshape(lanes, width) % 16, jnp.int32)
    decode = jax.jit(functools.partial(transformer_decode, cfg, chunk_blocks=1, counters=bool(cfg.moe_experts)))
    yield "decode", decode.lower(
        params, jnp.zeros(lanes, jnp.int32), jnp.arange(lanes, dtype=jnp.int32), tables, cache
    ).compile().as_text()
    pad = 2 * prefill_chunk_tokens(8, 64)
    prefill = jax.jit(functools.partial(transformer_prefill_chunked, cfg))
    yield "prefill", prefill.lower(
        params, jnp.zeros((1, pad), jnp.int32), jnp.zeros(1, jnp.int32), jnp.full(1, 40, jnp.int32), tables[:1], cache
    ).compile().as_text()


def _gqa():
    from determined_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=96, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=96, max_seq_len=64,
        attention_impl="reference", partition_params=False,
    )


def _latent_experts():
    from determined_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=96, d_model=64, n_layers=2, n_heads=4, d_ff=96, max_seq_len=64, attention_impl="reference",
        partition_params=False, q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        dense_prefix=1, moe_experts=16, moe_every=1, moe_top_k=4, moe_intermediate_size=32, moe_experts_held=(4, 4),
        moe_router="sigmoid_grouped", moe_n_group=4, moe_topk_group=2, moe_routed_scaling=2.5, moe_shared_experts=1,
    )


def _programs(which, tmp_path):
    if which == "train.dense":
        yield "step", _step_text(tmp_path, _TRAIN_HP)
    elif which == "train.dense.chunked_ce":  # the scan that makes dx and dk beside a chunk's logits (64 tokens a step)
        yield "step", _step_text(tmp_path, {**_TRAIN_HP, "ce_chunk": 16})
    elif which == "train.routed_experts":
        yield "step", _step_text(tmp_path, {**_TRAIN_HP, **_EXPERTS_HP})
    else:
        yield from _serve_texts(_gqa() if which == "serve.gqa" else _latent_experts())


@pytest.mark.parametrize("which", ["train.dense", "train.dense.chunked_ce", "train.routed_experts", "serve.gqa", "serve.latent_experts"])
def test_the_programs_stay_scoped(which, tmp_path):
    """Of the instructions that do work (all but parameters, constants,
    tuples and copies), at least 95 % by count fall under a scope; and each
    program lists the scopes its layers state."""
    want = {
        "train.dense": {"lm.embed", "block.norm", "attn.qkv", "attn.full", "attn.out", "mlp.dense", "loss.ce",
                        "optim.clip", "optim.update", "train.metrics"},
        "train.routed_experts": {"lm.embed", "block.norm", "attn.qkv", "attn.full", "attn.out", "moe.route", "moe.dispatch",
                                 "moe.experts", "moe.combine", "moe.shared", "loss.ce", "optim.clip", "optim.update"},
        "serve.gqa": {"serve.embed", "serve.norm", "serve.attn.qkv", "serve.kv.write", "serve.attn.attend",
                      "serve.attn.out", "serve.mlp", "serve.head"},
        "serve.latent_experts": {"serve.embed", "serve.norm", "serve.kv.write", "serve.mla", "serve.mla.attend", "serve.mlp",
                                 "serve.moe.route", "serve.moe.experts", "serve.moe.shared", "serve.head"},
    }
    want = want[which.removesuffix(".chunked_ce")]
    for program, text in _programs(which, tmp_path):
        found = cc.read_program_scopes(text)
        assert want <= set(found.scopes), (program, sorted(want - set(found.scopes)))
        named = 1.0 - len(found.unnamed) / found.listable
        assert named >= LEAST_NAMED, (program, named, found.unnamed[:30])


# ---------------------------------------------------------------------------
# what a first call holds: jax's own events as spans, and two children
# ---------------------------------------------------------------------------


def _long_to_trace(x, w):
    for _ in range(60):  # a trace of well over ``_TRACE_FLOOR_S``
        x = jnp.tanh(x @ w) + x
    return jnp.sum(x)


@pytest.fixture()
def tracer():
    from determined_tpu.observability import get_tracer

    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)
    tracer.reset()
    cc._listen_to_xla()
    yield tracer
    tracer.configure(enabled=was)
    tracer.reset()


def _inside(child, parent, eps=1.0):
    return parent["ts"] - eps <= child["ts"] and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + eps


def test_a_first_call_holds_jaxs_own_events_and_the_two_children(tracer):
    step = cc.timed_first_call(jax.jit(_long_to_trace), "jit.compile.test_first_call")
    step(X, W)
    spans = [e for e in tracer.chrome_events() if e["ph"] == "X"]
    (parent,) = [e for e in spans if e["name"] == "jit.compile.test_first_call"]
    mine = lambda name: [  # noqa: E731
        e for e in spans if e["name"] == name and "_long_to_trace" in str((e.get("args") or {}).get("fun_name"))
    ]
    trace, lower = mine("xla.trace"), mine("xla.lower")
    load = mine("xla.compile") or [e for e in spans if e["name"] == "xla.cache_load"]
    assert trace and lower and load
    assert trace[0]["args"]["fun_name"] == "_long_to_trace" and lower[0]["args"]["fun_name"] == "jit(_long_to_trace)"
    assert all(e["cat"] == "compile" and _inside(e, parent) for e in trace + lower + load)
    # the lowering traces, then lowers, then compiles: a span ends where jax's listener was called
    assert trace[0]["ts"] + trace[0]["dur"] <= lower[0]["ts"] + lower[0]["dur"] <= load[0]["ts"] + load[0]["dur"]
    (inspect,) = [e for e in spans if e["name"] == "jit.compile.test_first_call.inspect"]
    (first_run,) = [e for e in spans if e["name"] == "jit.compile.test_first_call.first_run"]
    assert _inside(inspect, parent) and _inside(first_run, parent) and inspect["args"]["text_bytes"] > 1000
    assert inspect["ts"] + inspect["dur"] <= first_run["ts"] + 1.0  # the two do not overlap
    assert load[0]["ts"] + load[0]["dur"] <= inspect["ts"] + 1.0    # the inspection reads what was compiled
    # the counter that said the parent's seconds again is gone
    assert "jit_cache.compile_s" not in tracer.counters()
    # a later call is no first call; a forced retrace (another shape) yields jax's events again, same name
    before = len(spans)
    step(X, W)
    assert len([e for e in tracer.chrome_events() if e["ph"] == "X"]) == before
    step(jnp.ones((4, 16)), W)
    again = [e for e in tracer.chrome_events() if e["ph"] == "X"][before:]
    names = {(e["name"], (e.get("args") or {}).get("fun_name")) for e in again}
    assert {("xla.trace", "_long_to_trace"), ("xla.lower", "jit(_long_to_trace)"), ("xla.compile", "jit(_long_to_trace)")} <= names
    assert not any(e["name"].startswith("jit.compile.") for e in again)


def test_a_short_trace_is_left_out_and_a_program_no_first_call_wraps_is_in(tracer):
    jax.jit(lambda x: x + 1.0)(jnp.ones((3, 5)))  # wrapped by nothing: lowered and compiled all the same
    spans = [e for e in tracer.chrome_events() if e["ph"] == "X"]
    assert {"xla.lower", "xla.compile"} <= {e["name"] for e in spans}
    assert all(e["dur"] >= cc._TRACE_FLOOR_S * 1e6 - 1.0 for e in spans if e["name"] == "xla.trace")


def test_the_listeners_are_registered_once_and_a_disabled_tracer_records_none_of_it(tracer, tmp_path, monkeypatch):
    from jax._src import monitoring

    n_durations, n_events = len(monitoring._event_duration_secs_listeners), len(monitoring._event_listeners)
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    try:
        for k in range(3):
            monkeypatch.setattr(cc, "_configured", None)  # as a new process has it
            cc.setup_compilation_cache(str(tmp_path / f"xla-{k}"))
            cc.setup_compilation_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert len(monitoring._event_duration_secs_listeners) == n_durations
    assert len(monitoring._event_listeners) == n_events
    # each first application of a directory left its mark, with what it found there
    marks = [e for e in tracer.chrome_events() if e["name"] == "setup.cache_configured"]
    assert [e["args"] for e in marks] == [{"path": str(tmp_path / f"xla-{k}"), "entries": 0} for k in range(3)]
    assert all(e["ph"] == "i" and e["cat"] == "setup" for e in marks)
    tracer.configure(enabled=False)
    tracer.reset()
    step = cc.timed_first_call(jax.jit(_long_to_trace), "jit.compile.test_disabled")
    step(jnp.ones((2, 16)), W)
    assert tracer.stats()["events"] == 0
