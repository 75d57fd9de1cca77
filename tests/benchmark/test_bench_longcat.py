"""The LongCat-Flash-Omni configuration, its adapter, reference and the two
metrics it brings: the arithmetic the cell's numbers rest on (the program's
tree to the parameter, two rows a token a block), the share test of the
guide's section 4, the readers on hand-made events, and the cell run end to
end in a throw-away root on the CPU at a tiny size (``correct: true``, and
``false`` against a reference that is told something else than the
configuration states)."""

import dataclasses
import json
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib as B
from benchlib import costs, harness, model, readers, spec as S
from benchlib.observe import Observations

CELL = "serve-longcat-omni-l4-ep32-dialogue"
CONFIG = "longcat-flash-omni-l4-ep32"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = {"moe_zero_picks_per_token", "serve_moe_identity_device_share"}

# 2 double layers; 8 real + 4 identity experts, top-3, experts 2..5 held; 4 heads of [16 | 8], both latents scaled
TINY = B.tiny_form("longcat_scmoe")["config"]
#: an adapter of the test's own, whose reference is told something else than the configuration states
TOLD_OTHERWISE = '''
from benchlib import model

_base = model.beside(__file__, "archs", "longcat_scmoe")
_told = _base.numerics
_base.numerics = lambda config: {{**_told(config), **{told!r}}}
globals().update({{k: v for k, v in vars(_base).items() if not k.startswith("__")}})
'''
#: references that are not the program's: what each is told instead
NOT_THE_PROGRAMS = {
    "no-scales": {"q_scale": 1.0, "kv_scale": 1.0},        # the latents as their norms leave them
    "no-scaling": {"scaling": 1.0},                         # the picks' weights without routed_scaling_factor
    "no-identity": {"real_experts": 12},                    # outputs 8..11 taken for real experts held elsewhere: they add nothing
    "other-experts": {"first_expert": 4},
}


def _tiny_cell(config=TINY):
    return types.SimpleNamespace(config=config, data_dir=B.BENCH, config_file="tiny/longcat_scmoe.json")


@pytest.fixture(scope="module")
def cell():
    return S.Spec().cell(CELL)


# ---------------------------------------------------------------------------
# the configuration as published, and the arithmetic of its cut
# ---------------------------------------------------------------------------


def the_configuration_keeps_every_published_width_and_states_its_cut(spec):
    doc, cell = spec.doc, spec.cell(CELL)
    assert S.check_document(doc) == []
    assert [(w["config"], w["traffic"], w["chips"]) for w in doc["workloads"] if w["name"] == CELL] == [(CONFIG, "dialogue-closed", 1)]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "LongCat-Flash-Omni")
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == published["source_url"] == cell.config["source"]
    assert entry["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"] == list(cell.config["reduced"])
    for key, value in published["config"].items():
        if key not in entry["reduced"]:
            assert cell.config[key] == value, key
    assert [cell.config[k] for k in entry["reduced"]] == [4, 16, 16384]
    assert (cell.config["n_routed_experts_published"], cell.config["first_expert_held"]) == (512, 0)
    assert {"deployment", "assumed", "deviations", "dtypes", "tolerance"} <= set(cell.config)
    assert {"mla_scale_values", "norm_topk_prob", "e_score_correction_bias", "expert_order", "rope_pairing", "initialiser"} <= set(cell.config["assumed"])
    assert {"torch_dtype", "encoders_and_codec"} <= set(cell.config["deviations"])
    assert cell.config["dtypes"] == {"serve_params": "bfloat16", "kv_cache": "bfloat16", "compute": "bfloat16"}
    assert cell.config["tolerance"]["serve_logits"]["sequence_tokens"] == 512
    # the cell's traffic and engine are ISSUE 55's, to the number
    t = cell.traffic
    assert (t["kind"], t["clients"], t["requests_per_client"], t["temperature"], t["slices"]) == ("serve-closed", 64, 4, 0.7, 10)
    assert t["prompt_tokens"] == {"shape": "uniform", "min": 256, "max": 1024} and t["output_tokens"] == {"shape": "uniform", "min": 512, "max": 2048}
    engine = dict(t["engine"])
    assert engine.pop("num_blocks") >= 10240 and engine == {
        "block_size": 16, "max_batch": 64, "decode_chunk_blocks": 1, "prefix_cache": False, "max_prompt_len": 3072,
        "max_new_tokens": 2048, "queue_depth": 128}
    mine = {m["name"]: m for m in cell.per_layer}
    assert NEW <= set(mine) and all(mine[n]["moves"] == "tpot_p50_ms" and CELL in mine[n]["workloads"] for n in NEW)
    assert {"serve_mla_device_share", "serve_mlp_device_share", "serve_moe_device_share", "serve_vocab_device_share"} <= set(mine)   # by rule: its scopes
    assert "decode_hbm_roofline" not in mine and "serve_attn_device_share" not in mine
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms", "setup_s"}


#: what this file asserts of the DOCUMENT: each takes a ``Spec``, so that
#: test_bench_rules.py can hold a document with one more cell to all of them
DOCUMENT_CHECKS = [the_configuration_keeps_every_published_width_and_states_its_cut]


def test_the_configuration_keeps_every_published_width_and_states_its_cut():
    the_configuration_keeps_every_published_width_and_states_its_cut(S.Spec())


def test_the_adapter_counts_what_the_issue_counts_and_the_programs_tree_agrees(cell):
    arch, config = model.adapter(cell), cell.config
    assert all(callable(getattr(arch, name)) for name in model.INTERFACE)
    mla = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 64 * 128 * 6144
    assert arch.attention_params(config) == mla and mla + 1536 + 512 == 90_572_800
    ffn, expert, router = 3 * 6144 * 12288, 3 * 6144 * 2048, 6144 * 768 + 768
    outside = 2 * 90_572_800 + 2 * ffn + router + 4 * 6144
    assert (ffn, expert, router, outside) == (226_492_416, 37_748_736, 4_719_360, 638_874_368)
    assert outside + 16 * expert == 1_242_854_144
    assert arch.total_params(config) == 4 * 1_242_854_144 + 2 * 16384 * 6144 + 6144 == 5_172_749_312
    assert arch.embedding_params(config) == 16384 * 6144
    # a token: both attention sublayers, both dense FFNs, the router and 12 x 16 / 768 = a quarter of an expert, four times; the head
    assert arch.matmul_params(config) == 4 * (2 * mla + 2 * ffn + 6144 * 768 + 0.25 * expert) + 16384 * 6144
    shape = arch.expert_shape(config)
    assert (shape["held"], shape["layers"], shape["shared"], shape["expected_held_picks"], shape["zero"], shape["expected_zero_picks"]) == (16, 4, 0, 0.25, 256, 4.0)
    assert arch.latent_shape(config) == {"heads": 64, "layers": 8, "kv_lora_rank": 512, "qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
                                         "v_head_dim": 128, "q_lora_rank": 1536}
    assert arch.attention_shape(config)["head_dim"] == 192
    cfg = arch.model_config(config, 5120)
    assert (cfg.shortcut_block, cfg.moe_experts, cfg.moe_zero_experts, cfg.moe_top_k, cfg.moe_experts_held, cfg.moe_router) == (True, 512, 256, 12, (0, 16), "softmax_bias")
    assert (cfg.q_latent_scale, cfg.kv_latent_scale, cfg.norm_eps, cfg.rope_theta, cfg.moe_routed_scaling) == (2.0, pytest.approx(12 ** 0.5), 1e-5, 1e7, 6.0)
    assert cfg.param_dtype == jnp.bfloat16 and cfg.ff_dim == 12288 and cfg.moe_intermediate_size == 2048
    # the program's own tree holds as many, bfloat16 but for four float32 biases (shapes only); two rows a token a block
    from determined_tpu.models.transformer import TransformerLM, kv_bytes_per_token, kv_cache_shape

    shapes = jax.tree_util.tree_leaves(jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0)))
    assert sum(x.size for x in shapes) == 5_172_749_312
    assert sum(x.size * x.dtype.itemsize for x in shapes) == 2 * 5_172_749_312 + 2 * 4 * 768
    assert kv_bytes_per_token(cfg) == 8 * 1152 and kv_cache_shape(cfg, 10240, 16) == (8, 10240, 16, 640)
    assert 8 * 10240 * 16 * 640 * 2 == 1_677_721_600


def test_the_adapter_refuses_what_the_program_does_not_run(cell):
    arch, config = model.adapter(cell), cell.config
    for key, value, match in [
        ("zero_expert_type", "constant", "zero_expert_type"), ("attention_method", "GQA", "attention_method"),
        ("mla_scale_q_lora", False, "mla_scale_q_lora"), ("mla_scale_kv_lora", False, "mla_scale_kv_lora"),
        ("rope_scaling", {"type": "yarn", "factor": 4}, "no rope_scaling"), ("first_expert_held", 500, "identity expert is held by nobody"),
        ("dtypes", {"serve_params": "int8", "kv_cache": "bfloat16", "compute": "bfloat16"}, "float32 or bfloat16"),
        ("dtypes", {"serve_params": "bfloat16", "kv_cache": "float8", "compute": "bfloat16"}, "caches in its compute dtype"),
    ]:
        with pytest.raises(ValueError, match=match):
            arch.check_as_run({**config, key: value})
    # a parent whose config lacks the fields is refused by name, with the harness's own error (exit code 3)
    from unittest import mock

    from determined_tpu.models import transformer as T

    few = [f for f in dataclasses.fields(T.TransformerConfig) if f.name not in ("shortcut_block", "moe_zero_experts")]
    with mock.patch.object(dataclasses, "fields", lambda cls: few), pytest.raises(S.SpecError, match="lacks moe_zero_experts, shortcut_block"):
        arch.check_as_run(config)


def test_the_cost_functions_that_are_there_count_this_step(cell):
    """``mla_paged_attention``, ``moe_decode_experts`` and ``mla_moe_decode_step``
    ask the adapter for shapes: eight latent rows a live token, every parameter
    once less the embedding and the experts no row reached."""
    arch, config, traffic = model.adapter(cell), cell.config, cell.traffic
    live = 64 * 1400.0
    counters = {"traced.live_kv_tokens": live, "traced.serve.moe.experts_hit": 14.0, "traced.serve.moe.held_picks": 16.0, "traced.active": 64.0}
    att = costs.find("mla_paged_attention", cell.data_dir)(config, traffic, 1, counters, arch)
    assert att == {"flops": pytest.approx(8 * live * 2 * 64 * 1088), "bytes": pytest.approx(8 * live * 1152)}
    assert att["bytes"] / 819e9 > att["flops"] / 197e12                                    # at 64 heads the rows' read bounds the kernel
    exp = costs.find("moe_decode_experts", cell.data_dir)(config, traffic, 1, counters, arch)
    assert exp["bytes"] == pytest.approx(14 * 3 * 6144 * 2048 * 2 + 16 * (2 * 6144 + 3 * 2048) * 2)
    step = costs.find("mla_moe_decode_step", cell.data_dir)(config, traffic, 1, counters, arch)
    swept = 5_172_749_312 - 16384 * 6144 - (64 - 14) * 37_748_736
    assert step["bytes"] == pytest.approx(2 * swept + 16 * (2 * 6144 + 3 * 2048) * 2 + att["bytes"], rel=1e-6)
    every_lane = arch.matmul_params(config) - 4 * 0.25 * 37_748_736
    assert step["flops"] == pytest.approx(2 * 64 * every_lane + exp["flops"] + att["flops"])
    assert step["bytes"] / 819e9 > step["flops"] / 197e12                                   # a decode step is bound by what it reads
    # ISSUE 55's count: 5.1 GB outside the experts and the head, 0.2 GB of head, ~1.0 GB of latent rows
    outside = 2 * (4 * 638_874_368 + 6144)
    assert outside / 1e9 == pytest.approx(5.11, abs=0.01) and 2 * 16384 * 6144 / 1e9 == pytest.approx(0.2, abs=0.01) and att["bytes"] / 1e9 == pytest.approx(0.826, abs=0.001)


# ---------------------------------------------------------------------------
# the readers of the two new metrics, on hand-made events
# ---------------------------------------------------------------------------


class _Traced:
    """A profiler that holds a trace: two whole decode steps on one device, between two prefills."""

    trace_dir = ""
    sync_marks_ns = [0.0]

    def data(self):
        from benchlib import trace as tr

        ms = 1e6
        step = [("%fusion.7 = bf16[64,6144] fusion(...)", 0.0, 6.0), ("%fusion.21 = f32[64,6144] fusion(...)", 6.0, 0.5),
                ("%moe_gmm.3 = bf16[64,2048] custom-call(...)", 6.5, 1.5)]
        events = [(n, (start + s) * ms, d * ms) for start in (10.0, 20.0) for n, s, d in step]
        events = [("%fusion.1 = bf16[1,256,6144] fusion(...)", 1 * ms, 2 * ms)] + events + [("%fusion.1 = bf16[1,256,6144] fusion(...)", 35 * ms, 2 * ms)]
        return tr.TraceData(devices={"d": events}, host=[(tr.SYNC_NAME, 0.0, 0.0)])


def test_the_new_metrics_read_the_counter_and_the_scope(cell):
    def span(start_ms, zero):
        return {"ph": "X", "name": "serve.decode", "ts": start_ms * 1e3, "dur": 9.5e3,
                "args": {"step": 1, "active": 64, "live_kv_tokens": 90_000, "serve.moe.held_picks": 16.0, "serve.moe.experts_hit": 14.0,
                         "serve.moe.zero_picks": zero}}

    scopes = {"serve.moe.identity": ["fusion.21"], "serve.moe.experts": ["moe_gmm.3"], "serve.mla": ["fusion.7"]}
    events = [{"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode", "scopes": scopes}},
              span(9.9, 1000.0), span(19.9, 1048.0), span(50.0, 1100.0)]
    obs = Observations(window=(0.0, 1.0), spans=[], counters={}, program_events=events, profiler=_Traced(), config=cell.config,
                       traffic=cell.traffic, chips=1, program_epoch=0.0, arch=model.adapter(cell), data_dir=cell.data_dir)
    metric = lambda name: next(m for m in cell.per_layer if m["name"] == name)  # noqa: E731
    # the median step's identity picks over 4 layers x 64 lanes; an even router reads 4.0
    assert readers.read(metric("moe_zero_picks_per_token"), obs, PEAK) == pytest.approx(1048.0 / 256)
    assert readers.read(metric("serve_moe_identity_device_share"), obs, PEAK) == pytest.approx(100 * 0.5 / 8)
    # a program without the counter or the scope (the parent commit): nothing, and nothing raised
    bare = [{**e, "args": {k: v for k, v in e["args"].items() if k != "serve.moe.zero_picks"}} for e in events]
    bare[0] = {**bare[0], "args": {"program": "jit.compile.serve.decode", "scopes": {k: v for k, v in scopes.items() if k != "serve.moe.identity"}}}
    parent = dataclasses.replace(obs, program_events=bare)
    assert all(readers.read(metric(name), parent, PEAK) is None for name in NEW)


# ---------------------------------------------------------------------------
# the share test: all shares' parts add up to the uncut layer
# ---------------------------------------------------------------------------


def test_all_shares_of_the_real_experts_add_up_to_the_uncut_block():
    """Experts 0-3 on one chip and 4-7 on the other, as the deployment shares
    a layer: each runs both attention sublayers, both dense FFNs, the router
    over all 12 outputs and the identity experts alike, and adds what ITS
    experts give.  With all that every chip computes alike counted once, the
    sum is the uncut reference's block output."""
    from determined_tpu.models.transformer import Block

    arch = model.adapter(_tiny_cell())
    whole_config = {**TINY, "n_routed_experts": 8, "first_expert_held": 0, "num_layers": 1}
    whole_cfg = dataclasses.replace(arch.model_config(whole_config, 64), attention_impl="reference", partition_params=False)
    params = arch.init_params(whole_cfg, 5)
    params["block_0"]["moe"]["router_bias"] = params["block_0"]["moe"]["router_bias"] * 2.0    # large enough to change picks
    layer = arch.reference_weights(params, whole_config)["layers"][0]
    x = jax.random.normal(jax.random.key(1), (40, 64))
    numerics = arch.numerics(whole_config)
    mla = {k: numerics[k] for k in ("nope", "latent", "rope_theta", "q_scale", "kv_scale")}
    moe = {k: numerics[k] for k in ("top_k", "scaling", "real_experts")}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda w: arch.reference.block(x, w, eps=numerics["eps"], mla=mla, moe={**moe, "first_expert": 0, "held": 8}))(layer)
    outs, branches, rests = [], [], []
    for first in (0, 4):
        cfg = dataclasses.replace(whole_cfg, moe_experts_held=(first, 4))
        block = params["block_0"]
        mine = {**block, "moe": {**block["moe"], **{k: block["moe"][k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}}}
        (out, _, _), seen = jax.jit(lambda p: Block(cfg, use_moe=True).apply({"params": p}, x[None], capture_intermediates=True))(mine)
        inter = seen["intermediates"]
        branch, u = inter["moe"]["__call__"][0][0][0], inter["ln2"]["__call__"][0][0]
        outs.append(out[0]), branches.append(branch), rests.append(out[0] - branch)
    # what every chip computes alike: the stream without the expert branch, and the identity experts' part of the branch
    np.testing.assert_allclose(rests[0], rests[1], atol=1e-5)
    nobody = {**layer, **{k: layer[k][:0] for k in ("e_gate", "e_up", "e_down")}}
    with jax.default_matmul_precision("highest"):
        identity = jax.jit(lambda w: arch.reference.experts(u, w, **moe, first_expert=0, held=0))(nobody)
    assert float(jnp.abs(identity).max()) > 1e-2 and all(float(jnp.abs(b - identity).max()) > 1e-3 for b in branches)
    total = outs[0] + (branches[1] - identity)
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert float(jnp.abs(outs[0] - want).max()) > 1e-3       # one share alone is not the layer


# ---------------------------------------------------------------------------
# the cell, end to end at a tiny size
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tests' throw-away root plus this cell at a tiny size."""
    tmp = B.throwaway_root(str(tmp_path_factory.mktemp("longcat_root")))
    shutil.copytree(os.path.join(B.BENCH, "costs"), os.path.join(tmp, "benchmark", "costs"), dirs_exist_ok=True)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        doc = json.load(f)
    configs = {"tiny-longcat": TINY}
    for k, told in NOT_THE_PROGRAMS.items():
        arch = "longcat_" + k.replace("-", "_")
        configs[f"tiny-longcat-{k}"] = dict(TINY, arch=arch)
        with open(os.path.join(tmp, "benchmark", "archs", arch + ".py"), "w") as f:
            f.write(TOLD_OTHERWISE.format(told=told))
    for name, config in configs.items():
        with open(os.path.join(tmp, "benchmark", "configs", f"{name}.json"), "w") as f:
            json.dump(config, f)
        doc["configs"].append({"name": name, "source": "none", "file": f"benchmark/configs/{name}.json", "reduced": [], "why": "test"})
    with open(os.path.join(tmp, "benchmark", "traffic", "tiny-closed-no-prefix.json"), "w") as f:
        json.dump(dict(B.TINY_TRAFFIC["tiny-closed"], engine=dict(B.TINY_TRAFFIC["tiny-closed"]["engine"], prefix_cache=False)), f)
    cells = {f"{name}.closed": name for name in configs}
    for name, config in cells.items():
        doc["workloads"].append({"name": name, "config": config, "traffic": "tiny-closed-no-prefix", "chips": 1, "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] += list(cells)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return tmp


def test_the_cell_runs_through_the_engine_and_agrees_with_its_reference(root, capsys):
    line = harness.run_cell("tiny-longcat.closed", seed=2**31 + 11, seconds=1.5, traced=True, root=root, require_tpu=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    check = next(x for x in out if x["event"] == "serve.check")
    assert check["rows"] == 17 and check["rel_rms"] < 1e-4 and check["top1_agree"] == 1.0
    values = next(x for x in out if x["event"] == "end_to_end_of_traced_run")["values"]
    assert {"tpot_p50_ms", "setup_s"} <= set(values)
    # the span- and counter-based metrics the cell lists read true for it; device metrics have nothing to read on a CPU
    assert {"serve_prefill_share", "moe_decode_experts_hit", "moe_zero_picks_per_token", "serve_step_ms"} <= set(line["metrics"])
    assert not any("roofline" in k or "device" in k for k in line["metrics"])
    # the metric's scale is the cell's (4 layers x 64 lanes); here 2 layers x at most 4 lanes pick 3 of 12 outputs, 4 of them identity
    assert 0.0 < line["metrics"]["moe_zero_picks_per_token"]["value"] * 256 <= 2 * 4 * 3
    # the engine's own account, for an operator without a trace: three counters, and the pool says four rows a token
    stats = next(x for x in out if x["event"] == "serve.window")["engine"]
    assert set(stats["step_counters"]) == {"serve.moe.held_picks", "serve.moe.experts_hit", "serve.moe.zero_picks"}
    assert stats["step_counters"]["serve.moe.zero_picks"] > 0 and stats["rows_per_token"] == 4


@pytest.mark.parametrize("told", sorted(NOT_THE_PROGRAMS))
def test_the_check_catches_a_reference_that_is_not_the_programs(root, capsys, told):
    line = harness.run_cell(f"tiny-longcat-{told}.closed", seed=5, seconds=1.0, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 10 * check["tolerance"]["rel_rms"]
