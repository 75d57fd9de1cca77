"""The DeepSeek-V3 configuration, its adapter, reference, cost functions and
reader: the arithmetic the cell's numbers rest on, the readings of a small
synthetic trace, and the cell run end to end in a throw-away root on the CPU
at a tiny size (``correct: true``, and ``false`` against a reference that is
told something else than the configuration states)."""

import dataclasses
import json
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

import bench_testlib as B
from benchlib import costs, harness, model, readers, spec as S
from benchlib.observe import Observations

CELL = "serve-dsv3-l5-ep16-reason"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# 3 layers, the first dense; 16 experts in 4 groups of which 2 stay, top-4, experts 8..11 held; 4 heads of [16 | 8]
TINY_DSV3 = B.tiny_form("deepseek_mla_moe")["config"]
#: an adapter of the test's own, whose reference is told something else than the configuration states
TOLD_OTHERWISE = '''
from benchlib import model

_base = model.beside(__file__, "archs", "deepseek_mla_moe")
_told = _base.numerics
_base.numerics = lambda config: {{**_told(config), **{told!r}}}
globals().update({{k: v for k, v in vars(_base).items() if not k.startswith("__")}})
'''
#: references that are not the program's: what each is told instead
NOT_THE_PROGRAMS = {
    "no-group-limit": {"topk_group": 4},                                          # every group stays
    "no-scaling": {"scaling": 1.0},
    "no-mscale": {"rope_scaling": dict(TINY_DSV3["rope_scaling"], mscale_all_dim=0)},   # softmax scale without m^2
    "plain-rotary": {"rope_scaling": dict(TINY_DSV3["rope_scaling"], factor=1.0000001)},
    "other-experts": {"first_expert": 4},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tests' throw-away root plus this cell at a tiny size."""
    tmp = B.throwaway_root(str(tmp_path_factory.mktemp("dsv3_root")))
    shutil.copytree(os.path.join(B.BENCH, "costs"), os.path.join(tmp, "benchmark", "costs"), dirs_exist_ok=True)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        doc = json.load(f)
    configs = {"tiny-dsv3": TINY_DSV3}
    for k, told in NOT_THE_PROGRAMS.items():
        arch = "dsv3_" + k.replace("-", "_")
        configs[f"tiny-dsv3-{k}"] = dict(TINY_DSV3, arch=arch)
        with open(os.path.join(tmp, "benchmark", "archs", arch + ".py"), "w") as f:
            f.write(TOLD_OTHERWISE.format(told=told))
    for name, config in configs.items():
        with open(os.path.join(tmp, "benchmark", "configs", f"{name}.json"), "w") as f:
            json.dump(config, f)
        doc["configs"].append({"name": name, "source": "none", "file": f"benchmark/configs/{name}.json", "reduced": [], "why": "test"})
    cells = {f"{name}.closed": name for name in configs}
    for name, config in cells.items():
        doc["workloads"].append({"name": name, "config": config, "traffic": "tiny-closed", "chips": 1, "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] += list(cells)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return tmp


@pytest.fixture(scope="module")
def cell():
    return S.Spec().cell(CELL)


# ---------------------------------------------------------------------------
# the configuration as published, and the arithmetic of its cut
# ---------------------------------------------------------------------------


def the_document_and_the_configuration_keep_the_contract(spec):
    doc, cell = spec.doc, spec.cell(CELL)
    assert S.check_document(doc) == []
    # what the cell needs of the document and no more: it is there, once, on one chip, under
    # its configuration and traffic (no count of cells, no place in the list: a later cell needs no edit here)
    assert [(w["config"], w["traffic"], w["chips"]) for w in doc["workloads"] if w["name"] == CELL] == [("deepseek-v3-l5-ep16", "reason-closed", 1)]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "DeepSeek-V3")
    entry = next(c for c in doc["configs"] if c["name"] == "deepseek-v3-l5-ep16")
    assert entry["source"] == published["source_url"] == cell.config["source"]
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"] == list(cell.config["reduced"])
    for key, value in published["config"].items():
        if key not in entry["reduced"]:
            assert cell.config[key] == value, key
    assert [cell.config[k] for k in entry["reduced"]] == [5, 1, 16, 16160] and cell.config["n_routed_experts_published"] == 256
    assert {"deployment", "assumed", "deviations", "dtypes", "tolerance"} <= set(cell.config)
    assert {"torch_dtype", "num_nextn_predict_layers"} <= set(cell.config["deviations"])     # FP8 and MTP are stated
    assert cell.config["dtypes"] == {"serve_params": "bfloat16", "kv_cache": "bfloat16", "compute": "bfloat16"}
    assert cell.config["tolerance"]["serve_logits"]["sequence_tokens"] == 512
    # the cell's traffic and engine are ISSUE 34's, to the number
    t = cell.traffic
    assert (t["kind"], t["clients"], t["temperature"]) == ("serve-closed", 64, 0.6)
    assert t["prompt_tokens"] == {"shape": "uniform", "min": 256, "max": 1024} and t["output_tokens"] == {"shape": "uniform", "min": 1024, "max": 3072}
    assert t["engine"] == {"block_size": 16, "num_blocks": 24576, "max_batch": 64, "decode_chunk_blocks": 1, "prefix_cache": True,
                           "max_prompt_len": 4096, "max_new_tokens": 3072, "queue_depth": 128}
    new = {"mla_decode_attn_roofline", "moe_decode_experts_roofline", "mla_moe_decode_hbm_roofline", "serve_mla_device_share",
           "serve_moe_device_share", "moe_decode_experts_hit"}
    mine = {m["name"]: m for m in cell.per_layer}
    assert new <= set(mine) and all(mine[n]["moves"] == "tpot_p50_ms" and CELL in mine[n]["workloads"] for n in new)
    assert "decode_hbm_roofline" not in mine  # a dense GQA decoder's cost: it would read past 105 % here
    # the engine's span metrics and the two set-up metrics are every serving cell's by rule (test_bench_span_readers.py)
    assert {"serve_step_ms", "serve_step_sample_ms", "serve_logits_d2h_ms", "setup_program_load_s"} <= set(mine)
    assert {"serve_lane_occupancy", "serve_kv_pool_live", "serve_device_idle_share", "decode_device_ms", "serve_prefill_share"} <= set(mine)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}


#: what this file asserts of the DOCUMENT: each takes a ``Spec``, so that
#: test_bench_rules.py can hold a document with one more cell to all of them
DOCUMENT_CHECKS = [the_document_and_the_configuration_keep_the_contract]


def test_the_document_and_the_configuration_keep_the_contract():
    the_document_and_the_configuration_keep_the_contract(S.Spec())


def test_the_adapter_meets_the_interface_and_counts_what_the_issue_counts(cell):
    arch, config = model.adapter(cell), cell.config
    assert all(callable(getattr(arch, name)) for name in model.INTERFACE)
    attention = 7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256 + 16384 * 7168
    assert arch.attention_params(config) + 1536 + 512 == attention + 2048 == 187_107_328
    expert = 3 * 7168 * 2048
    sparse = 187_107_328 + 14_336 + 1_835_264 + expert + 16 * expert
    dense = 187_107_328 + 14_336 + 3 * 7168 * 18432
    assert (expert, sparse, dense) == (44_040_192, 937_640_192, 583_483_392)
    assert arch.total_params(config) == dense + 4 * sparse + 2 * 16160 * 7168 + 7168 == 4_565_721_088
    assert arch.embedding_params(config) == 16160 * 7168
    # a token: attention everywhere, the dense MLP once, router + shared + half an expert (8 x 16 / 256) four times, the head
    active = 5 * attention + 3 * 7168 * 18432 + 4 * (7168 * 256 + 1.5 * expert) + 16160 * 7168
    assert arch.matmul_params(config) == active
    assert arch.softmax_scale(config) == pytest.approx(0.135234, abs=1e-6)
    assert arch.rope_parameters(config)["full_attention"]["attention_factor"] == 1.0
    cfg = arch.model_config(config, 7168)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (512, 1536, 128, 64, 128)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_experts_held, cfg.moe_n_group, cfg.moe_topk_group, cfg.dense_prefix) == (256, 8, (0, 16), 8, 4, 1)
    assert cfg.param_dtype == jnp.bfloat16 and cfg.ff_dim == 18432 and cfg.moe_intermediate_size == 2048
    # the program's own tree holds as many, bfloat16 but for four float32 biases (shapes only)
    from determined_tpu.models.transformer import TransformerLM, kv_bytes_per_token, kv_cache_shape

    shapes = jax.tree_util.tree_leaves(jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0)))
    assert sum(x.size for x in shapes) == 4_565_721_088
    assert sum(x.size * x.dtype.itemsize for x in shapes) == 2 * 4_565_721_088 + 2 * 4 * 256
    assert kv_bytes_per_token(cfg) == 5 * 1152 and kv_cache_shape(cfg, 24576, 16) == (5, 24576, 16, 640)
    # a parent whose config lacks the fields is refused by name, with the harness's own error (exit code 3)
    from unittest import mock

    from determined_tpu.models import transformer as T

    few = [f for f in dataclasses.fields(T.TransformerConfig) if f.name != "kv_lora_rank"]
    with mock.patch.object(dataclasses, "fields", lambda cls: few), pytest.raises(S.SpecError, match="lacks kv_lora_rank"):
        arch.check_as_run(config)


def test_cost_functions_count_rows_experts_and_the_whole_step(cell):
    arch, config, traffic = model.adapter(cell), cell.config, cell.traffic
    live = 64 * 1750.0
    counters = {"traced.live_kv_tokens": live, "traced.serve.moe.experts_hit": 55.6, "traced.serve.moe.held_picks": 128.0, "traced.active": 64.0}
    att = costs.find("mla_paged_attention", cell.data_dir)(config, traffic, 1, counters, arch)
    assert att == {"flops": pytest.approx(5 * live * 2 * 128 * 1088), "bytes": pytest.approx(5 * live * 1152)}
    assert att["flops"] / att["bytes"] == pytest.approx(241.8, abs=0.1)                    # the v5e's ridge: 197e12 / 819e9 = 240.5
    # without the traced steps' own count: the window's mean, from the runner's counters
    window = costs.find("mla_paged_attention", cell.data_dir)(config, traffic, 1, {"serve.live_kv_tokens": 4 * live, "serve.decode_steps": 4.0}, arch)
    assert window == att
    exp = costs.find("moe_decode_experts", cell.data_dir)(config, traffic, 1, counters, arch)
    assert exp["bytes"] == pytest.approx(55.6 * 3 * 7168 * 2048 * 2 + 128 * (2 * 7168 + 3 * 2048) * 2)
    assert exp["flops"] == pytest.approx(128 * 6 * 7168 * 2048)
    assert 3 * 7168 * 2048 * 2 / 819e9 == pytest.approx(107.5e-6, rel=0.01)               # an expert's read: ISSUE 34's 107 us
    step = costs.find("mla_moe_decode_step", cell.data_dir)(config, traffic, 1, counters, arch)
    swept = 4_565_721_088 - 16160 * 7168 - (64 - 55.6) * 44_040_192
    assert step["bytes"] == pytest.approx(2 * swept + 128 * (2 * 7168 + 3 * 2048) * 2 + att["bytes"], rel=1e-6)
    every_lane = arch.matmul_params(config) - 4 * 0.5 * 44_040_192
    assert step["flops"] == pytest.approx(2 * 64 * every_lane + exp["flops"] + att["flops"])
    assert step["bytes"] / 819e9 > step["flops"] / 197e12                                   # a decode step is bound by what it reads
    # all 64 held experts hit: ISSUE 34's 8.9 GB of weights a step, and 0.65 GB of latent rows
    full = costs.find("mla_moe_decode_step", cell.data_dir)(config, traffic, 1, {**counters, "traced.serve.moe.experts_hit": 64.0}, arch)
    assert (full["bytes"] - att["bytes"]) / 1e9 == pytest.approx(8.9, abs=0.05) and att["bytes"] / 1e9 == pytest.approx(0.645, abs=0.005)
    # the dense decoder's cost would count K and V a head: 57 times the latent row
    assert 2 * 128 * 128 * 2 / 1152 == pytest.approx(56.9, abs=0.1)


# ---------------------------------------------------------------------------
# the reader, on a small synthetic trace
# ---------------------------------------------------------------------------


class _Traced:
    """A profiler that holds a trace: one prefill and two whole decode steps
    on one device, the third step cut by the trace's end.  The prefill's
    ``fusion.7`` is not the decode step's ``fusion.7``."""

    trace_dir = ""
    sync_marks_ns = [0.0]

    def data(self):
        from benchlib import trace as tr

        ms = 1e6
        step = [("%fusion.7 = bf16[64,7168] fusion(...)", 0.0, 2.0), ("%paged_latent_attention.5 = f32[64,128,512] custom-call(...)", 2.0, 1.0),
                ("%moe_gmm.3 = bf16[1024,2048] custom-call(...)", 3.0, 4.0), ("%fusion.9 = f32[65,16160] fusion(...)", 7.0, 1.0)]
        events = [("%fusion.7 = bf16[1,4096,7168] fusion(...)", 1 * ms, 5 * ms)]
        for start in (10.0, 20.0, 30.0):
            events += [(n, (start + s) * ms, d * ms) for n, s, d in step]
        events = [e for e in events if e[1] + e[2] <= 36 * ms]
        return tr.TraceData(devices={"d": events}, host=[(tr.SYNC_NAME, 0.0, 0.0)])


def _decode_span(start_ms, live, hit):
    return {"ph": "X", "name": "serve.decode", "ts": start_ms * 1e3, "dur": 9.5e3,
            "args": {"step": 1, "active": 64, "live_kv_tokens": live, "max_context": 3000,
                     "serve.moe.held_picks": 120.0, "serve.moe.experts_hit": hit}}


def test_the_reader_times_the_decode_steps_the_trace_holds_whole(cell):
    scopes = {"serve.mla": ["fusion.7", "paged_latent_attention.5"], "serve.mla.attend": ["paged_latent_attention.5"],
              "serve.moe.experts": ["moe_gmm.3"], "serve.moe.route": ["fusion.11"]}
    events = [
        {"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode", "scopes": scopes}},
        {"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.prefill", "scopes": {"serve.mla": ["fusion.9"]}}},
        {"ph": "X", "name": "serve.prefill", "ts": 0.5e3, "dur": 6e3, "args": {}},
        _decode_span(9.9, 100_000, 50.0), _decode_span(19.9, 120_000, 60.0),
        _decode_span(29.9, 500_000, 64.0),       # its end lies past the trace's last operation: not counted
    ]
    obs = Observations(window=(0.0, 1.0), spans=[], counters={}, program_events=events, profiler=_Traced(), config=cell.config,
                       traffic=cell.traffic, chips=1, program_epoch=0.0, arch=model.adapter(cell), data_dir=cell.data_dir)
    metric = lambda name: next(m for m in cell.per_layer if m["name"] == name)  # noqa: E731
    # a step is 8 ms of operations: 3 under serve.mla (the prefill's fusion.7 is not counted, nor its fusion.9), 4 in the experts
    assert readers.read(metric("serve_mla_device_share"), obs, PEAK) == pytest.approx(100 * 3 / 8)
    assert readers.read(metric("serve_moe_device_share"), obs, PEAK) == pytest.approx(100 * 4 / 8)
    arch = model.adapter(cell)
    traced = {"traced.live_kv_tokens": 110_000.0, "traced.serve.moe.experts_hit": 55.0, "traced.serve.moe.held_picks": 120.0, "traced.active": 64.0}
    need = costs.find("mla_paged_attention", cell.data_dir)(cell.config, cell.traffic, 1, traced, arch)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert readers.read(metric("mla_decode_attn_roofline"), obs, PEAK) == pytest.approx(100 * least / 1e-3)
    need = costs.find("moe_decode_experts", cell.data_dir)(cell.config, cell.traffic, 1, traced, arch)
    assert readers.read(metric("moe_decode_experts_roofline"), obs, PEAK) == pytest.approx(100 * need["bytes"] / 819e9 / 4e-3)
    need = costs.find("mla_moe_decode_step", cell.data_dir)(cell.config, cell.traffic, 1, traced, arch)
    assert readers.read(metric("mla_moe_decode_hbm_roofline"), obs, PEAK) == pytest.approx(100 * need["bytes"] / 819e9 / 8e-3)
    # the counter's own metric reads the window's spans: the median step's experts hit, an expert layer
    assert readers.read(metric("moe_decode_experts_hit"), dataclasses.replace(obs, window=(0.0, 1.0)), PEAK) == pytest.approx(60.0 / 4)
    # a program that sends no scopes or whose spans carry no counters (the parent commit): nothing, and nothing raised
    bare = [e for e in events if e["name"] != "jit.scopes"]
    for e in bare:
        e["args"] = {k: v for k, v in e["args"].items() if not k.startswith("serve.moe")}
    obs_bare = dataclasses.replace(obs, program_events=bare)
    for name in ("serve_mla_device_share", "serve_moe_device_share", "mla_decode_attn_roofline", "moe_decode_experts_roofline",
                 "mla_moe_decode_hbm_roofline", "moe_decode_experts_hit"):
        assert readers.read(metric(name), obs_bare, PEAK) is None, name
    # no trace at all: nothing
    assert readers.read(metric("mla_decode_attn_roofline"), dataclasses.replace(obs, profiler=None), PEAK) is None


# ---------------------------------------------------------------------------
# the cell, end to end at a tiny size
# ---------------------------------------------------------------------------


def test_the_cell_runs_through_the_engine_and_agrees_with_its_reference(root, capsys):
    line = harness.run_cell("tiny-dsv3.closed", seed=2**31 + 11, seconds=1.5, traced=True, root=root, require_tpu=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    check = next(x for x in out if x["event"] == "serve.check")
    assert check["rows"] == 17 and check["rel_rms"] < 1e-4 and check["top1_agree"] == 1.0
    values = next(x for x in out if x["event"] == "end_to_end_of_traced_run")["values"]
    assert {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"} <= set(values)
    # the span- and counter-based metrics the cell lists read true for it; device metrics have nothing to read on a CPU
    assert {"serve_lane_occupancy", "serve_kv_pool_live", "serve_prefill_share", "moe_decode_experts_hit"} <= set(line["metrics"])
    assert not any("roofline" in k or "device" in k for k in line["metrics"])
    assert 0.0 <= line["metrics"]["moe_decode_experts_hit"]["value"] <= 4.0
    # the engine's own account, for an operator without a trace
    stats = next(x for x in out if x["event"] == "serve.window")["engine"]
    assert set(stats["step_counters"]) == {"serve.moe.held_picks", "serve.moe.experts_hit"}
    assert stats["step_counters"]["serve.moe.held_picks"] >= stats["step_counters"]["serve.moe.experts_hit"] > 0


@pytest.mark.parametrize("told", sorted(NOT_THE_PROGRAMS))
def test_the_check_catches_a_reference_that_is_not_the_programs(root, capsys, told):
    line = harness.run_cell(f"tiny-dsv3-{told}.closed", seed=5, seconds=1.0, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 10 * check["tolerance"]["rel_rms"]
