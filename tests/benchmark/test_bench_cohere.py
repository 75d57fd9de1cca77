"""The Command A+ configuration, its adapter, reference and cost functions:
the arithmetic the cell's numbers rest on, the readings of a small synthetic
trace, and the cell run end to end in a throw-away root on the CPU at a tiny
size (``correct: true``, and ``false`` against a reference that is told
something else than the configuration states)."""

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

CELL = "serve-cmdaplus-l4-ep8-ragreason"
DSV3 = "serve-dsv3-l5-ep16-reason"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = {"window_decode_attn_roofline", "swa_moe_decode_hbm_roofline", "serve_window_attn_device_share",
       "serve_full_attn_device_share", "serve_window_tokens_per_lane", "swa_moe_decode_experts_roofline"}
#: the device metrics among them: a step is the device's own burst (readers/decode_burst_ops.py)
BY_BURST = NEW - {"serve_window_tokens_per_lane"}

# layers W W W F with a window of 8; 8 heads of 16 over 2 KV heads; 16 experts, top-4, experts 8..11 held, 2 shared;
# 32 prefilled, 32 decoded: the decode crosses the window (8) and the ring's end (8 + 40)
TINY = B.tiny_form("cohere2_moe")["config"]
TINY_TRAFFIC = {
    "kind": "serve-closed", "clients": 4, "requests_per_client": 2,
    "prompt_tokens": {"shape": "uniform", "min": 4, "max": 24}, "output_tokens": {"shape": "uniform", "min": 6, "max": 16},
    "temperature": 0.3, "slices": 4,
    "engine": B.tiny_form("cohere2_moe")["serve_engine"],  # no prefix cache beside window layers
}
TOLD_OTHERWISE = '''
from benchlib import model

_base = model.beside(__file__, "archs", "cohere2_moe")
_told = _base.numerics
_base.numerics = lambda config: {{**_told(config), **{told!r}}}
globals().update({{k: v for k, v in vars(_base).items() if not k.startswith("__")}})
'''
#: references that are not the program's: what each is told instead
NOT_THE_PROGRAMS = {
    "wider-window": {"window": 12},                                                   # off by one block
    "rotary-everywhere": {"layer_types": ("sliding_attention",) * 4},                  # the full layer rotated and clipped
    "no-logit-scale": {"logit_scale": 1.0},
    "other-experts": {"first_expert": 4},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tests' throw-away root plus this cell at a tiny size."""
    tmp = B.throwaway_root(str(tmp_path_factory.mktemp("cohere_root")))
    shutil.copytree(os.path.join(B.BENCH, "costs"), os.path.join(tmp, "benchmark", "costs"), dirs_exist_ok=True)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(tmp, "benchmark", "traffic", "tiny-ragreason.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    configs = {"tiny-cohere": TINY}
    for k, told in NOT_THE_PROGRAMS.items():
        arch = "cohere_" + k.replace("-", "_")
        configs[f"tiny-cohere-{k}"] = dict(TINY, arch=arch)
        with open(os.path.join(tmp, "benchmark", "archs", arch + ".py"), "w") as f:
            f.write(TOLD_OTHERWISE.format(told=told))
    for name, config in configs.items():
        with open(os.path.join(tmp, "benchmark", "configs", f"{name}.json"), "w") as f:
            json.dump(config, f)
        doc["configs"].append({"name": name, "source": "none", "file": f"benchmark/configs/{name}.json", "reduced": [], "why": "test"})
    cells = {f"{name}.closed": name for name in configs}
    for name, config in cells.items():
        doc["workloads"].append({"name": name, "config": config, "traffic": "tiny-ragreason", "chips": 1, "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] += list(cells)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return tmp


@pytest.fixture(scope="module")
def cell():
    return S.Spec().cell(CELL)


def mine_of(spec, cell_name, metric):
    return next(m for m in spec.cell(cell_name).per_layer if m["name"] == metric)


# ---------------------------------------------------------------------------
# the configuration as published, and the arithmetic of its cut
# ---------------------------------------------------------------------------


def the_document_and_the_configuration_keep_the_contract(spec):
    doc, cell = spec.doc, spec.cell(CELL)
    assert S.check_document(doc) == []
    assert [(w["config"], w["traffic"], w["chips"]) for w in doc["workloads"] if w["name"] == CELL] == [("command-a-plus-l4-ep8", "ragreason-closed", 1)]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "command-a-plus-05-2026")
    entry = next(c for c in doc["configs"] if c["name"] == "command-a-plus-l4-ep8")
    assert entry["source"] == published["source_url"] == cell.config["source"]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"] == list(cell.config["reduced"])
    for key, value in published["config"].items():
        if key not in entry["reduced"]:
            assert cell.config[key] == value, key
    assert [cell.config[k] for k in ("num_hidden_layers", "num_experts", "vocab_size")] == [4, 16, 32768]
    assert cell.config["layer_types"] == published["config"]["layer_types"][:4] and cell.config["num_experts_published"] == 128
    assert {"deployment", "assumed", "deviations", "dtypes", "tolerance"} <= set(cell.config)
    assert "vision_tower" in cell.config["deviations"] and "4,733,292,544 parameters" in cell.config["deployment"]
    assert {"shared_expert_combination_strategy", "sliding_window", "initialiser", "temperature"} <= set(cell.config["assumed"])
    assert cell.config["dtypes"] == {"serve_params": "bfloat16", "kv_cache": "bfloat16", "compute": "bfloat16"}
    assert cell.config["tolerance"]["serve_logits"]["sequence_tokens"] == 4608
    # the cell's traffic and engine are ISSUE 38's, to the number
    t = cell.traffic
    assert (t["kind"], t["clients"], t["requests_per_client"], t["temperature"], t["slices"]) == ("serve-closed", 32, 4, 0.3, 10)
    assert t["prompt_tokens"] == {"shape": "uniform", "min": 2048, "max": 8192} and t["output_tokens"] == {"shape": "uniform", "min": 2048, "max": 6144}
    assert t["engine"] == {"block_size": 16, "num_blocks": 24576, "max_batch": 32, "decode_chunk_blocks": 1, "prefix_cache": False,
                           "max_prompt_len": 14336, "max_new_tokens": 6144, "queue_depth": 64}
    mine = {m["name"]: m for m in cell.per_layer}
    assert NEW <= set(mine) and all(mine[n]["moves"] == "tpot_p50_ms" and CELL in mine[n]["workloads"] for n in NEW)
    assert {"serve_prefill_share", "decode_device_ms", "serve_device_idle_share", "serve_moe_device_share",
            "moe_decode_experts_hit"} <= set(mine)
    assert not {"decode_hbm_roofline", "serve_mla_device_share"} & set(mine)
    assert {"tpot_p50_ms", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    for n in BY_BURST:
        assert mine[n]["reader"]["reader"] == "decode_burst_ops" and mine[n]["source"] == "device_trace", n
    assert mine["swa_moe_decode_experts_roofline"]["reader"]["args"] == mine_of(spec, DSV3, "moe_decode_experts_roofline")["reader"]["args"]
    # the scale of the tokens-a-lane metric is this cell's: 3 window layers x 32 lanes
    with open(os.path.join(cell.data_dir, "metrics", "serve_window_tokens_per_lane.json")) as f:
        assert json.load(f)["args"]["scale"] == pytest.approx(1 / (3 * 32))


#: what this file asserts of the DOCUMENT: each takes a ``Spec``, so that
#: test_bench_rules.py can hold a document with one more cell to all of them
DOCUMENT_CHECKS = [the_document_and_the_configuration_keep_the_contract]


def test_the_document_and_the_configuration_keep_the_contract():
    the_document_and_the_configuration_keep_the_contract(S.Spec())


def test_the_adapter_meets_the_interface_and_counts_what_the_issue_counts(cell):
    arch, config = model.adapter(cell), cell.config
    assert all(callable(getattr(arch, name)) for name in model.INTERFACE)
    attention = 4096 * 16384 + 2 * 4096 * 1024 + 16384 * 4096
    expert = 3 * 4096 * 4096
    layer = attention + 4096 + 4096 * 128 + 4 * expert + 16 * expert
    assert (attention, expert, layer) == (142_606_336, 50_331_648, 1_149_767_680) and arch.attention_params(config) == attention
    assert arch.total_params(config) == 4 * layer + 32768 * 4096 + 4096 == 4_733_292_544
    assert arch.embedding_params(config) == 32768 * 4096
    # a token: attention, the router, four shared experts and ONE routed expert (8 x 16 / 128) a layer, and the tied head
    assert arch.matmul_params(config) == 4 * (attention + 4096 * 128 + 5 * expert) + 32768 * 4096
    assert arch.expert_shape(config) == {"d_model": 4096, "d_ff": 4096, "held": 16, "layers": 4, "shared": 4, "expected_held_picks": 1.0}
    assert arch.window_shape(config) == {"window": 4096, "window_layers": 3, "full_layers": 1, "heads": 128, "kv_heads": 8, "head_dim": 128}
    assert arch.rope_parameters(config) == {"sliding_attention": {"rope_type": "default", "rope_theta": 50000.0},
                                            "full_attention": {"rope_type": "none"}}
    cfg = arch.model_config(config, 20480)
    assert (cfg.norm, cfg.norm_eps, cfg.parallel_block, cfg.tie_embeddings, cfg.logit_scale) == ("layernorm", 1e-5, True, True, 1.0)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_experts_held, cfg.moe_router, cfg.moe_shared_experts, cfg.moe_shared_combine) == (
        128, 8, (0, 16), "sigmoid", 4, "mean")
    assert cfg.layer_types == ("sliding_attention",) * 3 + ("full_attention",) and cfg.sliding_window == 4096
    assert cfg.param_dtype == jnp.bfloat16 and (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (128, 8, 128)
    # the program's own tree holds as many, all bfloat16 (shapes only); the cache is of two kinds
    from determined_tpu.models.transformer import TransformerLM, kv_cache_shape, window_store_shape

    shapes = jax.tree_util.tree_leaves(jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0)))
    assert sum(x.size for x in shapes) == 4_733_292_544 and {str(x.dtype) for x in shapes} == {"bfloat16"}
    pool, ring = kv_cache_shape(cfg, 24576, 16), window_store_shape(cfg, 32, 16, 256)
    assert pool == (1, 24576, 16, 1024) and ring == (3, 32 * 272, 16, 1024) and 272 * 16 == 4352
    two_kinds = 2 * 2 * (24576 * 16 * 1024 + 3 * 32 * 4352 * 1024)
    assert two_kinds / 1e9 == pytest.approx(3.32, abs=0.01)                          # 1.61 GB + 1.71 GB
    assert 2 * 2 * 4 * 24576 * 16 * 1024 / 1e9 == pytest.approx(6.44, abs=0.01)      # a uniform pool of four layers
    assert (2 * 4_733_292_544 + two_kinds) / 1e9 == pytest.approx(12.79, abs=0.01)
    # a parent whose config lacks the fields is refused by name, with the harness's own error (exit code 3)
    from unittest import mock

    from determined_tpu.models import transformer as T

    few = [f for f in dataclasses.fields(T.TransformerConfig) if f.name != "parallel_block"]
    with mock.patch.object(dataclasses, "fields", lambda cls: few), pytest.raises(S.SpecError, match="lacks parallel_block"):
        arch.check_as_run(config)
    with pytest.raises(ValueError, match="use_parallel_block"):
        arch.check_as_run(dict(config, use_parallel_block=False))


def test_cost_functions_count_the_window_the_experts_hit_and_the_whole_step(cell):
    arch, config, traffic = model.adapter(cell), cell.config, cell.traffic
    # 32 lanes at ~7.2 k tokens, 26 of them past the window
    full_tokens, window_tokens = 32 * 7200.0 * 1, (26 * 4096 + 6 * 2500.0) * 3
    counters = {"traced.serve.kv.full_tokens": full_tokens, "traced.serve.kv.window_tokens": window_tokens,
                "traced.serve.moe.experts_hit": 55.6, "traced.serve.moe.held_picks": 256.0, "traced.active": 32.0}
    win = costs.find("window_paged_attention", cell.data_dir)(config, traffic, 1, counters, arch)
    assert win == {"flops": pytest.approx(window_tokens * 4 * 128 * 128), "bytes": pytest.approx(window_tokens * 4096)}
    assert win["flops"] / win["bytes"] == pytest.approx(16.0)                              # against a ridge of 240: the read bounds it
    with pytest.raises(KeyError):                                                          # a program that counts no such thing
        costs.find("window_paged_attention", cell.data_dir)(config, traffic, 1, {"traced.active": 32.0}, arch)
    exp = costs.find("moe_decode_experts", cell.data_dir)(config, traffic, 1, counters, arch)
    assert exp["bytes"] == pytest.approx(55.6 * 3 * 4096 * 4096 * 2 + 256 * (2 * 4096 + 3 * 4096) * 2)
    step = costs.find("swa_moe_decode_step", cell.data_dir)(config, traffic, 1, counters, arch)
    swept = 4_733_292_544 - (64 - 55.6) * 50_331_648
    assert step["bytes"] == pytest.approx(2 * swept + 256 * 5 * 4096 * 2 + (full_tokens + window_tokens) * 4096, rel=1e-6)
    every_lane = arch.matmul_params(config) - 4 * 1.0 * 50_331_648
    assert step["flops"] == pytest.approx(2 * 32 * every_lane + exp["flops"] + (full_tokens + window_tokens) * 4 * 128 * 128)
    assert step["bytes"] / 819e9 > step["flops"] / 197e12                                  # a decode step is bound by what it reads
    # ISSUE 38's arithmetic: 0.94 GB of full-layer and ~1.5 GB of window-layer K and V; 3.8 GB if the window layers read the context
    assert full_tokens * 4096 / 1e9 == pytest.approx(0.94, abs=0.01) and window_tokens * 4096 / 1e9 == pytest.approx(1.49, abs=0.02)
    assert 4 * full_tokens * 4096 / 1e9 == pytest.approx(3.8, abs=0.05)
    # the dense decoder's cost would read every layer at the context, every held expert, and an embedding beside the head
    dense = costs.find("decode_step", cell.data_dir)
    assert dense is not None


# ---------------------------------------------------------------------------
# the readers, on a small synthetic trace
# ---------------------------------------------------------------------------


class _Traced:
    """A prefill and two whole decode steps on one device, a third cut by the
    trace's end; 8 ms of operations a step and 2 ms idle between two.  ``early_ms``:
    the device's line runs that far ahead of the host's annotations, as the cell's
    real traces do (PERF.md section 7); ``prefill_at``: a prefill's fusion that
    runs into the second step."""

    trace_dir = ""
    sync_marks_ns = [0.0]

    def __init__(self, early_ms=0.0, prefill_at=None):
        self.early_ms, self.prefill_at = early_ms, prefill_at

    def data(self):
        from benchlib import trace as tr

        ms = 1e6
        step = [("%fusion.7 = bf16[32,4096] fusion(...)", 0.0, 2.0), ("%paged_window_attention.5 = f32[32,128,128] custom-call(...)", 2.0, 3.0),
                ("%paged_decode_attention.6 = f32[32,128,128] custom-call(...)", 5.0, 1.0), ("%fusion.9 = f32[33,32768] fusion(...)", 6.0, 2.0)]
        events = [("%fusion.7 = bf16[1,256,4096] fusion(...)", 1 * ms, 5 * ms)]
        for start in (10.0, 20.0, 30.0):
            events += [(n, (start + s) * ms, d * ms) for n, s, d in step]
        events = [e for e in events if e[1] + e[2] <= 36 * ms]
        if self.prefill_at is not None:
            events.append(("%fusion.7 = bf16[1,256,4096] fusion(...)", self.prefill_at * ms, 1.9 * ms))
        events = [(n, s - self.early_ms * ms, d) for n, s, d in events]
        return tr.TraceData(devices={"d": sorted(events, key=lambda e: e[1])}, host=[(tr.SYNC_NAME, 0.0, 0.0)])


def _decode_span(start_ms, window_tokens):
    return {"ph": "X", "name": "serve.decode", "ts": start_ms * 1e3, "dur": 8.5e3,
            "args": {"step": 1, "active": 32, "live_kv_tokens": 230_000, "max_context": 9000, "serve.kv.full_tokens": 230_000.0,
                     "serve.kv.window_tokens": window_tokens, "serve.moe.held_picks": 250.0, "serve.moe.experts_hit": 56.0}}


def test_the_new_metrics_read_the_two_scopes_and_the_counters(cell):
    scopes = {"serve.attn.attend": ["paged_window_attention.5", "paged_decode_attention.6"],
              "serve.attn.window": ["paged_window_attention.5"], "serve.attn.full": ["paged_decode_attention.6"]}
    events = [
        {"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode", "scopes": scopes}},
        _decode_span(9.9, 360_000.0), _decode_span(19.9, 372_000.0), _decode_span(29.9, 999_000.0),   # the third is cut: not counted
    ]
    obs = Observations(window=(0.0, 1.0), spans=[], counters={}, program_events=events, profiler=_Traced(), config=cell.config,
                       traffic=cell.traffic, chips=1, program_epoch=0.0, arch=model.adapter(cell), data_dir=cell.data_dir)
    metric = lambda name: next(m for m in cell.per_layer if m["name"] == name)  # noqa: E731
    assert readers.read(metric("serve_window_attn_device_share"), obs, PEAK) == pytest.approx(100 * 3 / 8)
    assert readers.read(metric("serve_full_attn_device_share"), obs, PEAK) == pytest.approx(100 * 1 / 8)
    assert readers.read(metric("window_decode_attn_roofline"), obs, PEAK) == pytest.approx(100 * 366_000 * 4096 / 819e9 / 3e-3)
    arch = model.adapter(cell)
    traced = {"traced.serve.kv.full_tokens": 230_000.0, "traced.serve.kv.window_tokens": 366_000.0, "traced.serve.moe.experts_hit": 56.0,
              "traced.serve.moe.held_picks": 250.0, "traced.active": 32.0}
    need = costs.find("swa_moe_decode_step", cell.data_dir)(cell.config, cell.traffic, 1, traced, arch)
    assert readers.read(metric("swa_moe_decode_hbm_roofline"), obs, PEAK) == pytest.approx(100 * need["bytes"] / 819e9 / 8e-3)
    need = costs.find("moe_decode_experts", cell.data_dir)(cell.config, cell.traffic, 1, traced, arch)
    scopes["serve.moe.experts"] = ["fusion.7"]
    assert readers.read(metric("swa_moe_decode_experts_roofline"), obs, PEAK) == pytest.approx(100 * need["bytes"] / 819e9 / 2e-3)
    # the device's line 0.9 ms ahead of the host's annotations: a step's first operation starts before its span.  The burst
    # reader reads what it read; decode_step_ops, which counts what starts inside the span, loses the head (2 of a step's 8 ms)
    early = dataclasses.replace(obs, profiler=_Traced(early_ms=0.9))
    for name in sorted(BY_BURST):
        assert readers.read(metric(name), early, PEAK) == pytest.approx(readers.read(metric(name), obs, PEAK)), name
    by_span = dict(metric("serve_window_attn_device_share"), reader=dict(metric("serve_window_attn_device_share")["reader"], reader="decode_step_ops"))
    assert readers.read(by_span, obs, PEAK) == pytest.approx(100 * 3 / 8) and readers.read(by_span, early, PEAK) == pytest.approx(100 * 3 / 6)
    by_span = dict(metric("swa_moe_decode_experts_roofline"), reader=dict(metric("swa_moe_decode_experts_roofline")["reader"], reader="decode_step_ops"))
    assert readers.read(by_span, early, PEAK) is None                                       # its one instruction started before the span
    # a prefill that runs into a step makes one burst of two programs, whose fusion.N collide: neither step is counted
    merged = dataclasses.replace(obs, profiler=_Traced(prefill_at=18.05))
    assert all(readers.read(metric(name), merged, PEAK) is None for name in BY_BURST)
    # the median step's tokens a lane's window layer reads (all three spans END inside the window)
    assert readers.read(metric("serve_window_tokens_per_lane"), obs, PEAK) == pytest.approx(372_000.0 / 96)
    # the parent commit: no such scopes, no such counters: nothing, and nothing raised
    bare = [dict(e, args={k: v for k, v in e["args"].items() if not k.startswith("serve.kv")}) for e in events if e["name"] != "jit.scopes"]
    bare.append({"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode",
                                                                        "scopes": {"serve.attn.attend": ["paged_decode_attention.6"]}}})
    obs_bare = dataclasses.replace(obs, program_events=bare)
    for name in sorted(NEW):
        assert readers.read(metric(name), obs_bare, PEAK) is None, name


# ---------------------------------------------------------------------------
# the cell, end to end at a tiny size
# ---------------------------------------------------------------------------


def test_the_cell_runs_through_the_engine_and_agrees_with_its_reference(root, capsys):
    line = harness.run_cell("tiny-cohere.closed", seed=2**31 + 38, seconds=1.5, traced=True, root=root, require_tpu=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    check = next(x for x in out if x["event"] == "serve.check")
    assert check["rows"] == 33 and check["rel_rms"] < 1e-4 and check["top1_agree"] == 1.0
    values = next(x for x in out if x["event"] == "end_to_end_of_traced_run")["values"]
    assert {"tpot_p50_ms", "setup_s"} <= set(values)
    # the span- and counter-based metrics the cell lists read true for it; device metrics have nothing to read on a CPU
    assert {"serve_prefill_share", "moe_decode_experts_hit", "serve_window_tokens_per_lane"} <= set(line["metrics"])
    assert not any("roofline" in k or "device" in k for k in line["metrics"])
    assert 0.0 < line["metrics"]["serve_window_tokens_per_lane"]["value"] * 96 / (3 * 4) <= 8.0     # a lane reads its window at most
    # the engine's own account, for an operator without a trace
    stats = next(x for x in out if x["event"] == "serve.window")["engine"]
    assert set(stats["step_counters"]) == {"serve.kv.full_tokens", "serve.kv.window_tokens", "serve.moe.held_picks", "serve.moe.experts_hit"}
    assert stats["window_store"]["ring_tokens"] == 8 + 40


@pytest.mark.parametrize("told", sorted(NOT_THE_PROGRAMS))
def test_the_check_catches_a_reference_that_is_not_the_programs(root, capsys, told):
    line = harness.run_cell(f"tiny-cohere-{told}.closed", seed=5, seconds=1.0, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 10 * check["tolerance"]["rel_rms"]
