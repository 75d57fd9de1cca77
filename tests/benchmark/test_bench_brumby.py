"""The Brumby-14B configuration, its adapter, reference and cost functions:
the arithmetic the cell's numbers rest on, the readings of a small synthetic
trace, and the cell run end to end in a throw-away root on the CPU at a tiny
size (``correct: true``, and ``false`` under each control of the check: a
reference told something else than the configuration states, and a program
whose state is held in bfloat16)."""

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

CELL = "serve-brumby14b-l5-pp8-longdoc"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = {"serve_retention_device_share", "retention_state_roofline", "retention_decode_hbm_roofline"}
LAYER, PARAMS, SLOT = 330_352_896, 3_207_594_240, 34_344_960

TINY = B.tiny_form("power_retention")["config"]
TINY_TRAFFIC = {
    "kind": "serve-closed", "clients": 4, "requests_per_client": 2,
    "prompt_tokens": {"shape": "uniform", "min": 4, "max": 24}, "output_tokens": {"shape": "uniform", "min": 6, "max": 16},
    "temperature": 0.6, "slices": 4,
    "engine": B.tiny_form("power_retention")["serve_engine"],  # no prefix cache beside a state
}
TOLD_OTHERWISE = '''
from benchlib import model

_base = model.beside(__file__, "archs", "power_retention")
_told = _base.numerics
_base.numerics = lambda config: {{**_told(config), **{told!r}}}
globals().update({{k: v for k, v in vars(_base).items() if not k.startswith("__")}})
'''
#: the controls of the check that are the reference's to run: what each is told instead
NOT_THE_PROGRAMS = {
    "no-gate": {"gated": False},
    "no-normaliser": {"normalised": False},
    "degree-1": {"degree": 1},
    "no-rotary": {"rotary": False},
    "kv-int8": {"kv_int8": True},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tests' throw-away root plus this cell at a tiny size."""
    tmp = B.throwaway_root(str(tmp_path_factory.mktemp("brumby_root")))
    shutil.copytree(os.path.join(B.BENCH, "costs"), os.path.join(tmp, "benchmark", "costs"), dirs_exist_ok=True)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(tmp, "benchmark", "traffic", "tiny-longdoc.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    configs = {"tiny-brumby": TINY}
    for k, told in NOT_THE_PROGRAMS.items():
        arch = "retention_" + k.replace("-", "_")
        configs[f"tiny-brumby-{k}"] = dict(TINY, arch=arch)
        with open(os.path.join(tmp, "benchmark", "archs", arch + ".py"), "w") as f:
            f.write(TOLD_OTHERWISE.format(told=told))
    for name, config in configs.items():
        with open(os.path.join(tmp, "benchmark", "configs", f"{name}.json"), "w") as f:
            json.dump(config, f)
        doc["configs"].append({"name": name, "source": "none", "file": f"benchmark/configs/{name}.json", "reduced": [], "why": "test"})
    cells = {f"{name}.closed": name for name in configs}
    for name, config in cells.items():
        doc["workloads"].append({"name": name, "config": config, "traffic": "tiny-longdoc", "chips": 1, "why": "test"})
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
    assert [(w["config"], w["traffic"], w["chips"]) for w in doc["workloads"] if w["name"] == CELL] == [("brumby-14b-l5-pp8", "longdoc-closed", 1)]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "Brumby-14B-Base")
    entry = next(c for c in doc["configs"] if c["name"] == "brumby-14b-l5-pp8")
    assert entry["source"] == published["source_url"] == cell.config["source"]
    assert entry["reduced"] == ["num_hidden_layers"] == list(cell.config["reduced"])
    for key, value in published["config"].items():
        if key not in entry["reduced"]:
            assert cell.config[key] == value, key
    assert cell.config["num_hidden_layers"] == 5 and cell.config["arch"] == "power_retention"
    assert {"deployment", "assumed", "deviations", "dtypes", "tolerance"} <= set(cell.config)
    assert "3,207,594,240 parameters" in cell.config["deployment"] and "34,344,960 B a lane a layer" in cell.config["deployment"]
    assumed = cell.config["assumed"]
    assert {"retention_degree", "gate", "retention_gate_bias", "normaliser", "qk_norm", "rope", "state_dtype", "feature_layout", "initialiser"} <= set(assumed)
    assert all("Not run" in assumed[k] for k in ("retention_degree", "gate", "retention_gate_bias", "normaliser", "qk_norm", "rope", "state_dtype"))
    assert cell.config["dtypes"] == {"serve_params": "bfloat16", "compute": "bfloat16", "state": "float32"}
    # 2,048 and not the issue's 4,608: the harness keeps a view of every step's whole [32, 151936] logits (PERF.md section 7)
    assert cell.config["tolerance"]["serve_logits"]["sequence_tokens"] == 2048
    # the cell's traffic and engine are ISSUE 44's, to the number
    t = cell.traffic
    assert (t["kind"], t["clients"], t["requests_per_client"], t["temperature"], t["slices"]) == ("serve-closed", 32, 4, 0.6, 10)
    assert t["prompt_tokens"] == {"shape": "uniform", "min": 4096, "max": 16384} and t["output_tokens"] == {"shape": "uniform", "min": 2048, "max": 6144}
    assert t["engine"] == {"block_size": 16, "num_blocks": 57345, "max_batch": 32, "decode_chunk_blocks": 1, "prefix_cache": False,
                           "max_prompt_len": 22528, "max_new_tokens": 6144, "queue_depth": 64}
    assert 57345 == 32 * (22528 + 6144) // 16 + 1
    mine = {m["name"]: m for m in cell.per_layer}
    assert NEW <= set(mine) and all(mine[n]["moves"] == "tpot_p50_ms" and mine[n]["workloads"] == [CELL] for n in NEW)
    assert {"serve_prefill_share", "decode_device_ms", "serve_device_idle_share", "serve_mlp_device_share", "serve_vocab_device_share",
            "serve_step_sample_ms", "serve_decode_named_device_share"} <= set(mine)
    assert not {"decode_hbm_roofline", "serve_attn_device_share", "serve_kv_pool_live", "serve_mla_device_share"} & set(mine)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms", "setup_s"} and "serve_lane_occupancy" not in mine
    for n in NEW:
        assert mine[n]["reader"]["reader"] == "decode_burst_ops" and mine[n]["source"] == "device_trace", n
    assert mine["serve_retention_device_share"]["reader"]["cells"] == {"of": "serving", "scope": "serve.retention.state"}


#: what this file asserts of the DOCUMENT: each takes a ``Spec``, so that
#: test_bench_rules.py can hold a document with one more cell to all of them
DOCUMENT_CHECKS = [the_document_and_the_configuration_keep_the_contract]


def test_the_document_and_the_configuration_keep_the_contract():
    the_document_and_the_configuration_keep_the_contract(S.Spec())


def test_the_adapter_meets_the_interface_and_counts_what_the_issue_counts(cell):
    arch, config = model.adapter(cell), cell.config
    assert all(callable(getattr(arch, name)) for name in model.INTERFACE)
    attn = 2 * 5120 * 40 * 128 + 2 * 5120 * 8 * 128 + 5120 * 8 + 2 * 128
    assert attn + 3 * 5120 * 17408 + 2 * 5120 == LAYER == arch.layer_params(config)
    assert arch.total_params(config) == 5 * LAYER + 2 * 151_936 * 5120 + 5120 == PARAMS
    assert arch.embedding_params(config) == 151_936 * 5120 == 777_912_320
    assert arch.matmul_params(config) == 5 * (LAYER - 256 - 10_240) + 777_912_320
    assert arch.state_shape(config) == {"heads": 40, "kv_heads": 8, "head_dim": 128, "layers": 5, "features": 8320,
                                        "query_heads_per_kv_head": 5, "bytes_per_slot": SLOT}
    cfg = arch.model_config(config, 28672)
    assert cfg.layer_types == ("power_retention",) * 5 and cfg.qk_norm and cfg.retention_gate_bias == 6.0 and cfg.rope_theta == 1e6
    assert cfg.param_dtype == jnp.bfloat16 and (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.ff_dim) == (40, 8, 128, 17408)
    # the program's own tree holds as many, all bfloat16 (shapes only); the cache is a state pool and nothing else
    from determined_tpu.models.transformer import STATE_DTYPE, TransformerLM, state_bytes_per_slot, state_pool_shapes

    shapes = jax.tree_util.tree_leaves(jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0)))
    assert sum(x.size for x in shapes) == PARAMS and {str(x.dtype) for x in shapes} == {"bfloat16"}
    assert state_pool_shapes(cfg, 32) == ((5, 32, 8, 8320, 128), (5, 32, 8, 65, 128)) and jnp.dtype(STATE_DTYPE) == jnp.float32
    assert state_bytes_per_slot(cfg) == SLOT and 32 * 5 * SLOT == 5_495_193_600
    assert (2 * PARAMS + 32 * 5 * SLOT) / 1e9 == pytest.approx(11.91, abs=0.01)           # 6.42 GB + 5.50 GB: 74 % of 16 GB
    # a parent whose program lacks the layer type or the fields is refused by name, with the harness's own error (exit code 3)
    from unittest import mock

    from determined_tpu.models import transformer as T

    few = [f for f in dataclasses.fields(T.TransformerConfig) if f.name != "qk_norm"]
    with mock.patch.object(dataclasses, "fields", lambda cls: few), pytest.raises(S.SpecError, match="lacks qk_norm"):
        arch.check_as_run(config)
    with mock.patch.object(T, "LAYER_TYPES", ("full_attention", "sliding_attention")), pytest.raises(S.SpecError, match="lacks the layer type"):
        arch.check_as_run(config)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        arch.check_as_run(dict(config, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="degree 2"):
        arch.check_as_run(dict(config, retention_degree=4))
    with pytest.raises(ValueError, match="float32 state"):
        arch.check_as_run(dict(config, dtypes=dict(config["dtypes"], state="bfloat16")))


def test_cost_functions_count_the_state_once_and_the_whole_step(cell):
    arch, config, traffic = model.adapter(cell), cell.config, cell.traffic
    held = 32 * 5 * SLOT
    counters = {"traced.serve.state.bytes": float(held), "traced.serve.state.live_lanes": 32.0, "traced.active": 32.0}
    state = costs.find("retention_state", cell.data_dir)(config, traffic, 1, counters, arch)
    assert state == {"flops": pytest.approx(13 * held / 4), "bytes": float(held)}
    assert state["flops"] / state["bytes"] == pytest.approx(3.25)                           # against a ridge of 240: the read bounds it
    assert state["bytes"] / 819e9 * 1e3 == pytest.approx(6.71, abs=0.01)                    # ms a step, read once
    with pytest.raises(KeyError):                                                           # a program that counts no such thing
        costs.find("retention_state", cell.data_dir)(config, traffic, 1, {"traced.active": 32.0}, arch)
    step = costs.find("retention_decode_step", cell.data_dir)(config, traffic, 1, counters, arch)
    swept = PARAMS - 777_912_320
    assert step["bytes"] == pytest.approx(2 * swept + held) and 2 * swept == 4_859_363_840
    assert step["flops"] == pytest.approx(2 * 32 * arch.matmul_params(config) + 13 * held / 4)
    assert step["bytes"] / 819e9 > step["flops"] / 197e12                                   # a decode step is bound by what it reads
    # half of the lanes idle: the state's half, every weight all the same
    half = {**counters, "traced.serve.state.bytes": held / 2, "traced.active": 16.0}
    assert costs.find("retention_decode_step", cell.data_dir)(config, traffic, 1, half, arch)["bytes"] == pytest.approx(2 * swept + held / 2)


# ---------------------------------------------------------------------------
# the readers, on a small synthetic trace
# ---------------------------------------------------------------------------


class _Traced:
    """A prefill and two whole decode steps on one device, a third cut by the
    trace's end; 20 ms of operations a step and 6 ms idle between two."""

    trace_dir = ""
    sync_marks_ns = [0.0]

    def data(self):
        from benchlib import trace as tr

        ms = 1e6
        step = [("%fusion.3 = bf16[32,48,128] fusion(...)", 0.0, 1.0), ("%retention_decode.5 = f32[32,8,8,128] custom-call(...)", 1.0, 12.0),
                ("%fusion.8 = bf16[32,5120] fusion(...)", 13.0, 1.0), ("%fusion.9 = f32[33,151936] fusion(...)", 14.0, 6.0)]
        events = [("%fusion.3 = bf16[1,256,5120] fusion(...)", 1 * ms, 5 * ms)]
        for start in (10.0, 36.0, 62.0):
            events += [(n, (start + s) * ms, d * ms) for n, s, d in step]
        events = [e for e in events if e[1] + e[2] <= 80 * ms]
        return tr.TraceData(devices={"d": sorted(events, key=lambda e: e[1])}, host=[(tr.SYNC_NAME, 0.0, 0.0)])


def _decode_span(start_ms, lanes):
    return {"ph": "X", "name": "serve.decode", "ts": start_ms * 1e3, "dur": 20.5e3,
            "args": {"step": 1, "active": lanes, "live_kv_tokens": 400_000, "max_context": 21000,
                     "serve.state.live_lanes": float(lanes), "serve.state.bytes": float(lanes * 5 * SLOT)}}


def test_the_new_metrics_read_the_scopes_and_the_counters(cell):
    scopes = {"serve.retention.qkvg": ["fusion.3"], "serve.retention.state": ["retention_decode.5"], "serve.retention.out": ["fusion.8"],
              "serve.head": ["fusion.9"]}
    events = [
        {"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode", "scopes": scopes}},
        _decode_span(9.9, 32), _decode_span(35.9, 30), _decode_span(61.9, 32),              # the third is cut: not counted
    ]
    obs = Observations(window=(0.0, 1.0), spans=[], counters={}, program_events=events, profiler=_Traced(), config=cell.config,
                       traffic=cell.traffic, chips=1, program_epoch=0.0, arch=model.adapter(cell), data_dir=cell.data_dir)
    metric = lambda name: next(m for m in cell.per_layer if m["name"] == name)  # noqa: E731
    assert readers.read(metric("serve_retention_device_share"), obs, PEAK) == pytest.approx(100 * 14 / 20)
    held = 31 * 5 * SLOT                                                                    # the two whole steps' mean
    assert readers.read(metric("retention_state_roofline"), obs, PEAK) == pytest.approx(100 * held / 819e9 / 12e-3)
    swept = 2 * (PARAMS - 777_912_320)
    assert readers.read(metric("retention_decode_hbm_roofline"), obs, PEAK) == pytest.approx(100 * (swept + held) / 819e9 / 20e-3)
    assert all(readers.read(metric(n), obs, PEAK) < 100.0 for n in NEW)
    # the parent commit: no such scopes, no such counters: nothing, and nothing raised
    bare = [dict(e, args={k: v for k, v in e["args"].items() if not k.startswith("serve.state")}) for e in events if e["name"] != "jit.scopes"]
    bare.append({"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode", "scopes": {"serve.head": ["fusion.9"]}}})
    obs_bare = dataclasses.replace(obs, program_events=bare)
    for name in sorted(NEW):
        assert readers.read(metric(name), obs_bare, PEAK) is None, name


# ---------------------------------------------------------------------------
# the cell, end to end at a tiny size
# ---------------------------------------------------------------------------


def test_the_cell_runs_through_the_engine_and_agrees_with_its_reference(root, capsys):
    line = harness.run_cell("tiny-brumby.closed", seed=2**31 + 44, seconds=1.5, traced=True, root=root, require_tpu=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    check = next(x for x in out if x["event"] == "serve.check")
    assert check["rows"] == 33 and check["rel_rms"] < 1e-4 and check["top1_agree"] == 1.0
    values = next(x for x in out if x["event"] == "end_to_end_of_traced_run")["values"]
    # not serve_tokens_per_s: on the chip it spreads over half its bound by how many prefills a window holds (PERF.md section 4)
    assert {"tpot_p50_ms", "setup_s"} <= set(values)
    # the span- and counter-based metrics the cell lists read true for it; device metrics have nothing to read on a CPU
    assert {"serve_prefill_share", "serve_step_ms", "serve_queue_wait_ms"} <= set(line["metrics"]) and "serve_lane_occupancy" not in line["metrics"]
    assert not any("roofline" in k or "device" in k for k in line["metrics"])
    # the engine's own account, for an operator without a trace
    stats = next(x for x in out if x["event"] == "serve.window")["engine"]
    assert set(stats["step_counters"]) == {"serve.state.live_lanes", "serve.state.bytes"}
    assert stats["state"]["slots"] == 4 and stats["block_ids_address_nothing"] is True and stats["kv_cache"]["used"] == 0
    assert stats["state"]["bytes_per_slot"] == 2 * 2 * (9 * 16 * 16 + 9 * 16) * 4


@pytest.mark.parametrize("told", sorted(NOT_THE_PROGRAMS))
def test_the_check_catches_a_reference_that_is_not_the_programs(root, capsys, told):
    line = harness.run_cell(f"tiny-brumby-{told}.closed", seed=5, seconds=1.0, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 10 * check["tolerance"]["rel_rms"]


def test_the_check_catches_a_state_held_in_bfloat16(root, capsys, monkeypatch):
    from determined_tpu.models import transformer as T

    monkeypatch.setattr(T, "STATE_DTYPE", jnp.bfloat16)
    line = harness.run_cell("tiny-brumby.closed", seed=6, seconds=1.0, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 3 * check["tolerance"]["rel_rms"]
