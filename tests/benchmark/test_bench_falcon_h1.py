"""The Falcon-H1-34B configuration, its adapter, reference and cost functions:
the arithmetic the cell's numbers rest on, the readings of a small synthetic
trace, and the cell run end to end in a throw-away root on the CPU at a tiny
size (``correct: true``, and ``false`` under each control of the check: a
reference told something else than the configuration states, and a program
whose state is held in bfloat16 or whose convolution tail is dropped at a
chunk's edge or between the walk and the decode step)."""

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

CELL = "serve-falconh1-l6-pp12-chat"
CONFIG = "falcon-h1-34b-l6-pp12"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = {"serve_ssm_device_share", "ssm_state_roofline", "hybrid_decode_hbm_roofline"}
LAYER, PARAMS, SLOT, TABLE = 430_120_032, 5_254_594_112, 4_194_304, 1_336_934_400

TINY = B.tiny_form("falcon_h1")["config"]
TINY_TRAFFIC = {
    "kind": "serve-closed", "clients": 4, "requests_per_client": 2,
    "prompt_tokens": {"shape": "uniform", "min": 4, "max": 24}, "output_tokens": {"shape": "uniform", "min": 6, "max": 16},
    "temperature": 0.7, "slices": 4,
    "engine": B.tiny_form("falcon_h1")["serve_engine"],  # no prefix cache beside a state
}
TOLD_OTHERWISE = '''
from benchlib import model

_base = model.beside(__file__, "archs", "falcon_h1")
_told = _base.numerics
_base.numerics = lambda config: {{**_told(config), **{told!r}}}
globals().update({{k: v for k, v in vars(_base).items() if not k.startswith("__")}})
'''
#: the controls of the check that are the reference's to run: what each is told instead
NOT_THE_PROGRAMS = {
    "no-skip": {"skip": False},
    "norm-over-all": {"norm_groups": 1},
    "group-0-for-all": {"shared_group": True},
    "no-key-multiplier": {"key_multiplier": 1.0},
    "no-ssm-out-multiplier": {"ssm_out_multiplier": 1.0},
    "no-attention": {"attention": False},
    "no-ssm": {"ssm": False},
    "no-mlp": {"mlp": False},
}


# -- the controls of the check that are the program's: each breaks ONE thing (the chip's runs use these too) ----


def state_in_bfloat16(monkeypatch):
    from determined_tpu.models import transformer as T

    monkeypatch.setattr(T, "STATE_DTYPE", jnp.bfloat16)


def tail_dropped_at_a_chunks_edge(monkeypatch):
    """Every chunk of the walk starts from an empty tail: the convolution sees
    zeros before the chunk's first token."""
    from determined_tpu.models import cache_kinds as K

    (tail_leaf,) = K.SSM_SLOT.leaves[1:]

    def walk(cfg, cache, lanes, chunk_tokens):
        def at_chunk(rows):
            mix = K._ssm_walk(cfg, rows)
            # this layer's tails in the walk's own lanes, and no other: the layers before it have written theirs
            return lambda p, x, h, cache, j: mix(p, x, h, {**cache, tail_leaf: cache[tail_leaf].at[j, lanes].set(0)}, j)

        return at_chunk

    kinds = tuple(dataclasses.replace(k, walk=walk) if k is K.SSM_SLOT else k for k in K.CACHE_KINDS)
    monkeypatch.setattr(K, "CACHE_KINDS", kinds)


def tail_not_handed_to_the_decode_step(monkeypatch):
    """The walk's tails are forgotten when it returns: the first decode steps
    convolve over zeros."""
    from determined_tpu.models import cache_kinds as K
    from determined_tpu.serve import engine as E

    (tail_leaf,) = K.SSM_SLOT.leaves[1:]
    prefill_from = E.DecodeKernels._prefill_from

    def forgetful(self, *args):
        out = prefill_from(self, *args)
        self.cache = {**self.cache, tail_leaf: jnp.zeros_like(self.cache[tail_leaf])}
        return out

    monkeypatch.setattr(E.DecodeKernels, "_prefill_from", forgetful)


THE_PROGRAMS = {
    "state-bfloat16": state_in_bfloat16,
    "tail-dropped-at-chunk-edge": tail_dropped_at_a_chunks_edge,
    "tail-not-handed-to-decode": tail_not_handed_to_the_decode_step,
}


def told_otherwise(root, name, told):
    """An adapter file in ``root`` whose reference is told ``told`` instead of what the configuration states."""
    arch = "falcon_h1_" + name.replace("-", "_")
    with open(os.path.join(root, "benchmark", "archs", arch + ".py"), "w") as f:
        f.write(TOLD_OTHERWISE.format(told=told))
    return arch


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tests' throw-away root plus this cell at a tiny size."""
    tmp = B.throwaway_root(str(tmp_path_factory.mktemp("falcon_root")))
    shutil.copytree(os.path.join(B.BENCH, "costs"), os.path.join(tmp, "benchmark", "costs"), dirs_exist_ok=True)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(tmp, "benchmark", "traffic", "tiny-chat.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    configs = {"tiny-falcon": TINY}
    for k, told in NOT_THE_PROGRAMS.items():
        configs[f"tiny-falcon-{k}"] = dict(TINY, arch=told_otherwise(tmp, k, told))
    for name, config in configs.items():
        with open(os.path.join(tmp, "benchmark", "configs", f"{name}.json"), "w") as f:
            json.dump(config, f)
        doc["configs"].append({"name": name, "source": "none", "file": f"benchmark/configs/{name}.json", "reduced": [], "why": "test"})
    cells = {f"{name}.closed": name for name in configs}
    for name, config in cells.items():
        doc["workloads"].append({"name": name, "config": config, "traffic": "tiny-chat", "chips": 1, "why": "test"})
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
    assert [(w["config"], w["traffic"], w["chips"]) for w in doc["workloads"] if w["name"] == CELL] == [(CONFIG, "chat-closed", 1)]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "Falcon-H1-34B-Instruct")
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == published["source_url"] == cell.config["source"]
    assert entry["reduced"] == ["num_hidden_layers"] == list(cell.config["reduced"])
    for key, value in published["config"].items():                                     # every multiplier is as published
        if key not in entry["reduced"]:
            assert cell.config[key] == value, key
    assert cell.config["num_hidden_layers"] == 6 and cell.config["arch"] == "falcon_h1"
    assert {"deployment", "assumed", "deviations", "dtypes", "tolerance"} <= set(cell.config)
    assert "5,254,594,112 parameters" in cell.config["deployment"] and "4,194,304 B a layer" in cell.config["deployment"]
    assumed = cell.config["assumed"]
    assert {"gated_norm", "state_dtype", "conv_tail", "time_step", "initialiser", "temperature"} <= set(assumed)
    assert all("Not run" in assumed[k] for k in ("gated_norm", "state_dtype", "conv_tail", "initialiser"))
    assert cell.config["dtypes"] == {"serve_params": "bfloat16", "compute": "bfloat16", "state": "float32"}
    # the check compares the last prefilled row and the decoded ones: only a row within the convolution's reach of a
    # chunk's edge shows a tail dropped there (at 700 prefilled the control came out correct on the chip: the file's `why`)
    from determined_tpu.serve.config import ServeConfig
    prefilled = cell.config["tolerance"]["serve_logits"]["sequence_tokens"] // 2
    assert 0 < prefilled % ServeConfig(**cell.traffic["engine"]).prefill_chunk < cell.config["mamba_d_conv"]
    # the cell's traffic and engine are ISSUE 48's, to the number
    t = cell.traffic
    assert (t["kind"], t["clients"], t["requests_per_client"], t["temperature"], t["slices"]) == ("serve-closed", 64, 8, 0.7, 10)
    assert t["prompt_tokens"] == {"shape": "lognormal", "median": 256, "sigma": 0.8, "min": 32, "max": 1536}
    assert t["output_tokens"] == {"shape": "lognormal", "median": 256, "sigma": 0.6, "min": 32, "max": 512}
    assert t["engine"] == {"block_size": 16, "num_blocks": 8193, "max_batch": 64, "decode_chunk_blocks": 1, "prefix_cache": False,
                           "max_prompt_len": 2048, "max_new_tokens": 512, "queue_depth": 128}
    assert 8193 == 64 * (1536 + 512) // 16 + 1                                          # 64 worst-case requests and the scratch block
    mine = {m["name"]: m for m in cell.per_layer}
    assert NEW <= set(mine) and all(mine[n]["moves"] == "tpot_p50_ms" and mine[n]["workloads"] == [CELL] for n in NEW)
    assert {"serve_prefill_share", "decode_device_ms", "serve_device_idle_share", "serve_attn_device_share", "serve_mlp_device_share",
            "serve_vocab_device_share", "serve_step_sample_ms", "serve_decode_named_device_share"} <= set(mine)
    assert not {"decode_hbm_roofline", "serve_retention_device_share", "serve_mla_device_share", "retention_state_roofline"} & set(mine)
    # not serve_tokens_per_s: one of its three sets of six seeds spread by 1.73 %, over half its bound of 3 % (PERF.md section 4)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms", "setup_s"} and not {"serve_lane_occupancy", "serve_kv_pool_live"} & set(mine)
    for n in NEW:
        assert mine[n]["reader"]["reader"] == "decode_burst_ops" and mine[n]["source"] == "device_trace", n
    assert mine["serve_ssm_device_share"]["reader"]["cells"] == {"of": "serving", "scope": "serve.ssm.state"}


#: what this file asserts of the DOCUMENT: each takes a ``Spec``, so that
#: test_bench_rules.py can hold a document with one more cell to all of them
DOCUMENT_CHECKS = [the_document_and_the_configuration_keep_the_contract]


def test_the_document_and_the_configuration_keep_the_contract():
    the_document_and_the_configuration_keep_the_contract(S.Spec())


def test_the_adapter_meets_the_interface_and_counts_what_the_issue_counts(cell):
    arch, config = model.adapter(cell), cell.config
    assert all(callable(getattr(arch, name)) for name in model.INTERFACE)
    attn = 2 * 5120 * 20 * 128 + 2 * 5120 * 4 * 128
    mixer = arch.mixer_params(config)
    assert attn == 31_457_280 and mixer == {"w_in": 47_349_760, "conv": 25_600, "scalars": 96, "norm": 4_096, "w_out": 20_971_520}
    assert sum(mixer.values()) == 68_351_072 and 3 * 5120 * 21504 == 330_301_440
    assert attn + 68_351_072 + 330_301_440 + 2 * 5120 == LAYER == arch.layer_params(config)
    assert arch.total_params(config) == 6 * LAYER + 2 * TABLE + 5120 == PARAMS and 6 * LAYER == 2_580_720_192
    assert arch.embedding_params(config) == 261_120 * 5120 == TABLE
    assert arch.matmul_params(config) == 6 * (LAYER - 25_600 - 96 - 4_096 - 10_240) + TABLE
    assert arch.ssm_shape(config) == {"heads": 32, "head_dim": 128, "d_state": 256, "groups": 2, "conv": 4, "channels": 5120,
                                      "layers": 6, "bytes_per_slot": SLOT}
    cfg = arch.model_config(config, 2560)
    assert cfg.layer_types == ("attention_mamba2",) * 6 and cfg.rope_theta == 1e11 and cfg.norm_eps == 1e-5
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.ff_dim) == (20, 4, 128, 21504) and cfg.param_dtype == jnp.bfloat16
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv, cfg.ssm_channels) == (32, 128, 256, 2, 4, 5120)
    # every scalar is the published one
    assert (cfg.embedding_multiplier, cfg.key_multiplier, cfg.attention_in_multiplier, cfg.attention_out_multiplier) == (
        5.656854249492381, 0.011048543456039804, 1.0, 0.0375)
    assert (cfg.ssm_in_multiplier, cfg.ssm_out_multiplier, cfg.logit_scale) == (0.25, 0.08838834764831845, 0.0078125)
    assert cfg.ssm_multipliers == tuple(config["ssm_multipliers"]) and cfg.mlp_multipliers == tuple(config["mlp_multipliers"])
    # the program's own tree holds as many, all bfloat16 (shapes only); the cache is K and V in blocks AND a state and a tail a lane
    from determined_tpu.models.cache_kinds import PAGED_KV, SSM_SLOT, cache_kinds
    from determined_tpu.models.transformer import STATE_DTYPE, TransformerLM, kv_cache_shape, ssm_bytes_per_slot, ssm_pool_shapes

    shapes = jax.tree_util.tree_leaves(jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0)))
    assert sum(x.size for x in shapes) == PARAMS and {str(x.dtype) for x in shapes} == {"bfloat16"}
    assert cache_kinds(cfg) == (PAGED_KV, SSM_SLOT) and jnp.dtype(STATE_DTYPE) == jnp.float32
    assert ssm_pool_shapes(cfg, 64) == ((6, 65, 32, 128, 256), (6, 64, 3, 5120)) and ssm_bytes_per_slot(cfg) == SLOT
    assert kv_cache_shape(cfg, 8193, 16) == (6, 8193, 16, 512) and 2 * 6 * 8193 * 16 * 512 * 2 == 1_610_809_344
    held = 2 * PARAMS + 6 * 65 * SLOT + 6 * 64 * 3 * 5120 * 2 + 1_610_809_344
    assert held / 1e9 == pytest.approx(13.77, abs=0.01) and 0.6 < held / 2**34 < 0.92     # 80 % of 16 GiB
    # a parent whose program lacks the layer type or the fields is refused by name, with the harness's own error (exit code 3)
    from unittest import mock

    from determined_tpu.models import transformer as T

    few = [f for f in dataclasses.fields(T.TransformerConfig) if f.name != "ssm_heads"]
    with mock.patch.object(dataclasses, "fields", lambda cls: few), pytest.raises(S.SpecError, match="lacks ssm_heads"):
        arch.check_as_run(config)
    with mock.patch.object(T, "LAYER_TYPES", ("full_attention", "sliding_attention", "power_retention")), pytest.raises(S.SpecError, match="lacks the layer type"):
        arch.check_as_run(config)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        arch.check_as_run(dict(config, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        arch.check_as_run(dict(config, mamba_norm_before_gate=True))
    with pytest.raises(ValueError, match="float32 state"):
        arch.check_as_run(dict(config, dtypes=dict(config["dtypes"], state="bfloat16")))
    # a leaf behind one of muP's scalars is drawn over that scalar, the five segments of W_in each over its own
    scales = arch.init_scales(cfg)
    assert scales["wk"] == pytest.approx(1 / 0.011048543456039804) and scales["lm_head"] == 128.0 and scales["embed"] == pytest.approx(1 / 5.656854249492381)
    assert scales["w_in"].shape == (9248,) and scales["w_in"][0] == pytest.approx(1 / (0.25 * 0.3535533905932738))
    assert scales["w_in"][4096] == pytest.approx(16.0) and scales["w_in"][8192] == pytest.approx(1 / (0.25 * 0.1767766952966369))
    assert scales["w_in"][8704] == pytest.approx(8.0) and scales["w_in"][-1] == scales["w_in"][0]
    # every head is drawn to remember 333 to 53,333 tokens (the file's assumed.initialiser (a)), whatever the seed
    for seed in (0, 3_000_000_001):
        drawn = arch.slow_heads(jax.random.key(seed), 32, jnp.float32)
        memory = 1.0 / (jnp.exp(drawn["A_log"]) * jax.nn.softplus(drawn["dt_bias"]))
        assert drawn["A_log"].shape == drawn["dt_bias"].shape == (32,) and 333 <= float(memory.min()) and float(memory.max()) <= 53_334


def test_cost_functions_count_the_state_twice_and_the_whole_step(cell):
    arch, config, traffic = model.adapter(cell), cell.config, cell.traffic
    held = 64 * 6 * SLOT
    counters = {"traced.serve.ssm.bytes": float(held), "traced.serve.ssm.live_lanes": 64.0, "traced.active": 64.0, "traced.live_kv_tokens": 38_400.0}
    state = costs.find("ssm_state", cell.data_dir)(config, traffic, 1, counters, arch)
    assert held == 1_610_612_736 and state == {"flops": pytest.approx(5 * held / 4), "bytes": 2.0 * held}
    assert state["flops"] / state["bytes"] == pytest.approx(0.625)                         # against a ridge of 240: the bytes bound it
    assert state["bytes"] / 819e9 * 1e3 == pytest.approx(3.93, abs=0.01)                    # ms a step, read once and written once
    with pytest.raises(KeyError):                                                           # a program that counts no such thing
        costs.find("ssm_state", cell.data_dir)(config, traffic, 1, {"traced.active": 64.0}, arch)
    step = costs.find("hybrid_decode_step", cell.data_dir)(config, traffic, 1, counters, arch)
    swept, kv = PARAMS - TABLE, 38_400 * 6 * 2 * 4 * 128 * 2
    assert 2 * swept == 7_835_319_424 and kv == 471_859_200
    assert step["bytes"] == pytest.approx(2 * swept + 2 * held + kv)
    assert step["flops"] == pytest.approx(2 * 64 * arch.matmul_params(config) + 5 * held / 4 + 4 * 20 * 128 * 38_400 * 6)
    assert step["bytes"] / 819e9 > step["flops"] / 197e12                                   # a decode step is bound by what it moves
    assert step["bytes"] / 819e9 * 1e3 == pytest.approx(14.08, abs=0.01)                    # ISSUE 48's 9.6 + 3.9 + 0.6 ms
    # half of the lanes idle: the state's half, every weight all the same
    half = {**counters, "traced.serve.ssm.bytes": held / 2, "traced.active": 32.0}
    assert costs.find("hybrid_decode_step", cell.data_dir)(config, traffic, 1, half, arch)["bytes"] == pytest.approx(2 * swept + held + kv)


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
        step = [("%fusion.3 = bf16[64,9248] fusion(...)", 0.0, 2.0), ("%ssm_decode.5 = f32[64,32,128] custom-call(...)", 2.0, 6.0),
                ("%fusion.8 = bf16[64,5120] fusion(...)", 8.0, 1.0), ("%fusion.9 = f32[65,261120] fusion(...)", 9.0, 11.0)]
        events = [("%fusion.3 = bf16[1,256,5120] fusion(...)", 1 * ms, 5 * ms)]
        for start in (10.0, 36.0, 62.0):
            events += [(n, (start + s) * ms, d * ms) for n, s, d in step]
        events = [e for e in events if e[1] + e[2] <= 80 * ms]
        return tr.TraceData(devices={"d": sorted(events, key=lambda e: e[1])}, host=[(tr.SYNC_NAME, 0.0, 0.0)])


def _decode_span(start_ms, lanes):
    return {"ph": "X", "name": "serve.decode", "ts": start_ms * 1e3, "dur": 20.5e3,
            "args": {"step": 1, "active": lanes, "live_kv_tokens": 600 * lanes, "max_context": 2000,
                     "serve.ssm.live_lanes": float(lanes), "serve.ssm.bytes": float(lanes * 6 * SLOT)}}


def test_the_new_metrics_read_the_scopes_and_the_counters(cell):
    scopes = {"serve.ssm.in": ["fusion.3"], "serve.ssm.state": ["ssm_decode.5"], "serve.ssm.out": ["fusion.8"], "serve.head": ["fusion.9"]}
    events = [
        {"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode", "scopes": scopes}},
        _decode_span(9.9, 64), _decode_span(35.9, 60), _decode_span(61.9, 64),              # the third is cut: not counted
    ]
    obs = Observations(window=(0.0, 1.0), spans=[], counters={}, program_events=events, profiler=_Traced(), config=cell.config,
                       traffic=cell.traffic, chips=1, program_epoch=0.0, arch=model.adapter(cell), data_dir=cell.data_dir)
    metric = lambda name: next(m for m in cell.per_layer if m["name"] == name)  # noqa: E731
    assert readers.read(metric("serve_ssm_device_share"), obs, PEAK) == pytest.approx(100 * 9 / 20)
    held = 62 * 6 * SLOT                                                                    # the two whole steps' mean
    assert readers.read(metric("ssm_state_roofline"), obs, PEAK) == pytest.approx(100 * 2 * held / 819e9 / 6e-3)
    moved = 2 * (PARAMS - TABLE) + 2 * held + 600 * 62 * 6 * 2048
    assert readers.read(metric("hybrid_decode_hbm_roofline"), obs, PEAK) == pytest.approx(100 * moved / 819e9 / 20e-3)
    assert all(readers.read(metric(n), obs, PEAK) < 100.0 for n in NEW)
    # the parent commit: no such scopes, no such counters: nothing, and nothing raised
    bare = [dict(e, args={k: v for k, v in e["args"].items() if not k.startswith("serve.ssm")}) for e in events if e["name"] != "jit.scopes"]
    bare.append({"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode", "scopes": {"serve.head": ["fusion.9"]}}})
    obs_bare = dataclasses.replace(obs, program_events=bare)
    for name in sorted(NEW):
        assert readers.read(metric(name), obs_bare, PEAK) is None, name


# ---------------------------------------------------------------------------
# the cell, end to end at a tiny size
# ---------------------------------------------------------------------------


def test_the_cell_runs_through_the_engine_and_agrees_with_its_reference(root, capsys):
    line = harness.run_cell("tiny-falcon.closed", seed=2**31 + 48, seconds=1.5, traced=True, root=root, require_tpu=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    check = next(x for x in out if x["event"] == "serve.check")
    # 300 prefilled (a chunk of 256 and 44 of the second: the state and the tail carried, 212 padded rows advancing neither), 300 decoded
    assert check["rows"] == 301 and check["rel_rms"] < 1e-4 and check["top1_agree"] == 1.0
    values = next(x for x in out if x["event"] == "end_to_end_of_traced_run")["values"]
    assert {"tpot_p50_ms", "setup_s"} <= set(values)
    # the span- and counter-based metrics the cell lists read true for it; device metrics have nothing to read on a CPU
    assert {"serve_prefill_share", "serve_step_ms", "serve_queue_wait_ms"} <= set(line["metrics"]) and "serve_lane_occupancy" not in line["metrics"]
    assert not any("roofline" in k or "device" in k for k in line["metrics"])
    # the engine's own account, for an operator without a trace
    stats = next(x for x in out if x["event"] == "serve.window")["engine"]
    assert set(stats["step_counters"]) == {"serve.ssm.live_lanes", "serve.ssm.bytes"}
    assert stats["ssm"] == {"slots": 4, "live": stats["ssm"]["live"], "bytes_per_slot": 2 * 4 * 16 * 8 * 4}
    assert "block_ids_address_nothing" not in stats and stats["kv_cache"]["peak"] > 0 and stats["attn_products"] == "block_diagonal"


@pytest.mark.parametrize("told", sorted(NOT_THE_PROGRAMS))
def test_the_check_catches_a_reference_that_is_not_the_programs(root, capsys, told):
    line = harness.run_cell(f"tiny-falcon-{told}.closed", seed=5, seconds=0.5, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 10 * check["tolerance"]["rel_rms"]


@pytest.mark.parametrize("broken", sorted(THE_PROGRAMS))
def test_the_check_catches_a_program_that_is_not_the_references(root, capsys, monkeypatch, broken):
    THE_PROGRAMS[broken](monkeypatch)
    line = harness.run_cell("tiny-falcon.closed", seed=6, seconds=0.5, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 3 * check["tolerance"]["rel_rms"]
