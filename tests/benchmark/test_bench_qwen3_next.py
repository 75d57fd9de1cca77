"""The Qwen3-Next configuration, its adapter, reference, cost functions and
metrics: the arithmetic the cell's numbers rest on, the readings of a small
synthetic trace, and the cell run end to end in a throw-away root on the CPU at
a tiny size (``correct: true``, and ``false`` under each control of the check:
a reference told something else than the configuration states, and a program
whose delta-rule state is held in bfloat16)."""

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
from test_bench_falcon_h1 import state_in_bfloat16

CELL = "serve-qwen3next-l12-ep8-assistant"
CONFIG = "qwen3-next-80b-a3b-l12-ep8"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = {"gdn_state_roofline", "serve_gdn_device_share", "gdn_moe_decode_hbm_roofline"}
MIXER, ATTENTION, EXPERT, BESIDE, PARAMS, SLOT, TABLE = 33_718_464, 27_263_488, 3_145_728, 4_200_448, 2_929_374_400, 2_097_152, 38_895_616

TINY = B.tiny_form("qwen3_next")["config"]
TINY_TRAFFIC = {
    "kind": "serve-closed", "clients": 4, "requests_per_client": 2,
    "prompt_tokens": {"shape": "uniform", "min": 4, "max": 24}, "output_tokens": {"shape": "uniform", "min": 6, "max": 16},
    "temperature": 0.7, "slices": 4,
    "engine": B.tiny_form("qwen3_next")["serve_engine"],  # no prefix cache beside a state
}
TOLD_OTHERWISE = '''
from benchlib import model

_base = model.beside(__file__, "archs", "qwen3_next")
_told = _base.numerics
_base.numerics = lambda config: {{**_told(config), **{told!r}}}
globals().update({{k: v for k, v in vars(_base).items() if not k.startswith("__")}})
'''
#: the controls of the check that are the reference's to run: what each is told instead (ISSUE 64, Tentpole 9)
NOT_THE_PROGRAMS = {
    "update-without-r": {"correct": False},
    "beta-of-one": {"beta_one": True},
    "no-output-gate": {"output_gate": False},
    "no-shared-gate": {"shared_gate": False},
    "gate-before-norm": {"gate_before_norm": True},
    "rotary-on-all": {"rotary_all": True},
}
#: and the program's: the delta-rule state held in bfloat16 (the chip's runs use it too)
THE_PROGRAMS = {"state-bfloat16": state_in_bfloat16}


def told_otherwise(root, name, told):
    """An adapter file in ``root`` whose reference is told ``told`` instead of what the configuration states."""
    arch = "qwen3_next_" + name.replace("-", "_")
    with open(os.path.join(root, "benchmark", "archs", arch + ".py"), "w") as f:
        f.write(TOLD_OTHERWISE.format(told=told))
    return arch


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tests' throw-away root plus this cell at a tiny size."""
    tmp = B.throwaway_root(str(tmp_path_factory.mktemp("qwen3_next_root")))
    for part in ("costs", "readers"):
        shutil.copytree(os.path.join(B.BENCH, part), os.path.join(tmp, "benchmark", part), dirs_exist_ok=True)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(tmp, "benchmark", "traffic", "tiny-assistant.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    configs = {"tiny-qwen3next": TINY}
    for k, told in NOT_THE_PROGRAMS.items():
        configs[f"tiny-qwen3next-{k}"] = dict(TINY, arch=told_otherwise(tmp, k, told))
    for name, config in configs.items():
        with open(os.path.join(tmp, "benchmark", "configs", f"{name}.json"), "w") as f:
            json.dump(config, f)
        doc["configs"].append({"name": name, "source": "none", "file": f"benchmark/configs/{name}.json", "reduced": [], "why": "test"})
    cells = {f"{name}.closed": name for name in configs}
    for name, config in cells.items():
        doc["workloads"].append({"name": name, "config": config, "traffic": "tiny-assistant", "chips": 1, "why": "test"})
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
    assert [(w["config"], w["traffic"], w["chips"]) for w in doc["workloads"] if w["name"] == CELL] == [(CONFIG, "assistant-closed", 1)]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "Qwen3-Next-80B-A3B-Instruct")
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == published["source_url"] == cell.config["source"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"] == list(cell.config["reduced"])
    for key, value in published["config"].items():                                     # every width is as published
        if key not in entry["reduced"]:
            assert cell.config[key] == value, key
    config = cell.config
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (12, 64, 18_992)
    assert (config["num_experts_published"], config["first_expert_held"], config["arch"]) == (512, 0, "qwen3_next")
    assert 12 % config["full_attention_interval"] == 0 and 64 >= 8 and 18_992 * 8 == 151_936 and config["num_experts_per_tok"] == 10   # the guide's floors
    assert {"deployment", "assumed", "deviations", "dtypes", "tolerance"} <= set(config)
    assert "2,929,374,400 parameters" in config["deployment"] and "2,097,152 B a linear layer" in config["deployment"] and "32 chips" in config["deployment"]
    assert {"mtp", "rope_pairs", "in_projection_columns", "zero_centred_norms", "prefix_cache", "training"} <= set(config["deviations"])
    assumed = config["assumed"]
    assert {"state_dtype", "conv_tail", "l2_norm", "gated_norm", "initialiser", "temperature", "unused"} <= set(assumed)
    assert all("Not run" in assumed[k] for k in ("state_dtype", "conv_tail", "l2_norm", "gated_norm", "initialiser"))
    assert "intermediate_size" in assumed["unused"]
    assert config["dtypes"] == {"serve_params": "bfloat16", "compute": "bfloat16", "state": "float32", "kv_cache": "bfloat16"}
    # the check prefills a wide chunk, a narrow one and 2 rows of another: the last prefilled row reads two of the
    # convolution's four taps across a chunk's edge (Falcon-H1's file argues the half)
    from determined_tpu.serve.config import ServeConfig
    serve_cfg = ServeConfig(**cell.traffic["engine"])
    prefilled = config["tolerance"]["serve_logits"]["sequence_tokens"] // 2
    assert 0 < prefilled % serve_cfg.prefill_chunk < config["linear_conv_kernel_dim"] and prefilled > 1024 + serve_cfg.prefill_chunk
    # the cell's traffic and engine are ISSUE 64's, to the number
    t = cell.traffic
    assert (t["kind"], t["clients"], t["requests_per_client"], t["temperature"], t["slices"]) == ("serve-closed", 64, 4, 0.7, 10)
    assert t["prompt_tokens"] == {"shape": "uniform", "min": 2048, "max": 8192} and t["output_tokens"] == {"shape": "uniform", "min": 1024, "max": 4096}
    assert t["engine"] == {"block_size": 16, "num_blocks": 49_153, "max_batch": 64, "decode_chunk_blocks": 1, "prefix_cache": False,
                           "max_prompt_len": 12_288, "max_new_tokens": 4096, "queue_depth": 128}
    assert 49_153 == 64 * (8192 + 4096) // 16 + 1                                        # 64 worst-case requests and the scratch block
    mine = {m["name"]: m for m in cell.per_layer}
    assert NEW <= set(mine) and all(mine[n]["moves"] == "tpot_p50_ms" and mine[n]["workloads"] == [CELL] for n in NEW)
    assert {"serve_prefill_share", "serve_prefill_wide_share", "decode_device_ms", "serve_device_idle_share", "serve_attn_device_share",
            "serve_moe_device_share", "serve_moe_route_device_share", "serve_vocab_device_share", "serve_step_sample_ms",
            "serve_decode_named_device_share", "tpot_decode_wait_ms", "moe_decode_experts_roofline", "moe_decode_rows_per_expert"} <= set(mine)
    # no serve.mlp scope in this program; the metrics other architectures' costs count; moe_decode_experts_hit's file
    # scales by a QUARTER (four expert layers a cell): twelve here, so the cell is not on it
    assert not {"serve_mlp_device_share", "decode_hbm_roofline", "hybrid_decode_hbm_roofline", "ssm_moe_decode_hbm_roofline", "moe_decode_experts_hit",
                "ssm_state_roofline", "serve_ssm_device_share", "serve_mamba2_device_share", "serve_mla_device_share"} & set(mine)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms", "setup_s"} and not {"serve_lane_occupancy", "serve_kv_pool_live"} & set(mine)
    assert {n: mine[n]["reader"]["reader"] for n in NEW} == {n: "decode_burst_ops" for n in NEW}
    assert mine["serve_gdn_device_share"]["reader"]["cells"] == {"of": "serving", "scope": "serve.gdn.state"}
    assert mine["gdn_state_roofline"]["reader"]["args"]["scopes"] == ["serve.gdn.state"] and "cells" not in mine["gdn_state_roofline"]["reader"]


#: what this file asserts of the DOCUMENT: each takes a ``Spec``, so that
#: test_bench_rules.py can hold a document with one more cell to all of them
DOCUMENT_CHECKS = [the_document_and_the_configuration_keep_the_contract]


def test_the_document_and_the_configuration_keep_the_contract():
    the_document_and_the_configuration_keep_the_contract(S.Spec())


def test_the_adapter_meets_the_interface_and_counts_what_the_issue_counts(cell):
    arch, config = model.adapter(cell), cell.config
    assert all(callable(getattr(arch, name)) for name in model.INTERFACE)
    mixer = arch.mixer_params(config)
    assert mixer == {"w_in": 25_165_824, "w_ba": 131_072, "conv": 32_768, "scalars": 64, "norm": 128, "w_out": 8_388_608}
    assert sum(mixer.values()) == MIXER and 2048 * 12_288 == 25_165_824
    assert arch.attention_params(config) == {"matrices": 16_777_216 + 2 * 1_048_576 + 8_388_608, "norms": 512} and sum(arch.attention_params(config).values()) == ATTENTION
    beside = arch.expert_layer_params(config)
    assert beside == {"router": 1_048_576, "shared": EXPERT, "shared_gate": 2048, "expert": EXPERT, "norms": 4096}
    assert EXPERT == 3 * 2048 * 512 and sum(beside.values()) - EXPERT == BESIDE
    # a layer outside its routed experts: ISSUE 64's 37,918,912 and 31,463,936
    assert MIXER + BESIDE == 37_918_912 and ATTENTION + BESIDE == 31_463_936
    assert arch.layer_params(config) == {"linear_attention": 37_918_912 + 64 * EXPERT, "full_attention": 31_463_936 + 64 * EXPERT}
    assert arch.layer_counts(config) == {"linear_attention": 9, "full_attention": 3}
    assert arch.pattern(config) == (["linear_attention"] * 3 + ["full_attention"]) * 3
    assert arch.total_params(config) == 9 * 37_918_912 + 3 * 31_463_936 + 12 * 64 * EXPERT + 2 * TABLE + 2048 == PARAMS
    assert arch.embedding_params(config) == 18_992 * 2048 == TABLE
    # the published model by the same functions: 79.67 B in all ("80B"), ~3.6 B a token with one table ("A3B")
    whole = dict(config, num_experts=512, vocab_size=151_936, num_hidden_layers=48)
    assert arch.layer_counts(whole) == {"linear_attention": 36, "full_attention": 12}
    assert arch.total_params(whole) / 1e9 == pytest.approx(79.67, abs=0.01)
    active = arch.total_params(whole) - 48 * (512 - 10) * EXPERT - 151_936 * 2048
    assert active / 1e9 == pytest.approx(3.6, abs=0.1)
    # what a token multiplies with here: 10 x 64 / 512 = 1.25 of its picks are held
    assert arch.expert_shape(config) == {"d_model": 2048, "d_ff": 512, "matrices": 3, "held": 64, "layers": 12, "shared_d_ff": 512, "expected_held_picks": 1.25}
    per_layer = 1_048_576 + EXPERT + 2048 + 1.25 * EXPERT
    assert arch.matmul_params(config) == 9 * (25_165_824 + 131_072 + 8_388_608) + 3 * (ATTENTION - 512) + 12 * per_layer + TABLE
    assert arch.attention_shape(config) == {"heads": 16, "kv_heads": 2, "head_dim": 256, "layers": 3}
    assert arch.gdn_shape(config) == {"key_heads": 16, "value_heads": 32, "key_dim": 128, "value_dim": 128, "conv": 4, "channels": 8192,
                                      "layers": 9, "bytes_per_slot": SLOT}
    cfg = arch.model_config(config, 16_384)
    assert cfg.layer_types == (("linear_attention",) * 3 + ("full_attention",)) * 3 and cfg.norm_eps == 1e-6 and not cfg.mixer_block
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.param_dtype) == (16, 2, 256, jnp.bfloat16) and cfg.rope("full_attention").theta == 1e7
    assert (cfg.qk_norm, cfg.attn_output_gate, cfg.partial_rotary_factor, cfg.moe_shared_gate) == (True, True, 0.25, True)
    assert (cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim, cfg.linear_conv, cfg.linear_channels) == (16, 32, 128, 128, 4, 8192)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_experts_held, cfg.moe_intermediate_size, cfg.moe_every, cfg.moe_router) == (512, 10, (0, 64), 512, 1, "softmax")
    assert (cfg.moe_shared_experts, cfg.moe_shared_intermediate_size) == (1, 512) and all(cfg.use_moe(i) for i in range(12))
    # the program's own tree holds as many (shapes only), all bfloat16; the cache is K and V in blocks for THREE layers
    # and a delta-rule state and a tail a lane for nine
    from determined_tpu.models.cache_kinds import DELTA_SLOT, PAGED_KV, cache_kinds, layers_by_kind
    from determined_tpu.models.transformer import STATE_DTYPE, TransformerLM, gdn_bytes_per_slot, gdn_pool_shapes, kv_bytes_per_token, kv_cache_shape
    from determined_tpu.ops import gated_delta, paged_attention

    tree = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
    shapes = jax.tree_util.tree_leaves(tree)
    assert sum(x.size for x in shapes) == PARAMS and {str(x.dtype) for x in shapes} == {"bfloat16"}
    assert cache_kinds(cfg) == (PAGED_KV, DELTA_SLOT) and jnp.dtype(STATE_DTYPE) == jnp.float32
    assert layers_by_kind(cfg) == {"paged_kv": 3, "delta_slot": 9, "none": 0}
    assert gdn_pool_shapes(cfg, 64) == ((9, 65, 32, 128, 128), (9, 64, 3, 8192)) and gdn_bytes_per_slot(cfg) == SLOT
    assert kv_cache_shape(cfg, 49_153, 16) == (3, 49_153, 16, 512) and kv_bytes_per_token(cfg) == 6144
    assert gated_delta.kernel_takes(32, 128, 128, jnp.float32) and gated_delta.heads_a_program(32, 128, 128, jnp.float32) == 32
    assert paged_attention.attn_products(16 // 2) is not None
    pool = 2 * 3 * 49_153 * 16 * 512 * 2
    held = 2 * PARAMS + 9 * 65 * SLOT + 9 * 64 * 3 * 8192 * 2 + pool
    assert pool == 4_831_936_512 and held / 1e9 == pytest.approx(11.95, abs=0.01) and held / 2**34 > 0.25      # 70 % of 16 GiB
    # a parent whose program lacks the layer type is refused by name, with the harness's own error (exit code 3)
    from unittest import mock

    from determined_tpu.models import transformer as T

    few = [f for f in dataclasses.fields(T.TransformerConfig) if f.name not in ("linear_key_heads", "attn_output_gate")]
    with mock.patch.object(dataclasses, "fields", lambda cls: few), pytest.raises(S.SpecError, match="lacks attn_output_gate, linear_key_heads"):
        arch.check_as_run(config)
    with mock.patch.object(T, "LAYER_TYPES", ("full_attention", "attention_mamba2")), pytest.raises(S.SpecError, match="lacks the layer type linear_attention"):
        arch.check_as_run(config)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        arch.check_as_run(dict(config, norm_topk_prob=False))
    with pytest.raises(ValueError, match="at least one whole period"):
        arch.check_as_run(dict(config, num_hidden_layers=3))
    with pytest.raises(ValueError, match="float32 state"):
        arch.check_as_run(dict(config, dtypes=dict(config["dtypes"], state="bfloat16")))
    for name in ("trial_hparams", "trial_overrides"):                                     # served only
        with pytest.raises(ValueError, match=f"served only \\({name}\\).*16 chips"):
            getattr(arch, name)(config)
    # an expert's three matrices are drawn at ITS fan-in, and every head to remember 333 to 53,333 tokens
    tiny_cfg = arch.model_config(TINY, 640)
    params = arch.init_params(tiny_cfg, 3)
    experts = params["block_1"]["moe"]
    assert float(jnp.std(experts["w_up"])) == pytest.approx(48 ** -0.5, rel=0.06) and float(jnp.std(experts["w_down"])) == pytest.approx(24 ** -0.5, rel=0.06)
    assert experts["shared_gate"].shape == (48,) and "gdn" in params["block_2"] and "attn" in params["block_3"]
    memory = 1.0 / (jnp.exp(params["block_0"]["gdn"]["A_log"]) * jax.nn.softplus(params["block_0"]["gdn"]["dt_bias"]))
    assert 333 <= float(memory.min()) and float(memory.max()) <= 53_334


def test_cost_functions_count_the_state_twice_and_the_whole_step(cell):
    arch, config, traffic = model.adapter(cell), cell.config, cell.traffic
    held = 64 * 9 * SLOT
    counters = {"traced.serve.gdn.bytes": float(held), "traced.serve.gdn.live_lanes": 64.0, "traced.active": 64.0,
                "traced.live_kv_tokens": 409_600.0, "traced.serve.moe.experts_hit": 545.0, "traced.serve.moe.held_picks": 960.0}
    state = costs.find("gdn_state", cell.data_dir)(config, traffic, 1, counters, arch)
    assert state == {"flops": 7.0 * held / 4, "bytes": 2.0 * held} and state["bytes"] / 1e9 == pytest.approx(2.416, abs=0.001)   # ISSUE 64's 2.4 GB
    assert state["bytes"] / 819e9 > state["flops"] / 197e12                                 # 0.875 operations a byte: the bytes bound it
    with pytest.raises(KeyError):                                                           # a program that counts no such thing
        costs.find("gdn_state", cell.data_dir)(config, traffic, 1, {"traced.active": 64.0}, arch)
    experts = costs.find("moe_decode_experts", cell.data_dir)(config, traffic, 1, counters, arch)
    matrices, rows = 545 * EXPERT * 2, 960 * (2 * 2048 + 3 * 512) * 2
    assert experts["bytes"] == matrices + rows and matrices / 1e9 == pytest.approx(3.43, abs=0.01)   # 0.71 of 768 held: ISSUE 64's 3.4 GB
    step = costs.find("gdn_moe_decode_step", cell.data_dir)(config, traffic, 1, counters, arch)
    not_routed = PARAMS - TABLE - 12 * 64 * EXPERT
    kv, tails, looked_up = 409_600 * 3 * 2 * 2 * 256 * 2, 2 * 64 * 9 * 3 * 8192 * 2, 64 * 2048 * 2
    assert 2 * not_routed / 1e9 == pytest.approx(0.949, abs=0.001) and kv / 1e9 == pytest.approx(2.517, abs=0.001)   # 0.95 and 2.5 GB
    assert step["bytes"] == pytest.approx(2 * not_routed + looked_up + matrices + rows + 2 * held + tails + kv)
    every_lane = arch.matmul_params(config) - 12 * 1.25 * EXPERT
    assert step["flops"] == pytest.approx(2 * 64 * every_lane + experts["flops"] + 7 * held / 4 + 4 * 16 * 256 * 3 * 409_600)
    assert step["bytes"] / 819e9 > step["flops"] / 197e12                                   # a decode step is bound by what it moves
    assert step["bytes"] / 819e9 * 1e3 == pytest.approx(11.45, abs=0.1)                     # ISSUE 64's 11.4 ms a step
    # half of the lanes idle: the state's, the tails' and the rows' half, every hit expert's matrices all the same
    half = {**counters, "traced.serve.gdn.bytes": held / 2, "traced.serve.gdn.live_lanes": 32.0, "traced.active": 32.0}
    less = costs.find("gdn_moe_decode_step", cell.data_dir)(config, traffic, 1, half, arch)["bytes"]
    assert step["bytes"] - less == pytest.approx(held + tails / 2 + looked_up / 2)


# ---------------------------------------------------------------------------
# the readers, on a small synthetic trace
# ---------------------------------------------------------------------------


class _Traced:
    """A prefill and two whole decode steps on one device, a third cut by the
    trace's end; 15 ms of operations a step and 6 ms idle between two."""

    trace_dir = ""
    sync_marks_ns = [0.0]

    def data(self):
        from benchlib import trace as tr

        ms = 1e6
        step = [("%fusion.3 = bf16[64,12288] fusion(...)", 0.0, 1.0), ("%fusion.4 = f32[64,8192] fusion(...)", 1.0, 0.5),
                ("%gdn_decode.5 = f32[64,32,128] custom-call(...)", 1.5, 3.5), ("%fusion.8 = bf16[64,2048] fusion(...)", 5.0, 1.0),
                ("%moe_gmm.2 = bf16[1024,512] custom-call(...)", 6.0, 6.0), ("%fusion.9 = f32[65,18992] fusion(...)", 12.0, 3.0)]
        events = [("%fusion.3 = bf16[1,256,2048] fusion(...)", 1 * ms, 5 * ms)]
        for start in (10.0, 31.0, 52.0):
            events += [(n, (start + s) * ms, d * ms) for n, s, d in step]
        events = [e for e in events if e[1] + e[2] <= 65 * ms]
        return tr.TraceData(devices={"d": sorted(events, key=lambda e: e[1])}, host=[(tr.SYNC_NAME, 0.0, 0.0)])


def _decode_span(start_ms, lanes, hit, picks):
    return {"ph": "X", "name": "serve.decode", "ts": start_ms * 1e3, "dur": 15.5e3,
            "args": {"step": 1, "active": lanes, "live_kv_tokens": 6400 * lanes, "max_context": 12000,
                     "serve.gdn.live_lanes": float(lanes), "serve.gdn.bytes": float(lanes * 9 * SLOT),
                     "serve.moe.experts_hit": float(hit), "serve.moe.held_picks": float(picks)}}


def test_the_new_metrics_read_the_scopes_and_the_counters(cell):
    scopes = {"serve.gdn.proj": ["fusion.3"], "serve.gdn.conv": ["fusion.4"], "serve.gdn.state": ["gdn_decode.5"], "serve.gdn.out": ["fusion.8"],
              "serve.moe.experts": ["moe_gmm.2"], "serve.head": ["fusion.9"]}
    events = [
        {"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode", "scopes": scopes}},
        _decode_span(9.9, 64, 545, 960), _decode_span(30.9, 60, 535, 900), _decode_span(51.9, 64, 550, 960),   # the third is cut: not counted
    ]
    obs = Observations(window=(0.0, 1.0), spans=[], counters={}, program_events=events, profiler=_Traced(), config=cell.config,
                       traffic=cell.traffic, chips=1, program_epoch=0.0, arch=model.adapter(cell), data_dir=cell.data_dir)
    metric = lambda name: next(m for m in cell.per_layer if m["name"] == name)  # noqa: E731
    assert readers.read(metric("serve_gdn_device_share"), obs, PEAK) == pytest.approx(100 * 6 / 15)
    held = 62 * 9 * SLOT                                                                    # the two whole steps' mean
    assert readers.read(metric("gdn_state_roofline"), obs, PEAK) == pytest.approx(100 * 2 * held / 819e9 / 3.5e-3)
    hit, picks = 540, 930
    experts = hit * EXPERT * 2 + picks * (2 * 2048 + 3 * 512) * 2
    assert readers.read(metric("moe_decode_experts_roofline"), obs, PEAK) == pytest.approx(100 * experts / 819e9 / 6e-3)
    moved = 2 * (PARAMS - TABLE - 12 * 64 * EXPERT) + 62 * 2048 * 2 + experts + 2 * held + 2 * 62 * 9 * 3 * 8192 * 2 + 6400 * 62 * 6144
    assert readers.read(metric("gdn_moe_decode_hbm_roofline"), obs, PEAK) == pytest.approx(100 * moved / 819e9 / 15e-3)
    assert readers.read(metric("moe_decode_rows_per_expert"), obs, PEAK) == pytest.approx((960 + 900 + 960) / (545 + 535 + 550))
    assert all(readers.read(metric(n), obs, PEAK) < 100.0 for n in NEW if n.endswith("roofline"))
    # the parent commit: no such scopes, no such counters: nothing, and nothing raised
    bare = [dict(e, args={k: v for k, v in e["args"].items() if not k.startswith(("serve.gdn", "serve.moe"))}) for e in events if e["name"] != "jit.scopes"]
    bare.append({"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode", "scopes": {"serve.head": ["fusion.9"]}}})
    obs_bare = dataclasses.replace(obs, program_events=bare)
    for name in sorted(NEW):
        assert readers.read(metric(name), obs_bare, PEAK) is None, name


# ---------------------------------------------------------------------------
# the cell, end to end at a tiny size
# ---------------------------------------------------------------------------


def test_the_cell_runs_through_the_engine_and_agrees_with_its_reference(root, capsys):
    line = harness.run_cell("tiny-qwen3next.closed", seed=2**31 + 64, seconds=1.5, traced=True, root=root, require_tpu=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    check = next(x for x in out if x["event"] == "serve.check")
    # 300 prefilled (a chunk of 256 and 44 of the second: the state and the tail carried, 212 padded rows advancing neither), 300 decoded
    assert check["rows"] == 301 and check["rel_rms"] < 1e-4 and check["top1_agree"] == 1.0
    values = next(x for x in out if x["event"] == "end_to_end_of_traced_run")["values"]
    assert {"tpot_p50_ms", "setup_s"} <= set(values)
    # the span- and counter-based metrics the cell lists read true for it; device metrics have nothing to read on a CPU
    assert {"serve_prefill_share", "serve_step_ms", "serve_queue_wait_ms", "moe_decode_rows_per_expert"} <= set(line["metrics"])
    assert not any("roofline" in k or "device" in k for k in line["metrics"])
    assert 1.0 <= line["metrics"]["moe_decode_rows_per_expert"]["value"] <= 4 * 4
    # the engine's own account, for an operator without a trace
    stats = next(x for x in out if x["event"] == "serve.window")["engine"]
    assert set(stats["step_counters"]) == {"serve.gdn.live_lanes", "serve.gdn.bytes", "serve.moe.held_picks", "serve.moe.experts_hit"}
    assert stats["gdn"] == {"slots": 4, "live": stats["gdn"]["live"], "bytes_per_slot": 3 * 4 * 8 * 16 * 4} and stats["kv_cache"]["peak"] > 0


def test_the_programs_scopes_are_the_ones_the_lists_rest_on():
    import bench_rules as R

    scopes = R.scopes_of("qwen3_next", True)
    assert {"serve.gdn.proj", "serve.gdn.conv", "serve.gdn.state", "serve.gdn.out", "serve.attn.qkv", "serve.attn.gate", "serve.kv.write",
            "serve.attn.attend", "serve.attn.out", "serve.moe.route", "serve.moe.experts", "serve.moe.shared", "serve.embed", "serve.head"} <= scopes
    assert not {"serve.mlp", "serve.ssm.state", "serve.mamba2.state", "serve.mla", "serve.moe.latent", "serve.attn.full"} & scopes


@pytest.mark.parametrize("told", sorted(NOT_THE_PROGRAMS))
def test_the_check_catches_a_reference_that_is_not_the_programs(root, capsys, told):
    line = harness.run_cell(f"tiny-qwen3next-{told}.closed", seed=5, seconds=0.5, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 10 * check["tolerance"]["rel_rms"]


@pytest.mark.parametrize("broken", sorted(THE_PROGRAMS))
def test_the_check_catches_a_program_that_is_not_the_references(root, capsys, monkeypatch, broken):
    THE_PROGRAMS[broken](monkeypatch)
    line = harness.run_cell("tiny-qwen3next.closed", seed=6, seconds=0.5, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 3 * check["tolerance"]["rel_rms"]
