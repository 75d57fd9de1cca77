"""The Nemotron-3-Super configuration, its adapter, reference, reader and cost
functions: the arithmetic the cell's numbers rest on, the readings of a small
synthetic trace, and the cell run end to end in a throw-away root on the CPU at
a tiny size (``correct: true``, and ``false`` under each control of the check:
a reference told something else than the configuration states, and a program
whose state is held in bfloat16, whose attention layer rotates or whose
convolution tail is dropped at a chunk's edge)."""

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
from test_bench_falcon_h1 import state_in_bfloat16, tail_dropped_at_a_chunks_edge

CELL = "serve-nemotron3-super-l11-ep4-agentreason"
CONFIG = "nemotron-3-super-120b-l11-ep4"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = {"latent_moe_decode_experts_roofline", "ssm_moe_decode_hbm_roofline", "serve_moe_latent_device_share", "moe_decode_rows_per_expert",
       "latent_moe_decode_experts_hit", "mamba2_state_roofline", "serve_mamba2_device_share"}
MAMBA, ATTENTION, EXPERT_LAYER, EXPERT, PARAMS, SLOT, TABLE = 109_640_064, 35_655_680, 759_173_632, 5_505_024, 4_648_163_712, 4_194_304, 134_217_728

TINY = B.tiny_form("nemotron_h")["config"]
TINY_TRAFFIC = {
    "kind": "serve-closed", "clients": 4, "requests_per_client": 2,
    "prompt_tokens": {"shape": "uniform", "min": 4, "max": 24}, "output_tokens": {"shape": "uniform", "min": 6, "max": 16},
    "temperature": 0.6, "slices": 4,
    "engine": B.tiny_form("nemotron_h")["serve_engine"],  # no prefix cache beside a state
}
TOLD_OTHERWISE = '''
from benchlib import model

_base = model.beside(__file__, "archs", "nemotron_h")
_told = _base.numerics
_base.numerics = lambda config: {{**_told(config), **{told!r}}}
globals().update({{k: v for k, v in vars(_base).items() if not k.startswith("__")}})
'''
#: the controls of the check that are the reference's to run: what each is told instead (ISSUE 57, Tentpole 6)
NOT_THE_PROGRAMS = {
    "no-latent-projections": {"latent": False},
    "silu-for-relu2": {"act": "silu"},
    "gated-expert": {"act": "gated"},
    "no-scaling": {"scaling": 1.0},
    "not-normalised": {"normalise": False},
    "bias-weighs": {"bias_weighs": True},
    "no-shared-expert": {"shared": False},
    "no-expert-residual": {"expert_residual": False},
    "norm-over-all": {"norm_groups": 1},
    "group-0-for-all": {"shared_group": True},
}


# -- the controls of the check that are the program's: each breaks ONE thing (the chip's runs use these too) ----


def rotary_on(monkeypatch):
    """The attention layer turns q and k by ``rope_theta`` 10000, as a config.json reader who trusted the key would."""
    from determined_tpu.models import transformer as T

    monkeypatch.setattr(T.TransformerConfig, "rope", lambda self, layer_type: T.Rope(10000.0))


THE_PROGRAMS = {
    "state-bfloat16": state_in_bfloat16,
    "rotary-on": rotary_on,
    "tail-dropped-at-chunk-edge": tail_dropped_at_a_chunks_edge,
}


def told_otherwise(root, name, told):
    """An adapter file in ``root`` whose reference is told ``told`` instead of what the configuration states."""
    arch = "nemotron_h_" + name.replace("-", "_")
    with open(os.path.join(root, "benchmark", "archs", arch + ".py"), "w") as f:
        f.write(TOLD_OTHERWISE.format(told=told))
    return arch


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tests' throw-away root plus this cell at a tiny size."""
    tmp = B.throwaway_root(str(tmp_path_factory.mktemp("nemotron_root")))
    for part in ("costs", "readers"):
        shutil.copytree(os.path.join(B.BENCH, part), os.path.join(tmp, "benchmark", part), dirs_exist_ok=True)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(tmp, "benchmark", "traffic", "tiny-agentreason.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    configs = {"tiny-nemotron": TINY}
    for k, told in NOT_THE_PROGRAMS.items():
        configs[f"tiny-nemotron-{k}"] = dict(TINY, arch=told_otherwise(tmp, k, told))
    for name, config in configs.items():
        with open(os.path.join(tmp, "benchmark", "configs", f"{name}.json"), "w") as f:
            json.dump(config, f)
        doc["configs"].append({"name": name, "source": "none", "file": f"benchmark/configs/{name}.json", "reduced": [], "why": "test"})
    cells = {f"{name}.closed": name for name in configs}
    for name, config in cells.items():
        doc["workloads"].append({"name": name, "config": config, "traffic": "tiny-agentreason", "chips": 1, "why": "test"})
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
    assert [(w["config"], w["traffic"], w["chips"]) for w in doc["workloads"] if w["name"] == CELL] == [(CONFIG, "agentreason-closed", 1)]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == published["source_url"] == cell.config["source"]
    assert entry["reduced"] == ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"] == list(cell.config["reduced"])
    for key, value in published["config"].items():                                     # every width is as published
        if key not in entry["reduced"]:
            assert cell.config[key] == value, key
    config = cell.config
    assert (config["num_hidden_layers"], config["hybrid_override_pattern"], config["n_routed_experts"], config["vocab_size"]) == (11, "MEMEMEMEM*E", 128, 32768)
    # the cut pattern is the published one's layers 27-37: a whole period, the model's own 5 : 5 : 1
    assert published["config"]["hybrid_override_pattern"][27:38] == "MEMEMEMEM*E" and published["config"]["hybrid_override_pattern"].count("M") == 40
    assert (config["n_routed_experts_published"], config["first_expert_held"], config["arch"]) == (512, 0, "nemotron_h")
    assert 128 >= 8 and 32768 * 8 >= 131072 and config["num_experts_per_tok"] == 22    # the guide's floors
    assert {"deployment", "assumed", "deviations", "dtypes", "tolerance"} <= set(config)
    assert "4,648,163,712 parameters" in config["deployment"] and "4,194,304 B a Mamba-2 layer" in config["deployment"] and "32 chips" in config["deployment"]
    assert {"num_nextn_predict_layers", "chunk_size", "rope_pairs", "torch_dtype"} <= set(config["deviations"])
    assumed = config["assumed"]
    assert {"latent_projections", "e_score_correction_bias", "time_step", "state_dtype", "conv_tail", "gated_norm", "attention_position_free",
            "initialiser", "temperature", "unused"} <= set(assumed)
    assert all("Not run" in assumed[k] for k in ("state_dtype", "conv_tail", "gated_norm", "attention_position_free", "initialiser"))
    assert all(k in assumed["unused"] for k in ("intermediate_size", "rope_theta", "partial_rotary_factor", "chunk_size"))
    assert config["dtypes"] == {"serve_params": "bfloat16", "compute": "bfloat16", "state": "float32", "kv_cache": "bfloat16"}
    # the check compares the last prefilled row and the decoded ones: only a row within the convolution's reach of a
    # chunk's edge shows a tail dropped there (Falcon-H1's file argues it)
    from determined_tpu.serve.config import ServeConfig
    prefilled = config["tolerance"]["serve_logits"]["sequence_tokens"] // 2
    assert 0 < prefilled % ServeConfig(**cell.traffic["engine"]).prefill_chunk < config["conv_kernel"]
    # the cell's traffic and engine are ISSUE 57's, to the number
    t = cell.traffic
    assert (t["kind"], t["clients"], t["requests_per_client"], t["temperature"], t["slices"]) == ("serve-closed", 64, 4, 0.6, 10)
    assert t["prompt_tokens"] == {"shape": "uniform", "min": 1024, "max": 4096} == t["output_tokens"]
    assert t["engine"] == {"block_size": 16, "num_blocks": 32769, "max_batch": 64, "decode_chunk_blocks": 1, "prefix_cache": False,
                           "max_prompt_len": 8192, "max_new_tokens": 4096, "queue_depth": 128}
    assert 32769 == 64 * (4096 + 4096) // 16 + 1                                        # 64 worst-case requests and the scratch block
    mine = {m["name"]: m for m in cell.per_layer}
    assert NEW <= set(mine) and all(mine[n]["moves"] == "tpot_p50_ms" and CELL in mine[n]["workloads"] for n in NEW)
    assert {"serve_prefill_share", "decode_device_ms", "serve_device_idle_share", "serve_attn_device_share", "serve_moe_device_share",
            "serve_vocab_device_share", "serve_step_sample_ms", "serve_decode_named_device_share", "tpot_decode_wait_ms"} <= set(mine)
    # no serve.mlp scope in this program (the shared expert runs under serve.moe.shared); the metrics other architectures'
    # costs count; and the two whose lists tests/benchmark/test_bench_falcon_h1.py holds to its own cell (PERF.md section 7)
    assert not {"serve_mlp_device_share", "decode_hbm_roofline", "hybrid_decode_hbm_roofline", "mla_moe_decode_hbm_roofline", "moe_decode_experts_hit",
                "moe_decode_experts_roofline", "ssm_state_roofline", "serve_ssm_device_share", "serve_mla_device_share"} & set(mine)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms", "setup_s"} and not {"serve_lane_occupancy", "serve_kv_pool_live"} & set(mine)
    by = {n: mine[n]["reader"]["reader"] for n in NEW}
    assert by == {**{n: "decode_burst_ops" for n in NEW}, "moe_decode_rows_per_expert": "span_arg_ratio", "latent_moe_decode_experts_hit": "span_arg_percentile"}
    assert mine["serve_moe_latent_device_share"]["reader"]["cells"] == {"of": "serving", "scope": "serve.moe.latent"}
    assert mine["serve_mamba2_device_share"]["reader"]["cells"] == {"of": "serving", "scope": "serve.mamba2.state"}
    assert mine["latent_moe_decode_experts_hit"]["reader"]["args"]["scale"] == 1 / 5   # FIVE expert layers


#: what this file asserts of the DOCUMENT: each takes a ``Spec``, so that
#: test_bench_rules.py can hold a document with one more cell to all of them
DOCUMENT_CHECKS = [the_document_and_the_configuration_keep_the_contract]


def test_the_document_and_the_configuration_keep_the_contract():
    the_document_and_the_configuration_keep_the_contract(S.Spec())


def test_the_adapter_meets_the_interface_and_counts_what_the_issue_counts(cell):
    arch, config = model.adapter(cell), cell.config
    assert all(callable(getattr(arch, name)) for name in model.INTERFACE)
    mixer = arch.mixer_params(config)
    assert mixer == {"w_in": 76_021_760, "conv": 51_200, "scalars": 384, "norm": 8_192, "w_out": 33_554_432} and 4096 * 18_560 == 76_021_760
    assert arch.attention_params(config) == 2 * 16_777_216 + 2 * 1_048_576 == ATTENTION - 4096
    experts = arch.expert_layer_params(config)
    assert experts == {"router": 2_097_152, "router_bias": 512, "latent": 2 * 4_194_304, "shared": 44_040_192, "expert": EXPERT}
    assert EXPERT == 2 * 1024 * 2688 and 54_530_560 == sum(experts.values()) - EXPERT + 4096   # an expert layer outside its experts
    assert arch.layer_params(config) == {"M": MAMBA, "*": ATTENTION, "E": EXPERT_LAYER} and EXPERT_LAYER == 54_530_560 + 128 * EXPERT
    assert arch.layer_counts(config) == {"M": 5, "*": 1, "E": 5}
    assert arch.total_params(config) == 5 * MAMBA + ATTENTION + 5 * EXPERT_LAYER + 2 * TABLE + 4096 == PARAMS
    assert arch.embedding_params(config) == 32_768 * 4096 == TABLE
    # the published model by the same functions: 120.67 B in all, 12.23 B a token ("120B-A12B"), 77.9 M a layer beside its experts
    whole = dict(config, n_routed_experts=512, vocab_size=131_072, num_hidden_layers=88,
                 hybrid_override_pattern="MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    assert arch.layer_counts(whole) == {"M": 40, "*": 8, "E": 40}
    assert arch.total_params(whole) / 1e9 == pytest.approx(120.67, abs=0.01)
    active = arch.total_params(whole) - 40 * (512 - 22) * EXPERT - 131_072 * 4096
    assert active / 1e9 == pytest.approx(12.23, abs=0.01) and (40 * MAMBA + 40 * 54_530_560 + 8 * ATTENTION) / 88 / 1e6 == pytest.approx(77.9, abs=0.05)
    # what a token multiplies with here: 22 x 128 / 512 = 5.5 of its picks are held
    assert arch.expert_shape(config) == {
        "d_model": 1024, "d_ff": 2688, "matrices": 2, "model_width": 4096, "held": 128, "layers": 5, "shared": 1, "shared_d_ff": 5376,
        "expected_held_picks": 5.5,
    }
    per_expert_layer = 2_097_152 + 2 * 4_194_304 + 44_040_192 + 5.5 * EXPERT
    assert arch.matmul_params(config) == 5 * (76_021_760 + 33_554_432) + (ATTENTION - 4096) + 5 * per_expert_layer + TABLE
    assert arch.attention_shape(config) == {"heads": 32, "kv_heads": 2, "head_dim": 128, "layers": 1}
    assert arch.ssm_shape(config) == {"heads": 128, "head_dim": 64, "d_state": 128, "groups": 8, "conv": 4, "channels": 10_240,
                                      "layers": 5, "bytes_per_slot": SLOT}
    cfg = arch.model_config(config, 12_288)
    assert cfg.mixer_block and cfg.layer_types == ("mamba2", "experts") * 4 + ("mamba2", "full_attention", "experts") and cfg.norm_eps == 1e-5
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.param_dtype) == (32, 2, 128, jnp.bfloat16) and cfg.rope("full_attention") is None
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv, cfg.ssm_channels) == (128, 64, 128, 8, 4, 10_240)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_experts_held, cfg.moe_intermediate_size, cfg.moe_latent_size) == (512, 22, (0, 128), 2688, 1024)
    assert (cfg.moe_router, cfg.moe_n_group, cfg.moe_topk_group, cfg.moe_routed_scaling) == ("sigmoid_grouped", 1, 1, 5.0)
    assert (cfg.moe_expert_act, cfg.moe_shared_experts, cfg.moe_shared_intermediate_size) == ("relu2", 1, 5376)
    # the program's own tree holds as many (shapes only), bfloat16 but for the selection bias; the cache is K and V in
    # blocks for ONE layer and a state and a tail a lane for five, nothing for the expert layers
    from determined_tpu.models.cache_kinds import PAGED_KV, SSM_SLOT, cache_kinds, layers_by_kind
    from determined_tpu.models.transformer import STATE_DTYPE, TransformerLM, kv_bytes_per_token, kv_cache_shape, ssm_bytes_per_slot, ssm_pool_shapes
    from determined_tpu.ops import ssm

    tree = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
    shapes = jax.tree_util.tree_leaves(tree)
    assert sum(x.size for x in shapes) == PARAMS
    assert sum(x.size for x in shapes if x.dtype == jnp.float32) == 5 * 512 and {str(x.dtype) for x in shapes} == {"bfloat16", "float32"}
    assert cache_kinds(cfg) == (PAGED_KV, SSM_SLOT) and jnp.dtype(STATE_DTYPE) == jnp.float32
    assert layers_by_kind(cfg) == {"paged_kv": 1, "ssm_slot": 5, "none": 5}
    assert ssm_pool_shapes(cfg, 64) == ((5, 65, 128, 64, 128), (5, 64, 3, 10_240)) and ssm_bytes_per_slot(cfg) == SLOT
    assert kv_cache_shape(cfg, 32_769, 16) == (1, 32_769, 16, 256) and kv_bytes_per_token(cfg) == 1024
    assert ssm.kernel_takes(128, 8, 64, 128, jnp.float32) and ssm.groups_a_program(8, 16, 64, 128, jnp.float32) == 4
    pool = 2 * 32_769 * 16 * 256 * 2
    held = 2 * PARAMS + 5 * 65 * SLOT + 5 * 64 * 3 * 10_240 * 2 + pool
    assert pool == 536_887_296 and held / 1e9 == pytest.approx(11.22, abs=0.01) and held / 2**34 > 0.25      # 65 % of 16 GiB
    # a parent whose program lacks the block form is refused by name, with the harness's own error (exit code 3)
    from unittest import mock

    from determined_tpu.models import transformer as T

    few = [f for f in dataclasses.fields(T.TransformerConfig) if f.name not in ("mixer_block", "moe_latent_size")]
    with mock.patch.object(dataclasses, "fields", lambda cls: few), pytest.raises(S.SpecError, match="lacks mixer_block, moe_latent_size"):
        arch.check_as_run(config)
    with mock.patch.object(T, "LAYER_TYPES", ("full_attention", "attention_mamba2")), pytest.raises(S.SpecError, match="lacks the layer types mamba2 and experts"):
        arch.check_as_run(config)
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        arch.check_as_run(dict(config, mlp_hidden_act="silu"))
    with pytest.raises(ValueError, match="no dense `-` layer"):
        arch.check_as_run(dict(config, hybrid_override_pattern="MEMEMEMEM*-"))
    with pytest.raises(ValueError, match="float32 state"):
        arch.check_as_run(dict(config, dtypes=dict(config["dtypes"], state="bfloat16")))
    for name in ("trial_hparams", "trial_overrides"):                                     # served only: 19.4 GB of training state
        with pytest.raises(ValueError, match=f"served only \\({name}\\).*19.4 GB"):
            getattr(arch, name)(config)
    # an expert's two matrices are drawn at ITS fan-in, not at held x its fan-in (the file's assumed.initialiser (c)):
    # the tiny form's 8 held experts of 32 x 24 and 24 x 32
    tiny_cfg = arch.model_config(TINY, 640)
    experts = arch.init_params(tiny_cfg, 3)["block_1"]["moe"]
    assert float(jnp.std(experts["w_up"])) == pytest.approx(32 ** -0.5, rel=0.05) and float(jnp.std(experts["w_down"])) == pytest.approx(24 ** -0.5, rel=0.05)
    assert float(jnp.std(experts["shared_w_up"])) == pytest.approx(64 ** -0.5, rel=0.08) and float(jnp.std(experts["router_bias"])) == pytest.approx(0.01, rel=0.5)
    # every head is drawn to remember 333 to 53,333 tokens (the file's assumed.initialiser (a)): Falcon-H1's draw
    drawn = arch.slow_heads(jax.random.key(3_000_000_001), 128, jnp.float32)
    memory = 1.0 / (jnp.exp(drawn["A_log"]) * jax.nn.softplus(drawn["dt_bias"]))
    assert drawn["A_log"].shape == (128,) and 333 <= float(memory.min()) and float(memory.max()) <= 53_334


def test_cost_functions_count_two_matrices_at_the_latent_width_and_the_whole_step(cell):
    arch, config, traffic = model.adapter(cell), cell.config, cell.traffic
    held = 64 * 5 * SLOT
    counters = {"traced.serve.ssm.bytes": float(held), "traced.serve.ssm.live_lanes": 64.0, "traced.active": 64.0,
                "traced.live_kv_tokens": 243_200.0, "traced.serve.moe.experts_hit": 600.0, "traced.serve.moe.held_picks": 1760.0}
    experts = costs.find("latent_moe_decode_experts", cell.data_dir)(config, traffic, 1, counters, arch)
    matrices, rows = 600 * 2 * 1024 * 2688 * 2, 1760 * 2 * 1024 * 2
    assert matrices == 6_606_028_800 and experts == {"flops": 2 * 2.0 * 1024 * 2688 * 1760, "bytes": float(matrices + rows)}
    assert experts["bytes"] / 819e9 > experts["flops"] / 197e12 and rows / matrices < 0.002   # at 2.9 rows an expert the matrices are all
    with pytest.raises(KeyError):                                                           # a program that counts no such thing
        costs.find("latent_moe_decode_experts", cell.data_dir)(config, traffic, 1, {"traced.active": 64.0}, arch)
    step = costs.find("ssm_latent_moe_decode_step", cell.data_dir)(config, traffic, 1, counters, arch)
    not_routed = PARAMS - TABLE - 5 * 128 * EXPERT
    kv, tails, looked_up = 243_200 * 1 * 2 * 2 * 128 * 2, 2 * 64 * 5 * 3 * 10_240 * 2, 64 * 4096 * 2
    assert 2 * not_routed == 1_981_461_248 and kv == 249_036_800 and tails == 39_321_600
    assert step["bytes"] == pytest.approx(2 * not_routed + looked_up + matrices + rows + 2 * held + tails + kv)
    every_lane = arch.matmul_params(config) - 5 * 5.5 * EXPERT
    assert step["flops"] == pytest.approx(2 * 64 * every_lane + experts["flops"] + 5 * held / 4 + 4 * 32 * 128 * 243_200)
    assert step["bytes"] / 819e9 > step["flops"] / 197e12                                   # a decode step is bound by what it moves
    assert step["bytes"] / 819e9 * 1e3 == pytest.approx(14.1, abs=0.1)                      # ISSUE 57's 14.2 ms a step
    # half of the lanes idle: the state's, the tails' and the rows' half, every hit expert's matrices all the same
    half = {**counters, "traced.serve.ssm.bytes": held / 2, "traced.serve.ssm.live_lanes": 32.0, "traced.active": 32.0}
    less = costs.find("ssm_latent_moe_decode_step", cell.data_dir)(config, traffic, 1, half, arch)["bytes"]
    assert step["bytes"] - less == pytest.approx(held + tails / 2 + looked_up / 2)


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
        step = [("%fusion.3 = bf16[64,18560] fusion(...)", 0.0, 1.0), ("%ssm_decode.5 = f32[64,128,64] custom-call(...)", 1.0, 4.0),
                ("%fusion.8 = bf16[64,4096] fusion(...)", 5.0, 1.0), ("%fusion.11 = bf16[64,1024] fusion(...)", 6.0, 0.5),
                ("%moe_gmm.2 = bf16[2048,2688] custom-call(...)", 6.5, 9.5), ("%fusion.9 = f32[65,32768] fusion(...)", 16.0, 4.0)]
        events = [("%fusion.3 = bf16[1,256,4096] fusion(...)", 1 * ms, 5 * ms)]
        for start in (10.0, 36.0, 62.0):
            events += [(n, (start + s) * ms, d * ms) for n, s, d in step]
        events = [e for e in events if e[1] + e[2] <= 80 * ms]
        return tr.TraceData(devices={"d": sorted(events, key=lambda e: e[1])}, host=[(tr.SYNC_NAME, 0.0, 0.0)])


def _decode_span(start_ms, lanes, hit, picks):
    return {"ph": "X", "name": "serve.decode", "ts": start_ms * 1e3, "dur": 20.5e3,
            "args": {"step": 1, "active": lanes, "live_kv_tokens": 3800 * lanes, "max_context": 8000,
                     "serve.ssm.live_lanes": float(lanes), "serve.ssm.bytes": float(lanes * 5 * SLOT),
                     "serve.moe.experts_hit": float(hit), "serve.moe.held_picks": float(picks)}}


def test_the_new_metrics_read_the_scopes_and_the_counters(cell):
    scopes = {"serve.mamba2.in": ["fusion.3"], "serve.mamba2.state": ["ssm_decode.5"], "serve.mamba2.out": ["fusion.8"],
              "serve.moe.latent": ["fusion.11"], "serve.moe.experts": ["moe_gmm.2"], "serve.head": ["fusion.9"]}
    events = [
        {"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode", "scopes": scopes}},
        _decode_span(9.9, 64, 600, 1760), _decode_span(35.9, 60, 580, 1640), _decode_span(61.9, 64, 610, 1770),   # the third is cut: not counted
    ]
    obs = Observations(window=(0.0, 1.0), spans=[], counters={}, program_events=events, profiler=_Traced(), config=cell.config,
                       traffic=cell.traffic, chips=1, program_epoch=0.0, arch=model.adapter(cell), data_dir=cell.data_dir)
    metric = lambda name: next(m for m in cell.per_layer if m["name"] == name)  # noqa: E731
    assert readers.read(metric("serve_mamba2_device_share"), obs, PEAK) == pytest.approx(100 * 6 / 20)
    assert readers.read(metric("serve_moe_latent_device_share"), obs, PEAK) == pytest.approx(100 * 0.5 / 20)
    held = 62 * 5 * SLOT                                                                    # the two whole steps' mean
    assert readers.read(metric("mamba2_state_roofline"), obs, PEAK) == pytest.approx(100 * 2 * held / 819e9 / 4e-3)
    hit, picks = 590, 1700
    experts = hit * 2 * 1024 * 2688 * 2 + picks * 2 * 1024 * 2
    assert readers.read(metric("latent_moe_decode_experts_roofline"), obs, PEAK) == pytest.approx(100 * experts / 819e9 / 9.5e-3)
    moved = 2 * (PARAMS - TABLE - 5 * 128 * EXPERT) + 62 * 4096 * 2 + experts + 2 * held + 2 * 62 * 5 * 3 * 10_240 * 2 + 3800 * 62 * 1024
    assert readers.read(metric("ssm_moe_decode_hbm_roofline"), obs, PEAK) == pytest.approx(100 * moved / 819e9 / 20e-3)
    # the counters' two, over every step that ENDS in the window (all three): rows a hit expert, and experts hit a layer
    assert readers.read(metric("moe_decode_rows_per_expert"), obs, PEAK) == pytest.approx((1760 + 1640 + 1770) / (600 + 580 + 610))
    assert readers.read(metric("latent_moe_decode_experts_hit"), obs, PEAK) == pytest.approx(600 / 5)
    assert all(readers.read(metric(n), obs, PEAK) < 100.0 for n in NEW if n.endswith("roofline"))
    # the parent commit: no such scopes, no such counters: nothing, and nothing raised
    bare = [dict(e, args={k: v for k, v in e["args"].items() if not k.startswith(("serve.ssm", "serve.moe"))}) for e in events if e["name"] != "jit.scopes"]
    bare.append({"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode", "scopes": {"serve.head": ["fusion.9"]}}})
    obs_bare = dataclasses.replace(obs, program_events=bare)
    for name in sorted(NEW):
        assert readers.read(metric(name), obs_bare, PEAK) is None, name


# ---------------------------------------------------------------------------
# the cell, end to end at a tiny size
# ---------------------------------------------------------------------------


def test_the_cell_runs_through_the_engine_and_agrees_with_its_reference(root, capsys):
    line = harness.run_cell("tiny-nemotron.closed", seed=2**31 + 57, seconds=1.5, traced=True, root=root, require_tpu=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    check = next(x for x in out if x["event"] == "serve.check")
    # 300 prefilled (a chunk of 256 and 44 of the second: the state and the tail carried, 212 padded rows advancing neither), 300 decoded
    assert check["rows"] == 301 and check["rel_rms"] < 1e-4 and check["top1_agree"] == 1.0
    values = next(x for x in out if x["event"] == "end_to_end_of_traced_run")["values"]
    assert {"tpot_p50_ms", "setup_s"} <= set(values)
    # the span- and counter-based metrics the cell lists read true for it; device metrics have nothing to read on a CPU
    assert {"serve_prefill_share", "serve_step_ms", "serve_queue_wait_ms", "moe_decode_rows_per_expert", "latent_moe_decode_experts_hit"} <= set(line["metrics"])
    assert not any("roofline" in k or "device" in k for k in line["metrics"])
    # two expert layers of 8 held experts here; a hit expert has at least one row
    rows, hit = (line["metrics"][n]["value"] for n in ("moe_decode_rows_per_expert", "latent_moe_decode_experts_hit"))
    assert 1.0 <= rows <= 4 * 4 and 0 < hit * 5 / 2 <= 8
    # the engine's own account, for an operator without a trace
    stats = next(x for x in out if x["event"] == "serve.window")["engine"]
    assert set(stats["step_counters"]) == {"serve.ssm.live_lanes", "serve.ssm.bytes", "serve.moe.held_picks", "serve.moe.experts_hit"}
    assert stats["ssm"] == {"slots": 4, "live": stats["ssm"]["live"], "bytes_per_slot": 2 * 8 * 16 * 8 * 4}
    assert stats["layers_by_kind"] == {"paged_kv": 1, "ssm_slot": 2, "none": 2} and stats["kv_cache"]["peak"] > 0


def test_the_programs_scopes_are_the_ones_the_lists_rest_on():
    import bench_rules as R

    scopes = R.scopes_of("nemotron_h", True)
    assert {"serve.mamba2.in", "serve.mamba2.state", "serve.mamba2.out", "serve.attn.qkv", "serve.kv.write", "serve.attn.attend", "serve.attn.out",
            "serve.moe.route", "serve.moe.latent", "serve.moe.experts", "serve.moe.shared", "serve.embed", "serve.head"} <= scopes
    assert not {"serve.mlp", "serve.ssm.state", "serve.mla", "serve.moe.identity"} & scopes


@pytest.mark.parametrize("told", sorted(NOT_THE_PROGRAMS))
def test_the_check_catches_a_reference_that_is_not_the_programs(root, capsys, told):
    line = harness.run_cell(f"tiny-nemotron-{told}.closed", seed=5, seconds=0.5, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 10 * check["tolerance"]["rel_rms"]


@pytest.mark.parametrize("broken", sorted(THE_PROGRAMS))
def test_the_check_catches_a_program_that_is_not_the_references(root, capsys, monkeypatch, broken):
    THE_PROGRAMS[broken](monkeypatch)
    line = harness.run_cell("tiny-nemotron.closed", seed=6, seconds=0.5, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 3 * check["tolerance"]["rel_rms"]
