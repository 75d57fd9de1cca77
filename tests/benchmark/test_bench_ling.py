"""The Ling-3.0-flash configuration, its adapter, reference, cost functions and
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

CELL = "serve-ling3-flash-l7-ep8-longgen"
CONFIG = "ling-3.0-flash-l7-ep8"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = {"kda_state_roofline", "serve_kda_device_share", "kda_mla_moe_decode_hbm_roofline"}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size"]
MIXER, LATENT, EXPERT, DENSE, PARAMS, SLOT, TABLE = 63_049_888, 31_965_696, 5_898_240, 47_185_920, 2_866_268_096, 2_097_152, 50_298_880
BESIDE = 1_310_720 + 512 + EXPERT                                                         # router, its bias, the shared expert

TINY = B.tiny_form("ling_kda_mla")["config"]
TINY_TRAFFIC = {
    "kind": "serve-closed", "clients": 4, "requests_per_client": 2,
    "prompt_tokens": {"shape": "lognormal", "median": 12, "sigma": 0.6, "min": 4, "max": 24}, "output_tokens": {"shape": "uniform", "min": 6, "max": 16},
    "temperature": 0.7, "slices": 4,
    "engine": B.tiny_form("ling_kda_mla")["serve_engine"],  # no prefix cache beside a state
}
TOLD_OTHERWISE = '''
from benchlib import model

_base = model.beside(__file__, "archs", "ling_kda_mla")
_told = _base.numerics
_base.numerics = lambda config: {{**_told(config), **{told!r}}}
globals().update({{k: v for k, v in vars(_base).items() if not k.startswith("__")}})
'''
#: the controls of the check that are the reference's to run: what each is told instead (ISSUE 66)
NOT_THE_PROGRAMS = {
    "head-mean-decay": {"head_decay": True},
    "beta-of-one": {"beta_one": True},
    "update-without-r": {"correct": False},
    "no-kda-output-gate": {"output_gate": False},
    "no-head-gate": {"head_gate": False},
    "softplus-gate": {"softplus_gate": True},
}
#: and the program's: the delta-rule state held in bfloat16 (the chip's runs use it too)
THE_PROGRAMS = {"state-bfloat16": state_in_bfloat16}


def told_otherwise(root, name, told):
    """An adapter file in ``root`` whose reference is told ``told`` instead of what the configuration states."""
    arch = "ling_kda_mla_" + name.replace("-", "_")
    with open(os.path.join(root, "benchmark", "archs", arch + ".py"), "w") as f:
        f.write(TOLD_OTHERWISE.format(told=told))
    return arch


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tests' throw-away root plus this cell at a tiny size."""
    tmp = B.throwaway_root(str(tmp_path_factory.mktemp("ling_root")))
    for part in ("costs", "readers"):
        shutil.copytree(os.path.join(B.BENCH, part), os.path.join(tmp, "benchmark", part), dirs_exist_ok=True)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(tmp, "benchmark", "traffic", "tiny-longgen.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    configs = {"tiny-ling": TINY}
    for k, told in NOT_THE_PROGRAMS.items():
        configs[f"tiny-ling-{k}"] = dict(TINY, arch=told_otherwise(tmp, k, told))
    for name, config in configs.items():
        with open(os.path.join(tmp, "benchmark", "configs", f"{name}.json"), "w") as f:
            json.dump(config, f)
        doc["configs"].append({"name": name, "source": "none", "file": f"benchmark/configs/{name}.json", "reduced": [], "why": "test"})
    cells = {f"{name}.closed": name for name in configs}
    for name, config in cells.items():
        doc["workloads"].append({"name": name, "config": config, "traffic": "tiny-longgen", "chips": 1, "why": "test"})
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
    assert [(w["config"], w["traffic"], w["chips"]) for w in doc["workloads"] if w["name"] == CELL] == [(CONFIG, "longgen-closed", 1)]
    assert not any(w["chips"] != 1 for w in doc["workloads"])                               # no four-chip cell
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "Ling-3.0-flash-VL")
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == published["source_url"] == cell.config["source"]
    assert entry["reduced"] == REDUCED == list(cell.config["reduced"])
    for key, value in published["config"].items():                                          # every width is as published, the lists whole
        if key not in entry["reduced"]:
            assert cell.config[key] == value, key
    config = cell.config
    assert (config["num_hidden_layers"], config["first_k_dense_replace"], config["num_experts"], config["vocab_size"]) == (7, 1, 64, 19_648)
    assert (config["num_experts_published"], config["first_expert_held"], config["arch"]) == (512, 0, "ling_kda_mla")
    # the guide's floors: a whole period and >= 4 layers after the leading dense one, >= 8 experts, an eighth of the vocabulary
    assert (7 - 1) % config["layer_group_size"] == 0 and 64 >= 8 and 19_648 * 8 == 157_184 and config["num_experts_per_tok"] == 8
    assert {"deployment", "assumed", "deviations", "dtypes", "tolerance"} <= set(config)
    assert "2,866,268,096 parameters" in config["deployment"] and "2,097,152 B a KDA layer" in config["deployment"] and "EIGHT chips share each layer" in config["deployment"]
    assert {"vision_tower", "mtp", "rope_pairs", "in_projection_columns", "norm_leaves", "prefix_cache", "torch_dtype", "training"} <= set(config["deviations"])
    assumed = config["assumed"]
    flags = ("layer_pattern", "kda_gate", "no_kda_lora", "head_wise_gate", "use_qk_norm", "kda_output", "state_dtype", "initialiser")
    assert set(flags) | {"variants_off", "mtp_use_kda", "rotary", "linear_heads", "router", "temperature", "unused"} <= set(assumed)
    assert all("Not run" in assumed[k] for k in flags)
    assert config["dtypes"] == {"serve_params": "bfloat16", "compute": "bfloat16", "state": "float32", "kv_cache": "bfloat16"}
    # the check prefills a wide chunk, a narrow one and 2 rows of another: the last prefilled row reads two of the
    # convolution's four taps across a chunk's edge (Falcon-H1's file argues the half)
    from determined_tpu.serve.config import ServeConfig
    serve_cfg = ServeConfig(**cell.traffic["engine"])
    prefilled = config["tolerance"]["serve_logits"]["sequence_tokens"] // 2
    assert 0 < prefilled % serve_cfg.prefill_chunk < config["short_conv_kernel_size"] and prefilled > 1024 + serve_cfg.prefill_chunk
    why = config["tolerance"]["serve_logits"]["why"]
    assert "bfloat16" in why and "head-mean decay" in why                                   # the reason names the controls that fail it
    # the cell's traffic and engine are ISSUE 66's, to the number
    t = cell.traffic
    assert (t["kind"], t["clients"], t["temperature"], t["slices"]) == ("serve-closed", 128, 0.7, 10)
    assert t["prompt_tokens"] == {"shape": "lognormal", "median": 1024, "sigma": 0.6, "min": 512, "max": 4096}
    assert t["output_tokens"] == {"shape": "uniform", "min": 4096, "max": 12_288}
    assert t["engine"] == {"block_size": 16, "num_blocks": 131_073, "max_batch": 128, "decode_chunk_blocks": 1, "prefix_cache": False,
                           "max_prompt_len": 16_384, "max_new_tokens": 12_288, "queue_depth": 256}
    assert 131_073 == 128 * (4096 + 12_288) // 16 + 1                                       # 128 worst-case requests and the scratch block
    mine = {m["name"]: m for m in cell.per_layer}
    assert NEW <= set(mine) and all(mine[n]["moves"] == "tpot_p50_ms" and mine[n]["workloads"] == [CELL] for n in NEW)
    assert {"decode_device_ms", "serve_device_idle_share", "serve_mla_device_share", "serve_moe_device_share", "serve_moe_route_device_share",
            "serve_mlp_device_share", "serve_vocab_device_share", "serve_step_sample_ms", "serve_decode_named_device_share", "tpot_decode_wait_ms",
            "mla_decode_attn_roofline", "moe_decode_experts_roofline", "moe_decode_rows_per_expert"} <= set(mine)
    # the scalar-decay form's scopes are not this program's, nor another architecture's costs
    assert not {"serve_gdn_device_share", "gdn_state_roofline", "gdn_moe_decode_hbm_roofline", "mla_moe_decode_hbm_roofline", "decode_hbm_roofline",
                "serve_attn_device_share", "serve_ssm_device_share", "serve_dsa_device_share"} & set(mine)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms", "setup_s"}
    assert {n: mine[n]["reader"]["reader"] for n in NEW} == {n: "decode_burst_ops" for n in NEW}
    assert mine["serve_kda_device_share"]["reader"]["cells"] == {"of": "serving", "scope": "serve.kda.state"}
    assert mine["kda_state_roofline"]["reader"]["args"]["scopes"] == ["serve.kda.state"] and "cells" not in mine["kda_state_roofline"]["reader"]


#: what this file asserts of the DOCUMENT: each takes a ``Spec``, so that
#: test_bench_rules.py can hold a document with one more cell to all of them
DOCUMENT_CHECKS = [the_document_and_the_configuration_keep_the_contract]


def test_the_document_and_the_configuration_keep_the_contract():
    the_document_and_the_configuration_keep_the_contract(S.Spec())


def test_the_adapter_meets_the_interface_and_counts_what_the_program_holds(cell):
    arch, config = model.adapter(cell), cell.config
    assert all(callable(getattr(arch, name)) for name in model.INTERFACE)
    mixer = arch.mixer_params(config)
    assert mixer == {"w_in": 41_943_040, "w_decay": 10_485_760, "w_b": 81_920, "conv": 49_152, "dt_bias": 4096, "A_log": 32, "norm": 128, "w_out": 10_485_760}
    assert sum(mixer.values()) == MIXER and 2560 * 16_384 == 41_943_040
    latent = arch.attention_params(config)
    assert latent == {"wq": 15_728_640, "wkv_a": 1_474_560, "kv_norm": 512, "wkv_b": 4_194_304, "w_gate": 81_920, "wo": 10_485_760} and sum(latent.values()) == LATENT
    assert arch.ffn_params(config) == {"dense": DENSE, "router": 1_310_720, "router_bias": 512, "shared": EXPERT, "expert": EXPERT}
    assert EXPERT == 3 * 2560 * 768 and DENSE == 3 * 2560 * 6144
    assert arch.layer_counts(config) == {"linear_attention": 6, "full_attention": 1, "dense": 1, "experts": 6}
    assert arch.pattern(config) == ["linear_attention"] * 5 + ["full_attention", "linear_attention"]
    # layer 0, a KDA expert layer, layer 5: the configuration file's 110,240,928, 447,751,840 and 416,667,648
    kda_layer, norms = MIXER + BESIDE + 64 * EXPERT + 5120, 5120
    assert (MIXER + DENSE + norms, kda_layer, LATENT + BESIDE + 64 * EXPERT + norms) == (110_240_928, 447_751_840, 416_667_648)
    assert arch.total_params(config) == 110_240_928 + 5 * 447_751_840 + 416_667_648 + 2 * TABLE + 2560 == PARAMS
    assert arch.embedding_params(config) == 19_648 * 2560 == TABLE
    # the published model by the same functions: ~125 B in all, ~5.5 B a token with one table (the catalog's "~125B-A5.5B")
    whole = dict(config, num_experts=512, vocab_size=157_184, num_hidden_layers=42, first_k_dense_replace=2)
    assert arch.layer_counts(whole) == {"linear_attention": 35, "full_attention": 7, "dense": 2, "experts": 40}
    assert arch.total_params(whole) / 1e9 == pytest.approx(124.3, abs=0.5)
    active = arch.total_params(whole) - 40 * (512 - 8) * EXPERT - 157_184 * 2560
    assert active / 1e9 == pytest.approx(5.4, abs=0.3)
    # what a token multiplies with here: 8 x 64 / 512 = 1 of its picks is held
    assert arch.expert_shape(config) == {"d_model": 2560, "d_ff": 768, "matrices": 3, "held": 64, "layers": 6, "shared_d_ff": 768, "expected_held_picks": 1.0}
    products = mixer["w_in"] + mixer["w_decay"] + mixer["w_b"] + mixer["w_out"]
    assert arch.matmul_params(config) == 6 * products + (LATENT - 512) + DENSE + 6 * (1_310_720 + EXPERT + 1.0 * EXPERT) + TABLE
    assert arch.attention_shape(config) == {"heads": 32, "kv_heads": 1, "layers": 1, "head_dim": 192, "latent": 512, "rope": 64, "v_head_dim": 128}
    assert arch.latent_shape(config) == {"kv_lora_rank": 512, "qk_rope_head_dim": 64, "heads": 32, "layers": 1}
    assert arch.kda_shape(config) == {"heads": 32, "key_dim": 128, "value_dim": 128, "conv": 4, "channels": 12_288, "layers": 6, "bytes_per_slot": SLOT}
    cfg = arch.model_config(config, 28_672)
    assert cfg.layer_types == ("linear_attention",) * 5 + ("full_attention", "linear_attention") and cfg.norm_eps == 1e-6 and cfg.latent
    assert (cfg.n_heads, cfg.head_dim, cfg.param_dtype, cfg.d_ff, cfg.dense_prefix) == (32, 128, jnp.bfloat16, 6144, 1) and cfg.rope("full_attention").theta == 6e6
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.attn_output_gate) == (None, 512, 128, 64, 128, True)
    assert (cfg.linear_channel_decay, cfg.linear_decay_floor, cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_conv, cfg.linear_channels) == (True, -5.0, 32, 32, 4, 12_288)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_experts_held, cfg.moe_intermediate_size, cfg.moe_router) == (512, 8, (0, 64), 768, "sigmoid_grouped")
    assert (cfg.moe_n_group, cfg.moe_topk_group, cfg.moe_routed_scaling, cfg.moe_shared_experts) == (8, 4, 2.5, 1)
    assert [cfg.use_moe(i) for i in range(7)] == [False] + [True] * 6
    # the program's own tree holds as many (shapes only), all bfloat16; the cache is ONE layer's latent rows in blocks
    # and a delta-rule state and a tail a lane for six
    from determined_tpu.models.cache_kinds import DELTA_SLOT, PAGED_LATENT, cache_kinds, layers_by_kind
    from determined_tpu.models.transformer import STATE_DTYPE, TransformerLM, gdn_bytes_per_slot, gdn_pool_shapes, kv_bytes_per_token, kv_cache_shape
    from determined_tpu.ops import gated_delta

    tree = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
    shapes = jax.tree_util.tree_leaves(tree)
    assert sum(x.size for x in shapes) == PARAMS
    assert {str(x.dtype): sum(y.size for y in shapes if y.dtype == x.dtype) for x in shapes} == {"bfloat16": PARAMS - 6 * 512, "float32": 6 * 512}   # the selection bias
    assert cache_kinds(cfg) == (PAGED_LATENT, DELTA_SLOT) and jnp.dtype(STATE_DTYPE) == jnp.float32
    assert layers_by_kind(cfg) == {"paged_latent": 1, "delta_slot": 6, "none": 0}
    assert gdn_pool_shapes(cfg, 128) == ((6, 129, 32, 128, 128), (6, 128, 3, 12_288)) and gdn_bytes_per_slot(cfg) == SLOT
    assert kv_cache_shape(cfg, 131_073, 16) == (1, 131_073, 16, 640) and kv_bytes_per_token(cfg) == 1152
    assert gated_delta.kernel_takes(32, 128, 128, jnp.float32) and gated_delta.heads_a_program(32, 128, 128, jnp.float32) == 32
    pool = 131_073 * 16 * 640 * 2
    held = 2 * PARAMS + 6 * 129 * SLOT + 6 * 128 * 3 * 12_288 * 2 + pool
    assert pool == 2_684_375_040 and held / 1e9 == pytest.approx(10.10, abs=0.01) and held / 2**34 > 0.25       # 59 % of 16 GiB
    # a parent whose program lacks the decay's form is refused by name, with the harness's own error (exit code 3)
    from unittest import mock

    from determined_tpu.models import transformer as T

    few = [f for f in dataclasses.fields(T.TransformerConfig) if f.name not in ("linear_decay_floor", "attn_output_gate")]
    with mock.patch.object(dataclasses, "fields", lambda cls: few), pytest.raises(S.SpecError, match="lacks attn_output_gate, linear_decay_floor"):
        arch.check_as_run(config)
    for name in ("trial_hparams", "trial_overrides"):                                       # served only
        with pytest.raises(ValueError, match=f"served only \\({name}\\).*14.1 GB"):
            getattr(arch, name)(config)
    # an expert's three matrices are drawn at ITS fan-in, the bias is not zeros, and every channel remembers 333 to 53,333 tokens
    tiny_cfg = arch.model_config(TINY, 640)
    params = arch.init_params(tiny_cfg, 3)
    experts = params["block_1"]["moe"]
    assert float(jnp.std(experts["w_up"])) == pytest.approx(48 ** -0.5, rel=0.06) and float(jnp.std(experts["w_down"])) == pytest.approx(24 ** -0.5, rel=0.06)
    assert float(jnp.abs(experts["router_bias"]).max()) > 0 and "gdn" in params["block_6"] and "attn" in params["block_5"] and "mlp" in params["block_0"]
    mixer = params["block_0"]["gdn"]
    memory = -1.0 / (-5.0 * jax.nn.sigmoid(jnp.exp(mixer["A_log"]).repeat(16) * mixer["dt_bias"]))
    assert mixer["dt_bias"].shape == (64,) and 333 <= float(memory.min()) and float(memory.max()) <= 53_334


@pytest.mark.parametrize("change,says", [
    (dict(expert_swiglu_limit_list=[0] * 6 + [4] + [0] * 35), "expert_swiglu_limit_list: the kept layers \\[6\\] clamp"),
    (dict(share_expert_swiglu_limit_list=[0, 5] + [0] * 40), "share_expert_swiglu_limit_list: the kept layers \\[1\\] clamp"),
    (dict(kda_safe_gate=False), "kda_safe_gate false leaves the decay's gate unstated"),
    (dict(kda_safe_gate=False, kda_gate="softplus"), "kda_safe_gate false with the softplus gate: the program runs the bounded gate alone"),
    (dict(vision_config={"depth": 27}), "the vision tower is not built .*vision_config"),
    (dict(q_lora_rank=1536), "the program runs q_lora_rank = None"),
    (dict(gated_attention_proj_granularity_type="elementwise"), "gated_attention_proj_granularity_type = 'head_wise'"),
    (dict(use_kda_lora=True), "use_kda_lora = False"),
    (dict(num_hidden_layers=5), "at least one whole period"),
    (dict(rotary_dim=32), "ONE width three times"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(dtypes={"serve_params": "bfloat16", "compute": "bfloat16", "state": "bfloat16", "kv_cache": "bfloat16"}), "float32 state"),
])
def test_a_file_the_program_cannot_run_as_stated_is_refused_by_name(cell, change, says):
    arch = model.adapter(cell)
    with pytest.raises(ValueError, match=says):
        arch.check_as_run(dict(cell.config, **change))
    # the layers past the cut may clamp: the published lists are copied whole, and pass
    arch.check_as_run(cell.config)
    assert any(cell.config["expert_swiglu_limit_list"][7:]) and not any(cell.config["expert_swiglu_limit_list"][:7])


def test_cost_functions_count_the_state_twice_its_operands_and_the_whole_step(cell):
    arch, config, traffic = model.adapter(cell), cell.config, cell.traffic
    held = 128 * 6 * SLOT
    counters = {"traced.serve.gdn.bytes": float(held), "traced.serve.gdn.live_lanes": 128.0, "traced.active": 128.0,
                "traced.live_kv_tokens": 729_600.0, "traced.serve.moe.experts_hit": 330.0, "traced.serve.moe.held_picks": 768.0}
    state = costs.find("kda_state", cell.data_dir)(config, traffic, 1, counters, arch)
    operands = 128 * 6 * 32 * (3 * 128 + 3 * 128) * 4
    assert state == {"flops": 7.0 * held / 4, "bytes": 2.0 * held + operands} and state["bytes"] / 1e9 == pytest.approx(3.30, abs=0.01)   # ISSUE 66's 3.2 GB and 2.3 % of operands
    assert operands / (2.0 * held) == pytest.approx(0.0234, abs=0.001) and state["bytes"] / 819e9 > state["flops"] / 197e12
    with pytest.raises(KeyError):                                                           # a program that counts no such thing
        costs.find("kda_state", cell.data_dir)(config, traffic, 1, {"traced.active": 128.0}, arch)
    experts = costs.find("moe_decode_experts", cell.data_dir)(config, traffic, 1, counters, arch)
    matrices, rows = 330 * EXPERT * 2, 768 * (2 * 2560 + 3 * 768) * 2
    assert experts["bytes"] == matrices + rows and matrices / 1e9 == pytest.approx(3.89, abs=0.01)   # 55 of 64 hit in six layers: ISSUE 66's 3.9 GB
    rows_read = costs.find("mla_paged_attention", cell.data_dir)(config, traffic, 1, counters, arch)
    assert rows_read == {"flops": 2.0 * 32 * (576 + 512) * 729_600, "bytes": 1152.0 * 729_600} and rows_read["bytes"] / 1e9 == pytest.approx(0.84, abs=0.01)
    step = costs.find("kda_mla_moe_decode_step", cell.data_dir)(config, traffic, 1, counters, arch)
    not_routed = PARAMS - TABLE - 6 * 64 * EXPERT
    tails, looked_up = 2 * 128 * 6 * 3 * 12_288 * 2, 128 * 2560 * 2
    assert 2 * not_routed / 1e9 == pytest.approx(1.10, abs=0.01)                            # mixers, shared experts, routers, dense FFN, head: 1.1 GB
    assert step["bytes"] == pytest.approx(2 * not_routed + looked_up + matrices + rows + state["bytes"] + tails + rows_read["bytes"])
    every_lane = arch.matmul_params(config) - 6 * 1.0 * EXPERT
    assert step["flops"] == pytest.approx(2 * 128 * every_lane + experts["flops"] + state["flops"] + rows_read["flops"])
    assert step["bytes"] / 819e9 > step["flops"] / 197e12                                   # a decode step is bound by what it moves
    assert step["bytes"] / 819e9 * 1e3 == pytest.approx(11.3, abs=0.15)                     # ISSUE 66 reckoned 9.05 GB = 11 ms a step
    # the two new things, a state with a decay a channel and a latent row beside it, are 45 % of the step's bytes
    assert (state["bytes"] + rows_read["bytes"]) / step["bytes"] == pytest.approx(0.45, abs=0.01)
    half = {**counters, "traced.serve.gdn.bytes": held / 2, "traced.serve.gdn.live_lanes": 64.0, "traced.active": 64.0}
    less = costs.find("kda_mla_moe_decode_step", cell.data_dir)(config, traffic, 1, half, arch)["bytes"]
    assert step["bytes"] - less == pytest.approx(held + operands / 2 + tails / 2 + looked_up / 2)


# ---------------------------------------------------------------------------
# the readers, on a small synthetic trace
# ---------------------------------------------------------------------------


class _Traced:
    """A prefill and two whole decode steps on one device, a third cut by the
    trace's end; 30 ms of operations a step and 6 ms idle between two."""

    trace_dir = ""
    sync_marks_ns = [0.0]

    def data(self):
        from benchlib import trace as tr

        ms = 1e6
        step = [("%fusion.3 = bf16[128,20512] fusion(...)", 0.0, 2.0), ("%fusion.4 = f32[128,12288] fusion(...)", 2.0, 1.0),
                ("%gdn_decode.5 = f32[128,32,128] custom-call(...)", 3.0, 6.0), ("%fusion.8 = bf16[128,2560] fusion(...)", 9.0, 1.0),
                ("%paged_latent.6 = f32[128,32,640] custom-call(...)", 10.0, 2.0), ("%fusion.7 = bf16[128,32,128] fusion(...)", 12.0, 0.5),
                ("%moe_gmm.2 = bf16[1024,768] custom-call(...)", 12.5, 12.5), ("%fusion.9 = f32[129,19648] fusion(...)", 25.0, 5.0)]
        events = [("%fusion.3 = bf16[1,1024,2560] fusion(...)", 1 * ms, 5 * ms)]
        for start in (10.0, 46.0, 82.0):
            events += [(n, (start + s) * ms, d * ms) for n, s, d in step]
        events = [e for e in events if e[1] + e[2] <= 110 * ms]
        return tr.TraceData(devices={"d": sorted(events, key=lambda e: e[1])}, host=[(tr.SYNC_NAME, 0.0, 0.0)])


def _decode_span(start_ms, lanes, hit, picks):
    return {"ph": "X", "name": "serve.decode", "ts": start_ms * 1e3, "dur": 30.5e3,
            "args": {"step": 1, "active": lanes, "live_kv_tokens": 5700 * lanes, "max_context": 16000,
                     "serve.gdn.live_lanes": float(lanes), "serve.gdn.bytes": float(lanes * 6 * SLOT),
                     "serve.moe.experts_hit": float(hit), "serve.moe.held_picks": float(picks)}}


def test_the_new_metrics_read_the_scopes_and_the_counters(cell):
    scopes = {"serve.kda.proj": ["fusion.3"], "serve.kda.conv": ["fusion.4"], "serve.kda.state": ["gdn_decode.5"], "serve.kda.out": ["fusion.8"],
              "serve.mla.attend": ["paged_latent.6"], "serve.mla.gate": ["fusion.7"], "serve.moe.experts": ["moe_gmm.2"], "serve.head": ["fusion.9"]}
    events = [
        {"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": {"program": "jit.compile.serve.decode", "scopes": scopes}},
        _decode_span(9.9, 128, 330, 768), _decode_span(45.9, 120, 320, 720), _decode_span(81.9, 128, 335, 768),   # the third is cut: not counted
    ]
    obs = Observations(window=(0.0, 1.0), spans=[], counters={}, program_events=events, profiler=_Traced(), config=cell.config,
                       traffic=cell.traffic, chips=1, program_epoch=0.0, arch=model.adapter(cell), data_dir=cell.data_dir)
    metric = lambda name: next(m for m in cell.per_layer if m["name"] == name)  # noqa: E731
    assert readers.read(metric("serve_kda_device_share"), obs, PEAK) == pytest.approx(100 * 10 / 30)
    held = 124 * 6 * SLOT                                                                   # the two whole steps' mean
    state = 2 * held + 124 * 6 * 32 * 768 * 4
    assert readers.read(metric("kda_state_roofline"), obs, PEAK) == pytest.approx(100 * state / 819e9 / 6e-3)
    hit, picks = 325, 744
    experts = hit * EXPERT * 2 + picks * (2 * 2560 + 3 * 768) * 2
    assert readers.read(metric("moe_decode_experts_roofline"), obs, PEAK) == pytest.approx(100 * experts / 819e9 / 12.5e-3)
    rows = 5700 * 124 * 1152
    assert readers.read(metric("mla_decode_attn_roofline"), obs, PEAK) == pytest.approx(100 * rows / 819e9 / 2e-3)
    moved = 2 * (PARAMS - TABLE - 6 * 64 * EXPERT) + 124 * 2560 * 2 + experts + state + 2 * 124 * 6 * 3 * 12_288 * 2 + rows
    assert readers.read(metric("kda_mla_moe_decode_hbm_roofline"), obs, PEAK) == pytest.approx(100 * moved / 819e9 / 30e-3)
    assert readers.read(metric("moe_decode_rows_per_expert"), obs, PEAK) == pytest.approx((768 + 720 + 768) / (330 + 320 + 335))
    assert all(readers.read(metric(n), obs, PEAK) < 100.0 for n in NEW | {"mla_decode_attn_roofline"} if n.endswith("roofline"))
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
    line = harness.run_cell("tiny-ling.closed", seed=2**31 + 66, seconds=1.5, traced=True, root=root, require_tpu=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    check = next(x for x in out if x["event"] == "serve.check")
    # 300 prefilled (a chunk of 256 and 44 of the second: the state and the tail carried, 212 padded rows advancing neither), 300 decoded
    assert check["rows"] == 301 and check["rel_rms"] < 1e-4 and check["top1_agree"] == 1.0
    values = next(x for x in out if x["event"] == "end_to_end_of_traced_run")["values"]
    assert {"tpot_p50_ms", "setup_s"} <= set(values)
    # the span- and counter-based metrics the cell lists read true for it; device metrics have nothing to read on a CPU
    assert {"serve_step_ms", "serve_queue_wait_ms", "moe_decode_rows_per_expert"} <= set(line["metrics"])
    assert not any("roofline" in k or "device" in k for k in line["metrics"])
    # the engine's own account, for an operator without a trace: both kinds a request holds
    stats = next(x for x in out if x["event"] == "serve.window")["engine"]
    assert set(stats["step_counters"]) == {"serve.gdn.live_lanes", "serve.gdn.bytes", "serve.moe.held_picks", "serve.moe.experts_hit"}
    assert stats["gdn"] == {"slots": 4, "live": stats["gdn"]["live"], "bytes_per_slot": 6 * 4 * 16 * 16 * 4} and stats["kv_cache"]["peak"] > 0


def test_the_programs_scopes_are_the_ones_the_lists_rest_on():
    import bench_rules as R

    scopes = R.scopes_of("ling_kda_mla", True)
    assert {"serve.kda.proj", "serve.kda.conv", "serve.kda.state", "serve.kda.out", "serve.mla", "serve.mla.attend", "serve.mla.gate",
            "serve.kv.write", "serve.mlp", "serve.moe.route", "serve.moe.experts", "serve.moe.shared", "serve.embed", "serve.head"} <= scopes
    assert not {"serve.gdn.state", "serve.gdn.proj", "serve.attn.qkv", "serve.ssm.state", "serve.dsa", "serve.moe.latent"} & scopes
    # and the scalar-decay form's program keeps its own names: one mixer, two forms, told apart by scope
    assert "serve.gdn.state" in R.scopes_of("qwen3_next", True) and "serve.kda.state" not in R.scopes_of("qwen3_next", True)


@pytest.mark.parametrize("told", sorted(NOT_THE_PROGRAMS))
def test_the_check_catches_a_reference_that_is_not_the_programs(root, capsys, told):
    line = harness.run_cell(f"tiny-ling-{told}.closed", seed=5, seconds=0.5, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 10 * check["tolerance"]["rel_rms"]


@pytest.mark.parametrize("broken", sorted(THE_PROGRAMS))
def test_the_check_catches_a_program_that_is_not_the_references(root, capsys, monkeypatch, broken):
    THE_PROGRAMS[broken](monkeypatch)
    line = harness.run_cell("tiny-ling.closed", seed=6, seconds=0.5, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 3 * check["tolerance"]["rel_rms"]
