"""The GLM-5.2 configuration, its adapter, reference and cost functions: the
arithmetic the cell's numbers rest on (the cut's count against the program's
own tree, the uncut model's against what is published), and the cell run end to
end in a throw-away root on the CPU at a tiny size whose ``index_topk`` its
contexts pass (``correct: true``, and ``false`` against a reference that is
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

CELL = "serve-glm52-l5-ep16-longreason"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "mlp_layer_types", "indexer_types", "n_routed_experts", "vocab_size"]

TINY_GLM = B.tiny_form("glm_moe_dsa")["config"]
#: an adapter of the test's own, whose reference is told something else than the configuration states
TOLD_OTHERWISE = '''
from benchlib import model

_base = model.beside(__file__, "archs", "glm_moe_dsa")
_told = _base.numerics
_base.numerics = lambda config: {{**_told(config), **{told!r}}}
globals().update({{k: v for k, v in vars(_base).items() if not k.startswith("__")}})
'''
#: references that are not the program's: what each is told instead
NOT_THE_PROGRAMS = {
    "no-selection": {"index_topk": 10**6},   # every live row attended
    "half-the-picks": {"index_topk": 4},
    "no-scaling": {"scaling": 1.0},
    "other-experts": {"first_expert": 4},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tests' throw-away root plus this cell at a tiny size."""
    tmp = B.throwaway_root(str(tmp_path_factory.mktemp("glm_root")))
    shutil.copytree(os.path.join(B.BENCH, "costs"), os.path.join(tmp, "benchmark", "costs"), dirs_exist_ok=True)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        doc = json.load(f)
    configs = {"tiny-glm": TINY_GLM}
    for k, told in NOT_THE_PROGRAMS.items():
        arch = "glm_" + k.replace("-", "_")
        configs[f"tiny-glm-{k}"] = dict(TINY_GLM, arch=arch)
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


def the_document_and_the_configuration_keep_the_contract(spec):
    doc, cell = spec.doc, spec.cell(CELL)
    assert S.check_document(doc) == []
    assert [(w["config"], w["traffic"], w["chips"]) for w in doc["workloads"] if w["name"] == CELL] == [("glm-5.2-l5-ep16", "longreason-closed", 1)]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "GLM-5.2")
    entry = next(c for c in doc["configs"] if c["name"] == "glm-5.2-l5-ep16")
    assert entry["source"] == published["source_url"] == cell.config["source"]
    assert entry["reduced"] == REDUCED == list(cell.config["reduced"])
    for key, value in published["config"].items():
        if key not in REDUCED:
            assert cell.config[key] == value, key
    c = cell.config
    assert [c[k] for k in REDUCED] == [5, 1, ["dense"] + ["sparse"] * 4, ["full", "shared", "shared", "shared", "full"], 16, 19360]
    assert c["indexer_types"] == published["config"]["indexer_types"][2:7]   # published layers 2-6, a contiguous piece
    assert (c["n_routed_experts_published"], c["first_expert_held"]) == (256, 0)
    # every published width is kept
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"]) == (6144, 64, 2048, 512, 192, 64, 256)
    assert (c["index_n_heads"], c["index_head_dim"], c["index_topk"], c["intermediate_size"], c["moe_intermediate_size"],
            c["num_experts_per_tok"]) == (32, 128, 2048, 12288, 2048, 8)
    assert {"deployment", "assumed", "deviations", "dtypes", "tolerance"} <= set(c)
    assert {"torch_dtype", "num_nextn_predict_layers", "index_keys", "latent_row"} <= set(c["deviations"])
    assert {"indexer", "shared_layers", "ties", "initialiser", "unused"} <= set(c["assumed"])
    assert c["dtypes"] == {"serve_params": "bfloat16", "kv_cache": "bfloat16", "compute": "bfloat16"}
    assert c["tolerance"]["serve_logits"]["sequence_tokens"] >= 4096
    t = cell.traffic
    assert (t["kind"], t["clients"], t["requests_per_client"], t["temperature"]) == ("serve-closed", 16, 4, 0.6)
    assert t["prompt_tokens"] == {"shape": "uniform", "min": 8192, "max": 16384} and t["output_tokens"] == {"shape": "uniform", "min": 4096, "max": 8192}
    assert t["engine"] == {"block_size": 16, "num_blocks": 28673, "max_batch": 16, "decode_chunk_blocks": 1, "prefix_cache": True,
                           "max_prompt_len": 24576, "max_new_tokens": 8192, "queue_depth": 32}
    assert 28673 == 16 * (24576 // 16) + 4096 + 1
    new = {"dsa_index_roofline", "dsa_sparse_attn_roofline", "dsa_moe_decode_hbm_roofline", "serve_dsa_device_share",
           "serve_dsa_topk_device_share", "dsa_selected_share"}
    mine = {m["name"]: m for m in cell.per_layer}
    assert new <= set(mine) and all(mine[n]["moves"] == "tpot_p50_ms" and mine[n]["workloads"] == [CELL] for n in new)
    # on no list whose cost counts rows it does not have to read
    assert not {"mla_decode_attn_roofline", "mla_moe_decode_hbm_roofline", "decode_hbm_roofline"} & set(mine)
    assert {"serve_mla_device_share", "serve_moe_device_share", "serve_moe_route_device_share", "serve_mlp_device_share",
            "serve_vocab_device_share", "serve_prefill_share", "moe_decode_experts_roofline", "moe_decode_experts_hit"} <= set(mine)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms", "setup_s"}


#: what this file asserts of the DOCUMENT (test_bench_rules.py holds a document with one more cell to it)
DOCUMENT_CHECKS = [the_document_and_the_configuration_keep_the_contract]


def test_the_document_and_the_configuration_keep_the_contract():
    the_document_and_the_configuration_keep_the_contract(S.Spec())


def test_the_adapter_counts_the_cut_against_the_programs_tree_and_the_uncut_model_as_published(cell):
    arch, config = model.adapter(cell), cell.config
    assert all(callable(getattr(arch, name)) for name in model.INTERFACE)
    attention = 6144 * 2048 + 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 + 512 * 64 * 448 + 64 * 256 * 6144
    indexer = 2048 * 32 * 128 + 6144 * 128 + 256 + 6144 * 32
    expert = 3 * 6144 * 2048
    assert (attention, indexer, expert) == (165_022_208, 9_371_904, 37_748_736)
    shares = attention + 2 * 6144 + 6144 * 256 + 256 + expert
    dense = attention + indexer + 2 * 6144 + 3 * 6144 * 12288
    assert (shares, shares + indexer, dense) == (204_356_352, 213_728_256, 400_898_816)
    table = 19360 * 6144
    assert arch.total_params(config) == dense + 3 * (shares + 16 * expert) + (shares + indexer + 16 * expert) + 2 * table + 6144 == 3_881_517_056
    assert arch.embedding_params(config) == table and arch.indexer_params(config) + 256 == indexer
    assert arch.index_shape(config) == {"heads": 32, "dim": 128, "topk": 2048, "full_layers": 2, "layers": 5}
    assert arch.latent_shape(config)["heads"] == 64 and arch.expert_shape(config)["expected_held_picks"] == 0.5
    # the program's own tree holds as many, bfloat16 but for the four routers' float32 biases (shapes only)
    cfg = arch.model_config(config, 24576)
    assert (cfg.indexer_types, cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (("full", "shared", "shared", "shared", "full"), 32, 128, 2048)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_experts_held, cfg.moe_n_group, cfg.dense_prefix, cfg.norm_eps, cfg.rope_theta) == (256, 8, (0, 16), 1, 1, 1e-5, 8e6)
    from determined_tpu.models.cache_kinds import PAGED_INDEXED, _nbytes
    from determined_tpu.models.transformer import TransformerLM, kv_bytes_per_token
    from determined_tpu.serve.config import ServeConfig

    shapes = jax.tree_util.tree_leaves(jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0)))
    assert sum(x.size for x in shapes) == 3_881_517_056
    assert sum(x.size * x.dtype.itemsize for x in shapes) == 2 * 3_881_517_056 + 2 * 4 * 256
    # the cache: 6,912 B a token as stored, 3.17 GB at the cell's pool
    sizes = ServeConfig(**cell.traffic["engine"])
    assert kv_bytes_per_token(cfg) == 5 * 1152 and PAGED_INDEXED.shapes(cfg, sizes) == ((5, 28673, 16, 640), (2, 28673, 16, 128))
    assert _nbytes(PAGED_INDEXED, cfg, sizes) == 28673 * 16 * 6912 and 28673 * 16 * 6912 / 1e9 == pytest.approx(3.171, abs=1e-3)
    # the uncut model: every published layer, every expert, the whole vocabulary
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "GLM-5.2")["config"]
    uncut = {**config, **{k: published[k] for k in REDUCED}, "n_routed_experts_published": 256}
    assert published["indexer_types"].count("full") == 21 and published["num_hidden_layers"] == 78
    assert arch.total_params(uncut) / 1e9 == pytest.approx(743.4, abs=0.05)
    assert arch.matmul_params(uncut) / 1e9 == pytest.approx(40.3, abs=0.05)      # 8 experts a token and one table
    # the training functions refuse by name
    for fn in (arch.trial_hparams, arch.trial_overrides):
        with pytest.raises(ValueError, match="served only .* 42.8 GB"):
            fn(config)
    # a parent whose config lacks the fields is refused by name, with the harness's own error (exit code 3)
    from unittest import mock

    from determined_tpu.models import transformer as T

    few = [f for f in dataclasses.fields(T.TransformerConfig) if f.name != "indexer_types"]
    with mock.patch.object(dataclasses, "fields", lambda cls: few), pytest.raises(S.SpecError, match="lacks indexer_types"):
        arch.check_as_run(config)
    with pytest.raises(ValueError, match="indexer_types states every layer"):
        arch.check_as_run({**config, "indexer_types": ["shared"] * 5})


def test_cost_functions_count_scored_keys_picked_rows_and_the_whole_step(cell):
    arch, config, traffic = model.adapter(cell), cell.config, cell.traffic
    live = 16 * 15_400.0
    counters = {"traced.serve.dsa.index_tokens": 2 * live, "traced.serve.dsa.selected_tokens": 5 * 16 * 2048.0,
                "traced.serve.dsa.live_tokens": 5 * live, "traced.serve.moe.experts_hit": 25.2, "traced.serve.moe.held_picks": 32.0,
                "traced.active": 16.0}
    index = costs.find("dsa_index_scores", cell.data_dir)(config, traffic, 1, counters, arch)
    assert index == {"flops": pytest.approx(2 * live * 2 * 32 * 128), "bytes": pytest.approx(2 * live * 256)}
    assert index["bytes"] / 1e9 == pytest.approx(0.126, abs=1e-3)                              # ISSUE 61's 0.13 GB
    att = costs.find("dsa_sparse_attention", cell.data_dir)(config, traffic, 1, counters, arch)
    assert att == {"flops": pytest.approx(163_840 * 2 * 64 * 1088), "bytes": pytest.approx(163_840 * 1152)}
    assert att["bytes"] / 1e9 == pytest.approx(0.189, abs=1e-3) and 5 * live * 1152 / 1e9 == pytest.approx(1.42, abs=0.01)  # 0.19 GB against 1.4
    exp = costs.find("moe_decode_experts", cell.data_dir)(config, traffic, 1, counters, arch)
    step = costs.find("dsa_moe_decode_step", cell.data_dir)(config, traffic, 1, counters, arch)
    swept = 3_881_517_056 - 19360 * 6144 - (64 - 25.2) * 37_748_736
    assert step["bytes"] == pytest.approx(2 * swept + 32 * (2 * 6144 + 3 * 2048) * 2 + index["bytes"] + att["bytes"], rel=1e-6)
    every_lane = arch.matmul_params(config) - 4 * 0.5 * 37_748_736
    assert step["flops"] == pytest.approx(2 * 16 * every_lane + exp["flops"] + index["flops"] + att["flops"])
    assert step["bytes"] / 819e9 > step["flops"] / 197e12 and step["bytes"] / 1e9 == pytest.approx(4.91, abs=0.05)   # ISSUE 61's ~4.9 GB
    # a parent's spans carry no such counter: nothing to read, and nothing raised (the reader returns None on KeyError)
    with pytest.raises(KeyError):
        costs.find("dsa_index_scores", cell.data_dir)(config, traffic, 1, {}, arch)


def test_the_cell_runs_through_the_engine_and_agrees_with_its_reference(root, capsys):
    line = harness.run_cell("tiny-glm.closed", seed=2**31 + 11, seconds=1.5, traced=True, root=root, require_tpu=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    check = next(x for x in out if x["event"] == "serve.check")
    assert check["rows"] == 17 and check["rel_rms"] < 1e-4 and check["top1_agree"] == 1.0
    # the counter's own metric reads true; device metrics have nothing to read on a CPU
    assert {"dsa_selected_share", "serve_prefill_share", "moe_decode_experts_hit"} <= set(line["metrics"])
    assert not any("roofline" in k or "device" in k for k in line["metrics"])
    assert 8 * 100 / 36 <= line["metrics"]["dsa_selected_share"]["value"] < 100.0   # contexts of 9 to 36 tokens pick 8
    stats = next(x for x in out if x["event"] == "serve.window")["engine"]
    assert set(stats["step_counters"]) == {"serve.dsa.index_tokens", "serve.dsa.selected_tokens", "serve.dsa.live_tokens",
                                           "serve.moe.held_picks", "serve.moe.experts_hit"}
    assert 0 < stats["step_counters"]["serve.dsa.selected_tokens"] < stats["step_counters"]["serve.dsa.live_tokens"]
    assert stats["step_counters"]["serve.dsa.index_tokens"] * 2 == stats["step_counters"]["serve.dsa.live_tokens"]   # 2 of 4 layers
    assert stats["index_keys"] == {"layers": 2, "bytes_per_token": 128, "bytes": 2 * 128 * 4 * 16 * 4, "index_topk": 8}


@pytest.mark.parametrize("told", sorted(NOT_THE_PROGRAMS))
def test_the_check_catches_a_reference_that_is_not_the_programs(root, capsys, told):
    line = harness.run_cell(f"tiny-glm-{told}.closed", seed=5, seconds=1.0, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"serve.check"' in x)
    assert check["rel_rms"] > 10 * check["tolerance"]["rel_rms"]
