"""Shared by the benchmark's tests: the harness on ``sys.path`` and a
throw-away root that ADDS tiny configurations, traffic mixes, per-layer
metrics, a reader, a cost function, two architectures (adapter and
reference: ``files/``) and cells to a copy of the benchmark's data — files
and entries only, which is all a later PR may do."""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmark")
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)



def tiny_form(arch: str) -> dict:
    """An architecture at a tiny size (``tiny/<arch>.json``: ``config``, and
    for one that is served its ``serve_engine``): what the tests run on the
    CPU and what decides, by a CPU lowering, which scopes its programs have.
    A PR that brings an architecture brings its file."""
    with open(os.path.join(HERE, "tiny", arch + ".json")) as f:
        return json.load(f)


TINY_CONFIG = tiny_form("dense_decoder")["config"]
ENGINE = {"block_size": 4, "num_blocks": 128, "max_batch": 4, "decode_chunk_blocks": 1,
          "prefix_cache": True, "queue_depth": 32}
TINY_TRAFFIC = {
    "tiny-train": {"kind": "train", "seq_len": 32, "dataset_batches": 4, "attention": "reference",
                   "fused_ce": False, "fused_adamw": False, "lr": 1e-3, "warmup_steps": 2,
                   "report_every_steps": 2, "warm_boundaries": 2, "decay_steps": 10000,
                   "weight_decay": 0.01, "grad_clip": 1.0, "adam": {"b1": 0.9, "b2": 0.999, "eps": 1e-8}},
    "tiny-closed": {"kind": "serve-closed", "clients": 4, "requests_per_client": 2,
                    "prompt_tokens": {"shape": "uniform", "min": 4, "max": 12},
                    "output_tokens": {"shape": "uniform", "min": 6, "max": 12},
                    "temperature": 0.7, "slices": 4,
                    "engine": dict(ENGINE, max_prompt_len=24, max_new_tokens=12)},
    "tiny-open": {"kind": "serve-open", "rate_per_s": 20.0, "ramp_s": 0.5, "tail_s": 1.0,
                  "prompt_tokens": {"shape": "lognormal", "median": 8, "sigma": 0.5, "min": 4, "max": 16},
                  "output_tokens": {"shape": "lognormal", "median": 4, "sigma": 0.5, "min": 2, "max": 8},
                  "shared_prefix": {"count": 2, "tokens": 8, "share": 0.25},
                  "temperature": 0.7, "slices": 4,
                  "engine": dict(ENGINE, max_prompt_len=24, max_new_tokens=8)},
}
# the same mix with the reference told another b1 than the program runs: the
# step check has to say so
TINY_TRAFFIC["tiny-train-wrong-b1"] = dict(
    TINY_TRAFFIC["tiny-train"], adam={"b1": 0.8, "b2": 0.999, "eps": 1e-8})
# an architecture brought by files (files/archs/moe_top2.py and its
# reference): the program's expert block, four experts in every block, a
# capacity at which no token is dropped (experts / 2 x the group)
TINY_MOE = dict(
    TINY_CONFIG, arch="moe_top2", num_experts=4, moe_capacity_factor=2.0, moe_aux_weight=0.01,
    norm_topk_prob=True,
)
# the dense decoder under another family's key names, through a second
# adapter with a reference file of its own: nothing in the harness may read
# a key by the name the first adapter knows it under
RENAMED = {"hidden_size": "n_embd", "intermediate_size": "n_inner", "num_attention_heads": "n_head",
           "num_key_value_heads": "n_head_kv", "num_hidden_layers": "n_layer"}
TINY_RENAMED = dict({RENAMED.get(k, k): v for k, v in TINY_CONFIG.items()}, arch="dense_renamed")
# training cells over four chips: the mesh comes with the configuration
MESHES = {"2x2": {"mesh": {"fsdp": 2, "tensor": 2}}, "4x1": {"mesh": {"fsdp": 4}},
          "4x1-overlap": {"mesh": {"fsdp": 4}, "optimizations": {"overlap_grad_sync": True}}}
# what the harness refuses (exit code 3): cell -> (configuration, traffic, chips)
REFUSED_CONFIGS = {
    "refused-arch": dict(TINY_CONFIG, arch="no_such_arch"),
    "refused-adapter": dict(TINY_CONFIG, arch="half_adapter"),
    "refused-cost": TINY_CONFIG,
    "refused-mesh": dict(TINY_CONFIG, train_batch={"global_batch_sequences": 4, "mesh": {"fsdp": 2}}),
    "refused-axis": dict(TINY_CONFIG, train_batch={"global_batch_sequences": 4, "mesh": {"fdsp": 4}}),
    "refused-optimization": dict(TINY_CONFIG, train_batch={"global_batch_sequences": 2, "optimizations": {"no_such_knob": 1}}),
    "refused-serve4": TINY_CONFIG,
}
REFUSED_CELLS = {
    "refused.arch": ("refused-arch", "tiny-train", 1), "refused.adapter": ("refused-adapter", "tiny-train", 1),
    "refused.cost": ("refused-cost", "tiny-train", 1), "refused.mesh": ("refused-mesh", "tiny-train", 4),
    "refused.axis": ("refused-axis", "tiny-train", 4), "refused.optimization": ("refused-optimization", "tiny-train", 1),
    "refused.serve4": ("refused-serve4", "tiny-closed", 4),
}
#: a reader a later PR brings as a file of its own
TINY_READER = """def read(obs, args, peak):
    return obs.counters.get(args["counter"])
"""


def renamed_adapter() -> str:
    """The dense adapter's own text under the other key names."""
    with open(os.path.join(BENCH, "archs", "dense_decoder.py")) as f:
        text = f.read().replace("dense_decoder", "dense_renamed")
    for ours, theirs in RENAMED.items():
        text = text.replace(f'"{ours}"', f'"{theirs}"')
    return text


def copy_of_the_benchmark(tmp: str) -> str:
    """BENCHMARK.json and the data files as they are, under another root."""
    os.makedirs(os.path.join(tmp, "benchmark"))
    for d in ("configs", "traffic", "metrics", "readers", "archs", "reference", "costs"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(tmp, "benchmark", d))
    shutil.copy(os.path.join(BENCH, "peaks.json"), os.path.join(tmp, "benchmark"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    return tmp


def throwaway_root(tmp: str) -> str:
    """Copy BENCHMARK.json and the data files, then add to them."""
    copy_of_the_benchmark(tmp)
    shutil.copytree(os.path.join(HERE, "files"), os.path.join(tmp, "benchmark"), dirs_exist_ok=True)
    shutil.copy(os.path.join(BENCH, "reference", "dense_decoder.py"),
                os.path.join(tmp, "benchmark", "reference", "dense_renamed.py"))
    with open(os.path.join(tmp, "benchmark", "archs", "dense_renamed.py"), "w") as f:
        f.write(renamed_adapter())
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)

    def put(rel, obj):
        with open(os.path.join(tmp, "benchmark", rel), "w") as f:
            json.dump(obj, f)

    configs = {
        "tiny": TINY_CONFIG, "tiny-moe": TINY_MOE, "tiny-moe-unnormalised": dict(TINY_MOE, norm_topk_prob=False),
        "tiny-renamed": TINY_RENAMED,
        **{f"tiny-{name}": dict(TINY_CONFIG, train_batch={"global_batch_sequences": 4, **brings})
           for name, brings in MESHES.items()},
        **REFUSED_CONFIGS,
    }
    for name, config in configs.items():
        put(f"configs/{name}.json", config)
    for name, t in TINY_TRAFFIC.items():
        put(f"traffic/{name}.json", t)
    put("metrics/tiny_decode_calls_ms.json",
        {"reader": "span_median_ms", "args": {"span": "bench.serve.decode_call"}})
    put("metrics/tiny_decode_steps.json", {"reader": "tiny_counter", "args": {"counter": "serve.decode_steps"}})
    put("metrics/tiny_sweep_roofline.json",
        {"reader": "op_roofline", "args": {"pattern": "^sweep", "cost": "tiny_sweep", "per": "tiny.calls"}})
    put("metrics/refused_cost_roofline.json",
        {"reader": "op_roofline", "args": {"pattern": "^sweep", "cost": "no_such_cost", "per": "tiny.calls"}})
    with open(os.path.join(tmp, "benchmark", "archs", "half_adapter.py"), "w") as f:
        f.write("def check_as_run(config):\n    pass\n")
    with open(os.path.join(tmp, "benchmark", "readers", "tiny_counter.py"), "w") as f:
        f.write(TINY_READER)
    for name in configs:
        doc["configs"].append({"name": name, "source": "none", "file": f"benchmark/configs/{name}.json",
                               "reduced": [], "why": "test"})
    # cell -> (configuration, traffic, chips)
    cells = {"tiny.train": ("tiny", "tiny-train", 1), "tiny.closed": ("tiny", "tiny-closed", 1),
             "tiny.open": ("tiny", "tiny-open", 1), "tiny.train-wrong-b1": ("tiny", "tiny-train-wrong-b1", 1),
             "tiny-moe.train": ("tiny-moe", "tiny-train", 1),
             "tiny-moe-unnormalised.train": ("tiny-moe-unnormalised", "tiny-train", 1),
             "tiny-renamed.closed": ("tiny-renamed", "tiny-closed", 1),
             **{f"tiny-{name}.train": (f"tiny-{name}", "tiny-train", 4) for name in MESHES},
             **REFUSED_CELLS}
    for name, (config, t, chips) in cells.items():
        doc["workloads"].append({"name": name, "config": config, "traffic": t, "chips": chips, "why": "test"})
    train = [name for name, (_, t, _) in cells.items() if t.startswith("tiny-train")]
    closed = ["tiny.closed", "tiny-renamed.closed"]
    serve = closed + ["tiny.open"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" not in m:
            continue
        if m["name"].startswith("train_") and "collective" not in m["name"]:
            m["workloads"] += train
        elif m["name"] in ("tpot_p50_ms", "serve_step_ms", "serve_step_sample_ms"):
            m["workloads"] += serve
        elif m["name"] in ("serve_tokens_per_s", "serve_lane_occupancy", "serve_kv_pool_live"):
            m["workloads"] += closed
        elif m["name"] in ("ttft_p90_ms", "ttft_p50_ms", "serve_prefill_share"):
            m["workloads"].append("tiny.open")
    doc["per_layer"].append({"name": "tiny_decode_calls_ms", "unit": "ms", "better": "lower",
                             "source": "program_span", "layer": "a test's layer",
                             "moves": "tpot_p50_ms", "workloads": serve})
    doc["per_layer"].append({"name": "tiny_decode_steps", "unit": "steps", "better": "higher",
                             "source": "program_counter", "layer": "a test's layer",
                             "moves": "tpot_p50_ms", "workloads": serve})
    for name, cell in (("tiny_sweep_roofline", "tiny-moe.train"), ("refused_cost_roofline", "refused.cost")):
        doc["per_layer"].append({"name": name, "unit": "%", "better": "higher", "source": "device_trace",
                                 "layer": "a test's layer", "moves": "train_tokens_per_s", "workloads": [cell]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return tmp
