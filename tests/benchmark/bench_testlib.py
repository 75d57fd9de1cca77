"""Shared by the benchmark's tests: the harness on ``sys.path`` and a
throw-away root that ADDS a tiny configuration, traffic mixes, a per-layer
metric and cells to a copy of the benchmark's data — files and entries only,
which is all a later PR may do."""

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "source": "none: a throw-away configuration of a test",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "head_dim": 16,
    "rms_norm_eps": 1e-5, "deviations": {"rms_norm_eps": {"as_run": 1e-6, "why": "the program's"}}, "rope_theta": 10000.0, "tie_word_embeddings": False,
    "vocab_size": 256,
    "dtypes": {"params": "float32", "optimizer_state": "float32", "compute": "float32",
               "serve_params": "float32", "kv_cache": "float32"},
    "train_batch": {"global_batch_sequences": 2, "why": "test"},
    "tolerance": {
        "serve_logits": {"sequence_tokens": 32, "rel_rms": 1e-3, "max_abs": 1e-3, "why": "float32 both sides"},
        "train_step": {"sequence_tokens": 32, "loss_rel": 1e-4, "logits_rel_rms": 1e-3, "grad_rel": 1e-2,
                       "moment2_rel": 1e-2, "update_rel": 1e-2, "why": "float32 both sides"},
    },
}
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
#: a reader a later PR brings as a file of its own
TINY_READER = """def read(obs, args, peak):
    return obs.counters.get(args["counter"])
"""


def throwaway_root(tmp: str) -> str:
    """Copy BENCHMARK.json and the data files, then add to them."""
    os.makedirs(os.path.join(tmp, "benchmark"))
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(tmp, "benchmark", d))
    shutil.copy(os.path.join(BENCH, "peaks.json"), os.path.join(tmp, "benchmark"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)

    def put(rel, obj):
        with open(os.path.join(tmp, "benchmark", rel), "w") as f:
            json.dump(obj, f)

    put("configs/tiny.json", TINY_CONFIG)
    for name, t in TINY_TRAFFIC.items():
        put(f"traffic/{name}.json", t)
    put("metrics/tiny_decode_calls_ms.json",
        {"reader": "span_median_ms", "args": {"span": "bench.serve.decode_call"}})
    put("metrics/tiny_decode_steps.json", {"reader": "tiny_counter", "args": {"counter": "serve.decode_steps"}})
    os.makedirs(os.path.join(tmp, "benchmark", "readers"))
    with open(os.path.join(tmp, "benchmark", "readers", "tiny_counter.py"), "w") as f:
        f.write(TINY_READER)
    doc["configs"].append({"name": "tiny", "source": "none", "file": "benchmark/configs/tiny.json",
                           "reduced": [], "why": "test"})
    cells = {"tiny.train": "tiny-train", "tiny.closed": "tiny-closed", "tiny.open": "tiny-open",
             "tiny.train-wrong-b1": "tiny-train-wrong-b1"}
    for name, t in cells.items():
        doc["workloads"].append({"name": name, "config": "tiny", "traffic": t, "chips": 1, "why": "test"})
    serve = ["tiny.closed", "tiny.open"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" not in m:
            continue
        if m["name"].startswith("train_") and "collective" not in m["name"]:
            m["workloads"] += ["tiny.train", "tiny.train-wrong-b1"]
        elif m["name"] in ("tpot_p50_ms", "serve_decode_step_ms", "serve_sample_ms"):
            m["workloads"] += serve
        elif m["name"] in ("serve_tokens_per_s", "serve_lane_occupancy", "serve_kv_pool_live"):
            m["workloads"].append("tiny.closed")
        elif m["name"] in ("ttft_p90_ms", "ttft_p50_ms", "serve_prefill_share"):
            m["workloads"].append("tiny.open")
    doc["per_layer"].append({"name": "tiny_decode_calls_ms", "unit": "ms", "better": "lower",
                             "source": "program_span", "layer": "a test's layer",
                             "moves": "tpot_p50_ms", "workloads": serve})
    doc["per_layer"].append({"name": "tiny_decode_steps", "unit": "steps", "better": "higher",
                             "source": "program_counter", "layer": "a test's layer",
                             "moves": "tpot_p50_ms", "workloads": serve})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return tmp
