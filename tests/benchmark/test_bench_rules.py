"""The lists of ``BENCHMARK.json`` are held by what each metric's own file
says under ``"cells"`` (``benchlib/spec.py``: ``CELLS``) and not by equality
with the cells that were there when a test was written: a metric with a rule
lists exactly the cells the rule names, and a SEVENTH cell, wherever it is
put among the six, joins its lists by rule and breaks no document-level
assertion of this directory."""

import glob
import importlib
import json
import os

import pytest

import bench_rules as R
import bench_testlib as B
from benchlib import spec as S

SPEC = S.Spec()
CELLS = [SPEC.cell(w["name"]) for w in SPEC.doc["workloads"]]
RULED = [m["name"] for m in SPEC.doc["per_layer"] if SPEC.belongs(m["name"], CELLS[0], lambda cell, scope: True) is not None]


@pytest.mark.parametrize("name", RULED)
def test_a_metric_lists_exactly_the_cells_its_file_names(name):
    entry = next(m for m in SPEC.doc["per_layer"] if m["name"] == name)
    listed = {c.name for c in CELLS if "workloads" not in entry or c.name in entry["workloads"]}
    assert listed == {c.name for c in CELLS if SPEC.belongs(name, c, R.has_scope)}


def test_the_host_side_of_a_step_and_set_up_are_every_cells_of_their_kind():
    """What ISSUE 43 found missing from three cells of six."""
    serving = {c.name for c in CELLS if c.traffic["kind"].startswith("serve-")}
    assert len(RULED) >= 30 and serving and len(serving) < len(CELLS)
    for name in ("serve_step_ms", "serve_step_sample_ms", "serve_decode_wait_ms", "serve_logits_d2h_ms", "serve_queue_wait_ms", "decode_device_ms"):
        assert {c.name for c in CELLS if SPEC.belongs(name, c, R.has_scope)} == serving, name
    for name in ("setup_program_build_s", "setup_program_load_s"):
        assert all(SPEC.belongs(name, c, R.has_scope) for c in CELLS), name
    # a cost function counts one architecture's work: its list is its builder's
    assert all(SPEC.belongs(name, CELLS[0], R.has_scope) is None for name in ("decode_hbm_roofline", "moe_decode_experts_roofline", "train_mfu"))


def test_the_programs_scopes_come_from_a_cpu_lowering_of_the_tiny_form():
    dense, latent = R.scopes_of("dense_decoder", True), R.scopes_of("deepseek_mla_moe", True)
    assert {"serve.attn.qkv", "serve.mlp", "serve.embed"} <= dense and not any(s.startswith("serve.moe") for s in dense)
    # a latent-attention program writes its cache under serve.mla: serve.kv.write alone puts it on no attention share
    assert {"serve.mla", "serve.kv.write", "serve.mlp", "serve.moe.route"} <= latent and "serve.attn.qkv" not in latent
    assert "mlp.dense" in R.scopes_of("dense_decoder", False) and "mlp.dense" not in R.scopes_of("mellum_moe", False)


def rewritten(tmp_path, change):
    """A copy of the benchmark whose document ``change(doc)`` has edited."""
    root = B.copy_of_the_benchmark(str(tmp_path / "root"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    change(doc)
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def test_a_list_that_departs_from_its_files_rule_is_a_fault(tmp_path):
    assert SPEC.list_faults(R.has_scope) == []
    train = next(c.name for c in CELLS if c.traffic["kind"] == "train")
    closed = next(c.name for c in CELLS if c.traffic["kind"] == "serve-closed")

    def change(doc):
        lists = {m["name"]: m["workloads"] for m in doc["per_layer"] if "workloads" in m}
        lists["serve_step_ms"].remove(closed)           # a serving cell off a list of every serving cell
        lists["ttft_p90_ms"].append(closed)             # a closed loop on an open-loop list
        lists["train_mlp_device_share"].append(closed)  # a serving cell on a training share
        lists["moe_experts_device_share"].append(next(c.name for c in CELLS if c.config.get("arch", "dense_decoder") == "dense_decoder" and c.traffic["kind"] == "train"))

    faults = S.Spec(rewritten(tmp_path, change)).list_faults(R.has_scope)
    assert len(faults) == 4 and sum("not on its list" in f for f in faults) == 1
    assert {f.split(":")[0] for f in faults} == {"serve_step_ms", "ttft_p90_ms", "train_mlp_device_share", "moe_experts_device_share"}
    assert train  # the document has cells of every kind the faults above name


@pytest.mark.parametrize("cells", ["decode", {"of": "serving"}, {"of": "serving", "scope": "serve.mlp", "also": 1}, {"of": "some", "scope": "serve.mlp"}])
def test_a_rule_the_harness_does_not_know_is_refused(tmp_path, cells):
    root = B.copy_of_the_benchmark(str(tmp_path / "root"))
    path = os.path.join(root, "benchmark", "metrics", "serve_step_ms.json")
    with open(path) as f:
        file = json.load(f)
    with open(path, "w") as f:
        json.dump(dict(file, cells=cells), f)
    with pytest.raises(S.SpecError, match='"cells" is one of'):
        S.Spec(root).list_faults(R.has_scope)


def document_checks():
    """Every ``DOCUMENT_CHECKS`` of this directory's test files: what each
    asserts of the document, as functions of a ``Spec``.  A file a later PR
    adds is found the same way."""
    checks = []
    for path in sorted(glob.glob(os.path.join(B.HERE, "test_bench_*.py"))):
        module = importlib.import_module(os.path.basename(path)[:-3])
        checks += [(module.__name__, check) for check in getattr(module, "DOCUMENT_CHECKS", [])]
    return checks


SEVENTH = {"name": "serve-internlm2-reason", "config": "internlm2-1.8b", "traffic": "reason-closed", "chips": 1,
           "why": "a test's seventh cell: a configuration and a traffic file that are there, under a new name"}


@pytest.mark.parametrize("at", [0, 3, 6])
def test_a_seventh_cell_joins_its_lists_by_rule_and_needs_no_edit(tmp_path, at):
    def change(doc):
        assert not any(w["name"] == SEVENTH["name"] for w in doc["workloads"])
        doc["workloads"].insert(at, SEVENTH)
        # the end-to-end metrics its builder judges it on: a per-layer metric's `moves` has to be reported
        for m in doc["end_to_end"]:
            if m["name"] in ("tpot_p50_ms", "serve_tokens_per_s"):
                m["workloads"].append(SEVENTH["name"])

    root = rewritten(tmp_path, change)
    # the lists its files' rules put it on
    spec = S.Spec(root)
    lists = [m["name"] for m in spec.doc["per_layer"] if spec.belongs(m["name"], spec.cell(SEVENTH["name"]), R.has_scope)]
    root = rewritten(tmp_path / "joined", lambda doc: (change(doc), [m["workloads"].append(SEVENTH["name"]) for m in doc["per_layer"] if m["name"] in lists]))
    spec = S.Spec(root)
    assert spec.list_faults(R.has_scope) == []
    checks = document_checks()
    assert {"test_bench_contract", "test_bench_deepseek", "test_bench_cohere", "test_bench_mellum"} <= {name for name, _ in checks}
    for _, check in checks:
        check(spec)
    # a dense GQA decoder under a closed loop: the host's half of a step, set-up, the decode step by scope
    mine = {m["name"] for m in spec.cell(SEVENTH["name"]).per_layer}
    assert mine == set(lists) >= {
        "serve_step_ms", "serve_step_sample_ms", "serve_decode_wait_ms", "serve_logits_d2h_ms", "serve_queue_wait_ms",
        "decode_device_ms", "setup_program_build_s", "setup_program_load_s", "serve_attn_device_share", "serve_mlp_device_share",
        "serve_vocab_device_share", "serve_decode_named_device_share", "serve_decode_mixed_fusion_device_share", "serve_device_idle_share"}
    assert not mine & {"serve_queue_wait_p90_ms", "prefill_device_ms", "ttft_p90_ms", "serve_moe_device_share", "train_named_device_share"}
