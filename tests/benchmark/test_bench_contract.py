"""BENCHMARK.json and the harness against the contract, on the CPU at a
tiny size: names and units, the last line's keys, a cell added by files
alone, and the command's refusal to measure anything but a TPU."""

import json
import os
import subprocess
import sys

import pytest

import bench_testlib as B
from benchlib import costs, harness, readers, spec as S


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(B.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_the_contracts_rules(doc):
    assert S.check_document(doc) == []
    assert os.path.getsize(os.path.join(B.REPO, "BENCHMARK.json")) < 64 * 1024
    assert doc["command"] == ["python3", "benchmark/run.py"]


def test_the_checker_catches_what_the_contract_forbids(doc):
    bad = json.loads(json.dumps(doc))
    bad["per_layer"][0]["unit"] = "tokens per second"
    bad["workloads"][0]["name"] = "has space"
    bad["end_to_end"][0]["bound"] = 0.5
    faults = " ".join(S.check_document(bad))
    assert "unit" in faults and "valid name" in faults and "bound" in faults


def test_every_cell_resolves_to_its_files(doc):
    spec = S.Spec()
    for w in doc["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.traffic["kind"] in ("train", "serve-open", "serve-closed")
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for m in cell.per_layer:
            assert m["reader"]["reader"] in readers.READERS, m["name"]
            cost = m["reader"].get("args", {}).get("cost")
            assert cost is None or callable(getattr(costs, cost))
    with pytest.raises(S.SpecError):
        spec.cell("no-such-cell")
    with pytest.raises(S.SpecError):
        spec.peak("TPU v99")
    assert spec.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_flops_per_token_match_the_issues_arithmetic():
    spec = S.Spec()
    l2 = spec.cell("train-mistral7b-l2-seq4k").config
    assert costs.matmul_params(l2) == 570_425_344
    assert costs.total_params(l2) == 704_663_552
    assert costs.train_flops_per_token(l2, 4096) == pytest.approx(3.6239e9, rel=1e-4)
    assert costs.total_params(spec.cell("serve-internlm2-decode").config) == 1_889_110_016


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return B.throwaway_root(str(tmp_path_factory.mktemp("bench_root")))


LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("workload,traced,expect", [
    ("tiny.train", False, {"train_tokens_per_s", "setup_s"}),
    ("tiny.closed", False, {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}),
    ("tiny.open", False, {"tpot_p50_ms", "setup_s"}),
    ("tiny.open", True, {"ttft_p90_ms", "ttft_p50_ms", "serve_prefill_share", "serve_decode_step_ms"}),
    ("tiny.closed", True, {"serve_decode_step_ms", "serve_sample_ms", "serve_lane_occupancy", "tiny_decode_calls_ms", "tiny_decode_steps"}),
    ("tiny.train", True, {"train_data_wait_share", "train_mfu"}),
])
def test_a_cell_added_by_files_alone_runs_and_prints_the_contracts_line(tiny_root, capsys, workload, traced, expect):
    line = harness.run_cell(workload, seed=2**31 + 5, seconds=1.5, traced=traced, root=tiny_root, require_tpu=False)
    assert set(line) == LINE_KEYS  # no device trace on a CPU, so no breakdown
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) >= expect
    # device metrics have nothing to read on a CPU and are left out
    assert not any("roofline" in k or "device" in k for k in line["metrics"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float) and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    out = capsys.readouterr().out.strip().splitlines()
    assert all(json.loads(x)["event"] for x in out)  # earlier lines are JSON too


def test_the_step_check_catches_an_optimizer_that_is_not_the_references(tiny_root, capsys):
    line = harness.run_cell("tiny.train-wrong-b1", seed=7, seconds=1.0, traced=False, root=tiny_root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"train.check"' in x)
    assert check["grad_rel"] > check["tolerance"]["grad_rel"] and check["loss_rel"] < 1e-4


def test_the_command_refuses_to_measure_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(B.BENCH, "run.py"), "--workload", "train-mistral7b-l2-seq4k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=B.REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert not any(line.startswith('{"correct"') for line in p.stdout.splitlines())
    assert "TPU" in p.stderr
