"""BENCHMARK.json and the harness against the contract, on the CPU at a
tiny size: names and units, the last line's keys, cells added by files alone
(a configuration, an architecture with its reference, a cost function, a
training mesh), what the harness refuses, and the command's refusal to
measure anything but a TPU."""

import json
import os
import subprocess
import sys

import pytest

import bench_testlib as B
from benchlib import costs, harness, model, readers, spec as S
from benchlib.observe import Observations


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(B.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def benchmark_json_keeps_the_contracts_rules(spec):
    assert S.check_document(spec.doc) == []
    assert os.path.getsize(os.path.join(spec.root, "BENCHMARK.json")) < 64 * 1024
    assert spec.doc["command"] == ["python3", "benchmark/run.py"]


def test_benchmark_json_keeps_the_contracts_rules():
    benchmark_json_keeps_the_contracts_rules(S.Spec())


def test_the_checker_catches_what_the_contract_forbids(doc):
    bad = json.loads(json.dumps(doc))
    bad["per_layer"][0]["unit"] = "tokens per second"
    bad["workloads"][0]["name"] = "has space"
    bad["end_to_end"][0]["bound"] = 0.5
    faults = " ".join(S.check_document(bad))
    assert "unit" in faults and "valid name" in faults and "bound" in faults


def every_cell_resolves_to_its_files(spec):
    for w in spec.doc["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.traffic["kind"] in ("train", "serve-open", "serve-closed")
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for m in cell.per_layer:
            # a generic reader or a file under benchmark/readers/; a function
            # of costs.py or a file under benchmark/costs/
            assert callable(readers.find(m, cell.data_dir)), m["name"]
            cost = m["reader"].get("args", {}).get("cost")
            assert cost is None or callable(costs.find(cost, cell.data_dir))
        # the configuration's `arch` resolves to an adapter with the whole interface
        arch = model.adapter(cell)
        assert all(callable(getattr(arch, name)) for name in model.INTERFACE)
    with pytest.raises(S.SpecError):
        spec.cell("no-such-cell")
    with pytest.raises(S.SpecError):
        spec.peak("TPU v99")
    assert spec.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12


#: what this file asserts of the DOCUMENT: each takes a ``Spec``, so that
#: test_bench_rules.py can hold a document with one more cell to all of them
DOCUMENT_CHECKS = [benchmark_json_keeps_the_contracts_rules, every_cell_resolves_to_its_files]


def test_every_cell_resolves_to_its_files():
    every_cell_resolves_to_its_files(S.Spec())


def test_flops_per_token_match_the_issues_arithmetic():
    spec = S.Spec()
    cell = spec.cell("train-mistral7b-l2-seq4k")
    l2, dense = cell.config, model.adapter(cell)
    assert dense.matmul_params(l2) == 570_425_344
    assert dense.total_params(l2) == 704_663_552
    assert costs.train_flops_per_token(l2, 4096, dense) == pytest.approx(3.6239e9, rel=1e-4)
    assert dense.total_params(spec.cell("serve-internlm2-decode").config) == 1_889_110_016


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return B.throwaway_root(str(tmp_path_factory.mktemp("bench_root")))


LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


@pytest.mark.parametrize("workload,traced,expect", [
    ("tiny.train", False, {"train_tokens_per_s", "setup_s"}),
    ("tiny.closed", False, {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}),
    ("tiny.open", False, {"tpot_p50_ms", "setup_s"}),
    ("tiny.open", True, {"ttft_p90_ms", "ttft_p50_ms", "serve_prefill_share", "serve_step_ms"}),
    ("tiny.closed", True, {"serve_step_ms", "serve_step_sample_ms", "serve_lane_occupancy", "tiny_decode_calls_ms", "tiny_decode_steps"}),
    ("tiny.train", True, {"train_data_wait_share", "train_mfu"}),
    # an architecture with experts, brought as an adapter, a reference and a configuration
    ("tiny-moe.train", False, {"train_tokens_per_s", "setup_s"}),
    # serving checked through a second adapter and ITS reference file
    ("tiny-renamed.closed", False, {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}),
    # four chips (of the CPU's eight host devices), the mesh brought by the configuration
    ("tiny-2x2.train", False, {"train_tokens_per_s", "setup_s"}),
    ("tiny-4x1.train", False, {"train_tokens_per_s", "setup_s"}),
    ("tiny-4x1-overlap.train", False, {"train_tokens_per_s", "setup_s"}),
])
def test_a_cell_added_by_files_alone_runs_and_prints_the_contracts_line(tiny_root, capsys, workload, traced, expect):
    line = harness.run_cell(workload, seed=2**31 + 5, seconds=1.5, traced=traced, root=tiny_root, require_tpu=False)
    assert set(line) == LINE_KEYS  # no device trace on a CPU, so no breakdown
    # each number the verdict rests on beside its limit, under a key that comes last
    assert list(line)[-1] == "compared" and "compiles_in_window" in line["compared"]
    assert all(len(pair) == 2 and pair[0] <= pair[1] for pair in line["compared"].values())
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) >= expect
    # device metrics have nothing to read on a CPU and are left out
    assert not any("roofline" in k or "device" in k for k in line["metrics"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float) and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["count"] == S.Spec(tiny_root).cell(workload).chips
    out = capsys.readouterr().out.strip().splitlines()
    assert all(json.loads(x)["event"] for x in out)  # earlier lines are JSON too


@pytest.mark.parametrize("workload,off,sound", [
    # the reference told another b1 than the program runs
    ("tiny.train-wrong-b1", "grad_rel", "loss_rel"),
    # the experts' reference told not to renormalise the two probabilities, as the program does
    ("tiny-moe-unnormalised.train", "logits_rel_rms", None),
])
def test_the_step_check_catches_a_reference_that_is_not_the_programs(tiny_root, capsys, workload, off, sound):
    line = harness.run_cell(workload, seed=7, seconds=1.0, traced=False, root=tiny_root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"train.check"' in x)
    assert check[off] > check["tolerance"][off]
    assert sound is None or check[sound] < 1e-4


def test_train_mfu_counts_the_experts_a_token_uses(tiny_root, capsys):
    """hidden 64, 4 heads of 16 (2 KV), 2 layers, 4 experts of width 128, top-2, vocab 256."""
    cell = S.Spec(tiny_root).cell("tiny-moe.train")
    arch = model.adapter(cell)
    attention = 64 * 16 * (2 * 4 + 2 * 2)
    active = 2 * (attention + 64 * 4 + 2 * 3 * 64 * 128) + 64 * 256
    assert arch.matmul_params(cell.config) == active == 139_776
    every = 2 * (attention + 64 * 4 + 4 * 3 * 64 * 128 + 2 * 64) + 64 + 2 * 64 * 256
    assert arch.total_params(cell.config) == every == 254_784
    import jax

    leaves = jax.tree_util.tree_leaves(arch.init_params(arch.model_config(cell.config, 32), 0))
    assert sum(x.size for x in leaves) == every  # the program's own tree holds as many
    line = harness.run_cell("tiny-moe.train", seed=11, seconds=1.0, traced=True, root=tiny_root, require_tpu=False)
    assert line["correct"] is True
    out = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    rate = next(x for x in out if x["event"] == "end_to_end_of_traced_run")["values"]["train_tokens_per_s"]
    per_token = 6 * active + 3 * 2 * (2 * 2 * 4 * 16 * 32 / 2)  # + causal attention at sequence 32
    peak = next(iter(S.Spec(tiny_root).peaks["device_kinds"].values()))["bf16_flops_per_s"]
    assert line["metrics"]["train_mfu"]["value"] == pytest.approx(100.0 * per_token * rate / peak, rel=1e-9)
    check = next(x for x in out if x["event"] == "train.check")
    assert check["update_rel"] < 1e-3 and check["logits_rel_rms"] < 1e-4  # float32 on both sides


def test_a_cost_function_brought_as_a_file_is_read_by_op_roofline(tiny_root):
    from benchlib import trace as tr

    cell = S.Spec(tiny_root).cell("tiny-moe.train")
    arch = model.adapter(cell)
    metric = next(m for m in cell.per_layer if m["name"] == "tiny_sweep_roofline")
    readers.check(metric, cell.data_dir)

    class Traced:  # a profiler that holds a trace: two calls of 1 ms on one device
        def data(self):
            return tr.TraceData(devices={"d": [("sweep.1", 0.0, 1e6), ("other", 1e6, 5e5), ("sweep.2", 2e6, 1e6)]}, host=[])

    obs = Observations(
        window=(0.0, 1.0), spans=[], counters={"tiny.calls": 2.0, "tiny.flops": 0.0}, program_events=[],
        profiler=Traced(), config=cell.config, traffic=cell.traffic, chips=1, arch=arch, data_dir=cell.data_dir,
    )
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 4.0 * 254_784 / 0.5e-3}  # the sweep at its best: 0.5 ms
    assert readers.read(metric, obs, peak) == pytest.approx(50.0)
    obs.counters["tiny.flops"] = 2e9  # compute-bound now: 2 ms at best, against 1 ms measured
    assert readers.read(metric, obs, peak) == pytest.approx(200.0)


@pytest.mark.parametrize("workload,names", [
    ("refused.arch", ["benchmark/configs/refused-arch.json", "archs/no_such_arch.py"]),
    ("refused.adapter", ["archs/half_adapter.py", "model_config", "attention_shape"]),
    ("refused.cost", ["costs/no_such_cost.py"]),
    ("refused.mesh", ["benchmark/configs/refused-mesh.json", "spans 2 chips", "has 4"]),
    ("refused.axis", ["benchmark/configs/refused-axis.json", "fdsp"]),
    ("refused.optimization", ["benchmark/configs/refused-optimization.json", "no_such_knob"]),
    ("refused.serve4", ["refused.serve4", "one chip"]),
    ("no-such-cell", ["no workload"]),
])
def test_what_the_harness_refuses_exits_with_code_3_and_names_the_file(tiny_root, capsys, workload, names):
    argv = ["--workload", workload, "--seed", "1", "--seconds", "1", "--trace", "1"]
    assert harness.main(argv, root=tiny_root, require_tpu=False) == 3
    said = capsys.readouterr()
    assert all(name in said.err for name in names), said.err
    assert '"correct"' not in said.out  # and no result


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
