"""The step by scope (PR 36): the reader ``named_device_share`` on a small
synthetic trace and hand-made observations, and the thirteen metric files it
came with: each resolves to its reader in every cell that lists it (which
cells those are is each metric file's to say: ``"cells"``, ``test_bench_rules.py``)."""

import dataclasses

import pytest

import bench_testlib as B  # noqa: F401  (puts the benchmark on the path)
from benchlib import model, readers, spec as S
from benchlib.observe import Observations

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DECODE = {"span": "serve.decode", "program": "jit.compile.serve.decode"}

#: metric -> (reader, the arguments that matter)
METRICS = {
    "train_attn_device_share": ("scope_device_share", {"scopes": ["attn.qkv", "attn.window", "attn.full", "attn.out"]}),
    "train_mlp_device_share": ("scope_device_share", {"scopes": ["mlp.dense"]}),
    "train_norm_device_share": ("scope_device_share", {"scopes": ["block.norm"]}),
    "train_loss_device_share": ("scope_device_share", {"scopes": ["lm.embed", "loss.ce"]}),
    "train_optim_device_share": ("scope_device_share", {"scopes": ["optim.clip", "optim.update"]}),
    "moe_experts_device_share": ("scope_device_share", {"scopes": ["moe.experts", "moe.shared"]}),
    "serve_attn_device_share": (
        "decode_step_ops", {**DECODE, "scopes": ["serve.attn.qkv", "serve.kv.write", "serve.attn.attend", "serve.attn.out"]},
    ),
    "serve_mlp_device_share": ("decode_step_ops", {**DECODE, "scopes": ["serve.mlp"]}),
    "serve_vocab_device_share": ("decode_step_ops", {**DECODE, "scopes": ["serve.embed", "serve.head"]}),
    "train_named_device_share": ("named_device_share", {"also": "^%tpu_custom_call"}),
    "serve_decode_named_device_share": ("named_device_share", {**DECODE, "also": "^%tpu_custom_call"}),
    "train_mixed_fusion_device_share": ("named_device_share", {"count": "mixed"}),
    "serve_decode_mixed_fusion_device_share": ("named_device_share", {**DECODE, "count": "mixed"}),
}


@pytest.fixture(scope="module")
def spec():
    return S.Spec()


@pytest.mark.parametrize("name", list(METRICS))
def test_a_metric_file_resolves_to_its_reader_in_every_cell_that_lists_it(spec, name):
    reader, args = METRICS[name]
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"] and entry["source"] == "device_trace" and entry["unit"] == "%"
    # a reader of the decode step names the decode program; the others read the training step
    assert entry["moves"] == ("tpot_p50_ms" if "program" in args else "train_tokens_per_s")
    for cell_name in entry["workloads"]:
        cell = spec.cell(cell_name)
        assert cell.traffic["kind"].startswith("serve-") == ("program" in args)
        metric = next(m for m in cell.per_layer if m["name"] == name)
        assert metric["reader"]["reader"] == reader and metric["reader"]["args"] == args
        assert callable(readers.find(metric, cell.data_dir))
        readers.check(metric, cell.data_dir)


# ---------------------------------------------------------------------------
# the reader, on a small synthetic trace
# ---------------------------------------------------------------------------


class _Traced:
    """One prefill and two whole decode steps on one device, the third step
    cut by the trace's end.  A step is 10 ms of operations: a fusion under a
    scope (2), a fusion of mixed scopes (3), a bare Mosaic call (1), a
    prefetch the program lists (1), an operation under no scope (3)."""

    trace_dir = ""
    sync_marks_ns = [0.0]

    def data(self):
        from benchlib import trace as tr

        ms = 1e6
        step = [
            ("%fusion.7 = bf16[32,2048] fusion(...)", 0.0, 2.0),
            ("%multiply_reduce_fusion.3 = f32[32] fusion(...)", 2.0, 3.0),
            ("%tpu_custom_call.4 = f32[32,16,128] custom-call(...)", 5.0, 1.0),
            ("%slice-done.12 = f32[512,8,128] slice-done(...)", 6.0, 1.0),
            ("%copy.77 = bf16[24,3500,16,1024] copy(...)", 7.0, 3.0),
        ]
        events = [("%fusion.7 = bf16[1,1280,2048] fusion(...)", 1 * ms, 5 * ms)]   # the prefill's fusion.7
        for start in (10.0, 20.0, 30.0):
            events += [(n, (start + s) * ms, d * ms) for n, s, d in step]
        events = [e for e in events if e[1] + e[2] <= 38 * ms]
        return tr.TraceData(devices={"d": events}, host=[(tr.SYNC_NAME, 0.0, 0.0)])


def _decode_span(start_ms):
    return {"ph": "X", "name": "serve.decode", "ts": start_ms * 1e3, "dur": 10.05e3, "args": {"step": 1, "active": 32}}


def _instant(program, scopes, mixed=None):
    args = {"program": program, "scopes": scopes}
    if mixed is not None:
        args["mixed"] = mixed
    return {"ph": "i", "name": "jit.scopes", "ts": 0.0, "args": args}


SCOPES = {"serve.mlp": ["fusion.7"], "serve.attn.out": ["multiply_reduce_fusion.3", "fusion.99"], "serve.attn.qkv": ["slice-done.12"]}
MIXED = {"multiply_reduce_fusion.3": ["serve.attn.out", "serve.norm"]}


@pytest.fixture(scope="module")
def serve_cell(spec):
    return spec.cell("serve-internlm2-decode")


def _obs(cell, events, profiler=_Traced()):
    return Observations(
        window=(0.0, 1.0), spans=[], counters={}, program_events=events, profiler=profiler, config=cell.config,
        traffic=cell.traffic, chips=1, program_epoch=0.0, arch=model.adapter(cell), data_dir=cell.data_dir,
    )


def _read(cell, name, obs):
    return readers.read(next(m for m in cell.per_layer if m["name"] == name), obs, PEAK)


EVENTS = [
    _instant("jit.compile.serve.decode", SCOPES, MIXED),
    _instant("jit.compile.serve.prefill", {"serve.walk": ["copy.77"]}, {"copy.77": ["serve.embed", "serve.walk"]}),
    {"ph": "X", "name": "serve.prefill", "ts": 0.5e3, "dur": 6e3, "args": {}},
    _decode_span(9.9), _decode_span(19.9), _decode_span(29.9),   # the third ends past the trace's last operation
]

CASES = {
    # of a whole step's 10 ms: 2 + 3 scoped, 1 a bare Mosaic call, 1 a listed prefetch; the copy is the prefill's to name, not the decode program's
    "span.named": ("serve_decode_named_device_share", EVENTS, 70.0),
    "span.mixed": ("serve_decode_mixed_fusion_device_share", EVENTS, 30.0),
    # an instant without the table asked for (the parent's, which has no `mixed`): nothing; its scopes alone are read
    "span.mixed.parent": ("serve_decode_mixed_fusion_device_share", [_instant("jit.compile.serve.decode", SCOPES)] + EVENTS[2:], None),
    "span.named.parent": ("serve_decode_named_device_share", [_instant("jit.compile.serve.decode", SCOPES)] + EVENTS[2:], 70.0),
    # no instant of the decode program, or no span the trace holds whole: nothing
    "span.no_instant": ("serve_decode_named_device_share", EVENTS[1:], None),
    "span.no_span": ("serve_decode_named_device_share", EVENTS[:3], None),
    # a program that mixes nothing says so with an empty table: 0, not nothing
    "span.nothing_mixed": ("serve_decode_mixed_fusion_device_share", [_instant("jit.compile.serve.decode", SCOPES, {})] + EVENTS[2:], 0.0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_named_share_of_the_decode_steps_the_trace_holds_whole(serve_cell, case):
    name, events, want = CASES[case]
    got = _read(serve_cell, name, _obs(serve_cell, events))
    assert got is None if want is None else got == pytest.approx(want)


def test_the_named_share_of_a_whole_trace_reads_every_programs_instant(spec):
    """A training cell: no span, every ``jit.scopes`` instant, all 33 ms of
    the trace: the first ``fusion.7`` (5 ms) counts as the two later ones do."""
    cell = spec.cell("train-mistral7b-l2-seq4k")
    events = [_instant("jit.compile.train", SCOPES, MIXED), _instant("jit.compile.eval", {"loss.ce": ["copy.77"]}, {})]
    obs = _obs(cell, events)
    # 5 + 2 x (2 + 3 + 1 + 1 + 3) + 2 + 3 + 1 + 1 (the cut step's copy is gone): all but nothing unnamed
    assert _read(cell, "train_named_device_share", obs) == pytest.approx(100.0)
    assert _read(cell, "train_mixed_fusion_device_share", obs) == pytest.approx(100.0 * 9 / 32)
    only_train = _obs(cell, events[:1])
    assert _read(cell, "train_named_device_share", only_train) == pytest.approx(100.0 * (32 - 6) / 32)
    # the shares by scope read the same instants through the reader that was there
    assert _read(cell, "train_mlp_device_share", _obs(cell, [_instant("jit.compile.train", {"mlp.dense": ["fusion.7"]})])) == pytest.approx(100.0 * 11 / 32)


@pytest.mark.parametrize("name", list(METRICS))
def test_nothing_is_read_where_there_is_no_trace(spec, name):
    """Which is also what keeps the CPU contract's "no metric with `device` in
    its name" true: a run that traced nothing reports none of the thirteen."""
    cell = spec.cell(next(m for m in spec.doc["per_layer"] if m["name"] == name)["workloads"][0])
    events = [_instant("jit.compile.train", SCOPES, MIXED)] + EVENTS
    assert _read(cell, name, dataclasses.replace(_obs(cell, events), profiler=None)) is None
    assert _read(cell, name, _obs(cell, [])) is None
