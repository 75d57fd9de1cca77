"""Multi-slice hierarchical collectives (ISSUE 18): virtual 2-slice mesh.

The acceptance bars, on the virtual 2-slice x 4-chip CPU mesh
(``MeshConfig(num_slices=2, ...)`` under the 8-device conftest XLA flag):

- ``optimizations.hierarchical_collectives`` is numerically a no-op
  (params + opt_state allclose vs the FLAT all-reduce baseline after N
  steps), while the modeled cross-slice traffic drops to 1/N_ici of the
  flat plan's — reduce-scatter over the intra-slice ICI axes, all-reduce
  over ``dcn`` carrying only the sharded fragment, all-gather back
  within the slice;
- the compiled HLO proves it: summing the operand bytes of every
  collective whose replica group CROSSES the slice boundary, the
  hierarchical program moves a fraction of the flat program's
  cross-slice bytes (no full-gradient payload ever rides DCN);
- ``CommModel`` is link-aware: ``DTPU_COMM_BW_GBPS`` takes per-link
  ``ici:90,dcn:12`` (single float still applies to both), and
  ``split_hops`` gives the DCN hop first claim on the overlap budget;
- the knob composes across the matrix ``dcn2 x {fsdp, overlap, agg>1,
  int8, 1f1b}`` and keys the jit-reuse cache via the plan fingerprint.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from determined_tpu import core, train
from determined_tpu.config import ExperimentConfig, InvalidExperimentConfig, Length
from determined_tpu.models.transformer import LMTrial
from determined_tpu.parallel.mesh import MeshAxes, MeshConfig, make_mesh
from determined_tpu.train import _jit_cache, _overlap

HP = {
    "lr": 1e-3,
    "global_batch_size": 16,
    "seq_len": 32,
    "vocab_size": 128,
    "d_model": 64,
    "n_layers": 2,
    "n_heads": 4,
    "dataset_size": 64,
    "bf16": False,
    "attention": "reference",
    "warmup_steps": 1,
}

MESH2x4 = dict(num_slices=2, data=2, fsdp=2)  # the virtual 2-slice x 4-chip mesh


def _run(tmp_path, opts, steps=3, hp=None, tag="", mesh=None):
    _jit_cache.clear_step_cache()
    exp = ExperimentConfig.parse({"optimizations": opts})
    ctx = train.init(
        hparams=dict(hp or HP),
        mesh_config=MeshConfig(**(mesh or MESH2x4)),
        core_context=core._dummy_init(checkpoint_dir=str(tmp_path / f"ck{tag}")),
        exp_config=exp,
        seed=3,
    )
    trainer = train.Trainer(LMTrial(ctx))
    losses = []
    orig = ctx.core.train.report_training_metrics
    ctx.core.train.report_training_metrics = lambda s, m: (
        losses.append(float(m["loss"])),
        orig(s, m),
    )
    trainer.fit(
        Length.batches(steps),
        report_period=Length.batches(1),
        checkpoint_policy="none",
    )
    return trainer, losses


def _maxdiff(a, b):
    return max(
        float(np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64)).max())
        for x, y in zip(
            jax.tree.leaves(jax.device_get(a)), jax.tree.leaves(jax.device_get(b))
        )
    )


def _compiled_text(trainer):
    from determined_tpu.data import to_global

    host = next(trainer.train_loader.iter_epoch(0))
    if trainer.agg > 1:
        host = {k: np.stack([v] * trainer.agg) for k, v in host.items()}
    batch = to_global(host, trainer.mesh, micro_dim=trainer.agg > 1)
    with trainer.mesh:
        return trainer._train_step_jit.lower(trainer.state, batch).compile().as_text()


# ---------------------------------------------------------------------------
# HLO cross-slice accounting
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all")


def _replica_groups(line):
    """Decode replica_groups from HLO text: explicit ``{{0,4},{1,5}}`` or
    iota ``[4,2]<=[2,4]T(1,0)`` form."""
    m = re.search(r"replica_groups=\{(\{[0-9, ]+\}(?:,\{[0-9, ]+\})*)\}", line)
    if m:
        return [
            [int(x) for x in g.split(",") if x.strip()]
            for g in re.findall(r"\{([0-9, ]+)\}", m.group(1))
        ]
    m = re.search(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?", line
    )
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(x) for x in m.group(4).split(",")])
        return ids.reshape(n_groups, group_size).tolist()
    return []


def _shape_bytes(text):
    total = 0
    for dtype, dims in re.findall(r"(\w+)\[([0-9,]*)\]", text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def cross_slice_collective_bytes(hlo: str, per_slice: int):
    """Sum the result-shape bytes of every collective whose replica group
    spans the slice boundary (device ids on both sides of ``per_slice``).
    Local (post-SPMD) shapes — a relative measure between two programs
    compiled on the same mesh."""
    total = 0
    count = 0
    for line in hlo.splitlines():
        if "replica_groups=" not in line or " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        op_idx = None
        for op in _COLLECTIVES:
            i = rhs.find(op + "(")
            if i >= 0 and (op_idx is None or i < op_idx):
                op_idx = i
        if op_idx is None:
            continue
        groups = _replica_groups(line)
        crossing = any(
            ids and min(ids) // per_slice != max(ids) // per_slice
            for ids in groups
        )
        if not crossing:
            continue
        count += 1
        total += _shape_bytes(rhs[:op_idx])
    return total, count


def test_hlo_replica_group_decoder():
    groups = _replica_groups("x replica_groups={{0,4},{1,5},{2,6},{3,7}}, y")
    assert groups == [[0, 4], [1, 5], [2, 6], [3, 7]]
    groups = _replica_groups("x replica_groups=[1,8]<=[8], y")
    assert groups == [[0, 1, 2, 3, 4, 5, 6, 7]]
    groups = _replica_groups("x replica_groups=[4,2]<=[2,4]T(1,0), y")
    assert groups == [[0, 4], [1, 5], [2, 6], [3, 7]]
    groups = _replica_groups("x replica_groups=[2,4]<=[8], y")
    assert groups == [[0, 1, 2, 3], [4, 5, 6, 7]]


# ---------------------------------------------------------------------------
# plan accounting: dcn bytes = flat / N_ici
# ---------------------------------------------------------------------------


def _toy_plans(hier_flag):
    mesh = make_mesh(MeshConfig(**MESH2x4))
    tree = {
        "w": jax.ShapeDtypeStruct((256, 64), jnp.float32),
        "v": jax.ShapeDtypeStruct((128, 64), jnp.float32),
    }
    from determined_tpu.parallel.sharding import param_shardings

    shardings = param_shardings({k: None for k in tree}, mesh)
    return _overlap.build_plan(
        tree, shardings, mesh, enabled=True,
        bucket_bytes=1 << 20, min_sync_bytes=0, hierarchical=hier_flag,
    )


def test_hierarchical_plan_models_fragment_only_dcn_traffic():
    flat = _toy_plans(False)
    hier = _toy_plans(True)
    assert flat is not None and hier is not None
    assert flat.hierarchical_dcn == 0 and hier.hierarchical_dcn == 2
    n_ici = 4
    # flat: the full payload crosses dcn; hier: only the 1/N_ici fragment
    assert flat.comm.dcn_bytes_per_step > 0
    assert hier.comm.dcn_bytes_per_step == flat.comm.dcn_bytes_per_step // n_ici
    # the fingerprints (and so the jit-reuse cache keys) differ
    assert flat.fingerprint().endswith(":flat")
    assert hier.fingerprint().endswith(":hier=dcn2")
    assert flat.fingerprint() != hier.fingerprint()
    # hier sync shardings stay on ICI axes: dcn never appears in a spec
    # (flat ones carry it — that is the whole difference)
    flat_axes, hier_axes = set(), set()
    for plan_axes, p in ((flat_axes, flat), (hier_axes, hier)):
        for s in p.sync_shardings:
            if s is None:
                continue
            for ax in s.spec:
                plan_axes.update(ax if isinstance(ax, tuple) else (ax,))
    assert MeshAxes.DCN in flat_axes
    assert MeshAxes.DCN not in hier_axes


def test_split_hops_gives_dcn_first_claim_on_hiding_budget():
    comm = _overlap.CommModel(
        bytes_per_step=int(80e9), n_buckets=4, bandwidth=100e9,
        bwd_frac=0.5, dcn_bytes_per_step=int(10e9), dcn_bandwidth=10e9,
    )
    hops = comm.split_hops(avg_step_s=1.0)
    assert set(hops) == {"dcn", "ici"}
    dcn_exposed, dcn_hidden = hops["dcn"]
    ici_exposed, ici_hidden = hops["ici"]
    # dcn wants 1.0s, hideable 0.75s, budget 0.5s -> all budget to dcn
    assert dcn_hidden == pytest.approx(0.5)
    assert dcn_exposed == pytest.approx(0.5)
    assert ici_hidden == 0.0 and ici_exposed == pytest.approx(0.8)
    # the aggregate split() stays the sum of the hops (ledger back-compat)
    exposed, hidden = comm.split(1.0)
    assert exposed == pytest.approx(dcn_exposed + ici_exposed)
    assert hidden == pytest.approx(dcn_hidden + ici_hidden)


def test_link_bandwidth_env_per_link_and_back_compat(monkeypatch):
    monkeypatch.setenv("DTPU_COMM_BW_GBPS", "ici:90,dcn:12")
    ici, dcn = _overlap.link_bandwidths("cpu")
    assert ici == pytest.approx(90e9) and dcn == pytest.approx(12e9)
    monkeypatch.setenv("DTPU_COMM_BW_GBPS", "42")  # single value: both links
    ici, dcn = _overlap.link_bandwidths("cpu")
    assert ici == pytest.approx(42e9) and dcn == pytest.approx(42e9)
    for bad in ("ici:bogus", "ici:90,ici:80", "wan:5", "ici:-1"):
        monkeypatch.setenv("DTPU_COMM_BW_GBPS", bad)
        with pytest.raises(ValueError):
            _overlap.link_bandwidths("cpu")
    # empty counts as unset: fall back to the per-kind tables
    monkeypatch.setenv("DTPU_COMM_BW_GBPS", "")
    ici, dcn = _overlap.link_bandwidths("TPU v5p")
    assert ici == _overlap.ICI_BW_BY_KIND["TPU v5p"]
    assert dcn == _overlap.DCN_BW_BY_KIND["TPU v5p"]
    # a TPU the tables do not know is an error, not a default
    with pytest.raises(ValueError, match="TPU v99"):
        _overlap.link_bandwidths("TPU v99")


def test_hierarchical_requires_overlap():
    with pytest.raises(InvalidExperimentConfig):
        ExperimentConfig.parse(
            {"optimizations": {"hierarchical_collectives": True}}
        )


# ---------------------------------------------------------------------------
# the tentpole: parity + HLO fragment pin on the 2-slice x 4-chip mesh
# ---------------------------------------------------------------------------


def test_hierarchical_parity_and_fragment_only_dcn_hlo(tmp_path):
    """Hierarchical sync vs the flat all-reduce baseline on dcn2 x data2 x
    fsdp2: params AND opt_state allclose after N steps, the modeled DCN
    bytes drop to flat/N_ici, and the compiled HLO's cross-slice
    collectives carry a strict fraction of the flat program's bytes — no
    full-gradient payload crosses ``dcn``."""
    base, base_losses = _run(tmp_path, {}, tag="a")
    hier, hier_losses = _run(
        tmp_path,
        {"overlap_grad_sync": True, "overlap_bucket_mb": 1,
         "hierarchical_collectives": True},
        tag="b",
    )
    flat, _ = _run(
        tmp_path, {"overlap_grad_sync": True, "overlap_bucket_mb": 1}, tag="c"
    )
    plan = hier._overlap_plan
    assert plan is not None and plan.enabled and plan.hierarchical_dcn == 2

    # numerics: hier == flat-overlap == plain baseline
    assert _maxdiff(base.state.params, hier.state.params) < 1e-5
    assert _maxdiff(base.state.opt_state, hier.state.opt_state) < 1e-5
    assert _maxdiff(flat.state.params, hier.state.params) < 1e-5
    assert all(np.isfinite(base_losses)) and all(np.isfinite(hier_losses))

    # modeled traffic: dcn hop carries exactly the 1/N_ici fragment
    flat_plan = flat._overlap_plan
    assert flat_plan.comm.dcn_bytes_per_step > 0
    assert (
        plan.comm.dcn_bytes_per_step
        == flat_plan.comm.dcn_bytes_per_step // 4
    )

    # HLO pin: cross-slice collective bytes shrink by ~N_ici (allow 2x
    # slack for layout/fusion noise; the flat program all-reduces full
    # gradients across the slice boundary, the hier program only the
    # dcn fragments)
    hier_bytes, hier_n = cross_slice_collective_bytes(
        _compiled_text(hier), per_slice=4
    )
    flat_bytes, flat_n = cross_slice_collective_bytes(
        _compiled_text(flat), per_slice=4
    )
    assert flat_n > 0 and flat_bytes > 0, "flat program has no dcn collectives?"
    assert hier_n > 0, "hier program lost its cross-slice fragment all-reduce"
    assert hier_bytes * 2 <= flat_bytes, (hier_bytes, flat_bytes)


def test_per_hop_comm_counters_reach_the_profile_ledger(tmp_path):
    """The trainer splits step.comm by hop on a dcn2 mesh; the profile
    ledger folds the per-hop counters and the text report prints per-hop
    sub-lines (the `dtpu experiment profile` surface)."""
    from determined_tpu.observability import (
        compute_ledger, format_ledger_text, get_tracer,
    )

    tracer = get_tracer()
    tracer.reset()
    tracer.configure(enabled=True)
    tracer.start()
    try:
        with tracer.span("trial.run", cat="trial", trial="ms-test"):
            _run(
                tmp_path,
                {"overlap_grad_sync": True,
                 "hierarchical_collectives": True},
                steps=2, tag="h",
            )
    finally:
        tracer.stop()
    led = compute_ledger(tracer.chrome_events())
    comm = led["experiment"].get("step.comm")
    assert comm is not None
    hops = comm.get("hops")
    assert hops and "dcn" in hops and "ici" in hops, comm
    assert hops["dcn"]["bytes"] > 0 and hops["ici"]["bytes"] > 0
    # fragment-only dcn: the dcn hop moves fewer bytes than the ici hops
    assert hops["dcn"]["bytes"] < hops["ici"]["bytes"]
    text = format_ledger_text(led)
    assert "dcn" in text and "ici" in text
    tracer.reset()


# ---------------------------------------------------------------------------
# composition matrix: dcn2 x {fsdp, agg>1, int8, 1f1b}
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_hier_composes_with_pure_fsdp(tmp_path):
    mesh = dict(num_slices=2, fsdp=4)
    base, _ = _run(tmp_path, {}, tag="a", mesh=mesh)
    hier, _ = _run(
        tmp_path,
        {"overlap_grad_sync": True, "hierarchical_collectives": True},
        tag="b", mesh=mesh,
    )
    assert hier._overlap_plan is not None and hier._overlap_plan.hierarchical_dcn == 2
    assert _maxdiff(base.state.params, hier.state.params) < 1e-5
    assert _maxdiff(base.state.opt_state, hier.state.opt_state) < 1e-5


@pytest.mark.slow
def test_hier_composes_with_grad_accumulation(tmp_path):
    base, _ = _run(tmp_path, {"aggregation_frequency": 2}, steps=2, tag="a")
    hier, _ = _run(
        tmp_path,
        {"aggregation_frequency": 2, "overlap_grad_sync": True,
         "hierarchical_collectives": True},
        steps=2, tag="b",
    )
    assert _maxdiff(base.state.params, hier.state.params) < 1e-5


@pytest.mark.slow
def test_hier_composes_with_int8(tmp_path):
    tr, losses = _run(
        tmp_path,
        {"overlap_grad_sync": True, "hierarchical_collectives": True,
         "quantized_matmul": "int8"},
        steps=3, tag="q",
    )
    assert all(np.isfinite(losses))
    assert tr._overlap_plan is not None and tr._overlap_plan.hierarchical_dcn == 2


@pytest.mark.slow
def test_hier_composes_with_1f1b_pipeline(tmp_path):
    mesh = dict(num_slices=2, pipe=2, data=2)
    base, _ = _run(
        tmp_path, {"pipeline_schedule": "1f1b"}, steps=2, tag="a", mesh=mesh
    )
    hier, _ = _run(
        tmp_path,
        {"pipeline_schedule": "1f1b", "overlap_grad_sync": True,
         "hierarchical_collectives": True},
        steps=2, tag="b", mesh=mesh,
    )
    assert _maxdiff(base.state.params, hier.state.params) < 1e-4
