"""End-to-end training engine tests on the 8-device virtual CPU mesh.

The analog of the reference's trial-framework tests
(``harness/tests/experiment/pytorch/``): real training loops on tiny
fixture models with dummy core contexts, no cluster.
"""

import numpy as np
import pytest
import jax

from determined_tpu import core, train
from determined_tpu.config import ExperimentConfig, Length
from determined_tpu.models.mnist import MnistTrial
from determined_tpu.parallel.mesh import MeshConfig

# the trainer's checkpoint drain/save/restore paths issue control-plane
# collectives; running the suite under the collective-sequence sentinel
# proves the sequences stay rank-uniform on every path the tests drive
pytestmark = pytest.mark.collective_order


HPARAMS = {"lr": 1e-2, "hidden": 32, "global_batch_size": 32, "dataset_size": 256}


def make_context(tmp_path, mesh_config, hparams=None, exp_config=None):
    core_ctx = core._dummy_init(checkpoint_dir=str(tmp_path / "ckpts"))
    return train.init(
        hparams=hparams or dict(HPARAMS),
        mesh_config=mesh_config,
        core_context=core_ctx,
        exp_config=exp_config,
        seed=7,
    )


@pytest.mark.parametrize(
    "mesh_config",
    [
        MeshConfig(data=8),
        MeshConfig(data=2, fsdp=2, tensor=2),
        MeshConfig(fsdp=4, tensor=2),
    ],
    ids=["dp8", "dp2-fsdp2-tp2", "fsdp4-tp2"],
)
def test_fit_learns_under_parallelism(tmp_path, mesh_config):
    ctx = make_context(tmp_path, mesh_config)
    trial = MnistTrial(ctx)
    trainer = train.Trainer(trial)
    result = trainer.fit(
        Length.batches(40),
        validation_period=Length.batches(20),
        report_period=Length.batches(10),
    )
    assert result["steps_completed"] == 40
    vm = result["validation_metrics"]
    # synthetic mnist is class-separable: must beat random guessing by a lot
    assert vm["validation_accuracy"] > 0.5, vm
    assert result["latest_checkpoint"] is not None


def test_metrics_reported_and_loss_decreases(tmp_path):
    ctx = make_context(tmp_path, MeshConfig(data=4))
    trainer = train.Trainer(MnistTrial(ctx))
    reported = []
    orig = ctx.core.train.report_training_metrics
    ctx.core.train.report_training_metrics = lambda s, m: (reported.append((s, m)), orig(s, m))
    trainer.fit(Length.batches(30), report_period=Length.batches(10))
    steps = [s for s, _ in reported]
    assert steps == [10, 20, 30]
    assert all("loss" in m and "samples_per_second" in m for _, m in reported)
    assert reported[-1][1]["loss"] < reported[0][1]["loss"]


def test_checkpoint_resume_exact_continuation(tmp_path):
    """Train 30; train 15+resume+15; final params must match batch-for-batch."""
    ctx_a = make_context(tmp_path, MeshConfig(data=2))
    t_a = train.Trainer(MnistTrial(ctx_a))
    t_a.fit(Length.batches(30), report_period=Length.batches(30))
    params_a = jax.device_get(t_a.state.params)

    ctx_b = make_context(tmp_path, MeshConfig(data=2))
    t_b = train.Trainer(MnistTrial(ctx_b))
    res_b = t_b.fit(
        Length.batches(15),
        checkpoint_period=Length.batches(15),
        report_period=Length.batches(15),
    )
    sid = res_b["latest_checkpoint"]
    assert sid

    ctx_c = make_context(tmp_path, MeshConfig(data=2))
    t_c = train.Trainer(MnistTrial(ctx_c))
    t_c.fit(
        Length.batches(30),
        latest_checkpoint=sid,
        report_period=Length.batches(30),
    )
    assert t_c.steps_completed == 30
    params_c = jax.device_get(t_c.state.params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5),
        params_a,
        params_c,
    )


def test_resume_across_mesh_change(tmp_path):
    """Checkpoint under dp2, resume under fsdp4-tp2 (resharded restore)."""
    ctx_a = make_context(tmp_path, MeshConfig(data=2))
    t_a = train.Trainer(MnistTrial(ctx_a))
    sid = t_a.fit(
        Length.batches(10),
        checkpoint_period=Length.batches(10),
        report_period=Length.batches(10),
    )["latest_checkpoint"]

    ctx_b = make_context(tmp_path, MeshConfig(fsdp=4, tensor=2))
    t_b = train.Trainer(MnistTrial(ctx_b))
    t_b.fit(Length.batches(20), latest_checkpoint=sid, report_period=Length.batches(20))
    assert t_b.steps_completed == 20


def test_preemption_checkpoints_and_exits(tmp_path):
    ctx = make_context(tmp_path, MeshConfig(data=2))
    trainer = train.Trainer(MnistTrial(ctx))
    fired = []
    orig_should = ctx.core.preempt.should_preempt

    def fake_should(auto_ack=True):
        # preempt after the second report boundary
        return len(fired) >= 0 and trainer.steps_completed >= 20

    ctx.core.preempt.should_preempt = fake_should
    result = trainer.fit(Length.batches(100), report_period=Length.batches(10))
    assert result["stopped_early"]
    assert result["steps_completed"] == 20
    assert result["latest_checkpoint"] is not None


def test_checkpoint_policy_best_only_saves_improvements(tmp_path):
    exp = ExperimentConfig.parse(
        {
            "searcher": {"name": "single", "metric": "validation_accuracy", "smaller_is_better": False},
            "checkpoint_policy": "best",
        }
    )
    ctx = make_context(tmp_path, MeshConfig(data=2), exp_config=exp)
    ctx.hparams = dict(HPARAMS)
    trainer = train.Trainer(MnistTrial(ctx))
    saves = []
    orig = trainer._save_checkpoint

    def counting_save(asynchronous=True):
        sid = orig(asynchronous=asynchronous)
        saves.append(trainer.steps_completed)
        return sid

    trainer._save_checkpoint = counting_save
    trainer.fit(Length.batches(30), validation_period=Length.batches(10))
    assert len(saves) >= 1  # at least the first validation is an improvement


def test_epoch_units(tmp_path):
    ctx = make_context(tmp_path, MeshConfig(data=2))
    trainer = train.Trainer(MnistTrial(ctx))
    result = trainer.fit(Length.epochs(2), report_period=Length.batches(100))
    # 256 records / 32 batch = 8 batches/epoch -> 16 steps
    assert result["steps_completed"] == 16


def test_gradient_accumulation_matches_large_batch(tmp_path):
    """aggregation_frequency=N over batch B must produce the same params as
    one step over batch N*B (same records, same order, averaged grads) — the
    onevar-style equivalence proof (reference _pytorch_context.py
    aggregation_frequency)."""
    import optax

    from determined_tpu.config import ExperimentConfig
    from determined_tpu.data import DataLoader

    class SgdNoShuffle(MnistTrial):
        # plain SGD keeps the equivalence exact; unshuffled loader makes
        # 4 microbatches of 8 cover the same 32 records as 1 batch of 32
        def build_optimizer(self):
            return optax.sgd(0.1)

        def build_training_data_loader(self):
            return DataLoader(
                self._dataset(train=True),
                self.context.get_global_batch_size(),
                shuffle=False,
                seed=0,
            )

    def run(exp_cfg, bs, steps, tag):
        hp = dict(HPARAMS)
        hp["global_batch_size"] = bs
        ctx = make_context(
            tmp_path / tag, MeshConfig(data=2), hparams=hp, exp_config=exp_cfg
        )
        trainer = train.Trainer(SgdNoShuffle(ctx))
        trainer.fit(Length.batches(steps))
        return jax.device_get(trainer.state.params)

    agg_cfg = ExperimentConfig.parse({"optimizations": {"aggregation_frequency": 4}})
    p_agg = run(agg_cfg, 8, 2, "agg")   # 2 steps x (4 micro x 8)
    p_big = run(None, 32, 2, "big")     # 2 steps x 32
    flat_a, flat_b = jax.tree.leaves(p_agg), jax.tree.leaves(p_big)
    assert len(flat_a) == len(flat_b) and flat_a
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)


def test_custom_metric_reducers(tmp_path):
    """Non-mean validation reducers: max/sum/min/custom combine across the
    validation sweep inside the jitted eval step (reference _reducer.py)."""
    import jax.numpy as jnp

    from determined_tpu.train import MetricReducer

    class ReducerTrial(MnistTrial):
        def evaluate_batch(self, model, params, batch):
            base = super().evaluate_batch(model, params, batch)
            bs = batch["image"].shape[0]
            return {
                **base,
                "val_examples": jnp.asarray(bs, jnp.float32),
                "val_batch_max_label": batch["label"].max().astype(jnp.float32),
                "val_batch_min_label": batch["label"].min().astype(jnp.float32),
                "val_sq_examples": jnp.asarray(bs, jnp.float32),
            }

        def evaluation_reducers(self):
            return {
                "val_examples": "sum",
                "val_batch_max_label": "max",
                "val_batch_min_label": "min",
                # custom: sum of squares, then sqrt at finalize
                "val_sq_examples": MetricReducer(
                    init=0.0,
                    accumulate=lambda c, v: c + v * v,
                    finalize=lambda c, n: c ** 0.5,
                ),
            }

    ctx = make_context(tmp_path, MeshConfig(data=2))
    trainer = train.Trainer(ReducerTrial(ctx))
    result = trainer.fit(Length.batches(4), validation_period=Length.batches(4))
    vm = result["validation_metrics"]
    ds = HPARAMS["dataset_size"]
    bs = HPARAMS["global_batch_size"]
    n_batches = ds // bs
    assert vm["val_examples"] == ds  # sum of batch sizes = dataset size
    assert 0 <= vm["val_batch_min_label"] <= vm["val_batch_max_label"] <= 9
    assert vm["val_sq_examples"] == pytest.approx((n_batches * bs * bs) ** 0.5)
    # default mean still applies to unlisted metrics
    assert 0.0 <= vm["validation_accuracy"] <= 1.0


def test_resnet_cifar_learns(tmp_path):
    """CNN/ResNet model family (GroupNorm, bf16 convs) trains under dp
    and beats random guessing on the separable synthetic set."""
    from determined_tpu.models.resnet import CifarTrial

    hp = {
        "lr": 0.05,
        "momentum": 0.9,
        "global_batch_size": 32,
        "dataset_size": 256,
        "depth_per_stage": 1,
        "widths": (8, 16),
        "bf16": False,
        "num_classes": 4,
    }
    ctx = make_context(tmp_path, MeshConfig(data=4), hparams=hp)
    trainer = train.Trainer(CifarTrial(ctx))
    result = trainer.fit(Length.batches(24), validation_period=Length.batches(24))
    vm = result["validation_metrics"]
    assert vm["validation_accuracy"] > 0.4, vm  # 4 classes -> random = 0.25
    assert result["latest_checkpoint"]


def test_lr_schedule_surfaced_in_metrics(tmp_path):
    """A trial exposing `lr_schedule` gets its live learning rate reported
    with the training metrics (reference: the LRScheduler wrapper's state
    surfacing)."""
    import optax

    from determined_tpu import core, train
    from determined_tpu.models.mnist import MnistTrial
    from determined_tpu.parallel.mesh import MeshConfig

    class SchedTrial(MnistTrial):
        def build_optimizer(self):
            self.lr_schedule = optax.linear_schedule(1e-2, 0.0, 100)
            return optax.adam(self.lr_schedule)

    ctx = train.init(
        hparams={"lr": 1e-2, "hidden": 8, "global_batch_size": 8,
                 "dataset_size": 32},
        mesh_config=MeshConfig(data=1),
        core_context=core._dummy_init(checkpoint_dir=str(tmp_path)),
        seed=0,
    )
    trainer = train.Trainer(SchedTrial(ctx))
    trainer._setup()
    assert "lr" in trainer.state.metric_acc
    it = iter(trainer.train_loader)
    from determined_tpu.data import to_global

    trainer.state = trainer._train_step(
        trainer.state, to_global(next(it), trainer.mesh)
    )
    import numpy as np

    first = float(np.asarray(trainer.state.metric_acc["lr"]))
    assert 0 < first <= 1e-2  # step-0 rate of the linear schedule


# ---------------------------------------------------------------------------
# async-checkpoint drain: per-rank error-flag allgather (fail fast together)
# ---------------------------------------------------------------------------


class _FakeDist:
    """Stand-in multi-rank distributed context for the drain point: records
    the allgather and returns a scripted set of per-rank error flags."""

    def __init__(self, peer_flags, size=2):
        self.size = size
        self.is_chief = True
        self.allgather_calls = []
        self._peer_flags = peer_flags

    def allgather(self, obj):
        self.allgather_calls.append(obj)
        return [obj] + list(self._peer_flags)


def _trainer_with_pending_save(tmp_path, monkeypatch, local_write_fails=False):
    from determined_tpu.train import serialization

    ctx = make_context(tmp_path, MeshConfig(data=2))
    trainer = train.Trainer(MnistTrial(ctx))
    trainer._setup()
    if local_write_fails:
        def boom(path, tree):
            raise OSError("disk gone")

        monkeypatch.setattr(
            "determined_tpu.train._trainer.serialization.save_arrays", boom
        )
    trainer._save_checkpoint()  # async dispatch; writer runs in background
    assert trainer._pending_save is not None
    return trainer


def test_drain_fails_fast_when_remote_rank_writer_failed(tmp_path, monkeypatch):
    """A healthy rank whose PEER's background writer died must raise at the
    drain point instead of entering the collective finalize (where it would
    hang into the 600s collective timeout waiting for the dead rank)."""
    trainer = _trainer_with_pending_save(tmp_path, monkeypatch)
    fake = _FakeDist(peer_flags=[True])
    trainer.core.distributed = fake
    finished = []
    trainer._pending_save.finish = lambda: finished.append(True)
    with pytest.raises(RuntimeError, match=r"rank\(s\) \[1\]"):
        trainer._drain_pending_save()
    assert fake.allgather_calls == [False]  # local writer was healthy
    assert not finished  # never reached the collective finalize
    assert trainer._pending_save is None  # drained, not retried


def test_drain_local_failure_still_raises_with_cause(tmp_path, monkeypatch):
    trainer = _trainer_with_pending_save(tmp_path, monkeypatch, local_write_fails=True)
    fake = _FakeDist(peer_flags=[False])
    trainer.core.distributed = fake
    with pytest.raises(RuntimeError, match="failed") as ei:
        trainer._drain_pending_save()
    assert isinstance(ei.value.__cause__, OSError)
    assert fake.allgather_calls == [True]  # the local failure was exchanged


def test_drain_healthy_ranks_finalize_and_emit_stall_span(tmp_path, monkeypatch):
    from determined_tpu.observability import get_tracer

    tracer = get_tracer()
    tracer.reset()
    tracer.configure(enabled=True)  # whatever an earlier file of this worker left it as (a server set up without a trace directory turns it off)
    trainer = _trainer_with_pending_save(tmp_path, monkeypatch)
    fake = _FakeDist(peer_flags=[False])
    trainer.core.distributed = fake
    sid = trainer._drain_pending_save()
    assert sid is not None and trainer.latest_checkpoint == sid
    assert fake.allgather_calls == [False]
    # the stall span is emitted either way (healthy drain included)
    names = [e["name"] for e in tracer.chrome_events() if e.get("ph") == "X"]
    assert "checkpoint.stall" in names
