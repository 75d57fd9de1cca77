"""Overlapped (async) checkpointing: training advances while a save is in
flight, resume parity holds, preemption reuses the in-flight save, and the
config knob restores synchronous saves.

Judge order r4#5 / SURVEY §7(b): the reference blocks through its whole
serialize+upload (``core/_checkpoint.py`` ``_upload_sharded``); here array
serialization rides a background thread while the train loop continues,
with the collective finalize at the next deterministic drain point.
"""

import threading

import numpy as np
import pytest

from determined_tpu import core, train
from determined_tpu.config import ExperimentConfig, Length
from determined_tpu.models.mnist import MnistTrial
from determined_tpu.parallel.mesh import MeshConfig
from determined_tpu.train import serialization

HPARAMS = {"lr": 1e-2, "hidden": 32, "global_batch_size": 32, "dataset_size": 256}


def make_context(tmp_path, hparams=None, exp_config=None, tag=""):
    core_ctx = core._dummy_init(checkpoint_dir=str(tmp_path / f"ckpts{tag}"))
    return train.init(
        hparams=hparams or dict(HPARAMS),
        mesh_config=MeshConfig(data=2),
        core_context=core_ctx,
        exp_config=exp_config,
        seed=7,
    )


def test_steps_advance_while_save_in_flight(tmp_path, monkeypatch):
    """The background writer for the step-2 checkpoint is gated on an event
    that only a LATER training step's report hook sets: if saves blocked
    the loop (the reference's behavior), the event could never fire before
    the write and the gate would time out."""
    ctx = make_context(tmp_path)
    trainer = train.Trainer(MnistTrial(ctx))

    later_step_reported = threading.Event()
    writer_saw_event = []
    real_save = serialization.save_arrays

    def gated_save(path, tree):
        # runs on the writer thread; wait for step >= 4 to be reported
        writer_saw_event.append(later_step_reported.wait(timeout=60))
        real_save(path, tree)

    monkeypatch.setattr(
        "determined_tpu.train._trainer.serialization.save_arrays", gated_save
    )
    orig_report = ctx.core.train.report_training_metrics

    def report(step, metrics):
        if step >= 4:
            later_step_reported.set()
        return orig_report(step, metrics)

    ctx.core.train.report_training_metrics = report

    result = trainer.fit(
        Length.batches(6),
        checkpoint_period=Length.batches(2),
        report_period=Length.batches(1),
        checkpoint_policy="none",
    )
    assert result["steps_completed"] == 6
    # every gated write observed the later step's report -> overlap is real
    assert writer_saw_event and all(writer_saw_event)


def test_async_resume_parity(tmp_path):
    """Resume from an async-written checkpoint reproduces the uninterrupted
    loss trajectory exactly."""

    def losses_of(ctx, steps, resume=None):
        reported = []
        orig = ctx.core.train.report_training_metrics
        ctx.core.train.report_training_metrics = lambda s, m: (
            reported.append((s, m["loss"])),
            orig(s, m),
        )
        trainer = train.Trainer(MnistTrial(ctx))
        result = trainer.fit(
            Length.batches(steps),
            checkpoint_period=Length.batches(2),
            report_period=Length.batches(1),
            checkpoint_policy="none",
            latest_checkpoint=resume,
        )
        return result, dict(reported)

    ctx_full = make_context(tmp_path, tag="full")
    _, full_losses = losses_of(ctx_full, 6)

    ctx_a = make_context(tmp_path, tag="ab")
    result_a, _ = losses_of(ctx_a, 4)
    sid = result_a["latest_checkpoint"]
    assert sid is not None

    ctx_b = make_context(tmp_path, tag="ab")
    result_b, resumed_losses = losses_of(ctx_b, 6, resume=sid)
    assert result_b["steps_completed"] == 6
    for step in (5, 6):
        np.testing.assert_allclose(
            resumed_losses[step], full_losses[step], rtol=1e-5, atol=1e-6
        )


def test_preempt_waits_for_in_flight_save(tmp_path, monkeypatch):
    """When preemption lands at the same boundary as a just-started async
    save, the trainer waits for the in-flight save instead of writing a
    second checkpoint of the same step."""
    ctx = make_context(tmp_path)
    trainer = train.Trainer(MnistTrial(ctx))

    save_calls = []
    real_save = serialization.save_arrays
    monkeypatch.setattr(
        "determined_tpu.train._trainer.serialization.save_arrays",
        lambda path, tree: (save_calls.append(path), real_save(path, tree)),
    )
    # preempt on the same boundary as the step-2 periodic checkpoint
    ctx.core.preempt.should_preempt = lambda: trainer.steps_completed >= 2

    result = trainer.fit(
        Length.batches(10),
        checkpoint_period=Length.batches(2),
        report_period=Length.batches(1),
        checkpoint_policy="none",
    )
    assert result["stopped_early"]
    assert result["steps_completed"] == 2
    assert len(save_calls) == 1  # the in-flight save was reused, not duplicated
    assert result["latest_checkpoint"] is not None
    # and the checkpoint is restorable
    ctx2 = make_context(tmp_path)
    trainer2 = train.Trainer(MnistTrial(ctx2))
    result2 = trainer2.fit(
        Length.batches(4),
        latest_checkpoint=result["latest_checkpoint"],
        checkpoint_policy="none",
    )
    assert result2["steps_completed"] == 4


def test_sync_knob_restores_blocking_saves(tmp_path, monkeypatch):
    """optimizations.async_checkpointing: false -> saves run on the main
    thread (the pre-r5 behavior)."""
    exp = ExperimentConfig.parse(
        {"optimizations": {"async_checkpointing": False}}
    )
    ctx = make_context(tmp_path, exp_config=exp)
    trainer = train.Trainer(MnistTrial(ctx))

    threads = []
    real_save = serialization.save_arrays
    monkeypatch.setattr(
        "determined_tpu.train._trainer.serialization.save_arrays",
        lambda path, tree: (
            threads.append(threading.current_thread().name),
            real_save(path, tree),
        ),
    )
    trainer.fit(
        Length.batches(2),
        checkpoint_period=Length.batches(2),
        checkpoint_policy="none",
    )
    assert threads and all(t == "MainThread" for t in threads)


def test_async_saves_run_off_main_thread(tmp_path, monkeypatch):
    ctx = make_context(tmp_path)
    trainer = train.Trainer(MnistTrial(ctx))
    threads = []
    real_save = serialization.save_arrays
    monkeypatch.setattr(
        "determined_tpu.train._trainer.serialization.save_arrays",
        lambda path, tree: (
            threads.append(threading.current_thread().name),
            real_save(path, tree),
        ),
    )
    trainer.fit(
        Length.batches(4),
        checkpoint_period=Length.batches(2),
        checkpoint_policy="none",
    )
    assert threads and all(t == "dtpu-ckpt-writer" for t in threads)


def test_async_write_failure_surfaces_at_drain(tmp_path, monkeypatch):
    ctx = make_context(tmp_path)
    trainer = train.Trainer(MnistTrial(ctx))

    def boom(path, tree):
        raise OSError("disk full")

    monkeypatch.setattr(
        "determined_tpu.train._trainer.serialization.save_arrays", boom
    )
    with pytest.raises(RuntimeError, match="async checkpoint"):
        trainer.fit(
            Length.batches(4),
            checkpoint_period=Length.batches(2),
            checkpoint_policy="none",
        )


def test_async_written_checkpoint_corruption_falls_back(tmp_path):
    """Corruption of an async-written checkpoint is caught by its manifest
    on resume, and the restore falls back to its parent (also async-written)
    — the fault-tolerance guarantees hold on the overlapped save path."""
    import os

    from tests.faults import FaultInjector

    ctx = make_context(tmp_path)
    trainer = train.Trainer(MnistTrial(ctx))
    result = trainer.fit(
        Length.batches(8),
        checkpoint_period=Length.batches(4),
        report_period=Length.batches(4),
        checkpoint_policy="none",
    )
    sid_b = result["latest_checkpoint"]  # step-8 save (async, drained at exit)
    store = str(tmp_path / "ckpts")
    ckpt_ctx = core._dummy_init(checkpoint_dir=store).checkpoint
    sid_a = ckpt_ctx.get_checkpoint_parent(sid_b)
    assert sid_a is not None
    assert ckpt_ctx.get_metadata(sid_a)["steps_completed"] == 4

    # corrupt the biggest file of the newest checkpoint
    root = os.path.join(store, sid_b)
    files = [
        os.path.join(dp, f)
        for dp, _d, fs in os.walk(root)
        for f in fs
        if f != "manifest.json" and os.path.getsize(os.path.join(dp, f)) > 0
    ]
    FaultInjector.truncate_file(max(files, key=os.path.getsize))

    ctx2 = make_context(tmp_path)
    trainer2 = train.Trainer(MnistTrial(ctx2))
    trainer2._setup()
    trainer2._restore_checkpoint(sid_b)
    assert trainer2.steps_completed == 4  # fell back to the step-4 parent
    assert trainer2.latest_checkpoint == sid_a


def test_two_threads_never_enter_the_backends_save_together(tmp_path, monkeypatch):
    """orbax numbers a save from a process-wide counter that every future of
    the call reads as "the current one": two concurrent trials that start a
    save together wait on each other's signals until the time-out.  The
    ``save`` calls are serialized; the waits for the writes are not."""
    import time

    inside, most, waiting = [0], [0], []

    class Checkpointer:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def save(self, path, tree):
            inside[0] += 1
            most[0] = max(most[0], inside[0])
            time.sleep(0.05)
            inside[0] -= 1

        def wait_until_finished(self):
            waiting.append(threading.get_ident())
            time.sleep(0.05)

    class Backend:
        StandardCheckpointer = Checkpointer

    monkeypatch.setattr(serialization, "_backend", lambda: Backend)
    threads = [threading.Thread(target=serialization.save_arrays, args=(str(tmp_path / str(i)), {"w": np.ones(3)})) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert most[0] == 1 and len(set(waiting)) == 4
