"""Overlapped input pipeline (``data/_prefetch.py``): resume parity under
active prefetch, worker-exception propagation, clean shutdown, fault
injection through the supervised-restart path, validation parity, and the
sharding/compilation caches.  Tier-1 (no markers), CPU-fast.
"""

import os
import threading
import time

import numpy as np
import pytest
import jax

# prefetch workers must die with their pipeline/test — leaks previously
# bled between tests (conftest._thread_leak_guard + ThreadLeakChecker)
pytestmark = pytest.mark.no_thread_leaks

from determined_tpu import core, train
from determined_tpu.config import ExperimentConfig, Length
from determined_tpu.config.experiment import InvalidExperimentConfig
from determined_tpu.data import (
    DataLoader,
    InMemoryDataset,
    InputPipeline,
    PrefetchingIterator,
    cached_batch_sharding,
    to_global,
)
from determined_tpu.data._loader import _fetch
from determined_tpu.exec.run_trial import TrialSupervisor
from determined_tpu.models.mnist import MnistTrial
from determined_tpu.parallel.mesh import MeshConfig, make_mesh
from determined_tpu.train._restart import RestartPolicy
from determined_tpu.utils import compilation_cache
from tests.faults import FaultInjector, SimulatedCrash

HPARAMS = {"lr": 1e-2, "hidden": 16, "global_batch_size": 16, "dataset_size": 64}


def make_ds(n=64):
    return InMemoryDataset({"x": np.arange(n, dtype=np.float32)})


def make_loader(n=64, bs=8, **kw):
    return DataLoader(make_ds(n), bs, seed=3, shard_rank=0, num_shards=1, **kw)


def mesh2():
    return make_mesh(MeshConfig(data=2), jax.devices()[:2])


def prefetch_threads():
    return [
        t for t in threading.enumerate() if t.name.startswith("dtpu-prefetch") and t.is_alive()
    ]


# ---------------------------------------------------------------------------
# PrefetchingIterator unit behavior
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_prefetching_iterator_preserves_order_and_terminates(depth):
    items = list(range(17))
    it = PrefetchingIterator(iter(items), depth=depth)
    assert list(it) == items
    with pytest.raises(StopIteration):
        next(it)
    it.close()  # close after exhaustion is fine


def test_worker_exception_propagates_with_original_type():
    def source():
        yield "ok-0"
        yield "ok-1"
        raise ValueError("boom in worker")

    it = PrefetchingIterator(source(), depth=2)
    assert next(it) == "ok-0"
    assert next(it) == "ok-1"
    with pytest.raises(ValueError, match="boom in worker"):
        next(it)
    # a dead stream stays dead, it does not hang
    with pytest.raises(StopIteration):
        next(it)
    it.close()


def test_close_unblocks_a_producer_stuck_on_a_full_queue():
    def infinite():
        i = 0
        while True:
            yield i
            i += 1

    it = PrefetchingIterator(infinite(), depth=2)
    assert next(it) == 0
    deadline = time.monotonic() + 5
    while it._queue.qsize() < 2 and time.monotonic() < deadline:
        time.sleep(0.01)  # let the worker fill the queue and block on put
    it.close()
    assert not it._thread.is_alive()
    it.close()  # idempotent
    with pytest.raises(StopIteration):
        next(it)


def test_fault_injection_kills_worker_and_surfaces_at_consumer():
    inj = FaultInjector()
    inj.raise_at(
        "data.prefetch.fetch",
        lambda: SimulatedCrash("injected prefetch worker death"),
        when=lambda info: info.get("batches", 0) >= 2,
    )
    loader = make_loader()
    with inj.installed():
        # device_buffer=1: synchronous conversion, so every batch fetched
        # before the kill reaches the consumer (a deeper device buffer may
        # drop in-flight batches on error — fine, the restart path replays
        # from consumed state)
        pipe = InputPipeline(loader, mesh2(), prefetch_depth=2, device_buffer=1)
        got = []
        with pytest.raises(SimulatedCrash):
            for _ in range(10):
                got.append(np.asarray(next(pipe)["x"]).tolist())
        pipe.close()
    assert len(got) == 2  # exactly the batches fetched before the kill
    assert loader.state_dict() == {"epoch": 0, "batches_in_epoch": 2, "global_batch": 8}
    assert inj.count("data.prefetch.fetch") >= 2


# ---------------------------------------------------------------------------
# resume parity: consumed-vs-fetched invariant
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [0, 2, 4])
def test_pipeline_resume_parity_matches_sync_stream(depth):
    mesh = mesh2()
    ref = [b["x"].tolist() for _, b in zip(range(20), iter(make_loader()))]

    loader = make_loader()
    pipe = InputPipeline(loader, mesh, prefetch_depth=depth, device_buffer=2)
    first = [np.asarray(next(pipe)["x"]).tolist() for _ in range(7)]
    state = loader.state_dict()  # checkpoint boundary mid-epoch (8/epoch)
    pipe.close()
    assert first == ref[:7]
    # CONSUMED position, not fetched: with depth 4 the worker ran ahead,
    # but the checkpointed state must say exactly 7 batches taken
    assert state == {"epoch": 0, "batches_in_epoch": 7, "global_batch": 8}

    resumed = make_loader()
    resumed.load_state_dict(state)
    pipe2 = InputPipeline(resumed, mesh, prefetch_depth=depth, device_buffer=2)
    rest = [np.asarray(next(pipe2)["x"]).tolist() for _ in range(13)]
    pipe2.close()
    # zero skipped, zero replayed across the checkpoint/restore
    assert rest == ref[7:20]


def test_pipeline_stacks_microbatches_and_commits_once_per_step():
    loader = make_loader()
    pipe = InputPipeline(loader, mesh2(), agg=2, prefetch_depth=2, device_buffer=2)
    batch = next(pipe)
    assert batch["x"].shape == (2, 8)  # [agg, batch]
    assert loader.state_dict() == {"epoch": 0, "batches_in_epoch": 2, "global_batch": 8}
    pipe.close()


# ---------------------------------------------------------------------------
# Trainer integration: crash under active prefetch -> restart -> exact parity
# ---------------------------------------------------------------------------


def _factory(base_dir, exp_config):
    def factory():
        core_ctx = core._dummy_init(checkpoint_dir=str(base_dir / "ckpts"))
        ctx = train.init(
            hparams=dict(HPARAMS),
            mesh_config=MeshConfig(data=2),
            core_context=core_ctx,
            exp_config=exp_config,
            seed=7,
        )
        return train.Trainer(MnistTrial(ctx))

    return factory


SYNC_CKPT = ExperimentConfig.parse({"optimizations": {"async_checkpointing": False}})


def test_prefetch_worker_death_recovers_and_training_stream_is_exact(tmp_path):
    """The prefetch worker dying mid-stream is a TRANSIENT fault: the
    supervisor restarts from the last checkpoint (taken mid-epoch, under
    active prefetch) and the final model is bit-identical to a run that
    never crashed — proof of zero skipped/duplicated batches."""
    ref = _factory(tmp_path / "ref", SYNC_CKPT)()
    ref_summary = ref.fit(
        Length.batches(10),
        checkpoint_period=Length.batches(3),  # 4 batches/epoch -> mid-epoch saves
        report_period=Length.batches(5),
    )
    assert ref_summary["steps_completed"] == 10

    inj = FaultInjector()
    # kill the background fetch worker once, mid-stream of attempt 1
    inj.raise_at(
        "data.prefetch.fetch",
        lambda: SimulatedCrash("prefetch worker died"),
        when=lambda info: info.get("batches", 0) == 7,
    )
    trainers = []
    base_factory = _factory(tmp_path / "sup", SYNC_CKPT)

    def factory():
        t = base_factory()
        trainers.append(t)
        return t

    supervisor = TrialSupervisor(
        factory,
        policy=RestartPolicy(max_restarts=2, backoff_base=0.0, jitter=0.0),
        sleep=lambda s: None,
    )
    with inj.installed():
        summary = supervisor.run(
            Length.batches(10),
            checkpoint_period=Length.batches(3),
            report_period=Length.batches(5),
        )
    assert summary["steps_completed"] == 10
    assert summary["restarts"] == 1

    ref_params = jax.device_get(ref.state.params)
    got_params = jax.device_get(trainers[-1].state.params)
    jax.tree.map(np.testing.assert_array_equal, ref_params, got_params)
    assert prefetch_threads() == []  # every worker joined on the way out


def test_preemption_shuts_pipeline_down_cleanly(tmp_path):
    trainers = []
    base_factory = _factory(tmp_path, SYNC_CKPT)

    def factory():
        t = base_factory()
        trainers.append(t)
        return t

    inj = FaultInjector()
    inj.on(
        "train.step",
        lambda info: trainers[-1].core.preempt.simulate(),
        when=lambda info: info.get("step") == 3,
        times=1,
    )
    supervisor = TrialSupervisor(factory, policy=RestartPolicy(max_restarts=1), sleep=lambda s: None)
    with inj.installed():
        summary = supervisor.run(Length.batches(12), checkpoint_period=Length.batches(4))
    assert summary["stopped_early"]
    assert summary["latest_checkpoint"] is not None
    assert prefetch_threads() == []


def test_validation_prefetch_matches_sync_metrics(tmp_path):
    trainer = _factory(tmp_path, SYNC_CKPT)()
    trainer._setup()
    overlapped = trainer._validate()
    trainer._input_opts = lambda: (0, 0)  # force the synchronous sweep
    sync = trainer._validate()
    assert set(overlapped) == set(sync) and overlapped
    for k in sync:
        np.testing.assert_allclose(overlapped[k], sync[k], rtol=1e-6)


# ---------------------------------------------------------------------------
# satellites: sharding cache, fetch pool, config knobs, compilation cache
# ---------------------------------------------------------------------------


def test_batch_sharding_is_cached_per_mesh_ndim(devices8):
    mesh = make_mesh(MeshConfig(data=4, tensor=2), devices8)
    assert cached_batch_sharding(mesh, 2, False) is cached_batch_sharding(mesh, 2, False)
    assert cached_batch_sharding(mesh, 2, False) is not cached_batch_sharding(mesh, 3, False)
    # cache returns the same sharding to_global would build uncached
    g = to_global({"x": np.ones((8, 4), np.float32)}, mesh)
    assert g["x"].sharding is cached_batch_sharding(mesh, 2, False)


class _MapStyle:
    """Deliberately not an InMemoryDataset: exercises the per-item path."""

    def __init__(self, n, keys=("x", "y")):
        self.n = n
        self.keys = keys

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return {k: np.full((3,), i, np.float32) for k in self.keys}


def test_fetch_thread_pool_matches_sequential():
    idx = np.array([4, 1, 7])
    seq = _fetch(_MapStyle(10), idx)
    loader = DataLoader(_MapStyle(10), 2, shard_rank=0, num_shards=1, fetch_workers=3)
    pooled = _fetch(_MapStyle(10), idx, loader._fetch_pool())
    for k in ("x", "y"):
        np.testing.assert_array_equal(seq[k], pooled[k])
    # single-key fast path matches the generic stack
    single = _fetch(_MapStyle(10, keys=("x",)), idx)
    np.testing.assert_array_equal(single["x"], seq["x"])
    # close() releases the pool; the loader stays usable (lazy rebuild)
    loader.close()
    assert loader._pool is None
    assert loader._fetch_pool() is not None
    loader.close()


def test_fetch_single_key_mismatches_keep_stack_semantics():
    class Ragged:
        def __len__(self):
            return 4

        def __getitem__(self, i):
            # item 2 is a corrupted record (scalar instead of a vector)
            return {"x": np.float32(i) if i == 2 else np.full((3,), i, np.float32)}

    with pytest.raises(ValueError):  # np.stack semantics, not silent broadcast
        _fetch(Ragged(), np.array([0, 1, 2]))

    class Promoting:
        def __len__(self):
            return 4

        def __getitem__(self, i):
            dt = np.float64 if i else np.float32
            return {"x": np.full((2,), i, dt)}

    out = _fetch(Promoting(), np.array([0, 1]))
    assert out["x"].dtype == np.float64  # promoted, not silently downcast


def test_invalid_depth_rejected_without_del_noise():
    with pytest.raises(ValueError, match="depth"):
        PrefetchingIterator(iter([]), depth=0)  # __del__ on the half-built
        # object must not raise a secondary AttributeError


def test_optimizations_knobs_parse_and_validate():
    cfg = ExperimentConfig.parse(
        {
            "optimizations": {
                "prefetch_depth": 4,
                "device_prefetch": 0,
                "fetch_workers": 8,
                "compilation_cache_dir": "/tmp/xc",
            }
        }
    )
    assert cfg.optimizations.prefetch_depth == 4
    assert cfg.optimizations.device_prefetch == 0
    assert cfg.optimizations.fetch_workers == 8
    assert cfg.optimizations.compilation_cache_dir == "/tmp/xc"
    with pytest.raises(InvalidExperimentConfig):
        ExperimentConfig.parse({"optimizations": {"prefetch_depth": -1}})
    with pytest.raises(InvalidExperimentConfig):
        ExperimentConfig.parse({"optimizations": {"fetch_workers": -2}})


@pytest.fixture()
def fresh_cache_setup(monkeypatch):
    """setup_compilation_cache as a new process sees it: nothing applied
    yet, no ``JAX_COMPILATION_CACHE_DIR``; jax's own setting is put back."""
    prev = jax.config.jax_compilation_cache_dir
    prev_tb = jax.config.jax_include_full_tracebacks_in_locations
    monkeypatch.delenv(compilation_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compilation_cache, "_configured", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    jax.config.update("jax_include_full_tracebacks_in_locations", prev_tb)


def test_compilation_cache_setup_cold_then_warm(tmp_path, caplog, fresh_cache_setup):
    cache_dir = str(tmp_path / "xla-cache")
    log = "determined_tpu.utils.compilation_cache"
    with caplog.at_level("INFO", logger=log):
        path = compilation_cache.setup_compilation_cache(cache_dir)
    assert path == cache_dir
    assert jax.config.jax_compilation_cache_dir == cache_dir
    assert any("cold" in r.message for r in caplog.records)

    # repeat setup in the same process is a no-op (no duplicate logs), and
    # a later caller that declares nothing (the server's kernels after
    # train.init) keeps the declared directory
    n = len(caplog.records)
    assert compilation_cache.setup_compilation_cache(cache_dir) == cache_dir
    assert compilation_cache.setup_compilation_cache(None) == cache_dir
    assert len(caplog.records) == n

    # a restarted process with a populated dir reports warm
    (tmp_path / "xla-cache" / "entry").write_bytes(b"x")
    compilation_cache._configured = None
    with caplog.at_level("INFO", logger=log):
        compilation_cache.setup_compilation_cache(cache_dir)
    assert any("warm (1 entries)" in r.message for r in caplog.records)
    # and the key of a program no longer depends on its caller's stack
    # (tests/test_tpu_compile.py shows what that is about)
    assert jax.config.jax_include_full_tracebacks_in_locations is False


@pytest.mark.parametrize("declared", [None, "knob"])
def test_compilation_cache_env_dir_wins_and_nothing_else_is_set(
    tmp_path, caplog, monkeypatch, fresh_cache_setup, declared
):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself, the program sets
    no directory in code, and the experiment knob is only logged."""
    env_dir = str(tmp_path / "from-env")
    knob = str(tmp_path / declared) if declared else None
    monkeypatch.setenv(compilation_cache.ENV_VAR, env_dir)
    # what jax holds must come back untouched: a sentinel stands in for the
    # value it read from the variable at import
    jax.config.update("jax_compilation_cache_dir", "/sentinel/untouched")
    with caplog.at_level("INFO", logger="determined_tpu.utils.compilation_cache"):
        path = compilation_cache.setup_compilation_cache(knob)
    assert path == env_dir
    assert jax.config.jax_compilation_cache_dir == "/sentinel/untouched"
    overridden = [r for r in caplog.records if "overridden" in r.message]
    assert len(overridden) == (1 if declared else 0)
    assert not (declared and os.path.exists(knob))


def test_compilation_cache_default_is_one_fixed_path_in_the_checkout(
    fresh_cache_setup, monkeypatch
):
    """Nothing declared: the fixed git-ignored path inside the checkout —
    the same in every process, so a second run finds the first's entries."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    expect = os.path.join(repo, ".dtpu_cache", "xla")
    assert compilation_cache.DEFAULT_CACHE_DIR == expect
    assert compilation_cache.resolve_cache_dir() == expect
    made = []
    monkeypatch.setattr(compilation_cache.os, "makedirs", lambda p, **kw: made.append(p))
    monkeypatch.setattr(compilation_cache.os, "scandir", lambda p: iter(()))
    assert compilation_cache.setup_compilation_cache() == expect
    assert made == [expect]
    assert jax.config.jax_compilation_cache_dir == expect
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".dtpu_cache/" in f.read().split()
