"""The checkpoint backend is imported once a process, by the first save or
restore or by the prefetch a checkpointing run starts, and says so in one
``ckpt.backend_import`` span (``train/serialization.py``).

Every case is a fresh process: the import happens once a process, and a
pytest worker that has run any checkpoint test has already had it.
"""

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# counts real imports of the backend: a finder is asked once a module that is
# not in ``sys.modules`` yet, and never for one that is
_PRELUDE = """
import json, os, sys, tempfile, threading

class _CountImports:
    n = 0
    @classmethod
    def find_spec(cls, name, path=None, target=None):
        if name == "orbax.checkpoint":
            cls.n += 1
        return None

sys.meta_path.insert(0, _CountImports)

import jax.numpy as jnp
import numpy as np
from determined_tpu.observability import get_tracer
from determined_tpu.train import serialization

assert "orbax" not in sys.modules

def tree():
    return {"w": jnp.arange(12.0).reshape(3, 4), "step": jnp.asarray(3, jnp.int32)}

def report(**more):
    spans = [e["args"] for e in get_tracer().chrome_events() if e["name"] == "ckpt.backend_import"]
    print("BACKEND " + json.dumps({"imports": _CountImports.n, "spans": spans, **more}))
"""

_ROUND_TRIP = """
with tempfile.TemporaryDirectory() as d:
    serialization.save_arrays(d, tree())
    back = serialization.restore_arrays(d, serialization.abstract_like(tree()))
np.testing.assert_array_equal(back["w"], tree()["w"])
assert int(back["step"]) == 3
report()
"""

_PREFETCH_THEN_SAVE = """
serialization.prefetch_backend()
serialization.prefetch_backend()  # a second call does nothing
with tempfile.TemporaryDirectory() as d:
    serialization.save_arrays(d, tree())
    serialization.prefetch_backend()  # nor does one after the backend is loaded
    back = serialization.restore_arrays(d, serialization.abstract_like(tree()))
np.testing.assert_array_equal(back["w"], tree()["w"])
report(import_threads=sum(t.name == "dtpu-ckpt-import" for t in threading.enumerate()))
"""

# Two savers and thirty more callers (more threads than cores, a short
# switch interval) reach the accessor at once, as a writer thread, a restore
# and late prefetches would; the writes themselves take turns, because orbax
# does not take two saves at once in one process (the platform has one
# writer thread).
_MANY_THREADS = """
gate = threading.Barrier(32)
one_writer = threading.Lock()
errors, modules = [], []

def call(d):
    try:
        gate.wait(timeout=60)
        if d is None:
            serialization.prefetch_backend()
        modules.append(serialization._backend())
        if d is not None:
            with one_writer:
                serialization.save_arrays(d, tree())
    except BaseException as e:
        errors.append(repr(e))

interval = sys.getswitchinterval()
sys.setswitchinterval(1e-5)
try:
    with tempfile.TemporaryDirectory() as a, tempfile.TemporaryDirectory() as b:
        threads = [threading.Thread(target=call, args=(d,)) for d in [a, b] + [None] * 30]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        assert len(modules) == 32 and all(m is modules[0] for m in modules)
        for d in (a, b):
            back = serialization.restore_arrays(d, serialization.abstract_like(tree()))
            np.testing.assert_array_equal(back["w"], tree()["w"])
finally:
    sys.setswitchinterval(interval)
report()
"""

# An image without the backend: the prefetch fails quietly (a logged warning),
# and the save raises the import's own error where the run can handle it.
_BACKEND_MISSING = """
sys.modules["orbax.checkpoint"] = None  # what `import` finds when a module is barred
serialization.prefetch_backend()
for t in threading.enumerate():
    if t.name == "dtpu-ckpt-import":
        t.join(timeout=60)
        assert not t.is_alive()
with tempfile.TemporaryDirectory() as d:
    try:
        serialization.save_arrays(d, tree())
        raised = None
    except ImportError as e:
        raised = type(e).__name__
    del sys.modules["orbax.checkpoint"]
    serialization.save_arrays(d, tree())  # the backend is back: the next save imports it
    back = serialization.restore_arrays(d, serialization.abstract_like(tree()))
np.testing.assert_array_equal(back["w"], tree()["w"])
report(raised=raised)
"""

# argv[1] "eager" imports the backend first, as every process did before the
# import left ``serialization``'s top: the checkpoint that process writes is
# the one the lazy process has to write
_FIT_ALL = """
import hashlib
if sys.argv[1] == "eager":
    import orbax.checkpoint
import jax
from determined_tpu import core, train
from determined_tpu.config import Length
from determined_tpu.models.mnist import MnistTrial
from determined_tpu.parallel.mesh import MeshConfig

first_save = {}
real_save = serialization.save_arrays

def watched_save(path, state):
    # by its first save the run has the backend, or is about to wait for it
    first_save.setdefault("prefetch_started", serialization._prefetch_started)
    return real_save(path, state)

serialization.save_arrays = watched_save

with tempfile.TemporaryDirectory() as d:
    ctx = train.init(
        hparams={"lr": 1e-2, "hidden": 8, "global_batch_size": 8, "dataset_size": 32},
        mesh_config=MeshConfig(data=1),
        core_context=core._dummy_init(checkpoint_dir=d),
        seed=0,
    )
    result = train.Trainer(MnistTrial(ctx)).fit(
        Length.batches(2),
        report_period=Length.batches(2),
        validation_period=Length.batches(2),
        checkpoint_policy="all",
    )
    root = os.path.join(d, result["latest_checkpoint"])
    files = sorted(
        os.path.relpath(os.path.join(r, f), root) for r, _, fs in os.walk(root) for f in fs
    )
    _, restored = train.load_trial_from_checkpoint(root)
    leaves = {
        jax.tree_util.keystr(k): hashlib.sha256(np.asarray(v).tobytes()).hexdigest()
        for k, v in jax.tree_util.tree_leaves_with_path(
            {"params": restored.state.params, "opt_state": restored.state.opt_state}
        )
    }
report(files=files, leaves=leaves, steps=restored.steps_completed, **first_save)
"""


def _start(script, *argv):
    return subprocess.Popen(
        [sys.executable, "-c", _PRELUDE + script, *argv],
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )


def _finish(proc):
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, err[-4000:]
    line = [ln for ln in out.splitlines() if ln.startswith("BACKEND ")][-1]
    return json.loads(line[len("BACKEND "):])


def test_round_trip_imports_the_backend_once_and_says_so():
    got = _finish(_start(_ROUND_TRIP))
    assert got["imports"] == 1
    assert len(got["spans"]) == 1, got
    (span,) = got["spans"]
    assert span["prefetched"] is False
    # the save imported it itself: what it waited is the import
    assert 0 < span["seconds"] <= span["waited_s"]


def test_prefetch_then_save_is_one_import_on_the_background_thread():
    got = _finish(_start(_PREFETCH_THEN_SAVE))
    assert got["imports"] == 1
    assert len(got["spans"]) == 1, got
    (span,) = got["spans"]
    assert span["prefetched"] is True
    assert span["seconds"] > 0 and span["waited_s"] >= 0
    assert got["import_threads"] == 0  # the thread is done, and no second one ran


def test_two_threads_saving_at_once_import_once():
    got = _finish(_start(_MANY_THREADS))
    assert got["imports"] == 1
    assert len(got["spans"]) == 1, got
    assert got["spans"][0]["seconds"] > 0


def test_a_failed_prefetch_leaves_the_error_to_the_first_save():
    got = _finish(_start(_BACKEND_MISSING))
    assert got["raised"] == "ModuleNotFoundError"
    assert got["imports"] == 1
    assert len(got["spans"]) == 1, got
    assert got["spans"][0]["prefetched"] is False


def _layout(files):
    # ocdbt names its data files at random and batches its writes by timing
    # (6 to 9 files for the same state): the layout is where they lie
    return sorted({re.sub(r"/d/[0-9a-f]{32}$", "/d/*", f) for f in files})


def test_fit_that_checkpoints_prefetches_and_writes_the_eager_checkpoint():
    eager, lazy = _start(_FIT_ALL, "eager"), _start(_FIT_ALL, "lazy")
    eager, lazy = _finish(eager), _finish(lazy)
    # Trainer.fit started the import before set-up, so the first save found
    # it loaded or under way; one import, run by the background thread
    assert lazy["prefetch_started"] is True
    assert lazy["imports"] == 1
    assert len(lazy["spans"]) == 1, lazy
    assert lazy["spans"][0]["prefetched"] is True
    # what was written is what a process with the import at its top writes
    assert "state/manifest.ocdbt" in lazy["files"] and "trainer_state.json" in lazy["files"]
    assert _layout(lazy["files"]) == _layout(eager["files"])
    assert lazy["steps"] == eager["steps"] == 2
    assert lazy["leaves"] and lazy["leaves"] == eager["leaves"]
