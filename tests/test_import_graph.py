"""A process start imports what it runs: no entry point of the platform
loads the checkpoint backend (orbax, tensorstore) or a cloud SDK by being
imported, and a run that never checkpoints never loads them at all.

``orbax.checkpoint`` pulls ``google.cloud.logging`` and with it
``google.api_core``'s dependency-version checks: 9 s in the sandbox, 20-27 s
on the chip's host (PERF.md section 7), once in front of every trial, every
restart and every ``dtpu serve``.  ``train/serialization.py`` imports it on
first use; this file keeps it so.  A subprocess a case, because what one
case imports must not be what the next one finds in ``sys.modules``.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# printed by every case: the loaded modules a process start may not hold
# (``google.protobuf`` is jax's and stays; the bare namespace ``google.cloud``
# is made by an installed ``*-nspkg.pth`` before any program runs)
_REPORT = """
import json, sys
heavy = sorted(
    m for m in sys.modules
    if m.split(".")[0] in ("orbax", "tensorstore")
    or m.startswith(("google.cloud.", "google.api_core"))
)
print("IMPORT_GRAPH " + json.dumps({"heavy": heavy[:20], "spans": spans}))
"""

_FIT_WITHOUT_CHECKPOINT = """
import tempfile
from determined_tpu import core, train
from determined_tpu.config import Length
from determined_tpu.models.mnist import MnistTrial
from determined_tpu.observability import get_tracer
from determined_tpu.parallel.mesh import MeshConfig

with tempfile.TemporaryDirectory() as d:
    ctx = train.init(
        hparams={"lr": 1e-2, "hidden": 8, "global_batch_size": 8, "dataset_size": 32},
        mesh_config=MeshConfig(data=1),
        core_context=core._dummy_init(checkpoint_dir=d),
        seed=0,
    )
    result = train.Trainer(MnistTrial(ctx)).fit(
        Length.batches(2), report_period=Length.batches(2), checkpoint_policy="none"
    )
assert result["steps_completed"] == 2 and result["latest_checkpoint"] is None, result
spans = [e["name"] for e in get_tracer().chrome_events() if e.get("ph") == "X"]
assert "trainer.setup" in spans, spans  # the tracer was on: an absent span is absent
"""

# the last four were clean before the import left ``serialization``'s top and
# must stay so
MODULES = [
    "determined_tpu.models",
    "determined_tpu.models.transformer",
    "determined_tpu.train",
    "determined_tpu.serve.engine",
    "determined_tpu.cli.main",
    "determined_tpu.exec.run_trial",
    "determined_tpu.exec.serve_replica",
]
CASES = {**{m: f"import {m}" for m in MODULES}, "fit-without-checkpoint": _FIT_WITHOUT_CHECKPOINT}


@pytest.mark.parametrize("case", list(CASES))
def test_process_start_loads_no_checkpoint_backend(case):
    out = subprocess.run(
        [sys.executable, "-c", "spans = []\n" + CASES[case] + _REPORT],
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("IMPORT_GRAPH ")][-1]
    report = json.loads(line[len("IMPORT_GRAPH "):])
    assert report["heavy"] == [], (
        f"{case} loaded the checkpoint backend or a cloud SDK: {report['heavy']}"
    )
    assert "ckpt.backend_import" not in report["spans"]


# -- the serving forward's arrows --------------------------------------------
#
# ``serve/`` -> ``models/serving`` -> ``models/cache_kinds`` -> ``models/transformer``,
# ``ops/``, and never back: read off each module's own import statements,
# wherever in the file they stand (an import inside a function hides a cycle,
# it does not remove it).


def _imports(module: str) -> set:
    import ast

    with open(os.path.join(REPO, *module.split(".")) + ".py") as f:
        tree = ast.parse(f.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            found.add(node.module)
            found.update(f"{node.module}.{alias.name}" for alias in node.names)
    return found


def _defined(module: str) -> set:
    import ast

    with open(os.path.join(REPO, *module.split(".")) + ".py") as f:
        tree = ast.parse(f.read())
    names = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, ast.Assign):
            names.update(target.id for target in node.targets if isinstance(target, ast.Name))
    return names


@pytest.mark.parametrize("module", ["determined_tpu.models.serving", "determined_tpu.models.cache_kinds"])
def test_the_serving_forward_imports_neither_the_trainer_nor_the_engine(module):
    imported = _imports(module)
    back = sorted(m for m in imported if m.startswith(("determined_tpu.train", "determined_tpu.serve")))
    assert back == [], f"{module} imports {back}: the arrow points from serve/ to the forward, and the forward trains nothing"
    assert "determined_tpu.models.transformer" in imported


def test_the_model_module_holds_no_serving_entry_point():
    module = "determined_tpu.models.transformer"
    forward = {"transformer_decode", "transformer_prefill", "transformer_prefill_chunked", "_serve_layer", "init_kv_cache"}
    assert _defined(module) & forward == set()
    serving = sorted(m for m in _imports(module) if m.startswith(("determined_tpu.models.serving", "determined_tpu.models.cache_kinds")))
    assert serving == [], f"models/transformer.py imports {serving}: the serving forward imports it, never the reverse"
    # and the names files under the benchmark's paths import from it never left
    kept = {
        "TransformerConfig", "TransformerLM", "LMTrial", "kv_cache_shape", "window_store_shape", "kv_bytes_per_token",
        "STATE_DTYPE", "state_bytes_per_slot", "state_pool_shapes",
    }
    assert kept <= _defined(module)
