"""Trial preflight analyzer (``determined_tpu/lint``): per-rule bad/clean
fixtures, suppressions, JSON schema, CLI exit codes, preflight integration
(strict LocalExperiment rejects a host-syncing trial before any device
work), and the runtime sentinels (retrace + thread leaks)."""

import json
import os
import textwrap

import pytest

from determined_tpu.lint import (
    ERROR,
    Diagnostic,
    LintError,
    RetraceSentinel,
    ThreadLeakChecker,
    ThreadLeakError,
    all_rules,
    analyze_class,
    analyze_source,
    get_retrace_sentinel,
    to_json_payload,
)

# ---------------------------------------------------------------------------
# per-rule fixtures: one known-bad and one known-clean snippet per rule
# ---------------------------------------------------------------------------

BAD = {
    "host-sync": """
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        logits = model.apply(params, batch["x"])
        return float(logits.mean()), {"v": logits.mean().item()}
""",
    "block-until-ready": """
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        out = model.apply(params, batch["x"])
        out.block_until_ready()
        return out.mean(), {}
""",
    "traced-print": """
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        out = model.apply(params, batch["x"])
        print("loss is", out.mean())
        return out.mean(), {}
""",
    "python-rng": """
import numpy as np
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        noise = np.random.normal(size=(4,))
        return model.apply(params, batch["x"] + noise).mean(), {}
""",
    "trace-side-effect": """
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        out = model.apply(params, batch["x"])
        self.last_loss = out.mean()
        self.history.append(out.mean())
        return out.mean(), {}
""",
    "wall-clock": """
import time
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        t0 = time.time()
        return model.apply(params, batch["x"]).mean(), {}
""",
    "traced-control-flow": """
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        out = model.apply(params, batch["x"])
        if out.mean() > 0:
            out = out * 2
        for row in out:
            pass
        return out.mean(), {}
""",
    "mutable-default": """
class T(JaxTrial):
    def __init__(self, context, hparams={}):
        self.hparams = hparams
""",
    "unlocked-shared-state": """
import threading
class Pool:
    def __init__(self):
        self.jobs = []
        self._lock = threading.Lock()
    def start(self):
        threading.Thread(target=self._worker).start()
    def _worker(self):
        while True:
            self.jobs.pop()
    def add(self, j):
        self.jobs.append(j)
""",
    "lock-order-cycle": """
import threading
class Pair:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()
    def forward(self):
        with self._a:
            with self._b:
                pass
    def backward(self):
        with self._b:
            with self._a:
                pass
""",
    "blocking-under-lock": """
import os, threading
class Writer:
    def __init__(self):
        self._lock = threading.Lock()
    def save(self, fh):
        with self._lock:
            os.fsync(fh.fileno())
""",
    "signal-handler-unsafe": """
import signal, threading
class Guard:
    def __init__(self):
        self._lock = threading.Lock()
        self._hits = 0
    def arm(self):
        def handler(signum, frame):
            with self._lock:
                self._hits += 1
        signal.signal(signal.SIGTERM, handler)
""",
    "rank-dependent-collective": """
import jax
class Reporter:
    def report(self, dist, metrics):
        if jax.process_index() == 0:
            dist.allgather(metrics)
""",
    "conditional-collective-escape": """
class Saver:
    def save(self, dist, ok):
        dist.barrier()
        if not ok:
            raise RuntimeError("local failure")
        dist.barrier()
""",
    "unordered-iteration-feeding-collective": """
class Merger:
    def merge(self, dist, shards):
        for name in set(shards):
            dist.broadcast(name)
""",
    "rank-guarded-io-missing-barrier": """
import json
class Publisher:
    def publish(self, dist, path, manifest):
        if dist.is_chief:
            with open(path, "w") as f:
                json.dump(manifest, f)
        with open(path) as f:
            return json.load(f)
""",
    "wall-clock-divergence": """
import time
class Saver:
    def maybe_save(self, dist):
        if time.time() - self.last_save > 60:
            dist.barrier()
""",
}

CLEAN = {
    "host-sync": """
import jax.numpy as jnp
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        logits = model.apply(params, batch["x"])
        return logits.mean(), {"acc": (logits > 0).mean().astype(jnp.float32)}
""",
    "block-until-ready": """
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        return model.apply(params, batch["x"]).mean(), {}
""",
    "traced-print": """
import jax
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        out = model.apply(params, batch["x"])
        jax.debug.print("loss {l}", l=out.mean())
        return out.mean(), {}
""",
    "python-rng": """
import jax
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        noise = jax.random.normal(rng, (4,))
        return model.apply(params, batch["x"] + noise).mean(), {}
""",
    "trace-side-effect": """
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        out = model.apply(params, batch["x"])
        local = []
        local.append(out.mean())
        return out.mean(), {"loss_copy": out.mean()}
""",
    "wall-clock": """
import time
class T(JaxTrial):
    def build_callbacks(self):
        t0 = time.time()  # host-side, outside the traced step: fine
        return {}
    def loss(self, model, params, batch, rng):
        return model.apply(params, batch["x"]).mean(), {}
""",
    "traced-control-flow": """
import jax.numpy as jnp
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        out = model.apply(params, batch["x"])
        out = jnp.where(out.mean() > 0, out * 2, out)
        if batch["x"].shape[0] > 4:  # shape is static: legal
            out = out + 1
        for k, v in {"a": out}.items():  # structure iteration: legal
            pass
        return out.mean(), {}
""",
    "mutable-default": """
class T(JaxTrial):
    def __init__(self, context, hparams=None):
        self.hparams = dict(hparams or {})
""",
    "unlocked-shared-state": """
import threading
class Pool:
    def __init__(self):
        self.jobs = []
        self._lock = threading.Lock()
    def start(self):
        threading.Thread(target=self._worker).start()
    def _worker(self):
        while True:
            with self._lock:
                self.jobs.pop()
    def add(self, j):
        with self._lock:
            self.jobs.append(j)
""",
    "lock-order-cycle": """
import threading
class Pair:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()
    def forward(self):
        with self._a:
            with self._b:
                pass
    def also_forward(self):
        with self._a:
            with self._b:
                pass
""",
    "blocking-under-lock": """
import os, threading
class Writer:
    def __init__(self):
        self._lock = threading.Lock()
        self._dirty = False
    def save(self, fh):
        with self._lock:
            self._dirty = False
        os.fsync(fh.fileno())  # durability point OUTSIDE the lock
""",
    "signal-handler-unsafe": """
import signal
class Guard:
    def __init__(self):
        self._hit = False
    def arm(self):
        def handler(signum, frame):
            self._hit = True  # flag-set pattern: plain attribute write
        signal.signal(signal.SIGTERM, handler)
""",
    "rank-dependent-collective": """
import jax
class Reporter:
    def report(self, dist, metrics):
        flags = dist.allgather(metrics)  # every rank participates
        if jax.process_index() == 0:
            summarize(flags)  # chief-only HOST work is fine
""",
    "conditional-collective-escape": """
class Saver:
    def save(self, dist, ok):
        dist.barrier()
        flags = dist.allgather(ok)  # exchange the local fact first...
        if not all(flags):
            raise RuntimeError("some rank failed")  # ...all ranks escape together
        dist.barrier()
""",
    "unordered-iteration-feeding-collective": """
class Merger:
    def merge(self, dist, shards):
        for name in sorted(shards):  # every rank iterates the same order
            dist.broadcast(name)
""",
    "rank-guarded-io-missing-barrier": """
import json
class Publisher:
    def publish(self, dist, path, manifest):
        if dist.is_chief:
            with open(path, "w") as f:
                json.dump(manifest, f)
        dist.barrier()  # non-chief ranks wait for the chief's write
        with open(path) as f:
            return json.load(f)
""",
    "wall-clock-divergence": """
import time
class Saver:
    def maybe_save(self, dist, step):
        stamp = dist.broadcast(time.time())  # chief samples, all receive
        if step % 100 == 0:  # step counter: rank-uniform
            dist.barrier()
        return stamp
""",
}


def _rules_hit(src: str) -> set:
    return {d.rule for d in analyze_source(textwrap.dedent(src), "fixture.py")}


def test_rule_catalog_has_at_least_eight_rules():
    from determined_tpu.lint.rules import build_rules

    assert len(all_rules()) >= 8
    # native (control-plane contract) rules run over C++ sources, not
    # Python fixtures — they get their own bad/clean pairs further down
    native_ids = {r.id for r in build_rules(None, None) if getattr(r, "native", False)}
    assert len(native_ids) >= 8
    assert set(BAD) == set(CLEAN) == set(all_rules()) - native_ids


@pytest.mark.parametrize("rule", sorted(BAD))
def test_bad_fixture_is_flagged(rule):
    assert rule in _rules_hit(BAD[rule])


@pytest.mark.parametrize("rule", sorted(CLEAN))
def test_clean_fixture_passes(rule):
    diags = analyze_source(textwrap.dedent(CLEAN[rule]), "fixture.py")
    assert diags == [], [d.format() for d in diags]


#: a jitted kernel wrapper as ``ops/`` writes them: ``impl`` / ``window`` pick the form the trace is made for
_STATIC_ARGS = {
    "static_argnames": ("""
import functools, jax
@functools.partial(jax.jit, static_argnames=("scale", "impl"))
def _decode(q, pool, layer, *, scale, impl):
    if impl == "jnp":
        return q * scale
    return q + pool[layer]
""", []),
    "static_argnums": ("""
import functools, jax
@functools.partial(jax.jit, static_argnums=(2, 3), inline=True)
def _program(q, k, window, interpret):
    if window is not None:
        q = q[..., -window:]
    return q @ k
""", []),
    "traced_beside_static": ("""
import functools, jax
@functools.partial(jax.jit, static_argnames=("impl",))
def _decode(q, live, *, impl):
    if impl == "jnp":
        q = q * 2
    if live > 0:
        q = q + 1
    return q
""", [7]),
}


@pytest.mark.parametrize("case", sorted(_STATIC_ARGS))
def test_a_jit_decorators_static_arguments_are_not_traced(case):
    source, lines = _STATIC_ARGS[case]
    found = [d.line for d in analyze_source(source, "k.py") if d.rule == "traced-control-flow"]
    assert found == lines


def test_the_lint_gate_is_green(monkeypatch, capsys):
    """What ``scripts/lint.sh`` runs, argument for argument, in this process:
    any finding fails tier-1 and is printed."""
    import shlex
    import sys

    from determined_tpu.cli.main import main as cli_main

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "scripts", "lint.sh"), encoding="utf-8") as f:
        command = f.read().split("exec python -m determined_tpu.cli ", 1)[1]
    argv = [a for a in shlex.split(command.replace("\\\n", " ")) if a != "$@"]
    assert argv[:3] == ["lint", "--strict", "--native"] and argv[-3:] == ["determined_tpu", "examples", "scripts"]
    monkeypatch.chdir(repo)
    monkeypatch.setattr(sys, "path", list(sys.path))  # ``lint`` puts the working directory on it
    rc = cli_main(argv)
    out = capsys.readouterr().out
    assert rc == 0, out


def test_diagnostics_carry_anchor_and_severity():
    diags = analyze_source(textwrap.dedent(BAD["host-sync"]), "anchored.py")
    assert diags, "expected findings"
    for d in diags:
        assert d.file == "anchored.py"
        assert d.line > 0
        assert d.severity in ("error", "warning")
    assert any(d.severity == ERROR for d in diags)


def test_static_print_in_step_is_not_flagged():
    src = """
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        print("using fused kernel")  # static banner: harmless
        return model.apply(params, batch["x"]).mean(), {}
"""
    assert "traced-print" not in _rules_hit(src)


def test_closure_container_mutation_in_thread_target_flagged():
    """The log-shipper shape: a local-function thread target mutating a
    closure-shared container must be flagged unless a lock protects it."""
    src = """
import threading
def install():
    batch = []
    lock = threading.Lock()
    def pump_unlocked():
        batch.append(1)
    def pump_locked():
        with lock:
            batch.append(1)
    threading.Thread(target=pump_unlocked).start()
    threading.Thread(target=pump_locked).start()
"""
    diags = [
        d
        for d in analyze_source(textwrap.dedent(src), "f.py")
        if d.rule == "unlocked-shared-state"
    ]
    assert len(diags) == 1, [d.format() for d in diags]
    assert "batch.append" in diags[0].message


def test_nonlocal_rebind_in_thread_target_flagged():
    src = """
import threading
def install():
    count = 0
    def worker():
        nonlocal count
        count += 1
    threading.Thread(target=worker).start()
    return lambda: count
"""
    hits = {
        d.rule for d in analyze_source(textwrap.dedent(src), "f.py")
    }
    assert "unlocked-shared-state" in hits


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


def test_suppression_same_line():
    src = """
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        v = model.apply(params, batch["x"]).mean().item()  # dtpu: lint-ok[host-sync]
        return v, {}
"""
    assert "host-sync" not in _rules_hit(src)


def test_suppression_line_above():
    src = """
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        # dtpu: lint-ok[host-sync]
        v = model.apply(params, batch["x"]).mean().item()
        return v, {}
"""
    assert "host-sync" not in _rules_hit(src)


def test_suppression_bare_covers_all_rules():
    src = """
import time
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        t = time.time()  # dtpu: lint-ok
        return model.apply(params, batch["x"]).mean(), {}
"""
    assert _rules_hit(src) == set()


def test_suppression_of_other_rule_does_not_hide():
    src = """
class T(JaxTrial):
    def loss(self, model, params, batch, rng):
        v = model.apply(params, batch["x"]).mean().item()  # dtpu: lint-ok[wall-clock]
        return v, {}
"""
    assert "host-sync" in _rules_hit(src)


# ---------------------------------------------------------------------------
# JSON schema + CLI
# ---------------------------------------------------------------------------


def test_json_payload_schema():
    diags = analyze_source(textwrap.dedent(BAD["python-rng"]), "j.py")
    payload = to_json_payload(diags)
    assert payload["version"] == 1
    assert payload["counts"]["total"] == len(diags) > 0
    assert sum(payload["counts"]["by_severity"].values()) == len(diags)
    assert sum(payload["counts"]["by_rule"].values()) == len(diags)
    for f in payload["findings"]:
        assert set(f) == {"rule", "severity", "message", "file", "line", "col"}
        assert isinstance(f["line"], int)
    # round-trips through json
    assert json.loads(json.dumps(payload)) == payload


def test_cli_lint_file_exit_codes(tmp_path, capsys):
    from determined_tpu.cli.main import main as cli_main

    bad = tmp_path / "bad_trial.py"
    bad.write_text(textwrap.dedent(BAD["host-sync"]))
    clean = tmp_path / "clean_trial.py"
    clean.write_text(textwrap.dedent(CLEAN["host-sync"]))

    assert cli_main(["lint", str(clean)]) == 0
    out = capsys.readouterr().out
    assert "clean" in out

    assert cli_main(["lint", str(bad)]) == 1  # error-severity finding
    out = capsys.readouterr().out
    assert "host-sync" in out

    # warning-only file: default passes, --strict fails
    warn = tmp_path / "warn_trial.py"
    warn.write_text(textwrap.dedent(BAD["wall-clock"]))
    assert cli_main(["lint", str(warn)]) == 0
    capsys.readouterr()
    assert cli_main(["lint", "--strict", str(warn)]) == 1
    capsys.readouterr()

    # JSON output parses and carries the finding
    assert cli_main(["lint", str(bad), "--json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["counts"]["by_rule"].get("host-sync")


def test_cli_lint_entrypoint(capsys):
    from determined_tpu.cli.main import main as cli_main

    assert cli_main(["lint", "determined_tpu.models.mnist:MnistTrial"]) == 0
    assert cli_main(["lint", "no.such.module:Nope"]) == 2
    capsys.readouterr()


def _import_module_file(path, name):
    import importlib.util
    import sys

    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    # inspect.getsource (analyze_class) resolves source through sys.modules
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def test_analyze_class_has_absolute_anchors(tmp_path):
    mod = tmp_path / "offset_trial_mod.py"
    mod.write_text(
        "# padding line 1\n"
        "# padding line 2\n"
        "from determined_tpu.train import JaxTrial\n"
        + textwrap.dedent(
            """
            class T(JaxTrial):
                def build_model(self): ...
                def build_optimizer(self): ...
                def build_training_data_loader(self): ...
                def build_validation_data_loader(self): ...
                def loss(self, model, params, batch, rng):
                    out = model.apply(params, batch["x"])
                    return float(out.mean()), {}
            """
        )
    )
    module = _import_module_file(mod, "offset_trial_mod")
    diags = analyze_class(module.T)
    assert diags
    src_lines = mod.read_text().splitlines()
    for d in diags:
        assert d.file.endswith("offset_trial_mod.py")
        # the anchor points into the class body, past the padding
        assert d.line > 4
        assert "float(" in src_lines[d.line - 1]


def test_unknown_rule_id_rejected():
    with pytest.raises(ValueError, match="unknown lint rule"):
        analyze_source("x = 1", disabled=["no-such-rule"])


def test_lint_config_validates_suppress():
    from determined_tpu.config import ExperimentConfig, InvalidExperimentConfig

    with pytest.raises(InvalidExperimentConfig, match="unknown rules"):
        ExperimentConfig.parse({"lint": {"suppress": ["definitely-not-a-rule"]}})


# ---------------------------------------------------------------------------
# preflight integration
# ---------------------------------------------------------------------------


def _strict_config(extra_lint=None):
    from determined_tpu.config import ExperimentConfig

    return ExperimentConfig.parse(
        {
            "hyperparameters": {"global_batch_size": 8},
            "searcher": {
                "name": "single",
                "metric": "validation_loss",
                "max_length": {"batches": 2},
            },
            "checkpoint_policy": "none",
            "lint": {"strict": True, **(extra_lint or {})},
        }
    )


def test_preflight_strict_rejects_host_syncing_trial(tmp_path, monkeypatch):
    """A host-syncing trial dies in preflight — before any device query or
    scheduler slot allocation."""
    import jax

    from determined_tpu.experiment import LocalExperiment

    mod = tmp_path / "syncing_trial_mod.py"
    mod.write_text(
        textwrap.dedent(
            """
            from determined_tpu.train import JaxTrial

            class SyncingTrial(JaxTrial):
                def build_model(self): ...
                def build_optimizer(self): ...
                def build_training_data_loader(self): ...
                def build_validation_data_loader(self): ...
                def loss(self, model, params, batch, rng):
                    out = model.apply(params, batch["x"])
                    return float(out.mean()), {}
            """
        )
    )
    module = _import_module_file(mod, "syncing_trial_mod")

    calls = []
    monkeypatch.setattr(
        jax, "devices", lambda *a, **k: calls.append(1) or jax.local_devices()
    )
    exp = LocalExperiment(
        _strict_config(), module.SyncingTrial, checkpoint_dir=str(tmp_path / "ck")
    )
    with pytest.raises(LintError) as exc_info:
        exp.run()
    assert any(d.rule == "host-sync" for d in exc_info.value.diagnostics)
    assert calls == [], "preflight must reject before any device query"
    assert exp.results == {}


def test_preflight_warn_mode_logs_but_runs(tmp_path, caplog):
    """Default (non-strict) preflight only warns."""
    import logging

    from determined_tpu.experiment import LocalExperiment

    mod = tmp_path / "warning_trial_mod.py"
    mod.write_text(
        textwrap.dedent(
            """
            import time
            from determined_tpu.train import JaxTrial

            class WarningTrial(JaxTrial):
                def build_model(self): ...
                def build_optimizer(self): ...
                def build_training_data_loader(self): ...
                def build_validation_data_loader(self): ...
                def loss(self, model, params, batch, rng):
                    t0 = time.time()
                    return model.apply(params, batch["x"]).mean(), {}
            """
        )
    )
    module = _import_module_file(mod, "warning_trial_mod")

    cfg = _strict_config()
    import dataclasses

    from determined_tpu.config import LintConfig

    cfg = dataclasses.replace(cfg, lint=LintConfig(strict=False))
    exp = LocalExperiment(cfg, module.WarningTrial, checkpoint_dir=str(tmp_path / "ck"))
    with caplog.at_level(logging.WARNING, logger="determined_tpu.experiment"):
        exp._preflight_check()
    assert any("wall-clock" in r.message for r in caplog.records)


def test_preflight_opt_out_knob(tmp_path):
    from determined_tpu.experiment import LocalExperiment

    class Irrelevant:  # source unavailable classes skip cleanly anyway
        pass

    exp = LocalExperiment(
        _strict_config(), Irrelevant, checkpoint_dir=str(tmp_path / "ck"),
        preflight=False,
    )
    exp._preflight_check()  # no error despite strict config: knob wins


# ---------------------------------------------------------------------------
# retrace sentinel
# ---------------------------------------------------------------------------


def test_retrace_sentinel_flags_shape_unstable_trial():
    """The canonical footgun: a trial whose batches change shape retraces
    (recompiles) the step for every distinct shape — flagged on trace 2."""
    import jax
    import jax.numpy as jnp

    s = RetraceSentinel()

    def train_step(state, batch):
        return state + batch["x"].sum()

    wrapped = jax.jit(
        s.wrap("ShapeUnstableTrial.train_step", train_step, allowed=1)
    )
    state = jnp.zeros(())
    for n in (4, 5, 6):  # three shapes -> three traces, two over budget
        state = wrapped(state, {"x": jnp.ones((n, 3))})
    assert s.violations() == {"ShapeUnstableTrial.train_step": 2}
    # stable shapes after the fact add no traces
    state = wrapped(state, {"x": jnp.ones((6, 3))})
    assert s.violations() == {"ShapeUnstableTrial.train_step": 2}


def test_retrace_sentinel_allows_expected_trace_count():
    import jax
    import jax.numpy as jnp

    s = RetraceSentinel()

    def eval_step(acc, x):
        return {k: v + x.sum() for k, v in acc.items()} or {"m": x.sum()}

    wrapped = jax.jit(s.wrap("T.eval_step", eval_step, allowed=2))
    acc = wrapped({}, jnp.ones(3))
    acc = wrapped(acc, jnp.ones(3))  # second structure -> second trace: allowed
    assert s.violations() == {}


def test_retrace_sentinel_silent_on_normal_jit_cached_search(tmp_path):
    """A healthy LocalExperiment with the jit-reuse cache on compiles each
    step signature once — the sentinel must stay silent."""
    from determined_tpu.config import ExperimentConfig
    from determined_tpu.experiment import LocalExperiment
    from determined_tpu.models.mnist import MnistTrial
    from determined_tpu.train import clear_step_cache

    sentinel = get_retrace_sentinel()
    sentinel.reset()
    clear_step_cache()
    cfg = ExperimentConfig.parse(
        {
            "hyperparameters": {
                "lr": 0.01,
                "hidden": 16,
                "global_batch_size": 32,
                "dataset_size": 64,
            },
            "searcher": {
                "name": "random",
                "metric": "validation_accuracy",
                "smaller_is_better": False,
                "max_trials": 2,
                "max_length": {"batches": 4},
                "max_concurrent_trials": 2,
            },
            "resources": {"mesh": {"data": 2}},
            "checkpoint_policy": "none",
            "lint": {"retrace_sentinel": True},
        }
    )
    try:
        exp = LocalExperiment(cfg, MnistTrial, checkpoint_dir=str(tmp_path / "ck"))
        summary = exp.run()
        assert summary["trials"] == 2
        assert sentinel.violations() == {}, sentinel.violations()
        assert any(r.traces >= 1 for r in sentinel.records())
    finally:
        sentinel.disable()
        sentinel.reset()
        clear_step_cache()


# ---------------------------------------------------------------------------
# thread-leak checker
# ---------------------------------------------------------------------------


def test_thread_leak_checker_flags_leaked_worker():
    import threading

    release = threading.Event()
    try:
        with pytest.raises(ThreadLeakError, match="dtpu-leaky"):
            with ThreadLeakChecker(watch=("dtpu-*",), grace=0.3, scope="t"):
                threading.Thread(
                    target=release.wait, name="dtpu-leaky", daemon=True
                ).start()
    finally:
        release.set()


def test_thread_leak_checker_passes_when_threads_die():
    import threading

    with ThreadLeakChecker(watch=("dtpu-*",), grace=5.0, scope="t"):
        t = threading.Thread(target=lambda: None, name="dtpu-shortlived")
        t.start()
        t.join()


def test_thread_leak_checker_ignores_unwatched_threads():
    import threading

    release = threading.Event()
    try:
        with ThreadLeakChecker(watch=("dtpu-*",), grace=0.3, scope="t"):
            threading.Thread(
                target=release.wait, name="unrelated-pool-thread", daemon=True
            ).start()
    finally:
        release.set()


def test_thread_leak_checker_warn_mode_records(caplog):
    import logging
    import threading

    release = threading.Event()
    try:
        with caplog.at_level(logging.WARNING, logger="determined_tpu.lint.runtime"):
            with ThreadLeakChecker(
                watch=("dtpu-*",), grace=0.3, raise_on_leak=False, scope="warnscope"
            ) as checker:
                threading.Thread(
                    target=release.wait, name="dtpu-warn-leak", daemon=True
                ).start()
        assert [t.name for t in checker.leaked] == ["dtpu-warn-leak"]
        assert any("warnscope" in r.message for r in caplog.records)
    finally:
        release.set()


# ---------------------------------------------------------------------------
# concurrency pass: cross-module graphs, exact diagnostics, suppressions
# ---------------------------------------------------------------------------


def _concurrency_diags(src: str, rule: str):
    return [
        d
        for d in analyze_source(textwrap.dedent(src), "fixture.py")
        if d.rule == rule
    ]


def test_lock_cycle_bad_fixture_exactly_one_diagnostic():
    diags = _concurrency_diags(BAD["lock-order-cycle"], "lock-order-cycle")
    assert len(diags) == 1, [d.format() for d in diags]
    assert "fixture:Pair._a" in diags[0].message
    assert "fixture:Pair._b" in diags[0].message


def test_blocking_under_lock_bad_fixture_names_held_chain():
    diags = _concurrency_diags(BAD["blocking-under-lock"], "blocking-under-lock")
    assert len(diags) == 1, [d.format() for d in diags]
    assert "os.fsync" in diags[0].message
    assert "Writer._lock" in diags[0].message


def test_signal_handler_bad_fixture_names_lock():
    diags = _concurrency_diags(BAD["signal-handler-unsafe"], "signal-handler-unsafe")
    assert len(diags) == 1, [d.format() for d in diags]
    assert "Guard._lock" in diags[0].message


def test_lock_cycle_across_two_modules(tmp_path):
    """The tentpole case: each module is individually consistent; only the
    cross-module pass sees the inversion."""
    from determined_tpu.lint import analyze_paths

    (tmp_path / "mod_a.py").write_text(
        textwrap.dedent(
            """
            import threading
            from mod_b import poke_b
            A = threading.Lock()
            def poke_a():
                with A:
                    pass
            def a_then_b():
                with A:
                    poke_b()
            """
        )
    )
    (tmp_path / "mod_b.py").write_text(
        textwrap.dedent(
            """
            import threading
            from mod_a import poke_a
            B = threading.Lock()
            def poke_b():
                with B:
                    pass
            def b_then_a():
                with B:
                    poke_a()
            """
        )
    )
    diags = [
        d for d in analyze_paths([str(tmp_path)]) if d.rule == "lock-order-cycle"
    ]
    assert len(diags) == 1, [d.format() for d in diags]
    assert "mod_a:A" in diags[0].message and "mod_b:B" in diags[0].message
    # and each file alone is clean: the cycle is a property of the program
    for name in ("mod_a.py", "mod_b.py"):
        alone = analyze_paths([str(tmp_path / name)])
        assert [d for d in alone if d.rule == "lock-order-cycle"] == []


def test_blocking_under_lock_transitive_through_calls():
    src = """
    import os, threading
    class J:
        def __init__(self):
            self._lock = threading.Lock()
        def _write(self, fh):
            fh.flush()
            os.fsync(fh.fileno())
        def append(self, fh):
            with self._lock:
                self._write(fh)
    """
    diags = _concurrency_diags(src, "blocking-under-lock")
    assert len(diags) == 1, [d.format() for d in diags]
    assert "J._write" in diags[0].message  # the chain names the callee


def test_blocking_queue_get_under_lock_flagged_nowait_clean():
    src = """
    import queue, threading
    class Q:
        def __init__(self):
            self._lock = threading.Lock()
            self._q = queue.Queue()
        def bad(self):
            with self._lock:
                return self._q.get()
        def ok(self):
            with self._lock:
                return self._q.get_nowait()
        def ok2(self):
            with self._lock:
                return self._q.get(block=False)
    """
    diags = _concurrency_diags(src, "blocking-under-lock")
    assert len(diags) == 1, [d.format() for d in diags]
    assert diags[0].line == 9


def test_rmtree_under_lock_flagged():
    src = """
    import shutil, threading
    LOCK = threading.Lock()
    def gc(path):
        with LOCK:
            shutil.rmtree(path)
    """
    diags = _concurrency_diags(src, "blocking-under-lock")
    assert len(diags) == 1 and "shutil.rmtree" in diags[0].message


def test_nonreentrant_self_acquire_flagged_rlock_clean():
    src = """
    import threading
    class R:
        def __init__(self):
            self._lock = threading.Lock()
            self._rlock = threading.RLock()
        def outer(self):
            with self._lock:
                self.inner()
        def inner(self):
            with self._lock:
                pass
        def outer_r(self):
            with self._rlock:
                self.inner_r()
        def inner_r(self):
            with self._rlock:
                pass
    """
    diags = _concurrency_diags(src, "lock-order-cycle")
    # the non-reentrant Lock chain (outer holds, inner re-takes) is a
    # guaranteed self-deadlock; the identical RLock chain is legal
    assert len(diags) == 1, [d.format() for d in diags]
    assert "R._lock" in diags[0].message
    assert "_rlock" not in diags[0].message


def test_concurrency_suppression_line_above():
    src = """
    import os, threading
    class W:
        def __init__(self):
            self._lock = threading.Lock()
        def save(self, fh):
            with self._lock:
                # durability point must be inside: WAL contract
                # dtpu: lint-ok[blocking-under-lock]
                os.fsync(fh.fileno())
    """
    assert _concurrency_diags(src, "blocking-under-lock") == []


def test_concurrency_rules_in_json_payload():
    diags = analyze_source(
        textwrap.dedent(BAD["blocking-under-lock"]), "fixture.py"
    )
    payload = to_json_payload(diags)
    assert payload["version"] == 1
    assert payload["counts"]["by_rule"].get("blocking-under-lock", 0) >= 1
    parsed = json.loads(json.dumps(payload))
    assert parsed["findings"][0]["rule"] in set(all_rules())


def test_queue_put_positional_nonblocking_clean():
    src = """
    import queue, threading
    class Q:
        def __init__(self):
            self._lock = threading.Lock()
            self._q = queue.Queue()
        def bad(self, item):
            with self._lock:
                self._q.put(item)
        def ok(self, item):
            with self._lock:
                self._q.put(item, False)
    """
    diags = _concurrency_diags(src, "blocking-under-lock")
    assert len(diags) == 1, [d.format() for d in diags]
    assert diags[0].line == 9


def test_condition_wait_idiom_clean_under_other_lock_flagged():
    """``with cond: cond.wait()`` is THE condition-variable idiom (wait
    releases the lock it blocks on) — clean; the same wait reached while
    some other lock is held really does stall that lock's contenders —
    flagged, both directly and through a call chain."""
    src = """
    import threading
    class CV:
        def __init__(self):
            self._cond = threading.Condition()
            self._other = threading.Lock()
            self._ready = False
        def idiom(self):
            with self._cond:
                while not self._ready:
                    self._cond.wait()
        def bad_direct(self):
            with self._other:
                with self._cond:
                    self._cond.wait()
        def bad_transitive(self):
            with self._other:
                self.idiom()
    """
    diags = _concurrency_diags(src, "blocking-under-lock")
    assert len(diags) == 2, [d.format() for d in diags]
    assert all("CV._other" in d.message for d in diags)


def test_same_stem_scripts_all_indexed(tmp_path):
    """Non-package scripts sharing a stem (examples/*/model_def.py) must
    each stay in the program index — a collision that drops one hides its
    findings entirely."""
    from determined_tpu.lint import analyze_paths

    src = """
        import shutil, threading
        LOCK = threading.Lock()
        def gc(path):
            with LOCK:
                shutil.rmtree(path)
        """
    for sub in ("alpha", "beta"):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / "model_def.py").write_text(textwrap.dedent(src))
    diags = [
        d for d in analyze_paths([str(tmp_path)])
        if d.rule == "blocking-under-lock"
    ]
    assert len(diags) == 2, [d.format() for d in diags]
    assert {os.path.basename(os.path.dirname(d.file)) for d in diags} == {
        "alpha",
        "beta",
    }


def test_mutual_recursion_does_not_cache_truncated_summaries():
    """A query that prunes a mutually recursive callee must not poison the
    cache for later queries: `second` still owes the M -> L edge even
    though `first` computed (and pruned) the same component earlier."""
    src = """
    import threading
    L = threading.Lock()
    M = threading.Lock()
    N = threading.Lock()
    def f(n):
        with L:
            pass
        g(n)
    def g(n):
        if n:
            f(n - 1)
    def first():
        with N:
            f(0)
    def second():
        with M:
            g(1)
    def l_then_m():
        with L:
            with M:
                pass
    """
    diags = _concurrency_diags(src, "lock-order-cycle")
    assert len(diags) == 1, [d.format() for d in diags]
    assert "fixture:M" in diags[0].message and "fixture:L" in diags[0].message


def test_nested_def_rebinding_does_not_shadow_module_lock():
    """A lock ctor inside a NESTED def must not register as the enclosing
    function's local — that phantom binding would shadow the module lock
    and split one lock into two graph identities, silently hiding the
    real cycle."""
    src = """
    import threading
    A = threading.Lock()
    B = threading.Lock()
    def a_then_b():
        def make_private():
            A = threading.Lock()
            return A
        with A:
            with B:
                pass
    def b_then_a():
        with B:
            with A:
                pass
    """
    diags = _concurrency_diags(src, "lock-order-cycle")
    assert len(diags) == 1, [d.format() for d in diags]
    assert "fixture:A" in diags[0].message and "fixture:B" in diags[0].message


def test_analyze_paths_dedups_overlapping_targets(tmp_path):
    """The same physical file reached through two target spellings must
    lint exactly once (no doubled findings, no forked module identity)."""
    from determined_tpu.lint import analyze_paths

    (tmp_path / "m.py").write_text(
        textwrap.dedent(
            """
            import shutil, threading
            LOCK = threading.Lock()
            def gc(path):
                with LOCK:
                    shutil.rmtree(path)
            """
        )
    )
    diags = [
        d
        for d in analyze_paths([str(tmp_path), str(tmp_path / "." / "m.py")])
        if d.rule == "blocking-under-lock"
    ]
    assert len(diags) == 1, [d.format() for d in diags]


def test_signal_handler_logging_flagged():
    src = """
    import logging, signal
    logger = logging.getLogger("x")
    def handler(signum, frame):
        logger.warning("got signal")
    def arm():
        signal.signal(signal.SIGTERM, handler)
    """
    diags = _concurrency_diags(src, "signal-handler-unsafe")
    assert len(diags) == 1 and "logs via" in diags[0].message


# ---------------------------------------------------------------------------
# LockOrderSentinel: the runtime acquisition-order guard
# ---------------------------------------------------------------------------


def test_lock_order_sentinel_detects_inversion_deterministically():
    """Two threads, opposite nesting, fully sequenced by joins: no actual
    deadlock ever happens, yet the ORDER contradiction must be reported —
    every time, not only on the unlucky interleaving."""
    import threading

    from determined_tpu.lint import LockOrderSentinel

    sentinel = LockOrderSentinel()
    with sentinel:
        a = threading.Lock()
        b = threading.Lock()

        def forward():
            with a:
                with b:
                    pass

        def backward():
            with b:
                with a:
                    pass

        t1 = threading.Thread(target=forward)
        t1.start()
        t1.join()
        t2 = threading.Thread(target=backward)
        t2.start()
        t2.join()
    violations = sentinel.violations()
    assert len(violations) == 1, [v.format() for v in violations]
    msg = violations[0].format()
    assert "inversion" in msg and "test_lint.py" in msg


def test_lock_order_sentinel_consistent_order_is_silent():
    import threading

    from determined_tpu.lint import LockOrderSentinel

    sentinel = LockOrderSentinel()
    with sentinel:
        a = threading.Lock()
        b = threading.Lock()
        for _ in range(3):
            with a:
                with b:
                    pass
    assert sentinel.violations() == []


def test_lock_order_sentinel_cross_thread_handoff_no_phantom_edges():
    """``Lock`` legally supports acquire-in-A / release-in-B (gate
    pattern); the handed-off lock must not stay on A's held stack and
    manufacture phantom ordering edges for everything A takes later."""
    import threading

    from determined_tpu.lint import LockOrderSentinel

    sentinel = LockOrderSentinel()
    with sentinel:
        gate = threading.Lock()
        x = threading.Lock()
        gate.acquire()  # main thread holds the gate

        t = threading.Thread(target=gate.release)
        t.start()
        t.join()  # released by another thread: handoff complete

        with x:  # without the purge: phantom gate->x edge
            pass

        def consistent():
            with x:
                with gate:  # x->gate: fine unless the phantom edge exists
                    pass

        t2 = threading.Thread(target=consistent)
        t2.start()
        t2.join()
    assert sentinel.violations() == [], [
        q.format() for q in sentinel.violations()
    ]


def test_lock_order_sentinel_rlock_reentry_is_not_an_edge():
    import threading

    from determined_tpu.lint import LockOrderSentinel

    sentinel = LockOrderSentinel()
    with sentinel:
        r = threading.RLock()
        a = threading.Lock()
        with r:
            with a:
                with r:  # reentrant hold: no a->r ordering claim
                    pass
        with r:
            pass
    assert sentinel.violations() == []


def test_lock_order_sentinel_condition_and_event_still_work():
    """Condition/Event built on patched factories must behave normally
    (wait/notify/set), exercising the _release_save passthrough."""
    import threading

    from determined_tpu.lint import LockOrderSentinel

    sentinel = LockOrderSentinel()
    with sentinel:
        cond = threading.Condition()
        done = threading.Event()
        seen = []

        def waiter():
            with cond:
                while not seen:
                    cond.wait(timeout=5)
            done.set()

        t = threading.Thread(target=waiter)
        t.start()
        with cond:
            seen.append(1)
            cond.notify_all()
        assert done.wait(timeout=5)
        t.join(timeout=5)
    assert sentinel.violations() == []


def test_lock_order_sentinel_uninstall_restores_factories():
    import threading

    from determined_tpu.lint import LockOrderSentinel

    orig_lock = threading.Lock
    orig_rlock = threading.RLock
    sentinel = LockOrderSentinel()
    with sentinel:
        assert threading.Lock is not orig_lock
    assert threading.Lock is orig_lock
    assert threading.RLock is orig_rlock


# ---------------------------------------------------------------------------
# SPMD correctness pass (lint/_spmd.py): rank-divergence rules
# ---------------------------------------------------------------------------

_SPMD_RULES = (
    "rank-dependent-collective",
    "conditional-collective-escape",
    "unordered-iteration-feeding-collective",
    "rank-guarded-io-missing-barrier",
    "wall-clock-divergence",
)


@pytest.mark.parametrize("rule", _SPMD_RULES)
def test_spmd_bad_fixture_exactly_one_diagnostic(rule):
    diags = _concurrency_diags(BAD[rule], rule)
    assert len(diags) == 1, diags
    assert diags[0].severity == "warning"


def test_rank_dependent_collective_names_op_and_witness():
    (d,) = _concurrency_diags(
        BAD["rank-dependent-collective"], "rank-dependent-collective"
    )
    assert "`allgather`" in d.message
    assert "Reporter.report" in d.message  # witness chain qname


def test_rank_dependent_collective_matching_branches_clean():
    # the restore_path shape: both sides of a rank test reach the SAME
    # collective set (error vs ok broadcast) — legal
    src = """
class Restorer:
    def restore(self, dist):
        if dist.is_local_chief:
            dist.broadcast_local(("ok", "path"))
        else:
            dist.broadcast_local(None)
"""
    assert not _concurrency_diags(src, "rank-dependent-collective")


def test_rank_dependent_collective_rank_env_read_flagged():
    src = """
import os
class W:
    def go(self, dist):
        if os.environ.get("DTPU_RANK") == "0":
            dist.barrier()
"""
    assert len(_concurrency_diags(src, "rank-dependent-collective")) == 1


def test_conditional_escape_exchange_then_escape_is_clean():
    # the _drain_pending_save idiom verbatim: allgather the local flag,
    # raise on the EXCHANGED value — every rank raises together
    src = """
class Drainer:
    def drain(self, dist, local_failed):
        flags = dist.allgather(local_failed)
        failed_ranks = [r for r, f in enumerate(flags) if f]
        if failed_ranks:
            raise RuntimeError(f"failed on {failed_ranks}")
        dist.barrier()
"""
    assert not _concurrency_diags(src, "conditional-collective-escape")


def test_conditional_escape_tensor_plane_guard_is_clean():
    # python escapes around TRACED collectives are trace-time decisions
    # (jax forbids branching on runtime values): not a runtime divergence
    src = """
import jax
def redistribute(x, axis_name, n):
    if n == 1:
        return x
    y = jax.lax.psum(x, axis_name)
    if y.shape[0] == 1:
        return y
    return jax.lax.ppermute(y, axis_name, [(0, 1)])
"""
    assert not _concurrency_diags(src, "conditional-collective-escape")


def test_conditional_escape_rank_dependent_loop_flagged():
    src = """
class W:
    def go(self, dist, rank):
        for _ in range(rank):
            dist.allgather("tick")
"""
    diags = _concurrency_diags(src, "conditional-collective-escape")
    assert len(diags) == 1
    assert "rank-dependent" in diags[0].message


def test_conditional_escape_break_in_collective_loop_flagged():
    src = """
class W:
    def go(self, dist, jobs):
        for j in jobs:
            dist.allgather(j)
            if j is None:
                break
"""
    diags = _concurrency_diags(src, "conditional-collective-escape")
    assert len(diags) == 1
    assert "break" in diags[0].message


def test_unordered_iteration_payload_crossing_later_collective_flagged():
    src = """
class W:
    def go(self, dist, shards):
        names = []
        for s in set(shards):
            names.append(s)
        return dist.allgather(names)
"""
    diags = _concurrency_diags(
        src, "unordered-iteration-feeding-collective"
    )
    assert len(diags) == 1
    assert "names" in diags[0].message


def test_unordered_iteration_listdir_flagged_sorted_clean():
    bad = """
import os
class W:
    def go(self, dist, d):
        for f in os.listdir(d):
            dist.broadcast(f)
"""
    clean = """
import os
class W:
    def go(self, dist, d):
        for f in sorted(os.listdir(d)):
            dist.broadcast(f)
"""
    assert len(
        _concurrency_diags(bad, "unordered-iteration-feeding-collective")
    ) == 1
    assert not _concurrency_diags(
        clean, "unordered-iteration-feeding-collective"
    )


def test_rank_guarded_io_any_collective_counts_as_sync():
    # not just barrier(): ANY collective between write and read orders them
    src = """
import json
class P:
    def publish(self, dist, path, manifest):
        if dist.is_chief:
            with open(path, "w") as f:
                json.dump(manifest, f)
        dist.allgather("done")
        with open(path) as f:
            return json.load(f)
"""
    assert not _concurrency_diags(src, "rank-guarded-io-missing-barrier")


def test_rank_guarded_io_read_inside_guard_is_clean():
    # a read INSIDE the chief guard is chief-only too: no cross-rank race
    src = """
import json
class P:
    def publish(self, dist, path, manifest):
        if dist.is_chief:
            with open(path, "w") as f:
                json.dump(manifest, f)
            with open(path) as f:
                return json.load(f)
"""
    assert not _concurrency_diags(src, "rank-guarded-io-missing-barrier")


def test_wall_clock_divergence_broadcast_exempt():
    # broadcasting the chief's clock IS the fix: one sample, distributed
    src = """
import time
class S:
    def stamp(self, dist):
        return dist.broadcast(time.time())
"""
    assert not _concurrency_diags(src, "wall-clock-divergence")


def test_wall_clock_divergence_operand_crossing_allgather_flagged():
    src = """
import random
class S:
    def shuffle_order(self, dist):
        return dist.allgather(random.random())
"""
    diags = _concurrency_diags(src, "wall-clock-divergence")
    assert len(diags) == 1
    assert "allgather" in diags[0].message


def test_wall_clock_divergence_seeded_rng_object_clean():
    src = """
import random
class S:
    def pick(self, dist, seed):
        rng = random.Random(seed)  # journaled seed: rank-uniform stream
        if rng.random() > 0.5:
            dist.barrier()
"""
    assert not _concurrency_diags(src, "wall-clock-divergence")


def test_spmd_rule_cross_module_witness_chain(tmp_path):
    # rank guard in one module, the collective reached through a call into
    # ANOTHER module: only the joint ProgramIndex sees the chain
    (tmp_path / "transport.py").write_text(
        textwrap.dedent(
            """
            def flush_all(dist):
                dist.allgather("flush")
            """
        )
    )
    (tmp_path / "driver.py").write_text(
        textwrap.dedent(
            """
            import jax
            from transport import flush_all

            def finish(dist):
                if jax.process_index() == 0:
                    flush_all(dist)
            """
        )
    )
    from determined_tpu.lint import analyze_paths

    diags = [
        d
        for d in analyze_paths([str(tmp_path)])
        if d.rule == "rank-dependent-collective"
    ]
    assert len(diags) == 1
    assert "flush_all" in diags[0].message  # the cross-module hop is named
    # each file alone shows nothing: the guard and the collective only
    # connect through the cross-module call
    solo = [
        d
        for d in analyze_paths([str(tmp_path / "driver.py")])
        if d.rule == "rank-dependent-collective"
    ]
    assert not solo


def test_spmd_rule_suppression_line_above():
    src = """
import jax
class R:
    def report(self, dist, m):
        # dtpu: lint-ok[rank-dependent-collective]
        if jax.process_index() == 0:
            dist.allgather(m)
"""
    assert not _concurrency_diags(src, "rank-dependent-collective")


def test_spmd_rules_in_json_payload():
    diags = analyze_source(
        textwrap.dedent(BAD["rank-dependent-collective"]), "fixture.py"
    )
    payload = to_json_payload(diags)
    assert payload["counts"]["by_rule"].get("rank-dependent-collective") == 1


# ---------------------------------------------------------------------------
# dir-mode --exclude globs
# ---------------------------------------------------------------------------


def test_collect_py_files_exclude_prunes_directories(tmp_path):
    from determined_tpu.lint._concurrency import collect_py_files

    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "ok.py").write_text("x = 1\n")
    (tmp_path / "checkpoints").mkdir()
    (tmp_path / "checkpoints" / "shipped_model_def.py").write_text("x = 1\n")
    (tmp_path / "traces").mkdir()
    (tmp_path / "traces" / "gen.py").write_text("x = 1\n")
    files = collect_py_files(
        str(tmp_path), exclude=("checkpoints", "traces/*")
    )
    rels = [os.path.relpath(f, str(tmp_path)) for f in files]
    assert rels == [os.path.join("pkg", "ok.py")]


def test_cli_lint_exclude_glob(tmp_path, capsys):
    from determined_tpu.cli.main import main as cli_main

    (tmp_path / "good.py").write_text("x = 1\n")
    bad_dir = tmp_path / "journal_artifacts"
    bad_dir.mkdir()
    # a file that WOULD produce a finding if parsed
    (bad_dir / "snippet.py").write_text(
        textwrap.dedent(BAD["blocking-under-lock"])
    )
    rc = cli_main(
        ["lint", "--strict", str(tmp_path), "--exclude", "journal_artifacts"]
    )
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "clean" in out
    # without the exclude the same target fails strict
    rc = cli_main(["lint", "--strict", str(tmp_path)])
    assert rc == 1


# ---------------------------------------------------------------------------
# CollectiveSequenceSentinel: the runtime half of the SPMD pass
# ---------------------------------------------------------------------------


def _exec():
    from tests.parallel_utils import Execution

    return Execution


def test_collective_sentinel_matching_ranks_silent():
    from determined_tpu.lint import CollectiveSequenceSentinel

    sentinel = CollectiveSequenceSentinel()
    with sentinel:
        results = _exec()(3).run(
            lambda ctx, rank: (
                ctx.allgather(f"r{rank}"),
                ctx.broadcast("payload" if ctx.is_chief else None),
                ctx.gather(rank),
                ctx.barrier(),
            )
        )
    assert [r[0] for r in results] == [["r0", "r1", "r2"]] * 3
    assert [r[1] for r in results] == ["payload"] * 3
    assert results[0][2] == [0, 1, 2]
    assert results[1][2] is None
    assert sentinel.violations() == []


def test_collective_sentinel_wrong_branch_divergence_named():
    from determined_tpu.lint import (
        CollectiveDivergenceError,
        CollectiveSequenceSentinel,
    )

    sentinel = CollectiveSequenceSentinel()

    def diverge(ctx, rank):
        ctx.allgather("warm")
        try:
            if rank == 1:
                ctx.allgather(("extra", rank))  # the wrong-branch collective
            else:
                ctx.barrier()
            return None
        except CollectiveDivergenceError as e:
            return e

    with sentinel:
        results = _exec()(2, timeout=20).run(diverge)
    # BOTH ranks get the deterministic named error (no hang, no timeout)
    assert all(isinstance(r, CollectiveDivergenceError) for r in results)
    err = results[0]
    assert err.op_index == 1  # second collective is the divergent one
    assert "barrier" in str(err) and "allgather" in str(err)
    assert set(err.ranks) == {0, 1}  # both ranks' ops are named
    assert err.traces[0] and err.traces[1]
    assert len(sentinel.violations()) == 2


def test_collective_sentinel_injected_divergence_deterministic(monkeypatch):
    # the devcluster acceptance path, in-process: DTPU_CSEQ_INJECT makes
    # rank 1 advertise a phantom op at its 2nd exchange — every run, same
    # op index, same named error
    monkeypatch.setenv("DTPU_CSEQ_INJECT", "1:2:phantom-save-barrier")
    from determined_tpu.lint import (
        CollectiveDivergenceError,
        CollectiveSequenceSentinel,
    )

    for _ in range(2):  # deterministic across repeat runs
        sentinel = CollectiveSequenceSentinel()

        def body(ctx, rank):
            ctx.allgather("a")
            try:
                ctx.allgather("b")
                return None
            except CollectiveDivergenceError as e:
                return e

        with sentinel:
            results = _exec()(2, timeout=20).run(body)
        assert all(isinstance(r, CollectiveDivergenceError) for r in results)
        assert "phantom-save-barrier" in str(results[0])
        assert results[0].op_index == 1


def test_collective_sentinel_unexchanged_record_verified_at_next_exchange():
    # a dispatch-site record (the trainer's step segment) on ONE rank only
    # shifts its digest; the NEXT exchanged collective catches it
    from determined_tpu.lint import (
        CollectiveDivergenceError,
        CollectiveSequenceSentinel,
    )

    sentinel = CollectiveSequenceSentinel()

    def body(ctx, rank):
        ctx.allgather("warm")
        if rank == 1:
            sentinel.record(ctx, "step.segment", "0-100")  # rank 1 ran extra steps
        try:
            ctx.barrier()
            return None
        except CollectiveDivergenceError as e:
            return e

    with sentinel:
        results = _exec()(2, timeout=20).run(body)
    assert all(isinstance(r, CollectiveDivergenceError) for r in results)
    assert "step.segment" in str(results[0])


def test_collective_sentinel_raw_peer_named_not_garbled():
    from determined_tpu.lint import (
        CollectiveDivergenceError,
        CollectiveSequenceSentinel,
    )

    sentinel = CollectiveSequenceSentinel()
    with pytest.raises(CollectiveDivergenceError, match="WITHOUT the sentinel"):
        sentinel._unwrap({"raw": "payload"})


def test_collective_sentinel_uninstall_restores_methods():
    from determined_tpu.core import DistributedContext
    from determined_tpu.lint import CollectiveSequenceSentinel

    orig = DistributedContext.allgather
    sentinel = CollectiveSequenceSentinel()
    with sentinel:
        assert DistributedContext.allgather is not orig
    assert DistributedContext.allgather is orig


def test_collective_sentinel_digest_overhead_bounded():
    # the record path is one crc32 + deque append; bound it loosely so a
    # regression to something heavyweight fails (50 us/op on any box)
    import time as _time

    from determined_tpu.core import DummyDistributedContext
    from determined_tpu.lint import CollectiveSequenceSentinel

    sentinel = CollectiveSequenceSentinel()
    dist = DummyDistributedContext()
    n = 20_000
    t0 = _time.perf_counter()
    for i in range(n):
        sentinel.record(dist, "step.segment", f"{i}-{i + 10}")
    per_op = (_time.perf_counter() - t0) / n
    assert per_op < 50e-6, f"digest record cost {per_op * 1e6:.1f} us/op"


def test_collective_sentinel_single_rank_passthrough():
    # DummyDistributedContext under the sentinel: wrapped methods still
    # return correct values with zero peers
    from determined_tpu.core import DummyDistributedContext
    from determined_tpu.lint import CollectiveSequenceSentinel

    with CollectiveSequenceSentinel() as sentinel:
        dist = DummyDistributedContext()
        assert dist.allgather("x") == ["x"]
        assert dist.broadcast("y") == "y"
        assert dist.gather("z") == ["z"]
        dist.barrier()
    assert sentinel.violations() == []


def test_collect_py_files_named_file_ignores_exclude(tmp_path):
    # excludes prune DISCOVERED files; a target the user spelled out is
    # always linted (same contract as analyze_path's file mode)
    from determined_tpu.lint._concurrency import collect_py_files

    f = tmp_path / "build.py"
    f.write_text("x = 1\n")
    assert collect_py_files(str(f), exclude=("build*",)) == [str(f)]


# ---------------------------------------------------------------------------
# control-plane contract pass (dtpu lint --native): per-rule bad/clean
# fixture pairs over a synthetic native tree, C++ suppressions, real-repo
# index conformance, and seeded regressions against the real sources
# ---------------------------------------------------------------------------

NATIVE_MASTER_CLEAN = r"""
struct Master {
  void apply_event(const Json& ev) {
    const std::string type = ev["type"].as_string();
    if (type == "exp_created") {
      experiments_[ev["id"].as_int()] = ev;
    } else if (type == "exp_deleted") {
      experiments_.erase(ev["id"].as_int());
    }
  }
  void snapshot_state(Json& out) {
    out.set("experiments", Json(experiments_));
  }
  void restore_snapshot(const Json& snap) {
    experiments_ = snap["experiments"];
  }
  Json debug_state() {
    Json d = Json::object();
    d.set("experiments", Json(experiments_));
    return d;
  }
};

void routes(Server& srv, Master& m) {
  srv.route("GET", "/api/v1/experiments", authed([&m](const HttpRequest& req) {
    return R::json("[]");
  }));
  srv.route("POST", "/api/v1/experiments", authed([&m](const HttpRequest& req) {
    Json body;
    if (!Json::try_parse(req.body, &body)) return R::error(400, "bad json");
    Json ev = Json::object();
    ev.set("type", "exp_created");
    ev.set("id", body["id"]);
    m.record(ev);
    return R::json("{}");
  }));
  srv.route("DELETE", "/api/v1/experiments/{id}", authed([&m](const HttpRequest& req) {
    m.record(Json::object().set("type", "exp_deleted"));
    return R::json("{}");
  }));
  srv.route("POST", "/api/v1/agents", authed([&m](const HttpRequest& req) {
    Json body;
    if (!Json::try_parse(req.body, &body)) return R::error(400, "bad json");
    std::string id = body["id"].as_string();
    return R::json("{}");
  }));
  srv.route("GET", "/metrics", [&m](const HttpRequest&) {
    std::ostringstream out;
    out << "# TYPE dtpu_experiments gauge\n"
        << "dtpu_experiments " << m.experiments_.size() << "\n";
    HttpResponse r;
    r.body = out.str();
    return r;
  });
}
"""

NATIVE_AGENT_CLEAN = r"""
struct Agent {
  bool register_agent() {
    Json body = Json::object();
    body.set("id", opts_.id);
    auto resp = master_req("POST", "/api/v1/agents", body.dump(), 10);
    return resp.ok();
  }
};
"""

NATIVE_SPEC_CLEAN = """
ROUTES = [
    ("GET", "/api/v1/experiments", "token", "[]"),
    ("POST", "/api/v1/experiments", "token", set()),
    ("DELETE", "/api/v1/experiments/{id}", "token", set()),
    ("POST", "/api/v1/agents", "token", set()),
    ("GET", "/metrics", "anon", None),
]
"""

NATIVE_API_MD_CLEAN = """\
| method | path | auth | response |
|---|---|---|---|
| GET | `/api/v1/experiments` | token | array |
| POST | `/api/v1/experiments` | token | {} |
| DELETE | `/api/v1/experiments/{id}` | token | {} |
| POST | `/api/v1/agents` | token | {} |
| GET | `/metrics` | anon | raw |
"""

NATIVE_OPS_MD_CLEAN = "Metrics: `dtpu_experiments`.\n"

NATIVE_FUZZ_CLEAN = """
def sample_master_events():
    return [
        {"type": "exp_created", "id": 1},
        {"type": "exp_deleted", "id": 1},
    ]
"""

NATIVE_FAKE_CLEAN = """
class FakeMaster:
    def __init__(self):
        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                if self.path == "/api/v1/experiments":
                    self._send(200, [])
            def do_POST(self):
                if self.path == "/api/v1/agents":
                    self._send(200, {})
        self.handler = Handler
"""


def _native_sources(**overrides):
    from determined_tpu.lint import NativeSources

    base = dict(
        master=("native/master/master.cpp", NATIVE_MASTER_CLEAN),
        agent=("native/agent/agent.cpp", NATIVE_AGENT_CLEAN),
        spec=("determined_tpu/api/spec.py", NATIVE_SPEC_CLEAN),
        api_md=("API.md", NATIVE_API_MD_CLEAN),
        ops_md=("docs/operations.md", NATIVE_OPS_MD_CLEAN),
        fuzz=("scripts/devcluster.py", NATIVE_FUZZ_CLEAN),
        python={"determined_tpu/api/spec.py": NATIVE_SPEC_CLEAN},
        fakes={"tests/test_fake.py": NATIVE_FAKE_CLEAN},
    )
    base.update(overrides)
    return NativeSources(**base)


def _run_native(ns):
    from determined_tpu.lint import run_native_pass
    from determined_tpu.lint.rules import build_rules

    return run_native_pass(ns, build_rules(None, None))


def _native_by_rule(diags, rule):
    return [d for d in diags if d.rule == rule]


def test_native_clean_fixture_no_findings():
    assert _run_native(_native_sources()) == []


def test_native_wal_replay_gap_bad_and_witness():
    # retarget the exp_deleted arm: its emitted type loses replay coverage
    mutated = NATIVE_MASTER_CLEAN.replace(
        'type == "exp_deleted"', 'type == "exp_gone"', 1
    )
    ns = _native_sources(master=("native/master/master.cpp", mutated))
    found = _native_by_rule(_run_native(ns), "wal-replay-gap")
    assert len(found) == 1
    d = found[0]
    assert d.severity == ERROR
    assert "'exp_deleted'" in d.message
    # the witness is the emit site, not the arm
    emit_line = next(
        i + 1 for i, l in enumerate(mutated.splitlines())
        if '.set("type", "exp_deleted")' in l
    )
    assert f"native/master/master.cpp:{emit_line}" in d.message
    assert d.line == emit_line


def test_native_wal_replay_gap_unresolvable_type_literal():
    # builder variable with no reachable .set("type", ...): must flag, not
    # silently skip — unresolved sites are how coverage rots invisibly
    mutated = NATIVE_MASTER_CLEAN.replace(
        'Json ev = Json::object();\n    ev.set("type", "exp_created");\n'
        '    ev.set("id", body["id"]);',
        "Json ev = make_event(body);",
    )
    assert mutated != NATIVE_MASTER_CLEAN
    ns = _native_sources(master=("native/master/master.cpp", mutated))
    found = _native_by_rule(_run_native(ns), "wal-replay-gap")
    assert len(found) == 1 and "could not be resolved" in found[0].message


def test_native_wal_snapshot_gap_bad_clean_pair():
    mutated = NATIVE_MASTER_CLEAN.replace(
        'experiments_.erase(ev["id"].as_int());',
        'tombstones_[ev["id"].as_int()] = true;',
    )
    ns = _native_sources(master=("native/master/master.cpp", mutated))
    found = _native_by_rule(_run_native(ns), "wal-snapshot-gap")
    assert len(found) == 1
    assert "'exp_deleted'" in found[0].message
    assert "tombstones_" in found[0].message


def test_native_wal_fuzz_gap_bad_clean_pair():
    mutated = NATIVE_FUZZ_CLEAN.replace(
        '{"type": "exp_deleted", "id": 1},\n', ""
    )
    assert mutated != NATIVE_FUZZ_CLEAN
    ns = _native_sources(fuzz=("scripts/devcluster.py", mutated))
    found = _native_by_rule(_run_native(ns), "wal-fuzz-gap")
    assert len(found) == 1 and "'exp_deleted'" in found[0].message


def test_native_route_unbound_and_undocumented():
    mutated = NATIVE_MASTER_CLEAN.replace(
        'srv.route("GET", "/metrics"',
        'srv.route("GET", "/api/v1/debugz", authed([&m](const HttpRequest& req) {\n'
        '    return R::json("{}");\n'
        "  }));\n"
        '  srv.route("GET", "/metrics"',
    )
    ns = _native_sources(master=("native/master/master.cpp", mutated))
    diags = _run_native(ns)
    unbound = _native_by_rule(diags, "route-unbound")
    undoc = _native_by_rule(diags, "route-undocumented")
    assert len(unbound) == 1 and "/api/v1/debugz" in unbound[0].message
    assert len(undoc) == 1 and "/api/v1/debugz" in undoc[0].message
    assert undoc[0].severity == ERROR


def test_native_route_documented_but_undocumented_row_only():
    # spec keeps the route bound; only the API.md row is missing -> the
    # doc-drift rule fires alone
    mutated = NATIVE_API_MD_CLEAN.replace(
        "| DELETE | `/api/v1/experiments/{id}` | token | {} |\n", ""
    )
    assert mutated != NATIVE_API_MD_CLEAN
    ns = _native_sources(api_md=("API.md", mutated))
    diags = _run_native(ns)
    assert _native_by_rule(diags, "route-unbound") == []
    undoc = _native_by_rule(diags, "route-undocumented")
    assert len(undoc) == 1
    assert "DELETE /api/v1/experiments/{id}" in undoc[0].message


def test_native_metric_undocumented_bad_and_brace_expansion():
    mutated = NATIVE_MASTER_CLEAN.replace(
        '<< "dtpu_experiments " << m.experiments_.size() << "\\n";',
        '<< "dtpu_experiments " << m.experiments_.size() << "\\n"\n'
        '        << "dtpu_lat_us_avg 1\\n"\n'
        '        << "dtpu_lat_us_max 2\\n";',
    )
    assert mutated != NATIVE_MASTER_CLEAN
    ns = _native_sources(master=("native/master/master.cpp", mutated))
    found = _native_by_rule(_run_native(ns), "metric-undocumented")
    assert sorted(d.message.split("'")[1] for d in found) == [
        "dtpu_lat_us_avg", "dtpu_lat_us_max",
    ]
    # the {a,b} doc shorthand documents both variants
    ns = _native_sources(
        master=("native/master/master.cpp", mutated),
        ops_md=("docs/operations.md",
                "`dtpu_experiments`, `dtpu_lat_us_{avg,max}`.\n"),
    )
    assert _native_by_rule(_run_native(ns), "metric-undocumented") == []


def test_native_fake_master_conformance_bad_clean_pair():
    mutated = NATIVE_FAKE_CLEAN.replace('"/api/v1/experiments"', '"/api/v1/expz"')
    ns = _native_sources(fakes={"tests/test_fake.py": mutated})
    found = _native_by_rule(_run_native(ns), "fake-master-conformance")
    assert len(found) == 1
    d = found[0]
    assert d.file == "tests/test_fake.py" and "/api/v1/expz" in d.message
    assert "do_GET" in d.message


def test_native_wire_field_unread_bad_clean_pair():
    mutated = NATIVE_AGENT_CLEAN.replace(
        'body.set("id", opts_.id);',
        'body.set("id", opts_.id);\n    body.set("hostname", opts_.host);',
    )
    ns = _native_sources(agent=("native/agent/agent.cpp", mutated))
    found = _native_by_rule(_run_native(ns), "wire-field-unread")
    assert len(found) == 1
    d = found[0]
    assert d.file == "native/agent/agent.cpp"
    assert "'hostname'" in d.message and "POST /api/v1/agents" in d.message


def test_native_cpp_suppression_with_argument():
    mutated = NATIVE_MASTER_CLEAN.replace(
        'experiments_.erase(ev["id"].as_int());',
        'tombstones_[ev["id"].as_int()] = true;',
    ).replace(
        '} else if (type == "exp_deleted") {',
        "// dtpu: lint-ok[wal-snapshot-gap] tombstones are rebuilt from the journal\n"
        '    } else if (type == "exp_deleted") {',
    )
    ns = _native_sources(master=("native/master/master.cpp", mutated))
    assert _native_by_rule(_run_native(ns), "wal-snapshot-gap") == []


def test_native_index_real_repo_conformance():
    """The analyzer is pattern-anchored; this pins its grip on the real
    daemons so idiom drift collapses loudly (scripts/native_check.sh runs
    the same floor pre-merge)."""
    from determined_tpu.lint import build_native_index, collect_native_sources
    from determined_tpu.lint._native import _parse_fake_routes

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ns = collect_native_sources(repo)
    idx = build_native_index(ns)
    assert len(idx.routes) >= 80
    assert len(idx.wal_sites) >= 50
    assert sum(1 for s in idx.wal_sites if s.rtype is None) == 0
    assert len(idx.replay_arms) >= 40
    # every emitted type has a replay arm in the real master
    assert set(idx.record_types()) <= set(idx.replay_arms)
    assert len(idx.metrics) >= 15
    assert len(idx.dump_state_keys) >= 30
    assert len(idx.wire_payloads) >= 4
    fake_patterns = [
        fr for src in ns.fakes.values() for fr in _parse_fake_routes(src)
    ]
    assert len(fake_patterns) >= 15


def test_native_real_repo_lints_clean():
    from determined_tpu.lint import lint_native
    from determined_tpu.lint.rules import build_rules

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    diags = lint_native(repo, build_rules(None, None))
    assert diags == [], "\n".join(
        f"{d.file}:{d.line}: [{d.rule}] {d.message}" for d in diags
    )


def test_native_seeded_replay_arm_deletion_fires():
    """Acceptance regression: deleting one replay arm from the REAL master
    source makes wal-replay-gap fire with the exact emit-site witness."""
    from determined_tpu.lint import build_native_index, collect_native_sources

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ns = collect_native_sources(repo)
    src = ns.master[1]
    assert 'type == "ckpt_deleted"' in src
    mutated = src.replace('type == "ckpt_deleted"', 'type == "ckpt_gone"', 1)
    import dataclasses as _dc

    ns2 = _dc.replace(ns, master=(ns.master[0], mutated))
    found = _native_by_rule(_run_native(ns2), "wal-replay-gap")
    assert len(found) == 1
    d = found[0]
    assert "'ckpt_deleted'" in d.message
    emit_line = next(
        s.line for s in build_native_index(ns).wal_sites
        if s.rtype == "ckpt_deleted"
    )
    assert d.line == emit_line
    assert f"{ns.master[0]}:{emit_line}" in d.message


def test_native_seeded_api_md_row_deletion_fires():
    """Acceptance regression: deleting one API.md route row from the REAL
    contract table makes route-undocumented fire on the dispatch site."""
    from determined_tpu.lint import collect_native_sources

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ns = collect_native_sources(repo)
    row_prefix = "| GET | `/api/v1/checkpoints` "
    lines = ns.api_md[1].splitlines()
    assert any(l.startswith(row_prefix) for l in lines)
    mutated = "\n".join(l for l in lines if not l.startswith(row_prefix)) + "\n"
    import dataclasses as _dc

    ns2 = _dc.replace(ns, api_md=(ns.api_md[0], mutated))
    found = _native_by_rule(_run_native(ns2), "route-undocumented")
    assert len(found) == 1
    assert "GET /api/v1/checkpoints " in found[0].message + " "
    assert found[0].file == ns.master[0]


def test_native_cli_strict_from_repo(tmp_path, capsys):
    """CLI wiring: --native from inside the repo exits 0 strict (the repo
    ships clean), and exits 2 when no native tree is above the target."""
    from determined_tpu.cli.main import main

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cwd = os.getcwd()
    os.chdir(repo)
    try:
        rc = main(["lint", "--native", "--strict"])
    finally:
        os.chdir(cwd)
    capsys.readouterr()
    assert rc == 0

    outside = tmp_path / "elsewhere"
    outside.mkdir()
    (outside / "x.py").write_text("x = 1\n")
    rc = main(["lint", "--native", str(outside / "x.py")])
    err = capsys.readouterr().err
    assert rc == 2 and "no native/master/master.cpp" in err
