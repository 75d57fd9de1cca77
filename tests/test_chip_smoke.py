"""``chip_smoke.py``'s control flow, on the CPU.

The smoke proves the main path on a TPU; here only its own logic is held
to account: it must FAIL wherever there is no TPU (quickly, at full width),
fail when any phase fails, never import jax itself, cut nothing but length
from ``const.yaml``, and read the very lines the program logs.  The whole
five-phase run at ``--tiny`` widths (about a minute: it builds the daemons)
is the ``slow`` case at the end.
"""

import importlib.util
import json
import logging
import os
import shutil
import subprocess
import sys

import pytest
import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture()
def smoke(tmp_path, monkeypatch):
    """chip_smoke as a module, writing under tmp_path."""
    spec = importlib.util.spec_from_file_location("chip_smoke_under_test", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "OUT", str(tmp_path / "out"))
    os.makedirs(tmp_path / "out" / "tmp")
    yield mod
    mod.stop_everything()


def _run(argv, cwd=REPO, **env):
    return subprocess.run(
        [sys.executable, *argv], cwd=cwd, capture_output=True, text=True,
        timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
    )


def _no_ok_line(stdout: str) -> bool:
    return not any(line.lstrip().startswith('{"ok"') for line in stdout.splitlines())


def test_full_width_run_fails_at_the_probe_without_a_tpu():
    """What the driver's sandbox run must see: non-zero, no result, and the
    0.6 B-parameter model never started on a CPU."""
    r = _run([SMOKE])
    assert r.returncode != 0
    assert _no_ok_line(r.stdout)
    assert "no TPU" in r.stderr
    assert not os.path.exists(os.path.join(REPO, "chip_smoke_out", "train"))


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run(["chip_smoke.py"], cwd=str(tmp_path), PYTHONPATH="")
    assert r.returncode != 0
    assert _no_ok_line(r.stdout)


def test_the_script_itself_stays_off_jax():
    r = _run(["-c", "import sys, chip_smoke; assert 'jax' not in sys.modules; "
                    "import determined_tpu.core._checkpoint, yaml; "
                    "assert 'jax' not in sys.modules"])
    assert r.returncode == 0, r.stderr
    with open(SMOKE) as f:
        src = f.read()
    assert "import jax" not in src and "determined_tpu.train" not in src


def test_stop_reaches_what_a_child_moved_to_another_process_group(smoke, tmp_path):
    """The agent gives every trial and GC task a process group of its own;
    the smoke must still leave nothing running (chiprun found one such
    process after an early version)."""
    import time

    marker = tmp_path / "grandchild.pid"
    code = (
        "import os, subprocess, time\n"
        "p = subprocess.Popen(['sleep', '120'], preexec_fn=lambda: os.setpgid(0, 0))\n"
        f"open({str(marker)!r}, 'w').write(str(p.pid))\n"
        "time.sleep(120)\n"
    )
    proc = smoke.spawn([sys.executable, "-c", code], str(tmp_path / "out" / "p.log"))
    deadline = time.monotonic() + 20
    while not (marker.exists() and marker.read_text()) and time.monotonic() < deadline:
        time.sleep(0.05)
    grandchild = int(marker.read_text())
    assert os.getpgid(grandchild) != os.getpgid(proc.pid)
    smoke.stop(proc, grace=2.0)
    assert proc.poll() is not None
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        try:
            with open(f"/proc/{grandchild}/stat") as f:
                if f.read().rsplit(")", 1)[1].split()[0] == "Z":
                    break  # a zombie awaiting init: not running
        except OSError:
            break
        time.sleep(0.05)
    else:
        pytest.fail(f"grandchild {grandchild} survived stop()")


def test_any_failing_phase_fails_the_run(smoke, monkeypatch, capsys):
    """A phase made to fail — here a checkpoint that does not exist for
    ``dtpu serve`` — is a non-zero exit and no ok line, whatever passed."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(smoke, "phase_probe", lambda tiny: None)
    monkeypatch.setattr(smoke, "build_daemons", lambda: None)
    monkeypatch.setattr(
        smoke, "phase_train", lambda tiny: ("/no/such/checkpoint", {"device": device}, 512)
    )
    assert smoke.main([]) == 1
    out = capsys.readouterr()
    assert _no_ok_line(out.out) and "serve: exited" in out.err


@pytest.mark.parametrize(
    "devices,argv,ok",
    [
        ([{"platform": "tpu", "kind": "TPU v5 lite", "count": 1}] * 4, [], True),
        ([{"platform": "cpu", "kind": "cpu", "count": 1}] * 4, [], False),
        # one phase fell back to another device than the rest
        ([{"platform": "tpu", "kind": "TPU v5 lite", "count": 1}] * 3
         + [{"platform": "cpu", "kind": "cpu", "count": 1}], [], False),
        # a rehearsal is never a result, even on a chip
        ([{"platform": "tpu", "kind": "TPU v5 lite", "count": 1}] * 4, ["--tiny"], False),
        # the driver's run needs one chip: four visible is another run
        ([{"platform": "tpu", "kind": "TPU v5 lite", "count": 4}] * 4, [], False),
    ],
    ids=["tpu", "cpu", "mixed", "tiny_on_tpu", "wrong_count"],
)
def test_the_verdict_is_the_device_every_phase_logged(smoke, monkeypatch, capsys, devices, argv, ok):
    phases = iter(devices)
    monkeypatch.setattr(smoke, "phase_probe", lambda tiny: None)
    monkeypatch.setattr(smoke, "build_daemons", lambda: None)
    monkeypatch.setattr(smoke, "phase_train", lambda tiny: ("/ckpt", {"device": next(phases)}, 512))
    monkeypatch.setattr(smoke, "phase_serve", lambda c, v: ("/replies", {"device": next(phases)}))
    monkeypatch.setattr(smoke, "phase_compare", lambda c, r: {"device": next(phases)})
    monkeypatch.setattr(smoke, "phase_cluster", lambda b, tiny: {"device": next(phases)})
    # main() refuses a result if the script itself holds jax; pytest does
    monkeypatch.delitem(sys.modules, "jax")
    code = smoke.main(argv)
    last = (capsys.readouterr().out.strip().splitlines() or [""])[-1]
    if ok:
        assert code == 0
        assert json.loads(last) == {"ok": True, "device": devices[0]}
    else:
        assert code == 1 and not last.startswith('{"ok"')


def test_the_config_is_const_yaml_with_only_its_length_cut(smoke, tmp_path):
    with open(os.path.join(REPO, "examples", "transformer_lm", "const.yaml")) as f:
        shipped = yaml.safe_load(f)
    smoke.write_config(str(tmp_path / "c.yaml"), str(tmp_path / "ck"), tiny=False)
    with open(tmp_path / "c.yaml") as f:
        cut = yaml.safe_load(f)
    length_only = {"dataset_size"}
    for k, v in shipped["hyperparameters"].items():
        assert k in length_only or cut["hyperparameters"][k] == v, k
    assert set(cut["hyperparameters"]) == set(shipped["hyperparameters"])
    assert cut["entrypoint"] == shipped["entrypoint"]
    assert cut["searcher"]["name"] == "single"
    assert cut["searcher"]["max_length"] == {"batches": 8}
    assert cut["max_restarts"] == 0
    # parses as an experiment
    from determined_tpu.config import ExperimentConfig

    assert ExperimentConfig.parse(cut).max_restarts == 0


def test_shared_prefixes_span_two_kv_blocks(smoke):
    from determined_tpu.serve.config import ServeConfig

    sc = ServeConfig()
    p = smoke.make_prompts(32768)
    assert p[1][:40] == p[2][:40] and p[0][:48] == p[5][:48]
    assert 40 // sc.block_size >= 2
    assert max(map(len, p)) == sc.max_prompt_len and min(map(len, p)) < sc.block_size
    assert all(0 < t < 32768 for prompt in p for t in prompt)


def test_the_smoke_reads_the_lines_the_program_writes(smoke, tmp_path, caplog, monkeypatch):
    """Each regex against the REAL log call it depends on, so a reworded
    message fails here and not on the chip."""
    import jax
    import jax.numpy as jnp

    from determined_tpu import core, train
    from determined_tpu.parallel.mesh import MeshConfig
    from determined_tpu.utils import compilation_cache

    monkeypatch.delenv(compilation_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compilation_cache, "_configured", None)
    prev = jax.config.jax_compilation_cache_dir
    prev_tb = jax.config.jax_include_full_tracebacks_in_locations
    with caplog.at_level(logging.INFO):
        try:
            compilation_cache.setup_compilation_cache(str(tmp_path / "xla"))
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
            jax.config.update("jax_include_full_tracebacks_in_locations", prev_tb)
        train.init(
            hparams={}, mesh_config=MeshConfig(data=1),
            core_context=core._dummy_init(), devices=jax.devices()[:1],
        )
        step = compilation_cache.timed_first_call(
            jax.jit(lambda x: jnp.sin(x) + 1), "jit.compile.train"
        )
        step(jnp.ones((4,)))
        logging.getLogger("determined_tpu.train").info(
            "step %d/%d: %s", 4, 8, "loss=10.4 lr=1e-05"
        )
    text = "\n".join(r.getMessage() for r in caplog.records)
    assert smoke.device_of(text, "t") == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind, "count": len(jax.devices()),
    }
    assert smoke.CACHE_RE.search(text).group(3) == "cold"
    facts = smoke.facts_of(text, "t")
    assert facts["compiles"]["jit.compile.train"]["tpu_custom_call"] == 0
    assert facts["compiles"]["jit.compile.train"]["seconds"] >= 0
    assert [m.group(1) for m in smoke.STEP_RE.finditer(text)] == ["4"]


@pytest.mark.parametrize(
    "reports,fails",
    [
        ([(4, 10.41), (8, 10.39)], None),
        ([(4, 10.41), (8, 10.44)], None),                    # flat, inside the slack
        ([(4, 10.41), (8, 10.60)], "loss rose"),
        ([(4, 10.41), (8, float("nan"))], "non-finite"),
        ([(8, 10.41)], "trend"),
        ([(4, 10.41)], "up to step 8"),                      # stopped short
    ],
)
def test_loss_check(smoke, reports, fails):
    text = "\n".join(f"x: step {s}/8: loss={l} lr=1e-5" for s, l in reports)
    if fails is None:
        assert smoke.check_losses(text, "t", want_steps=8) == [l for _, l in reports]
    else:
        with pytest.raises(smoke.SmokeFailure, match=fails):
            smoke.check_losses(text, "t", want_steps=8)


@pytest.mark.slow
def test_tiny_run_goes_through_every_phase_and_still_fails_on_a_cpu(tmp_path):
    """All five phases through the real entry points at toy widths — the
    rehearsal made before a chip call — with the compile cache placed from
    outside: every cache file lands where JAX_COMPILATION_CACHE_DIR says."""
    cache = tmp_path / "cache-from-outside"
    r = _run([SMOKE, "--tiny"], JAX_COMPILATION_CACHE_DIR=str(cache),
             JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert r.returncode != 0 and _no_ok_line(r.stdout)
    for phase in ("train", "serve", "compare", "cluster"):
        assert f"[{phase}]" in r.stdout, r.stdout + r.stderr
    assert "not 1 TPU chip(s)" in r.stderr
    assert f"compile cache {cache} (JAX_COMPILATION_CACHE_DIR)" in r.stdout
    assert any(cache.iterdir())
