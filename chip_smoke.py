#!/usr/bin/env python3
"""chip_smoke.py: the platform's main path, once, on the chip.

The standing proof that train -> checkpoint -> serve still starts on a TPU,
through the entry points a user calls, at the full width of the model the
repo ships (``examples/transformer_lm/const.yaml``: d2048, 8 layers, 16
heads, vocab 32768, seq 1024, global batch 8, bf16, flash attention, fused
CE, fused AdamW).  Only the length is cut: a few batches, a 64-row dataset,
two validations.  Weights are random, from the config's seed.

    python3 chip_smoke.py             # one chip (what the driver runs)
    python3 chip_smoke.py --chips 4   # the sharded phase only, four chips
    python3 chip_smoke.py --tiny      # rehearsal widths for a CPU; never "ok"

Phases with one chip, in order, each a failure of the whole run:

  probe    one short child asks jax what it runs on; anything but a TPU
           ends the run here, before a 0.6 B-parameter model meets a CPU
  train    ``dtpu experiment run`` (masterless: LocalExperiment ->
           Trainer.fit): finite, flat-or-falling loss, Mosaic kernels in
           the compiled step, a manifest-verified checkpoint on disk
  serve    ``dtpu serve <that checkpoint>``: concurrent POST /v1/generate
           of mixed lengths, two sharing a prefix (a warm prefill), every
           reply tokens-and-no-error, then SIGTERM and the drain exit 75
  compare  after the server is gone, ``scripts/serve_reference_check.py``
           runs the plain training forward over prompt + reply and accepts
           a token only within a stated logit margin of the forward's top-1
  cluster  master + agent built from ``native/`` with the README's CMake
           line; the agent must register a ``tpu`` slot by itself; the same
           config through ``dtpu experiment create``; the trial COMPLETED
           and its log naming the TPU

With ``--chips 4`` the only phase is ``scripts/sharded_train_check.py``: the
same model on ``fsdp: 4`` and ``fsdp: 2, tensor: 2`` against one of the four
chips, loss curves compared, in one process that drives all four.

One process holds a chip at a time, so this script never imports jax (it
checks that at the end): the phases are separate programs, run one after
another, each waited for; the server is spoken to over HTTP; the device
facts of the last line are what the working processes logged.  Everything
is built from tracked files into fixed git-ignored places inside the
checkout: ``native/build-smoke/`` (daemons), ``chip_smoke_out/`` (configs,
logs, checkpoints, temp files), and the compile cache where
``JAX_COMPILATION_CACHE_DIR`` says or else ``.dtpu_cache/xla``.

Last line of stdout on success, and only then:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chip_smoke_out")
BUILD = os.path.join(REPO, "native", "build-smoke")
EXAMPLE = os.path.join(REPO, "examples", "transformer_lm")
CONFIG = os.path.join(EXAMPLE, "const.yaml")

#: the whole run must end inside the driver's 1200 s
BUDGET_S = 1150.0
#: the later half of the loss reports may exceed the earlier half by this much
#: and count as flat (lr is still in warm-up: 8 steps of 100)
FLAT_LOSS_SLACK = 0.05

#: what --tiny replaces (widths: a rehearsal is never a result)
TINY_HPARAMS = {
    "seq_len": 256, "vocab_size": 512, "d_model": 64, "n_layers": 2,
    "n_heads": 4, "bf16": False,
}

_T0 = time.monotonic()
_PROCS: List[subprocess.Popen] = []


class SmokeFailure(Exception):
    pass


def say(phase: str, msg: str) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s] [{phase}] {msg}", flush=True)


def remaining() -> float:
    return BUDGET_S - (time.monotonic() - _T0)


def child_env() -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env["TMPDIR"] = os.path.join(OUT, "tmp")          # nothing outside the checkout
    env["DTPU_AUTH_PATH"] = os.path.join(OUT, "auth.json")
    env.setdefault("TPU_LOG_DIR", "disabled")
    return env


def spawn(argv: List[str], log_path: str, cwd: str = REPO) -> subprocess.Popen:
    os.makedirs(os.path.dirname(log_path), exist_ok=True)
    log = open(log_path, "ab")
    try:
        proc = subprocess.Popen(
            argv, cwd=cwd, env=child_env(), stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,  # one group per child: its children die with it
        )
    finally:
        log.close()
    _PROCS.append(proc)
    return proc


def session_members(sid: int) -> List[int]:
    """Pids whose session is ``sid``.  Every child here leads a session of
    its own, and what it starts stays in it even after ``setpgid`` — the
    agent gives each trial and each checkpoint-GC task a process group of
    its own, so killing the agent's group alone leaves those running."""
    pids = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                # pid (comm) state ppid pgrp session ...; comm may hold spaces
                session = int(f.read().rsplit(")", 1)[1].split()[3])
        except (OSError, IndexError, ValueError):
            continue  # gone meanwhile
        if session == sid:
            pids.append(int(name))
    return pids


def stop(proc: subprocess.Popen, grace: float = 10.0) -> None:
    """End a child and everything it started, whatever group it moved to."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            pass
    deadline = time.monotonic() + 5.0
    while True:
        left = session_members(proc.pid)
        for pid in left:
            try:
                os.kill(pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        if proc.poll() is None:
            try:
                proc.wait(timeout=1)
            except subprocess.TimeoutExpired:
                pass
        if not left or time.monotonic() > deadline:
            return
        time.sleep(0.1)


def stop_everything() -> None:
    for proc in reversed(_PROCS):
        stop(proc, grace=5.0)


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode(errors="replace")
    except OSError as e:
        return f"<no log: {e}>"


def read(path: str) -> str:
    with open(path, "rb") as f:
        return f.read().decode(errors="replace")


def run_to_end(
    phase: str, argv: List[str], log_path: str, timeout: float, cwd: str = REPO
) -> str:
    """One child, waited for.  Returns its whole output."""
    timeout = min(timeout, max(remaining(), 1.0))
    proc = spawn(argv, log_path, cwd=cwd)
    try:
        code = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        stop(proc)
        raise SmokeFailure(
            f"{phase}: {' '.join(argv[:6])} ... still running after {timeout:.0f}s\n"
            + tail(log_path)
        ) from None
    finally:
        stop(proc)  # sweeps what the child may have left in its group
    if code != 0:
        raise SmokeFailure(
            f"{phase}: {' '.join(argv[:6])} ... exited {code}\n" + tail(log_path)
        )
    return read(log_path)


# ---------------------------------------------------------------------------
# what the working processes log (determined_tpu/train/_trainer.py,
# utils/compilation_cache.py)
# ---------------------------------------------------------------------------

DEVICE_RE = re.compile(r"jax devices: platform=(\S+) kind='([^']*)' count=(\d+)")
CACHE_RE = re.compile(r"compilation cache (\S+) \(([^)]*)\) is (warm|cold)")
COMPILE_RE = re.compile(
    r"(jit\.compile\.[\w.]+): first call .*? took ([\d.]+)s; program: (.*)"
)
STEP_RE = re.compile(r"step (\d+)/(\d+): (.*)")
VALID_RE = re.compile(r"validation at step (\d+): (.*)")


def device_of(text: str, phase: str) -> Dict[str, Any]:
    m = DEVICE_RE.search(text)
    if not m:
        raise SmokeFailure(f"{phase}: the process logged no 'jax devices:' line")
    return {"platform": m.group(1), "kind": m.group(2), "count": int(m.group(3))}


def facts_of(text: str, phase: str) -> Dict[str, Any]:
    """Device, cache state and per-program compile facts from one log."""
    cache = CACHE_RE.search(text)
    compiles = {
        m.group(1): {
            "seconds": float(m.group(2)),
            **{
                k: int(v)
                for k, v in (kv.split("=") for kv in m.group(3).split() if "=" in kv)
            },
        }
        for m in COMPILE_RE.finditer(text)
    }
    say(
        phase,
        "compile cache %s (%s): %s; first calls: %s"
        % (
            cache.group(1) if cache else "?",
            cache.group(2) if cache else "?",
            cache.group(3) if cache else "not logged",
            json.dumps(compiles, sort_keys=True),
        ),
    )
    return {"device": device_of(text, phase), "compiles": compiles}


def metric(line: str, name: str) -> float:
    m = re.search(rf"\b{name}=(\S+)", line)
    if not m:
        raise SmokeFailure(f"no {name}= in log line: {line!r}")
    return float(m.group(1))


def check_losses(text: str, phase: str, want_steps: int) -> List[float]:
    steps = [(int(m.group(1)), metric(m.group(3), "loss")) for m in STEP_RE.finditer(text)]
    if not steps or steps[-1][0] != want_steps:
        raise SmokeFailure(f"{phase}: wanted reports up to step {want_steps}, got {steps}")
    losses = [l for _, l in steps]
    if not all(math.isfinite(l) for l in losses):
        raise SmokeFailure(f"{phase}: non-finite loss in {steps}")
    if len(losses) < 2:
        raise SmokeFailure(f"{phase}: one loss report says nothing of a trend: {steps}")
    half = len(losses) // 2
    early, late = sum(losses[:half]) / half, sum(losses[half:]) / (len(losses) - half)
    if late > early + FLAT_LOSS_SLACK:
        raise SmokeFailure(f"{phase}: loss rose from {early:.4f} to {late:.4f}: {steps}")
    say(phase, f"loss by report (step, mean loss since last): {steps}")
    return losses


# ---------------------------------------------------------------------------
# configuration: const.yaml with only its length cut
# ---------------------------------------------------------------------------


def write_config(path: str, ckpt_dir: str, tiny: bool) -> Dict[str, Any]:
    import yaml

    with open(CONFIG) as f:
        cfg = yaml.safe_load(f)
    hp = cfg["hyperparameters"]
    hp["dataset_size"] = 64                      # 8 batches a pass (validation is one)
    if tiny:
        hp.update(TINY_HPARAMS)
    cfg["searcher"]["max_length"] = {"batches": 8}
    cfg["min_validation_period"] = {"batches": 4}
    cfg["max_restarts"] = 0                      # a failure shows at once
    cfg["checkpoint_storage"] = {"type": "shared_fs", "host_path": ckpt_dir}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f, sort_keys=False)
    return cfg


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_probe(tiny: bool) -> None:
    """Fail in seconds, not after a CPU has initialised 0.6 B parameters."""
    out = run_to_end(
        "probe",
        [sys.executable, "-c",
         "from determined_tpu.utils.chip import device_facts; import json; "
         "print('PROBE ' + json.dumps(device_facts()))"],
        os.path.join(OUT, "probe.log"), timeout=180,
    )
    m = re.search(r"^PROBE (.*)$", out, re.M)
    if not m:
        raise SmokeFailure("probe: no device facts\n" + out[-2000:])
    dev = json.loads(m.group(1))
    say("probe", f"jax reports {dev}")
    if dev["platform"] != "tpu" and not tiny:
        raise SmokeFailure(
            f"probe: jax finds no TPU (platform {dev['platform']!r}): nothing to prove here"
        )


def phase_train(tiny: bool) -> Tuple[str, Dict[str, Any], int]:
    """-> (checkpoint directory, facts, the model's vocabulary size)"""
    work = os.path.join(OUT, "train")
    cfg_path = os.path.join(work, "config.yaml")
    cfg = write_config(cfg_path, os.path.join(work, "unused-storage"), tiny)
    ckpt_root = os.path.join(work, "checkpoints")
    log_path = os.path.join(work, "experiment_run.log")
    t0 = time.monotonic()
    text = run_to_end(
        "train",
        [sys.executable, "-m", "determined_tpu.cli", "experiment", "run", cfg_path,
         "--checkpoint-dir", ckpt_root],
        log_path, timeout=600, cwd=EXAMPLE,
    )
    say("train", f"`dtpu experiment run` exited 0 after {time.monotonic() - t0:.1f}s")
    facts = facts_of(text, "train")
    check_losses(text, "train", want_steps=8)
    val = [metric(m.group(2), "validation_loss") for m in VALID_RE.finditer(text)]
    if not val or not all(math.isfinite(v) for v in val):
        raise SmokeFailure(f"train: no finite validation loss: {val}")
    say("train", f"validation loss: {val}")
    step = facts["compiles"].get("jit.compile.train")
    if step is None:
        raise SmokeFailure("train: the step's first call was not logged")
    if facts["device"]["platform"] == "tpu" and not step.get("tpu_custom_call"):
        raise SmokeFailure(
            f"train: no Mosaic kernel (tpu_custom_call) in the compiled step: {step}"
        )
    say("train", f"kernels in the compiled train step: {step.get('tpu_custom_call', 0)} tpu_custom_call")

    # the checkpoint to serve: the newest one, verified against its manifest
    from determined_tpu.core._checkpoint import verify_manifest

    found = []
    for trial in sorted(os.listdir(ckpt_root)):
        tdir = os.path.join(ckpt_root, trial)
        if not (trial.startswith("trial_") and os.path.isdir(tdir)):
            continue
        for sid in os.listdir(tdir):
            state = os.path.join(tdir, sid, "trainer_state.json")
            if os.path.exists(state):
                with open(state) as f:
                    found.append((json.load(f)["steps_completed"], os.path.join(tdir, sid)))
    if not found:
        raise SmokeFailure(f"train: no checkpoint under {ckpt_root}")
    steps, ckpt = max(found)
    if not verify_manifest(ckpt, require_manifest=True):
        raise SmokeFailure(f"train: checkpoint {ckpt} has no manifest")
    size = sum(
        os.path.getsize(os.path.join(d, f)) for d, _, fs in os.walk(ckpt) for f in fs
    )
    say("train", f"checkpoint at step {steps}, manifest verified, {size / 2**30:.2f} GiB: {ckpt}")
    return ckpt, facts, cfg["hyperparameters"]["vocab_size"]


def http_json(
    url: str, body: Optional[Dict[str, Any]] = None, token: Optional[str] = None,
    timeout: float = 30.0,
) -> Tuple[int, Any]:
    headers = {"Content-Type": "application/json"}
    if token:
        headers["Authorization"] = f"Bearer {token}"
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(), headers=headers
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            return e.code, json.loads(raw)
        except ValueError:
            return e.code, {"error": raw.decode(errors="replace")}


def make_prompts(vocab: int) -> List[List[int]]:
    """Mixed lengths; prompts 1 and 2 share a 40-token prefix (2.5 KV blocks
    of 16), prompt 5 repeats prompt 0's first 48: both take the suffix
    prefill once their partner's blocks are registered."""
    import random

    rng = random.Random(0)
    tok = lambda n: [rng.randrange(1, vocab) for _ in range(n)]  # noqa: E731
    shared = tok(40)
    first = tok(96)
    return [
        first,                      # 0: long
        shared + tok(25),           # 1
        shared + tok(60),           # 2: shares 40 with 1
        tok(5),                     # 3: short
        tok(128),                   # 4: the full max_prompt_len
        first[:48] + tok(10),       # 5: shares 48 with 0
    ]


def phase_serve(ckpt: str, vocab: int) -> Tuple[str, Dict[str, Any]]:
    work = os.path.join(OUT, "serve")
    log_path = os.path.join(work, "serve.log")
    t0 = time.monotonic()
    proc = spawn(  # from the model's directory: the checkpoint names `model_def:Trial`
        [sys.executable, "-m", "determined_tpu.cli", "serve", ckpt, "--port", "0"],
        log_path, cwd=EXAMPLE,
    )
    try:
        url = None
        deadline = time.monotonic() + min(420, remaining())
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise SmokeFailure(f"serve: exited {proc.returncode} before serving\n" + tail(log_path))
            m = re.search(r"^serving on (http://\S+)$", read(log_path), re.M)
            if m:
                url = m.group(1)
                break
            time.sleep(0.5)
        if url is None:
            raise SmokeFailure("serve: never printed 'serving on'\n" + tail(log_path))
        say("serve", f"`dtpu serve` up at {url} after {time.monotonic() - t0:.1f}s (checkpoint loaded)")

        prompts = make_prompts(vocab)
        new_tokens = [12, 8, 8, 16, 4, 8]
        replies: List[Optional[Dict[str, Any]]] = [None] * len(prompts)

        def ask(i: int) -> None:
            status, payload = http_json(
                url + "/v1/generate",
                {"prompt_tokens": prompts[i], "max_new_tokens": new_tokens[i]},
                timeout=max(min(420, remaining()), 1.0),
            )
            replies[i] = {"status": status, **(payload if isinstance(payload, dict) else {})}

        # two concurrent waves: the second's prefixes are registered by then
        for wave in ([0, 1, 3], [2, 4, 5]):
            threads = [threading.Thread(target=ask, args=(i,)) for i in wave]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        for i, r in enumerate(replies):
            if (
                r is None or r.get("status") != 200 or r.get("error")
                or len(r.get("tokens") or []) != new_tokens[i]
                or not all(isinstance(t, int) and 0 <= t < vocab for t in r["tokens"])
            ):
                raise SmokeFailure(f"serve: request {i} (prompt of {len(prompts[i])}): bad reply {r}\n" + tail(log_path))
        _, stats = http_json(url + "/stats")
        say(
            "serve",
            "%d requests answered, no error; ttft ms %s; prefix hits %s (%s tokens not recomputed); errored=%s"
            % (
                len(replies), [r["ttft_ms"] for r in replies], stats.get("prefix_hits"),
                stats.get("prefix_tokens_saved"), stats.get("errored"),
            ),
        )
        if stats.get("errored") or stats.get("failed"):
            raise SmokeFailure(f"serve: the engine counted errors: {stats}")
        if not stats.get("prefix_hits"):
            raise SmokeFailure(f"serve: no prefix hit, so no prefill started from a cached prefix: {stats}")

        os.kill(proc.pid, signal.SIGTERM)
        try:
            code = proc.wait(timeout=min(90, max(remaining(), 1.0)))
        except subprocess.TimeoutExpired:
            raise SmokeFailure("serve: no exit 90s after SIGTERM\n" + tail(log_path)) from None
        if code != 75:
            raise SmokeFailure(f"serve: drain exit code {code}, documented is 75\n" + tail(log_path))
        say("serve", "SIGTERM -> drained, exit 75")
    finally:
        stop(proc)
    text = read(log_path)
    facts = facts_of(text, "serve")
    for name in ("prefill", "decode"):
        if f"jit.compile.serve.{name}" not in facts["compiles"]:
            raise SmokeFailure(f"serve: {name} never ran (no first-call line)")
    replies_path = os.path.join(work, "replies.json")
    with open(replies_path, "w") as f:
        json.dump(
            [{"prompt": p, "tokens": r["tokens"]} for p, r in zip(prompts, replies)], f
        )
    return replies_path, facts


def phase_compare(ckpt: str, replies_path: str) -> Dict[str, Any]:
    text = run_to_end(
        "compare",
        [sys.executable, os.path.join(REPO, "scripts", "serve_reference_check.py"),
         ckpt, replies_path],
        os.path.join(OUT, "compare", "check.log"), timeout=420, cwd=EXAMPLE,
    )
    m = re.search(r"^RESULT (.*)$", text, re.M)
    if not m:
        raise SmokeFailure("compare: no RESULT line\n" + text[-3000:])
    result = json.loads(m.group(1))
    say("compare", f"training forward vs served tokens: {result}")
    if not result["ok"]:
        raise SmokeFailure(f"compare: replies disagree with the training forward: {result}")
    return {"device": result["device"]}


def build_daemons() -> subprocess.Popen:
    """The README's line, into a fixed git-ignored directory; started first
    so the compiler works while the chip trains."""
    gen = ["-G", "Ninja"] if shutil.which("ninja") else []
    cmd = (
        f"cmake -S native -B {BUILD} {' '.join(gen)} && cmake --build {BUILD}"
    )
    return spawn(["sh", "-c", cmd], os.path.join(OUT, "cluster", "build.log"))


def phase_cluster(build: subprocess.Popen, tiny: bool) -> Dict[str, Any]:
    work = os.path.join(OUT, "cluster")
    try:
        code = build.wait(timeout=min(300, max(remaining(), 1.0)))
    except subprocess.TimeoutExpired:
        raise SmokeFailure("cluster: the native build did not finish\n" + tail(os.path.join(work, "build.log"))) from None
    master_bin, agent_bin = (os.path.join(BUILD, b) for b in ("dtpu-master", "dtpu-agent"))
    if code != 0 or not (os.path.exists(master_bin) and os.path.exists(agent_bin)):
        raise SmokeFailure(f"cluster: native build exited {code}\n" + tail(os.path.join(work, "build.log")))
    say("cluster", f"daemons built into {os.path.relpath(BUILD, REPO)}")

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    cfg_path = os.path.join(work, "config.yaml")
    write_config(cfg_path, os.path.join(work, "checkpoints"), tiny)
    master = spawn(
        [master_bin, "--host", "127.0.0.1", "--port", str(port),
         "--state-dir", os.path.join(work, "state"),
         "--checkpoint-dir", os.path.join(work, "checkpoints")],
        os.path.join(work, "master.log"),
    )
    agent = None
    try:
        token = None
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and token is None:
            try:
                status, body = http_json(
                    url + "/api/v1/auth/login", {"username": "determined", "password": ""}, timeout=2
                )
                token = body["token"] if status == 200 else None
            except (urllib.error.URLError, OSError):
                time.sleep(0.2)
        if token is None:
            raise SmokeFailure("cluster: master did not come up\n" + tail(os.path.join(work, "master.log")))
        # no --slots: the agent has to find the chip itself
        agent = spawn(
            [agent_bin, "--master-host", "127.0.0.1", "--master-port", str(port),
             "--id", "smoke-agent", "--python", sys.executable,
             "--state-dir", os.path.join(work, "agent-state")],
            os.path.join(work, "agent.log"),
        )
        agents: List[Dict[str, Any]] = []
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not agents:
            _, agents = http_json(url + "/api/v1/agents", token=token)
            time.sleep(0.3)
        say("cluster", f"GET /api/v1/agents -> {[(a.get('id'), a.get('slots'), a.get('slot_type')) for a in agents]}")
        want = "cpu" if tiny else "tpu"
        if len(agents) != 1 or agents[0].get("slots") != 1 or agents[0].get("slot_type") != want:
            raise SmokeFailure(
                f"cluster: wanted one agent with one {want} slot, found {agents}\n"
                + tail(os.path.join(work, "agent.log"))
            )

        cli = [sys.executable, "-m", "determined_tpu.cli", "-m", url]
        run_to_end("cluster", cli + ["login", "-p", ""], os.path.join(work, "login.log"), 30)
        out = run_to_end(
            "cluster", cli + ["experiment", "create", cfg_path, EXAMPLE],
            os.path.join(work, "create.log"), 60,
        )
        m = re.search(r"Created experiment (\d+)", out)
        if not m:
            raise SmokeFailure("cluster: `experiment create` named no experiment\n" + out)
        exp_id = int(m.group(1))
        say("cluster", f"`dtpu experiment create` -> experiment {exp_id}")

        exp: Dict[str, Any] = {}
        deadline = time.monotonic() + min(600, remaining())
        while time.monotonic() < deadline:
            _, exp = http_json(f"{url}/api/v1/experiments/{exp_id}", token=token)
            if exp.get("state") in ("COMPLETED", "CANCELED", "ERROR"):
                break
            time.sleep(1.0)
        trials = exp.get("trials") or []
        trial_log = ""
        if trials:
            trial_log = run_to_end(
                "cluster", cli + ["trial", "logs", str(trials[0]["id"])],
                os.path.join(work, "trial.log"), 60,
            )
        # `experiment run --cluster` and the experiment's own state can read
        # success over an errored trial: the TRIAL's state decides
        if (
            exp.get("state") != "COMPLETED" or len(trials) != 1
            or trials[0].get("state") != "COMPLETED"
        ):
            raise SmokeFailure(
                "cluster: experiment %s, trials %s\n%s"
                % (exp.get("state"), [(t.get("id"), t.get("state")) for t in trials],
                   "\n".join(trial_log.splitlines()[-40:]))
            )
        say("cluster", f"experiment {exp_id} COMPLETED, trial {trials[0]['id']} COMPLETED (restarts {trials[0].get('restarts')})")
        facts = facts_of(trial_log, "cluster")
        check_losses(trial_log, "cluster", want_steps=8)
        say("cluster", f"the trial's log names its device: {facts['device']}")
        return facts
    finally:
        if agent is not None:
            stop(agent)
        stop(master)


def phase_four_chips(tiny: bool) -> Dict[str, Any]:
    work = os.path.join(OUT, "sharded")
    cfg_path = os.path.join(work, "config.yaml")
    write_config(cfg_path, os.path.join(work, "unused-storage"), tiny)
    text = run_to_end(
        "sharded",
        [sys.executable, os.path.join(REPO, "scripts", "sharded_train_check.py"),
         cfg_path],
        os.path.join(OUT, "sharded", "check.log"), timeout=1100, cwd=EXAMPLE,
    )
    for line in text.splitlines():
        if line.startswith(("RUN ", "MEM ", "RESULT ")):
            say("sharded", line)
    m = re.search(r"^RESULT (.*)$", text, re.M)
    if not m:
        raise SmokeFailure("sharded: no RESULT line\n" + text[-3000:])
    result = json.loads(m.group(1))
    if not result["ok"]:
        raise SmokeFailure(f"sharded: {result}")
    return {"device": result["device"]}


# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal widths (CPU control-flow check); never prints ok")
    args = ap.parse_args(argv)

    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(os.path.join(OUT, "tmp"))
    devices: List[Dict[str, Any]] = []
    try:
        phase_probe(args.tiny)
        if args.chips == 4:
            devices.append(phase_four_chips(args.tiny)["device"])
        else:
            build = build_daemons()
            ckpt, facts, vocab = phase_train(args.tiny)
            devices.append(facts["device"])
            replies_path, facts = phase_serve(ckpt, vocab)
            devices.append(facts["device"])
            devices.append(phase_compare(ckpt, replies_path)["device"])
            devices.append(phase_cluster(build, args.tiny)["device"])
        if "jax" in sys.modules:
            raise SmokeFailure("chip_smoke.py itself imported jax: it would hold the chip")
        first = devices[0]
        if any(d != first for d in devices):
            raise SmokeFailure(f"the phases disagree about the device: {devices}")
        if first["platform"] != "tpu" or first["count"] != args.chips:
            raise SmokeFailure(
                f"every phase ran, but on {first}: not {args.chips} TPU chip(s), so not a result"
            )
        if args.tiny:
            raise SmokeFailure("--tiny is a rehearsal at toy widths: not a result")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED after {time.monotonic() - _T0:.0f}s: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        stop_everything()
    say("done", f"all phases passed in {time.monotonic() - _T0:.0f}s")
    print(json.dumps({"ok": True, "device": first}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
