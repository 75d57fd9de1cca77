"""Model registry + continuous deployment (ISSUE 15): driver promotion.

The registry itself lives in the C++ master and is pinned there by
``tests/test_master_wal.py`` (WAL fuzz, idempotent re-register across
SIGKILL) and the devcluster e2e below.  These tests pin the DRIVER side
masterless: a fake in-process registry master (mirroring master.cpp's
idempotency semantics) hosts the routes, and real ``LocalExperiment``
searches promote into it — lineage payloads, journal records, GC pinning,
resume behavior.

The acceptance e2e (``devcluster`` + ``slow``) closes the whole loop
against the real binaries: seeded search with ``auto_promote`` -> registry
holds ``name@v1`` with lineage -> ``dtpu serve --model name@latest``
registers -> rolling deploy to v2 drains and replaces the replica with
zero failed in-flight requests under open-loop Poisson load.
"""

import json
import os
import shutil
import sys
import threading
import time

import pytest

from determined_tpu.api.session import Session
from determined_tpu.config import ExperimentConfig
from determined_tpu.experiment import LocalExperiment
from determined_tpu.experiment import registry as registry_mod
from determined_tpu.experiment.journal import journal_path, read_journal
from determined_tpu.models.mnist import MnistTrial

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# model ref grammar
# ---------------------------------------------------------------------------


def test_parse_model_ref():
    assert registry_mod.parse_model_ref("lm") == ("lm", "latest")
    assert registry_mod.parse_model_ref("lm@latest") == ("lm", "latest")
    assert registry_mod.parse_model_ref("lm@3") == ("lm", 3)
    assert registry_mod.parse_model_ref("lm@v12") == ("lm", 12)
    assert registry_mod.format_model_ref("lm", 3) == "lm@v3"
    for bad in ("", "@v1", "lm@", "lm@vx", "lm@1.5"):
        with pytest.raises(ValueError):
            registry_mod.parse_model_ref(bad)


# ---------------------------------------------------------------------------
# fake registry master (mirrors master.cpp's /api/v1/models semantics,
# including idempotent re-register: same version+uuid -> 200 no-op,
# taken version with a different uuid -> 409)
# ---------------------------------------------------------------------------


class FakeRegistryMaster:
    def __init__(self):
        self.models = {}          # name -> model json
        self.version_posts = []   # every POST .../versions body
        self.lock = threading.Lock()
        self._serve()

    def _latest(self, model):
        return max((int(v["version"]) for v in model["versions"]), default=0)

    def _serve(self):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        from urllib.parse import urlparse

        fake = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _json(self, obj, code=200):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                path = urlparse(self.path).path
                n = int(self.headers.get("Content-Length") or 0)
                body = json.loads(self.rfile.read(n) or b"{}") if n else {}
                parts = path.strip("/").split("/")
                with fake.lock:
                    if path == "/api/v1/auth/login":
                        return self._json({"token": "t"})
                    if path == "/api/v1/models":
                        name = body.get("name")
                        if name in fake.models:
                            return self._json({"error": "model exists"}, 409)
                        fake.models[name] = {
                            "name": name,
                            "labels": body.get("labels") or [],
                            "versions": [],
                        }
                        return self._json(fake.models[name], 201)
                    if len(parts) == 5 and parts[4] == "versions":
                        name = parts[3]
                        model = fake.models.get(name)
                        if model is None:
                            return self._json({"error": "no such model"}, 404)
                        fake.version_posts.append(dict(body))
                        uuid = body.get("checkpoint_uuid") or ""
                        next_v = fake._latest(model) + 1
                        want = int(body.get("version") or 0)
                        existing = None
                        if want:
                            existing = next(
                                (v for v in model["versions"]
                                 if v["version"] == want), None
                            )
                        elif next_v > 1:
                            latest = model["versions"][-1]
                            if latest["checkpoint_uuid"] == uuid:
                                existing = latest
                        if existing is not None:
                            if existing["checkpoint_uuid"] == uuid:
                                return self._json(existing, 200)
                            return self._json({"error": "conflict"}, 409)
                        if want and want != next_v:
                            return self._json({"error": "non-contiguous"}, 409)
                        ver = {
                            "version": next_v,
                            "checkpoint_uuid": uuid,
                            "storage_path": body.get("storage_path") or "",
                            "source_trial_id": body.get("source_trial_id") or 0,
                            "source_experiment_id":
                                body.get("source_experiment_id") or 0,
                            "metrics": body.get("metrics") or {},
                            "labels": body.get("labels") or [],
                        }
                        model["versions"].append(ver)
                        return self._json(ver, 201)
                return self._json({"error": f"no fake route {path}"}, 404)

            def do_GET(self):
                path = urlparse(self.path).path
                parts = path.strip("/").split("/")
                with fake.lock:
                    if path == "/api/v1/models":
                        return self._json(list(fake.models.values()))
                    if len(parts) == 4 and parts[2] == "models":
                        model = fake.models.get(parts[3])
                        if model is None:
                            return self._json({"error": "no such model"}, 404)
                        return self._json(model)
                    if len(parts) == 6 and parts[4] == "versions":
                        model = fake.models.get(parts[3])
                        if model is None:
                            return self._json({"error": "no such model"}, 404)
                        want = (fake._latest(model) if parts[5] == "latest"
                                else int(parts[5]))
                        ver = next(
                            (v for v in model["versions"]
                             if v["version"] == want), None
                        )
                        if ver is None:
                            return self._json({"error": "no such version"}, 404)
                        return self._json({**ver, "model": parts[3]})
                return self._json({"error": f"no fake route {path}"}, 404)

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.url = f"http://127.0.0.1:{self.server.server_address[1]}"
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True,
            name="fake-registry-master",
        )
        self.thread.start()

    def close(self):
        self.server.shutdown()
        self.server.server_close()


@pytest.fixture()
def fake_master():
    fake = FakeRegistryMaster()
    yield fake
    fake.close()


def _registry_config(**registry):
    return ExperimentConfig.parse(
        {
            "name": "registry-exp",
            "hyperparameters": {
                "lr": {"type": "log", "minval": -3, "maxval": -1},
                "hidden": 16,
                "global_batch_size": 16,
                "dataset_size": 64,
            },
            "searcher": {
                "name": "random",
                "metric": "validation_accuracy",
                "smaller_is_better": False,
                "max_trials": 2,
                "max_length": {"batches": 4},
            },
            "min_validation_period": {"batches": 2},
            "registry": registry or {"model": "mnist-clf", "auto_promote": True},
        }
    )


# ---------------------------------------------------------------------------
# LocalExperiment auto-promotion
# ---------------------------------------------------------------------------


def test_local_auto_promote_registers_winner(tmp_path, fake_master):
    """A completed search with ``registry.auto_promote`` ends with the
    best trial's manifest-verified checkpoint registered as name@v1,
    carrying lineage + metrics; the journal records the promotion."""
    cfg = _registry_config(
        model="mnist-clf", auto_promote=True, labels=["prod"]
    )
    exp = LocalExperiment(
        cfg, MnistTrial, checkpoint_dir=str(tmp_path / "ck"),
        session=Session(fake_master.url, token="t"),
    )
    summary = exp.run()
    assert summary["status"] == "completed"
    assert "registry_error" not in summary, summary.get("registry_error")
    reg = summary["registry"]
    assert reg["model"] == "mnist-clf" and reg["version"] == 1
    assert reg["target"] == "mnist-clf@v1"

    best_rid = summary["best_trial"]
    model = fake_master.models["mnist-clf"]
    assert model["labels"] == ["prod"]
    (ver,) = model["versions"]
    assert ver["checkpoint_uuid"] == reg["checkpoint_uuid"]
    assert ver["source_trial_id"] == best_rid
    assert ver["labels"] == ["prod"]
    assert ver["metrics"].get("validation_accuracy") is not None
    # the storage path is the trial's real on-disk checkpoint, with a
    # verified manifest (what `dtpu serve --model` will load)
    assert os.path.isdir(ver["storage_path"])
    assert os.path.isfile(os.path.join(ver["storage_path"], "manifest.json"))
    assert ver["storage_path"].endswith(
        os.path.join(f"trial_{best_rid}", ver["checkpoint_uuid"])
    )

    replay = read_journal(journal_path(exp.checkpoint_dir))
    assert replay.registered_models == [
        {"name": "mnist-clf", "version": 1, "uuid": ver["checkpoint_uuid"]}
    ]


def test_local_auto_promote_without_master_degrades(tmp_path, monkeypatch):
    """No session and no $DTPU_MASTER: the search completes normally and
    the summary carries registry_error instead of an exception."""
    monkeypatch.delenv("DTPU_MASTER", raising=False)
    cfg = _registry_config()
    exp = LocalExperiment(cfg, MnistTrial, checkpoint_dir=str(tmp_path / "ck"))
    summary = exp.run()
    assert summary["status"] == "completed"
    assert "registry" not in summary
    assert "no master configured" in summary["registry_error"]


def test_resume_repromotes_idempotently_and_gc_pins_checkpoint(
    tmp_path, fake_master
):
    """The GC-correctness satellite: promote, then compact — the promoted
    checkpoint's directory survives retention even when per-trial rotation
    would delete it, because the ``model_registered`` journal record keeps
    pinning it across resume.  Re-running the completed search re-fires
    the promotion hook, which must be a no-op against the registry (same
    uuid -> same version, no duplicate)."""
    cfg = _registry_config()
    session = Session(fake_master.url, token="t")
    ckdir = str(tmp_path / "ck")
    exp = LocalExperiment(cfg, MnistTrial, checkpoint_dir=ckdir, session=session)
    summary = exp.run()
    reg = summary["registry"]
    pinned_uuid = reg["checkpoint_uuid"]
    best_rid = summary["best_trial"]
    pinned_dir = os.path.join(ckdir, f"trial_{best_rid}", pinned_uuid)
    assert os.path.isdir(pinned_dir)

    # resume the completed experiment: nothing re-runs, but the promotion
    # hook fires again — the registry must still hold exactly one version
    exp2 = LocalExperiment(cfg, MnistTrial, checkpoint_dir=ckdir, session=session)
    summary2 = exp2.run(resume=True)
    assert summary2["status"] == "completed"
    assert summary2["registry"]["version"] == 1
    assert len(fake_master.models["mnist-clf"]["versions"]) == 1

    # simulate the search training PAST the promoted checkpoint (a newer
    # checkpoint for the same trial): per-trial keep-latest rotation now
    # wants the promoted directory gone
    newer = os.path.join(ckdir, f"trial_{best_rid}", "ffffffff-newer")
    shutil.copytree(pinned_dir, newer)
    meta_path = os.path.join(newer, "metadata.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["steps_completed"] = int(meta.get("steps_completed") or 0) + 100
    with open(meta_path, "w") as f:
        json.dump(meta, f)

    # control: WITHOUT the registry pin, the planner deletes the promoted
    # checkpoint (it is no longer the trial's latest)
    from determined_tpu.exec import gc_checkpoints

    infos = gc_checkpoints.scan_experiment_checkpoints(ckdir)
    keep, delete = gc_checkpoints.plan_retention(
        infos, gc_checkpoints.RetentionPolicy(keep_trial_latest=1)
    )
    assert pinned_uuid in delete, "control failed: rotation never threatened it"

    # the experiment's own GC pass protects it via _registry_pinned
    # (restored from the journal's model_registered record on resume)
    exp2._apply_gc_retention()
    assert os.path.isdir(pinned_dir), "registry-pinned checkpoint was deleted"
    assert os.path.isdir(newer)


# ---------------------------------------------------------------------------
# ClusterExperiment promotion (unit: canned results against the fake)
# ---------------------------------------------------------------------------


def test_cluster_promotion_payload(fake_master, tmp_path):
    """Cluster-side promotion registers the master-tracked uuid with
    master-trial + master-experiment lineage and NO storage_path (the
    master derives it from its own checkpoint record)."""
    from determined_tpu.experiment.cluster import ClusterExperiment, _Watch
    from determined_tpu.experiment.local import TrialResult

    cfg = _registry_config(model="mnist-clf", auto_promote=True)
    exp = ClusterExperiment(
        cfg,
        entrypoint="determined_tpu.models.mnist:MnistTrial",
        session=Session(fake_master.url, token="t"),
        checkpoint_dir=str(tmp_path / "driver"),
    )
    exp.master_experiment_id = 5
    exp.results[1] = TrialResult(
        request_id=1,
        hparams={"lr": 0.1},
        steps_completed=4,
        metrics={"validation_accuracy": 0.9},
        checkpoint="uuid-cluster",
        stopped_early=False,
    )
    exp._watches[1] = _Watch(request_id=1, master_trial_id=17)
    summary = {"best_trial": 1}
    exp.on_search_complete(summary)
    assert summary["registry"]["target"] == "mnist-clf@v1"
    (post,) = fake_master.version_posts
    assert post["checkpoint_uuid"] == "uuid-cluster"
    assert post["source_trial_id"] == 17
    assert post["source_experiment_id"] == 5
    assert "storage_path" not in post
    assert post["metrics"] == {"validation_accuracy": 0.9}


# ---------------------------------------------------------------------------
# deploy state machine against the real master (raw-HTTP replicas, no jax)
# ---------------------------------------------------------------------------


@pytest.mark.devcluster
def test_rolling_deploy_replacement_gate_and_label_matching(tmp_path):
    """Review regressions: (a) replicas already on the target BEFORE the
    roll are existing fleet capacity, not replacements — a drained
    replica's slot must be refilled by a NEW on-target registration
    before the roll advances or completes; (b) on-target matching uses
    the structured model_name/model_version registration fields when
    present (the display label is operator-overridable via
    --model-name), falling back to the label only for raw launches."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from devcluster import DevCluster

    cluster = DevCluster(tmp_path, agents=0)
    cluster.start_master()
    try:
        u = cluster.url
        ck = tmp_path / "ck-u1"
        ck.mkdir()
        cluster.register_model("lm", "u1", storage_path=str(ck))
        cluster.register_model("lm", "u1", storage_path=str(ck), version=2)

        def reg(url, model, name="", version=0):
            body = {"url": url, "model": model}
            if name:
                body.update(model_name=name, model_version=version)
            r = cluster.http.post(
                u + "/api/v1/serving/replicas", json=body, timeout=5
            )
            assert r.status_code == 201, r.text
            return r.json()["id"]

        # (b): custom display label, structured fields ON target -> not rolled
        reg("http://x:1", "custom-label", "lm", 2)
        # pre-existing on-target by label -> not rolled, and NOT a replacement
        reg("http://x:2", "lm@v2")
        # the only replica that actually needs rolling
        r_old = reg("http://x:3", "lm@v1", "lm", 1)

        state = cluster.deploy("lm", 2)
        assert state["pending"] == [] and state["draining"] == r_old, state

        # the drain signal rides r_old's heartbeat
        hb = cluster.http.post(
            u + f"/api/v1/serving/replicas/{r_old}/heartbeat", json={}, timeout=5
        ).json()
        assert hb.get("drain") is True and hb["deploy"]["target"] == "lm@v2"

        # r_old drains away: with two on-target replicas registered BEFORE
        # the roll, the deploy must NOT complete — no replacement yet
        cluster.http.delete(u + f"/api/v1/serving/replicas/{r_old}", timeout=5)
        state = cluster.deploy_status()
        assert state["status"] == "rolling" and state["rolled"] == [r_old], state

        # the relaunched replica registers on target -> NOW it completes
        reg("http://x:4", "lm@v2", "lm", 2)
        state = cluster.deploy_status()
        assert state["status"] == "completed", state
    finally:
        cluster.stop()


# ---------------------------------------------------------------------------
# devcluster e2e acceptance: the whole train->serve loop, zero dropped
# requests across the roll
# ---------------------------------------------------------------------------


class _PoissonLoad:
    """Open-loop Poisson load (exponential gaps) over the
    master's live routing table.  Every arrival MUST eventually succeed:
    a 503 (draining) or connection error (replica restarting) re-resolves
    the fleet and retries — those are the roll's expected transients — but
    an admitted request that fails, or an arrival that exhausts its
    retries, is a dropped request and fails the test."""

    def __init__(self, cluster, rate_hz=8.0, seed=0):
        import random

        self.cluster = cluster
        self.rate = rate_hz
        self.rng = random.Random(seed)
        self.ok = 0
        self.dropped = []
        self.served_by = set()
        self._stop = threading.Event()
        self._threads = []

    def _url(self):
        reps = self.cluster.serving()
        return (reps[0]["url"], reps[0]["model"]) if reps else (None, None)

    def _one(self, i):
        import requests as rq

        deadline = time.time() + 60
        while time.time() < deadline:
            url, label = self._url()
            if url is None:
                time.sleep(0.2)
                continue
            try:
                r = rq.post(
                    url + "/v1/generate",
                    json={"prompt_tokens": [1 + i % 6, 2], "max_new_tokens": 2,
                          "seed": i},
                    timeout=30,
                )
            except rq.RequestException:
                time.sleep(0.2)  # replica mid-restart: re-resolve
                continue
            if r.status_code == 200:
                self.ok += 1
                self.served_by.add(label)
                return
            if r.status_code in (429, 503):
                time.sleep(0.2)  # draining/backpressure: retry the fleet
                continue
            self.dropped.append((i, r.status_code, r.text[:200]))
            return
        self.dropped.append((i, "timeout", "arrival never served"))

    def run_for(self, seconds):
        t_end = time.time() + seconds
        i = 0
        while time.time() < t_end and not self._stop.is_set():
            t = threading.Thread(target=self._one, args=(i,), daemon=True)
            t.start()
            self._threads.append(t)
            i += 1
            time.sleep(self.rng.expovariate(self.rate))

    def join(self, timeout=90):
        for t in self._threads:
            t.join(timeout=max(0.1, timeout - 0))


@pytest.mark.devcluster
@pytest.mark.slow
def test_e2e_search_promote_serve_roll(tmp_path):
    """ISSUE 15 acceptance: seeded search with auto_promote -> registry
    holds name@v1 with lineage back to the winning trial -> `dtpu serve
    --model name@latest` resolves through the master and registers as
    name@v1 -> rolling deploy to v2 drains the replica (exit 75), the
    harness relaunches it, the deploy completes — with ZERO failed
    in-flight requests under open-loop Poisson load, and requests served
    on both sides of the roll."""
    pytest.importorskip("requests")
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from devcluster import DevCluster, _spawn_serve

    cluster = DevCluster(
        tmp_path, agents=0,
        master_args=("--serve-replica-timeout-sec", "5",
                     "--deploy-step-timeout-sec", "120"),
    )
    cluster.start_master()
    proc = None
    load = None
    try:
        # 1. seeded 4-trial search, auto_promote into the real master
        cfg = ExperimentConfig.parse(
            {
                "name": "e2e-loop",
                "hyperparameters": {
                    "lr": 1e-3, "global_batch_size": 8, "seq_len": 8,
                    "vocab_size": 64, "d_model": 32, "n_layers": 1,
                    "n_heads": 2, "n_kv_heads": 2, "dataset_size": 32,
                    "bf16": False, "attention": "reference",
                    "warmup_steps": 1,
                },
                "searcher": {
                    "name": "random",
                    "metric": "validation_loss",
                    "max_trials": 4,
                    "max_length": {"batches": 2},
                    "max_concurrent_trials": 1,
                },
                "min_validation_period": {"batches": 2},
                "registry": {"model": "e2e-lm", "auto_promote": True},
            }
        )
        from determined_tpu.api.session import login
        from determined_tpu.models.transformer import LMTrial

        session = login(cluster.url)
        exp = LocalExperiment(
            cfg, LMTrial, checkpoint_dir=str(tmp_path / "search"),
            seed=7, session=session,
        )
        summary = exp.run()
        assert summary["status"] == "completed", summary
        assert summary["registry"]["target"] == "e2e-lm@v1", summary

        # lineage is queryable through the registry
        ver = cluster.http.get(
            cluster.url + "/api/v1/models/e2e-lm/versions/latest", timeout=5
        ).json()
        assert ver["version"] == 1
        assert ver["source_trial_id"] == summary["best_trial"]
        assert os.path.isdir(ver["storage_path"])

        # 2. serve BY NAME: the worker resolves through the master
        proc, url, lines = _spawn_serve(cluster, "--model", "e2e-lm@latest")
        deadline = time.time() + 30
        while time.time() < deadline:
            reps = cluster.serving()
            if reps and reps[0].get("model") == "e2e-lm@v1":
                break
            time.sleep(0.3)
        else:
            raise AssertionError(f"replica never listed as e2e-lm@v1: "
                                 f"{cluster.serving()}\n" + "\n".join(lines))
        assert reps[0]["model_name"] == "e2e-lm"
        assert reps[0]["model_version"] == 1

        # 3. open-loop Poisson load across the roll
        load = _PoissonLoad(cluster, rate_hz=8.0, seed=3)
        gen = threading.Thread(target=load.run_for, args=(12.0,), daemon=True)
        gen.start()
        time.sleep(2.0)  # traffic flowing against v1

        # 4. roll to v2 (same weights re-registered under an explicit
        # version: content-identical, distinct registry version)
        cluster.register_model(
            "e2e-lm", ver["checkpoint_uuid"],
            storage_path=ver["storage_path"], version=2,
        )
        state = cluster.deploy("e2e-lm", 2)
        assert state["status"] == "rolling", state

        # the worker drains (exit 75) and the harness relaunches it
        proc.wait(timeout=120)
        assert proc.returncode == 75, "\n".join(lines)
        proc, url, lines = _spawn_serve(cluster, "--model", "e2e-lm@latest")

        deadline = time.time() + 60
        while time.time() < deadline:
            state = cluster.deploy_status()
            if state["status"] != "rolling":
                break
            time.sleep(0.5)
        assert state["status"] == "completed", state

        gen.join(timeout=30)
        load.join(timeout=90)
        assert not load.dropped, f"dropped requests across the roll: {load.dropped}"
        assert load.ok >= 20, f"too little load to prove anything: {load.ok}"
        # traffic landed on both sides of the roll
        assert "e2e-lm@v1" in load.served_by and "e2e-lm@v2" in load.served_by, (
            load.served_by
        )
        reps = cluster.serving()
        assert [r["model"] for r in reps] == ["e2e-lm@v2"]
    finally:
        if load is not None:
            load._stop.set()
        if proc is not None and proc.poll() is None:
            proc.kill()
        cluster.stop()
