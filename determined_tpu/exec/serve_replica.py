"""Supervisor-launched serving replica: the fleet's relaunch vehicle.

The master's serving-fleet supervisor (``PUT /api/v1/serving/fleet``)
replaces a dead/failed/drained replica by launching THIS module as a
generic agent task — the same launch path notebooks and commands ride —
so a replica that dies comes back without any out-of-band harness.  The
module loads the registry version the master resolved into the task
config, serves it as a registered replica (``ServeWorker``), reports the
task ready, and then polls for drain:

- a master-requested drain (rolling deploy walking this replica) or a
  SIGTERM runs the orderly drain and exits 75 (EX_TEMPFAIL) — the
  supervisor counts that as a relaunch, never a crash-loop failure;
- a bad checkpoint (the crash-loop case) fails FAST with a nonzero exit,
  which the agent reports back so the supervisor's capped backoff and
  crash-loop detection engage instead of thrashing the agent.

``DTPU_TASK_CONFIG`` fields (set by the master's ``launch_fleet_replica``):
  model            registry model name
  version          registry version number
  checkpoint_uuid  the version's checkpoint uuid (label only)
  storage_path     checkpoint directory to load
  serve            optional ServeConfig overrides (``ServeConfig.from_dict``;
                   ``trace_dir`` there turns the replica's tracer on)
  env              optional {name: value} environment overrides, applied
                   before anything else — the chaos hook (an injected
                   ``DTPU_SERVE_ERROR_RATE`` manufactures 5xxs on a canary
                   cohort, optionally gated to one registry version with
                   ``DTPU_SERVE_ERROR_VERSION``) rides here
"""

from __future__ import annotations

import errno
import json
import logging
import os
import random
import signal
import sys
import time
import urllib.request

from determined_tpu.exec._tls import urlopen as _tls_urlopen

logger = logging.getLogger("determined_tpu.exec.serve_replica")

#: orderly-drain exit code (mirrors determined_tpu.experiment
#: PREEMPTED_EXIT_CODE without importing the experiment package here)
DRAIN_EXIT_CODE = 75


def _report_ready() -> None:
    master = os.environ.get("DTPU_MASTER_URL")
    task_id = os.environ.get("DTPU_TASK_ID")
    if not master or not task_id:
        return
    req = urllib.request.Request(
        master.rstrip("/") + f"/api/v1/tasks/{task_id}/ready",
        data=b"{}",
        headers={
            "Authorization": f"Bearer {os.environ.get('DTPU_SESSION_TOKEN', '')}",
            "Content-Type": "application/json",
        },
    )
    try:
        with _tls_urlopen(req, timeout=10) as resp:
            resp.read()
    except Exception:  # noqa: BLE001 - replica still serves; state stays PENDING
        pass


class _ErrorRateInjector:
    """Raise on a fraction of ``serve.generate`` fires: the selfheal
    smoke's way of giving a canary cohort a real error-rate regression."""

    def __init__(self, rate: float) -> None:
        self.rate = rate
        self._rng = random.Random(0xD7B0)  # deterministic across replicas

    def fire(self, site: str, **info: object) -> None:
        if site == "serve.generate" and self._rng.random() < self.rate:
            raise RuntimeError(
                f"injected serve failure (DTPU_SERVE_ERROR_RATE={self.rate})"
            )


def main() -> int:
    cfg = json.loads(os.environ.get("DTPU_TASK_CONFIG", "{}") or "{}")
    # env overrides FIRST: fault-injection knobs must be live before the
    # engine or HTTP layer exists
    for k, v in (cfg.get("env") or {}).items():
        os.environ[str(k)] = str(v)

    model = str(cfg.get("model") or "")
    version = int(cfg.get("version") or 0)

    error_rate = float(os.environ.get("DTPU_SERVE_ERROR_RATE", "0") or 0.0)
    # optional version gate: fleet env applies to every slot the
    # supervisor launches, but a canary-regression drill needs only the
    # NEW version to misbehave (the old cohort is the healthy baseline)
    bad_version = os.environ.get("DTPU_SERVE_ERROR_VERSION", "")
    if bad_version and int(bad_version) != version:
        error_rate = 0.0
    if error_rate > 0.0:
        from determined_tpu.utils import faults

        faults.set_fault_injector(_ErrorRateInjector(error_rate))
        print(f"serve replica: injecting {error_rate:.0%} generate failures",
              flush=True)
    storage = str(cfg.get("storage_path") or "")
    if not storage or not os.path.isdir(storage):
        # fail FAST and nonzero: this is the crash-loop vehicle the
        # supervisor's backoff/degraded detection is tested against
        print(f"serve replica: storage path {storage!r} is not a directory",
              file=sys.stderr, flush=True)
        return 1

    from determined_tpu.api.session import Session
    from determined_tpu.serve import ServeConfig, ServeEngine, ServeWorker
    from determined_tpu.serve.tracing import finish_tracing, start_tracing

    try:
        serve_cfg = ServeConfig.from_dict(
            {
                "host": "127.0.0.1",
                "port": int(os.environ.get("DTPU_TASK_PORT", "0") or 0),
                **(cfg.get("serve") or {}),
            }
        )
    except (TypeError, ValueError) as e:
        print(f"serve replica: bad serve config: {e}", file=sys.stderr, flush=True)
        return 2

    print(f"serve replica: loading {model}@v{version} from {storage}", flush=True)
    # off unless the task's serve config names a trace_dir
    start_tracing(serve_cfg.trace_dir)
    try:
        engine = ServeEngine.from_checkpoint(storage, serve_cfg)
    except Exception as e:  # noqa: BLE001 - any load failure is a crash-loop input
        print(f"serve replica: checkpoint load failed: {e}",
              file=sys.stderr, flush=True)
        return 1

    session = None
    master = os.environ.get("DTPU_MASTER_URL")
    if master:
        session = Session(master, token=os.environ.get("DTPU_SESSION_TOKEN"))
    worker = ServeWorker(
        engine,
        host=serve_cfg.host,
        port=serve_cfg.port,
        session=session,
        model=f"{model}@v{version}" if model else "",
        checkpoint=storage,
        model_name=model,
        model_version=version,
        task_id=os.environ.get("DTPU_TASK_ID", ""),
    )
    try:
        url = worker.start()
    except OSError as e:
        if e.errno != errno.EADDRINUSE:
            raise
        # the master's assigned port is advisory: a restarted master's
        # port allocator starts fresh and can hand out a port a surviving
        # pre-restart replica still holds.  Registration carries the real
        # URL, so rebind on an OS-chosen port instead of crash-looping.
        from determined_tpu.serve import ServeHTTPServer

        print(
            f"serve replica: port {serve_cfg.port} in use; "
            "rebinding on an ephemeral port", flush=True,
        )
        worker.http = ServeHTTPServer(engine, host=serve_cfg.host, port=0)
        url = worker.start()
    print(f"serving on {url}", flush=True)
    _report_ready()

    # signal-flag poll pattern (cli/main.py serve_cmd): the handler only
    # flips a plain attribute; the drain runs on the main thread
    class _Flag:
        set_ = False

    def _on_signal(signum, frame):  # noqa: ARG001 - signal handler shape
        _Flag.set_ = True

    prev = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        prev[sig] = signal.signal(sig, _on_signal)
    try:
        while not _Flag.set_ and not worker.master_drain_requested():
            if engine.failed is not None:
                # the heartbeat already told the master (failed stat ->
                # immediate reap); exit nonzero so the supervisor counts
                # the crash and relaunches with backoff
                print(f"serve replica: engine failed: {engine.failed}",
                      file=sys.stderr, flush=True)
                worker.shutdown(deregister=False)
                return 1
            time.sleep(0.2)
        if worker.master_drain_requested() and not _Flag.set_:
            target = worker.master_drain_info.get("target") or "?"
            print(f"deploy drain requested by master (target {target})", flush=True)
        print("drain requested: rejecting new requests, finishing in-flight",
              flush=True)
        worker.request_drain()
        clean = worker.wait_drained(timeout=serve_cfg.drain_grace_s)
        worker.shutdown()
        print(f"drained ({'clean' if clean else 'grace expired'}); exiting",
              flush=True)
        return DRAIN_EXIT_CODE
    finally:
        finish_tracing(serve_cfg.trace_dir)
        for sig, handler in prev.items():
            signal.signal(sig, handler)


if __name__ == "__main__":
    sys.exit(main())
