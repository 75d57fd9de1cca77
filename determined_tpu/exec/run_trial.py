"""Trial process entrypoint: what the agent execs for each allocation group.

Reference: the container chain ``entrypoint.sh -> exec.prep_container ->
exec.launch -> launch/torch_distributed.py -> exec.harness``
(``master/static/srv/entrypoint.sh``, ``harness/determined/exec/``).  On a
TPU VM there is no container/launcher sandwich: the agent execs THIS module
directly; it applies the experiment's env, joins the jax.distributed
rendezvous when the allocation spans hosts, builds the Trial from the
``package.module:ClassName`` entrypoint, and drives ``Trainer.fit``.

Usage:  python -m determined_tpu.exec.run_trial "pkg.module:TrialClass"
"""

from __future__ import annotations

import importlib
import json
import logging
import os
import sys


def _tls_urlopen(req, timeout: float = 30.0):
    """urlopen trusting DTPU_MASTER_CERT.  Self-contained on purpose:
    importing determined_tpu.exec._tls would pull the package (and jax)
    before ``_apply_environment_early`` has fixed XLA_FLAGS/JAX_PLATFORMS."""
    import ssl
    import urllib.request

    ca = os.environ.get("DTPU_MASTER_CERT")
    ctx = ssl.create_default_context(cafile=ca) if ca else None
    return urllib.request.urlopen(req, timeout=timeout, context=ctx)


def _apply_environment_early() -> None:
    """Env vars from exp config must land BEFORE jax is imported
    (XLA_FLAGS, JAX_PLATFORMS and friends are read at import time).

    Config env OVERRIDES the inherited process env — the experiment's
    declaration is authoritative, same as the reference's task container env
    (``master/pkg/tasks/task.go`` env layering).  On the CPU platform the
    local device count is then forced to this node's slot count, so an
    N-slot allocation sees exactly N "chips" per host — the artificial-slots
    analog (``agent/internal/detect/detect.go:40-57``); without this, a
    multi-process gang's mesh would take its N devices from process 0 only.
    """
    raw = os.environ.get("DTPU_EXP_CONFIG")
    if raw:
        try:
            env = (json.loads(raw).get("environment") or {}).get("env") or {}
        except Exception:
            env = {}
        for k, v in env.items():
            os.environ[str(k)] = str(v)

    slots = os.environ.get("DTPU_NUM_SLOTS")
    if slots and "cpu" in os.environ.get("JAX_PLATFORMS", "").lower():
        flags = os.environ.get("XLA_FLAGS", "")
        kept = [
            f
            for f in flags.split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        kept.append(f"--xla_force_host_platform_device_count={int(slots)}")
        os.environ["XLA_FLAGS"] = " ".join(kept)


def _prepare_context(logger) -> None:
    """Download + unpack the experiment's context directory, then chdir in.

    The analog of the reference's ``prep_container
    --download_context_directory`` (``exec/prep_container.py:28-46``): user
    code submitted with the experiment becomes the working directory of the
    trial process, so the entrypoint import resolves against it.
    """
    ctx_url = os.environ.get("DTPU_CONTEXT_URL")
    master = os.environ.get("DTPU_MASTER_URL")
    if not ctx_url or not master:
        return
    import tempfile
    import time
    import urllib.request

    from determined_tpu.common import extract_context

    url = master.rstrip("/") + ctx_url
    # the context route requires auth; the master injects the allocation's
    # session token into the task env (reference: entrypoint runs authed via
    # DET_SESSION_TOKEN, master/pkg/tasks/task.go env injection)
    headers = {}
    token = os.environ.get("DTPU_SESSION_TOKEN")
    if token:
        headers["Authorization"] = f"Bearer {token}"
    data = None
    for attempt in range(4):
        try:
            req = urllib.request.Request(url, headers=headers)
            with _tls_urlopen(req, timeout=60) as resp:
                data = resp.read()
            break
        except Exception as e:  # noqa: BLE001 - transient master hiccups
            if attempt == 3:
                raise RuntimeError(f"context download failed from {url}: {e}") from e
            logger.warning("context download attempt %d failed (%s); retrying", attempt + 1, e)
            time.sleep(2 * (attempt + 1))
    workdir = tempfile.mkdtemp(
        prefix=f"dtpu-ctx-{os.environ.get('DTPU_ALLOCATION_ID', 'alloc')}-"
    )
    extract_context(data, workdir)
    os.chdir(workdir)
    logger.info("context: unpacked %d bytes into %s", len(data), workdir)


# set by _install_log_shipper; called before the exit self-report so the
# final lines land at the master before the trial record goes terminal
_log_shipper_flush = None


def _install_log_shipper() -> None:
    """Ship this process's stdout/stderr to the master task-log API.

    Agent-launched trials have the agent read their pipe and relay
    (``native/agent/agent.cpp`` ship_logs_and_wait).  External-RM jobs
    (kubernetes/slurm pools, ``native/master/rm.hpp``) have no agent, so
    the trial ships its own output — the analog of the reference's
    ``ship_logs.py`` wrapper running *inside* every task container
    (``master/static/srv/ship_logs.py``).  fd-level dup2 so subprocess and
    native writes are captured, not just Python-level prints.
    """
    master = os.environ.get("DTPU_MASTER_URL")
    trial_id = os.environ.get("DTPU_TRIAL_ID")
    # NTSC tasks on external pools ship with task_id instead of trial_id
    task_id = os.environ.get("DTPU_TASK_ID")
    if not master or not (trial_id or task_id):
        return
    import threading
    import time
    import urllib.request

    token = os.environ.get("DTPU_SESSION_TOKEN", "")
    agent = os.environ.get("DTPU_AGENT_ID", "external")
    url = master.rstrip("/") + "/api/v1/logs"

    read_fd, write_fd = os.pipe()
    os.dup2(write_fd, 1)
    os.dup2(write_fd, 2)
    os.close(write_fd)
    sys.stdout = os.fdopen(1, "w", buffering=1, closefd=False)
    sys.stderr = os.fdopen(2, "w", buffering=1, closefd=False)

    batch: list = []
    batch_lock = threading.Lock()
    # bound memory while the master is unreachable: keep the newest lines
    max_buffered = 10000

    seq = [0]
    pending: list = []  # last unacknowledged batch; resent verbatim
    flush_lock = threading.Lock()  # sender thread vs the exit-path flush
    alloc_id = os.environ.get("DTPU_ALLOCATION_ID", "")

    def post(lines, batch_seq) -> bool:
        # batch_seq (scoped to this allocation server-side) makes the
        # retry loop at-least-once-safe: if the master stored a batch but
        # answered too slowly, the identical re-send carries the same seq
        # and is dropped server-side
        payload = {"agent": agent, "lines": lines,
                   "allocation_id": alloc_id, "batch_seq": batch_seq}
        if trial_id:
            payload["trial_id"] = int(trial_id)
        else:
            payload["task_id"] = task_id
        body = json.dumps(payload).encode()
        req = urllib.request.Request(
            url,
            data=body,
            headers={
                "Authorization": f"Bearer {token}",
                "Content-Type": "application/json",
            },
        )
        try:
            with _tls_urlopen(req, timeout=10) as resp:
                resp.read()
            return True
        except Exception:  # noqa: BLE001 - retried by the next flush
            return False

    def flush() -> None:
        # a failed batch is retried as-is (same lines, same seq) before any
        # new lines ship, so the server-side dedup stays exact.  flush_lock
        # serializes the sender thread against the exit-path flush — two
        # concurrent flushes could otherwise post different batches under
        # one seq (one of them silently dropped as a duplicate).
        with flush_lock:
            if pending:
                # HTTP inside flush_lock is the design, not an accident:
                # the lock exists precisely to keep at most ONE batch in
                # flight per seq (sender thread vs exit-path flush), and
                # only those two slow-path threads ever contend — the
                # training process writes to the pipe, never to this lock.
                # dtpu: lint-ok[blocking-under-lock]
                if not post(pending, seq[0]):
                    return  # master still unreachable; new lines wait
                pending.clear()
                seq[0] += 1
            with batch_lock:
                lines, batch[:] = batch[:], []
            if lines:
                # same argument as the pending re-send above
                # dtpu: lint-ok[blocking-under-lock]
                if post(lines, seq[0]):
                    seq[0] += 1
                else:
                    pending[:] = lines[-max_buffered:]

    def pump() -> None:
        # reader only: never blocks on the network, so a master outage
        # cannot back-pressure the pipe and stall the training process's
        # writes to fd 1/2 (the sender thread does the HTTP)
        partial = b""
        while True:
            try:
                chunk = os.read(read_fd, 8192)
            except OSError:
                break
            if not chunk:
                break
            partial += chunk
            while b"\n" in partial:
                line, partial = partial.split(b"\n", 1)
                with batch_lock:
                    batch.append(line.decode("utf-8", "replace"))
                    if len(batch) > max_buffered:
                        del batch[: len(batch) - max_buffered]

    def sender() -> None:
        while True:
            time.sleep(0.5)
            flush()

    threading.Thread(target=pump, daemon=True, name="dtpu-log-pump").start()
    threading.Thread(target=sender, daemon=True, name="dtpu-log-shipper").start()
    global _log_shipper_flush
    _log_shipper_flush = flush


def _warm_start_extended_length(max_length, logger):
    """PBT exploit clones: the master seeds the trial with its parent's
    checkpoint and advertises the inherited step count
    (``DTPU_WARM_START_STEPS``); same horizon rule as the local driver
    (``config.experiment.clone_extended_length``)."""
    from determined_tpu.config.experiment import clone_extended_length

    warm = int(os.environ.get("DTPU_WARM_START_STEPS", "0") or 0)
    return clone_extended_length(max_length, warm, logger, context="warm-start ")


def _self_report_exit(code: int) -> None:
    """POST this process's exit to the trials API.

    Agent-launched trials get their exit reported by the agent's waitpid
    loop; external-RM jobs report their own (the master's job-status poll
    is only the crash safety net — ``rm.hpp`` poll_external_jobs).
    """
    master = os.environ.get("DTPU_MASTER_URL")
    trial_id = os.environ.get("DTPU_TRIAL_ID")
    task_id = os.environ.get("DTPU_TASK_ID")
    if not master or not (trial_id or task_id):
        return
    import time
    import urllib.request

    if _log_shipper_flush is not None:
        time.sleep(0.6)  # let the pump drain fds 1/2
        _log_shipper_flush()
    body = json.dumps(
        {"exit_code": code, "allocation_id": os.environ.get("DTPU_ALLOCATION_ID", "")}
    ).encode()
    path = (
        f"/api/v1/trials/{trial_id}/exit" if trial_id else f"/api/v1/tasks/{task_id}/exit"
    )
    req = urllib.request.Request(
        master.rstrip("/") + path,
        data=body,
        headers={
            "Authorization": f"Bearer {os.environ.get('DTPU_SESSION_TOKEN', '')}",
            "Content-Type": "application/json",
        },
    )
    try:
        with _tls_urlopen(req, timeout=10) as resp:
            resp.read()
    except Exception:  # noqa: BLE001 - master poll catches silent deaths
        pass


class TrialSupervisor:
    """Supervised trial execution: one attempt = one fresh ``Trainer``
    driven through ``fit``; failures are classified (``utils/errors.py``)
    and TRANSIENT ones re-enter ``fit(latest_checkpoint=...)`` from the
    newest FINALIZED checkpoint with exponential backoff, up to the
    experiment's ``max_restarts``.

    This is the harness-side analog of the reference master's allocation
    restart policy (``master/internal/trial.go``): on a TPU VM the agent
    execs the trial directly, so the retry loop that the master's
    allocation services provide for container jobs runs in-process here.
    Restart counts ship through the metrics context (group ``restarts``)
    so the master/UI can surface them against the trial record.

    Imports of the training stack are deferred: this class must be
    constructible before ``_apply_environment_early`` has run (jax reads
    XLA_FLAGS/JAX_PLATFORMS at import time).
    """

    def __init__(
        self,
        trainer_factory,
        *,
        policy=None,
        metrics=None,
        master_unreachable=None,
        sleep=None,
    ) -> None:
        self._trainer_factory = trainer_factory
        self._policy = policy
        self._metrics = metrics
        self._master_unreachable = master_unreachable
        self._sleep = sleep
        self._trainer = None
        self.restarts = 0

    def run(self, max_length, *, latest_checkpoint=None, **fit_kwargs):
        import time

        from determined_tpu.train._restart import RestartPolicy, run_with_restarts

        policy = self._policy or RestartPolicy()
        logger = logging.getLogger("determined_tpu.exec.supervisor")

        def attempt(latest):
            self._trainer = self._trainer_factory()
            return self._trainer.fit(
                max_length, latest_checkpoint=latest, **fit_kwargs
            )

        def get_latest_checkpoint():
            return self._trainer.latest_checkpoint if self._trainer is not None else None

        def on_failure(att) -> None:
            self.restarts = att.restarts
            unreachable = bool(self._master_unreachable and self._master_unreachable())
            if unreachable:
                logger.warning(
                    "master unreachable (heartbeat streak latched) while handling "
                    "trial failure; restart decisions proceed locally"
                )
            if self._metrics is not None:
                steps = self._trainer.steps_completed if self._trainer is not None else 0
                try:
                    self._metrics.report(
                        "restarts",
                        steps,
                        {
                            "restarts": att.restarts,
                            "failure_kind": att.kind.value,
                            "error": repr(att.exc),
                            "resume_checkpoint": att.latest_checkpoint,
                            "backoff_seconds": att.delay,
                            "master_unreachable": unreachable,
                        },
                    )
                except Exception:  # noqa: BLE001 - reporting must not mask the failure
                    logger.exception("failed to report restart metrics")

        return run_with_restarts(
            attempt,
            policy=policy,
            initial_checkpoint=latest_checkpoint,
            get_latest_checkpoint=get_latest_checkpoint,
            on_failure=on_failure,
            sleep=self._sleep or time.sleep,
        )


class _RankPrefixStream:
    """Line-wise rank prefixer over a text stream — the analog of the
    reference's per-rank log wrapper (``launch/wrap_rank.py``), so
    interleaved multi-process logs stay attributable after the agent ships
    them.  Wraps Python-level stdout/stderr (tracebacks, logging, print);
    native fd writes bypass it, which is acceptable for log dedup."""

    def __init__(self, stream, prefix: str) -> None:
        self._stream = stream
        self._prefix = prefix
        self._at_line_start = True

    def write(self, text: str) -> int:
        out = []
        for chunk in text.splitlines(keepends=True):
            if self._at_line_start:
                out.append(self._prefix)
            out.append(chunk)
            self._at_line_start = chunk.endswith("\n")
        self._stream.write("".join(out))
        return len(text)

    def flush(self) -> None:
        self._stream.flush()

    def __getattr__(self, name):
        return getattr(self._stream, name)


def main() -> int:
    # external-RM jobs ship their own logs; fd redirect must precede any
    # output (and the rank prefixer, which wraps whatever stdout is)
    if os.environ.get("DTPU_SHIP_LOGS"):
        _install_log_shipper()
    # per-rank prefix BEFORE logging configures its handlers
    rdzv_early = os.environ.get("DTPU_RENDEZVOUS")
    if rdzv_early:
        try:
            info_early = json.loads(rdzv_early)
            if int(info_early.get("num_nodes", 1)) > 1:
                prefix = f"[rank={int(info_early.get('node_rank', 0))}] "
                sys.stdout = _RankPrefixStream(sys.stdout, prefix)
                sys.stderr = _RankPrefixStream(sys.stderr, prefix)
        except Exception:  # noqa: BLE001 - malformed rendezvous fails later
            pass
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s [%(levelname)s] %(name)s: %(message)s"
    )
    logger = logging.getLogger("determined_tpu.exec")
    if os.environ.get("DTPU_TASK_TYPE"):
        # NTSC task placed on an external-RM pool: the pod runs the same
        # container entry as trials (the reference wraps every task type
        # through entrypoint.sh too); dispatch to the task module instead
        # of the trial machinery
        task_mod = importlib.import_module(os.environ["DTPU_TASK_MODULE"])
        return int(task_mod.main() or 0)
    if len(sys.argv) < 2 or ":" not in sys.argv[1]:
        print("usage: python -m determined_tpu.exec.run_trial pkg.module:TrialClass")
        return 2

    _apply_environment_early()

    import jax

    # join the multi-host rendezvous before touching devices.  The wait is
    # timed here (the tracer is not configured yet — that needs the parsed
    # exp config) and recorded as a rendezvous.wait span once the tracer
    # is up, so `dtpu experiment profile` attributes multi-host setup time
    # instead of lumping it into "other".
    rendezvous_window = None
    info = None
    rdzv = os.environ.get("DTPU_RENDEZVOUS")
    if rdzv:
        info = json.loads(rdzv)
        if int(info.get("num_nodes", 1)) > 1:
            import time as _time

            # XLA:CPU has no cross-process collectives by default
            # ("Multiprocess computations aren't implemented on the CPU
            # backend") — the gloo implementation shipped with jaxlib is
            # what makes devcluster CPU gangs real SPMD programs.  Must be
            # set before the backend client exists.  Applied whenever cpu
            # MAY be the backend: an explicit cpu in JAX_PLATFORMS, or the
            # env var unset (the default resolution picks cpu on CPU-only
            # hosts, and probing jax.default_backend() here would create
            # the client before the flag takes effect).  The flag only
            # configures the CPU client, so TPU/GPU gangs are unaffected.
            platforms = os.environ.get("JAX_PLATFORMS", "")
            if not platforms or "cpu" in platforms.split(","):
                jax.config.update("jax_cpu_collectives_implementation", "gloo")

            logger.info(
                "rendezvous: joining as rank %s/%s via coordinator %s",
                info["node_rank"], info["num_nodes"], info["coordinator"],
            )
            rdzv_t0 = _time.monotonic()
            jax.distributed.initialize(
                coordinator_address=info["coordinator"],
                num_processes=int(info["num_nodes"]),
                process_id=int(info["node_rank"]),
            )
            rendezvous_window = (rdzv_t0, _time.monotonic())
            logger.info(
                "rendezvous: joined in %.1fs (%d global devices)",
                rendezvous_window[1] - rendezvous_window[0],
                jax.device_count(),
            )

    from determined_tpu import core, train
    from determined_tpu.config.experiment import ExperimentConfig
    from determined_tpu.core._cluster_info import get_cluster_info

    cluster = get_cluster_info()
    if cluster is None:
        print("run_trial requires DTPU_* env (set by the agent)")
        return 2

    exp_config = ExperimentConfig.parse(cluster.exp_config or {})

    # Elastic reshard: the master stamps every launch with the number of
    # topology slices the placed gang actually spans.  num_slices is never
    # a wildcard axis, so the dcn axis is re-shaped here before any mesh is
    # built; the wildcard data/fsdp axis then absorbs the placed device
    # count (DTPU_ELASTIC_SLOTS wide) on its own.
    n_slices_env = os.environ.get("DTPU_NUM_SLICES")
    if n_slices_env and exp_config.resources.elastic is not None:
        import dataclasses as _dc

        mesh = exp_config.resources.mesh
        if mesh.num_slices != int(n_slices_env):
            logger.info(
                "elastic: mesh num_slices %d -> %s for this allocation "
                "(placed width %s slots)",
                mesh.num_slices, n_slices_env,
                os.environ.get("DTPU_ELASTIC_SLOTS", "?"),
            )
            exp_config = _dc.replace(
                exp_config,
                resources=_dc.replace(
                    exp_config.resources,
                    mesh=_dc.replace(mesh, num_slices=int(n_slices_env)),
                ),
            )

    # persistent XLA compilation cache: a supervised restart (or a relaunch
    # after a crash) re-jits from disk instead of paying the full compile
    from determined_tpu.utils.compilation_cache import setup_compilation_cache

    setup_compilation_cache(exp_config.optimizations.compilation_cache_dir)

    module_name, _, class_name = sys.argv[1].partition(":")
    _prepare_context(logger)
    sys.path.insert(0, os.getcwd())
    trial_cls = getattr(importlib.import_module(module_name), class_name)

    # preflight (determined_tpu/lint): vet the trial's source before any
    # Trainer is built — the allocation is already placed by this point,
    # but a strict-mode reject still saves the whole training run (and the
    # master's restart budget) from a host-syncing or retrace-prone trial
    lint_cfg = exp_config.lint
    if lint_cfg.retrace_sentinel:
        from determined_tpu.lint import get_retrace_sentinel

        get_retrace_sentinel().enable()
    # collective-sequence sentinel: the env is the launch-layer override in
    # BOTH directions — "1" turns it on for a whole gang without touching
    # the experiment config (devcluster harness), "0" turns it off even
    # when the config enables it; unset/empty defers to the config knob
    cseq_env = os.environ.get("DTPU_COLLECTIVE_SENTINEL")
    cseq_on = (
        lint_cfg.collective_sentinel
        if cseq_env in (None, "")
        else cseq_env != "0"
    )
    if cseq_on:
        # must be installed BEFORE core.init() builds the
        # DistributedContext so every collective this rank ever issues is
        # digested
        from determined_tpu.lint import get_collective_sentinel

        get_collective_sentinel().install()
    if lint_cfg.preflight:
        from determined_tpu import lint as lint_mod

        diags = lint_mod.check_trial(trial_cls, disabled=lint_cfg.suppress or None)
        for d in diags:
            logger.warning("preflight: %s", d.format())
        if lint_cfg.strict and diags:
            logger.error(
                "preflight rejected %s (lint.strict): %d finding(s)",
                trial_cls.__qualname__,
                len(diags),
            )
            return 3

    # experiment-wide tracing (determined_tpu/observability): spans record
    # from every harness thread; export (opt-in) writes Chrome trace JSON
    # the `dtpu experiment profile` ledger reads
    from determined_tpu.observability import get_tracer

    obs = exp_config.observability
    tracer = get_tracer()
    tracer.configure(
        enabled=obs.enabled,
        ring_capacity=obs.ring_capacity,
        flush_interval=obs.flush_interval_s,
        max_events=obs.max_events,
        out_dir=(
            os.path.join(os.getcwd(), "traces", f"trial_{cluster.trial_id or 0}")
            if obs.enabled and obs.trace_export
            else None
        ),
    )
    if obs.enabled:
        tracer.start()
        if rendezvous_window is not None:
            # recorded against monotonic endpoints captured above, so the
            # ledger sees the real wait even though the tracer came up later
            tracer.record_span(
                "rendezvous.wait",
                "rendezvous",
                rendezvous_window[0],
                rendezvous_window[1],
                {
                    "coordinator": (info or {}).get("coordinator"),
                    "num_nodes": (info or {}).get("num_nodes"),
                    "node_rank": (info or {}).get("node_rank"),
                },
            )

    core_ctx = core.init()
    try:
        # expconf-driven profiling (reference exec/harness.py:211): system
        # sampler + optional xplane trace into shared checkpoint storage;
        # inside the try so a trace-setup failure still closes the context
        prof = exp_config.profiling or {}
        if prof.get("enabled"):
            core_ctx.profiler.on(sampling=True, trace=bool(prof.get("trace", False)))

        def make_trainer():
            # one fresh Trainer per attempt: params/opt state re-init and
            # are immediately overwritten by the checkpoint restore; loop
            # and loader state never leak across a crashed attempt
            ctx = train.init(
                hparams=cluster.hparams,
                exp_config=exp_config,
                core_context=core_ctx,
                seed=cluster.trial_seed,
            )
            return train.Trainer(trial_cls(ctx))

        scfg = exp_config.searcher
        max_length = scfg.max_length or exp_config.min_validation_period
        if max_length is None:
            from determined_tpu.config.experiment import Length

            max_length = Length.batches(scfg.max_time or 100)
        max_length = _warm_start_extended_length(max_length, logger)
        from determined_tpu.train._restart import RestartPolicy

        supervisor = TrialSupervisor(
            make_trainer,
            policy=RestartPolicy.from_exp_config(exp_config),
            metrics=core_ctx.metrics,
            master_unreachable=lambda: core_ctx.master_unreachable,
        )

        def run_supervised():
            # trial.run is the goodput ledger's attribution unit; the
            # supervisor's restart backoffs and each attempt's setup/
            # restore/step spans all nest inside it
            with tracer.span("trial.run", cat="trial", trial=cluster.trial_id):
                return supervisor.run(
                    max_length,
                    validation_period=exp_config.min_validation_period,
                    checkpoint_period=exp_config.min_checkpoint_period,
                    latest_checkpoint=cluster.latest_checkpoint,
                    checkpoint_policy=exp_config.checkpoint_policy,
                )

        if lint_cfg.thread_sentinel:
            # warn-mode leak check over the whole supervised run: every
            # harness worker (prefetch, checkpoint writer, restart
            # attempts' loaders) must be gone when fit returns — leaked
            # workers across supervised restarts compound
            from determined_tpu.lint import ThreadLeakChecker

            with ThreadLeakChecker(
                watch=("dtpu-*",),
                raise_on_leak=False,
                scope=f"trial {cluster.trial_id}",
            ):
                summary = run_supervised()
        else:
            summary = run_supervised()
        logger.info(
            "trial finished: %s (restarts=%d)", summary, summary.get("restarts", 0)
        )
        # each supervised restart builds a fresh Trainer; its _setup hits the
        # in-process jit-reuse cache (train/_jit_cache.py), so hits here mean
        # restarts re-entered fit without re-tracing the step — the log line
        # tells operators which tier (step cache vs persistent XLA cache vs
        # full compile) the attempts actually paid
        logger.info("jit-reuse cache: %s", train.step_cache_stats())
        return 0
    finally:
        core_ctx.close()
        tracer.stop()
        if obs.enabled and obs.trace_export:
            try:
                tracer.export_chrome_trace(
                    os.path.join(
                        os.getcwd(), "traces", f"trial_{cluster.trial_id or 0}",
                        "trace.json",
                    )
                )
            except Exception:  # noqa: BLE001 - export must not mask the run
                logger.exception("trace export failed")


if __name__ == "__main__":
    try:
        _code = main()
    except SystemExit as e:
        # preserve sys.exit semantics: None = success, str = failure with
        # the message on stderr (the log shipper is watching fd 2)
        if e.code is None or isinstance(e.code, int):
            _code = e.code or 0
        else:
            print(e.code, file=sys.stderr)
            _code = 1
    except BaseException:  # noqa: BLE001 - report the crash, then re-raise path
        import traceback

        traceback.print_exc()
        _code = 1
    if os.environ.get("DTPU_SELF_REPORT_EXIT"):
        _self_report_exit(_code)
    sys.exit(_code)
