"""dtpu CLI: the ``det`` command-line equivalent.

Reference: ``harness/determined/cli/`` (declarative argparse per noun:
experiment/trial/agent/checkpoint/master/user).  Built on the Python SDK
(``determined_tpu.client``) the way the reference CLI sits on
``experimental/client.py``; authentication follows the reference contract
(token cache in ``~/.dtpu/auth.json``, auto-login as the ``determined``
user when no credentials are given; ``common/api/authentication.py``).
``run-local`` drives the in-process LocalExperiment runner for masterless
single-host searches.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional


def _client(args):
    from determined_tpu.client import Determined

    url = args.master or os.environ.get("DTPU_MASTER", "http://127.0.0.1:8080")
    # --cert rides the env so every Session (SDK, bindings, core) picks it
    # up without threading it through each constructor
    if getattr(args, "cert", None):
        os.environ["DTPU_MASTER_CERT"] = args.cert
    return Determined(url, user=getattr(args, "user", None) or None)


def _log_to_stderr() -> None:
    """The commands that run the harness in THIS process (masterless
    training, a serving replica) show its log the way a cluster task's log
    does (``exec/run_trial.py``): device, cache, compile, step and
    checkpoint lines.  stdout stays the command's own output."""
    import logging

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s [%(levelname)s] %(name)s: %(message)s",
    )


def _print_json(obj: Any) -> None:
    print(json.dumps(obj, indent=2, sort_keys=True, default=str))


def _table(rows: List[Dict[str, Any]], cols: List[str]) -> None:
    if not rows:
        print("(none)")
        return
    widths = {c: max(len(c), *(len(str(r.get(c, ""))) for r in rows)) for c in cols}
    print("  ".join(c.upper().ljust(widths[c]) for c in cols))
    for r in rows:
        print("  ".join(str(r.get(c, "")).ljust(widths[c]) for c in cols))


# ---- auth ------------------------------------------------------------------


def do_login(args) -> int:
    from determined_tpu import client

    url = args.master or os.environ.get("DTPU_MASTER", "http://127.0.0.1:8080")
    username = args.user or "determined"
    password = args.password
    if password is None:
        if sys.stdin.isatty():
            import getpass

            password = getpass.getpass(f"password for {username}: ")
        else:
            password = ""
    d = client.login(url, user=username, password=password)
    who = d.whoami()
    print(f"logged in as {who['username']} (admin={who['admin']}) at {url}")
    return 0


def do_whoami(args) -> int:
    _print_json(_client(args).whoami())
    return 0


def user_create(args) -> int:
    if args.admin and args.role in ("user", "viewer"):
        print(f"error: --admin contradicts --role {args.role}", file=sys.stderr)
        return 1
    _client(args).create_user(
        args.username, args.password or "", args.admin, role=args.role
    )
    print(f"created user {args.username}")
    return 0


def user_list(args) -> int:
    rows = _client(args).session.get("/api/v1/users").json()
    _table(rows, ["username", "role", "admin"])
    return 0


# ---- experiment ------------------------------------------------------------


def exp_create(args) -> int:
    d = _client(args)
    context_bytes = None
    if getattr(args, "context_dir", None):
        from determined_tpu.common import build_context

        context_bytes = build_context(args.context_dir)
        print(f"context: {args.context_dir} ({len(context_bytes)} bytes packed)")
    exp = d.create_experiment(
        args.config,
        context_dir=args.context_dir,
        context_bytes=context_bytes,
        template=getattr(args, "template", None),
    )
    print(f"Created experiment {exp.id}")
    if args.follow:
        return exp_wait(args, exp.id)
    return 0


def exp_wait(args, exp_id: int) -> int:
    exp = _client(args).get_experiment(exp_id)
    last_state = None
    while True:
        exp.reload()
        if exp.state != last_state:
            print(f"state: {exp.state} (progress {exp.progress:.0%})")
            last_state = exp.state
        if exp.state in ("COMPLETED", "CANCELED", "ERROR"):
            return 0 if exp.state == "COMPLETED" else 1
        time.sleep(2)


def exp_list(args) -> int:
    _table(
        [
            {
                "id": e.id,
                "name": e.get("name", ""),
                "workspace": e.get("workspace", ""),
                "state": e.state,
                "progress": f"{e.progress:.0%}",
                "trials": len(e.get("trials", [])),
            }
            for e in _client(args).list_experiments(
                workspace=getattr(args, "workspace", None),
                project=getattr(args, "project", None),
            )
        ],
        ["id", "name", "workspace", "state", "progress", "trials"],
    )
    return 0


def exp_describe(args) -> int:
    _print_json(_client(args).get_experiment(args.id).to_dict())
    return 0


def exp_fork(args) -> int:
    import yaml

    overrides = None
    if args.config_overrides:
        with open(args.config_overrides) as f:
            overrides = yaml.safe_load(f)
        if not isinstance(overrides, dict):
            print(
                f"error: {args.config_overrides} must contain a yaml mapping",
                file=sys.stderr,
            )
            return 1
    exp = _client(args).get_experiment(args.id)
    new = exp.continue_(overrides) if args.verb == "continue" else exp.fork(overrides)
    past = "continued" if args.verb == "continue" else "forked"
    print(f"{past} experiment {args.id} -> {new.id}")
    if args.follow:
        return exp_wait(args, new.id)
    return 0


def exp_delete(args) -> int:
    _client(args).get_experiment(args.id).delete()
    print(f"deleted experiment {args.id}")
    return 0


def exp_signal(args) -> int:
    exp = _client(args).get_experiment(args.id)
    exp = getattr(exp, args.verb)()
    print(f"experiment {args.id}: {exp.state}")
    return 0


# ---- searcher simulation (trial-free; no master required) -------------------


def searcher_simulate(args) -> int:
    """Replay search methods against a seeded learning-curve model and
    print a best-metric-vs-budget table — method choice and bracket/
    population math in milliseconds, no device time (docs/searchers.md)."""
    import yaml

    from determined_tpu import searcher as searcher_mod
    from determined_tpu.config.experiment import (
        ExperimentConfig,
        InvalidExperimentConfig,
    )

    if args.config:
        with open(args.config) as f:
            cfg = ExperimentConfig.parse(yaml.safe_load(f))
    else:
        # built-in lr-search space, matched to the synthetic curve model
        cfg = ExperimentConfig.parse(
            {
                "name": "searcher-simulate",
                "hyperparameters": {
                    "lr": {"type": "log", "minval": -4, "maxval": -1}
                },
                "searcher": {
                    "name": "random",
                    "metric": "validation_loss",
                    "max_trials": 16,
                    "max_time": 64,
                    "num_rungs": 3,
                    "divisor": 4,
                },
            }
        )
    methods = [m.strip() for m in args.methods.split(",") if m.strip()]
    from determined_tpu.experiment import ExperimentJournalError

    try:
        if args.journal:
            path = args.journal
            if os.path.isdir(path):
                from determined_tpu.experiment import journal_path

                path = journal_path(path)
            model = searcher_mod.JournalCurveModel.from_journal(
                path, cfg.searcher.metric, cfg.searcher.time_metric or "batches"
            )
        else:
            model = searcher_mod.SyntheticCurveModel(args.seed)
        reports = searcher_mod.compare_methods(
            cfg, methods, model, seed=args.seed, report_period=args.period
        )
    except (InvalidExperimentConfig, ExperimentJournalError, ValueError) as e:
        # covers unknown methods AND a missing/empty --journal
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.json:
        _print_json(
            [
                {
                    "method": r.method,
                    "seed": r.seed,
                    "trials_created": r.trials_created,
                    "total_units": r.total_units,
                    "best_metric": r.best_metric,
                    "best_trial": r.best_trial,
                    "best_hparams": r.best_hparams,
                    "curve": r.curve[-32:],
                    "lineage": {
                        str(k): v for k, v in r.lineage.items() if v is not None
                    },
                }
                for r in reports
            ]
        )
        return 0
    print(searcher_mod.format_comparison(reports))
    return 0


# ---- local experiment recovery (journal-backed; no master required) ---------


def exp_status_local(args) -> int:
    """Digest a LocalExperiment's journal: what completed, what's in
    flight, whether the directory is resumable (docs/fault-tolerance.md,
    "Experiment recovery & preemption")."""
    from determined_tpu.experiment import ExperimentJournalError, experiment_status

    try:
        st = experiment_status(args.checkpoint_dir)
    except ExperimentJournalError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.json:
        _print_json(st)
        return 0
    print(f"experiment:  {st['name'] or '(unnamed)'}")
    print(f"status:      {st['status']}" + ("  (resumable)" if st["resumable"] else ""))
    print(f"entrypoint:  {st['entrypoint'] or '(unknown)'}")
    if st.get("cluster"):
        # a resumed operator needs the master this search is attached to
        print(
            f"cluster:     experiment {st['cluster']['experiment_id']} "
            f"at {st['cluster']['master_url']}"
        )
    print(
        f"trials:      {st['trials_completed']} completed, "
        f"{st['trials_in_flight']} in flight, {st['trials_created']} created"
    )
    _table(
        [
            {
                "trial": t["request_id"],
                "state": t["state"],
                "steps": t["steps_completed"] if t["steps_completed"] is not None else "",
                "checkpoint": t["checkpoint"] or "",
            }
            for t in st["trials"]
        ],
        ["trial", "state", "steps", "checkpoint"],
    )
    return 0


def exp_profile_local(args) -> int:
    """Goodput ledger for a LOCAL experiment directory: where every second
    of wall-clock went (docs/observability.md).  Reads the Chrome trace
    events exported under ``<dir>/traces/`` (``observability.trace_export:
    true``); ``--xplane`` additionally summarizes a sampled jax.profiler
    window so the host timeline can be checked against device truth."""
    from determined_tpu.observability import (
        compute_ledger,
        format_ledger_text,
        load_trace_events,
    )

    traces_dir = os.path.join(args.checkpoint_dir, "traces")
    events = load_trace_events(traces_dir)
    if not events:
        print(
            f"error: no trace events under {traces_dir} (run the experiment "
            "with observability.trace_export: true)",
            file=sys.stderr,
        )
        return 2
    ledger = compute_ledger(events)

    # optional device-side cross-check: a jax.profiler xplane window
    # (profiling.trace) parsed into an op table via utils/xplane.py
    xplane_summary = None
    xplane_dir = args.xplane or os.path.join(traces_dir, "xplane")
    if args.xplane and not os.path.isdir(args.xplane):
        # an explicit request that cannot be honored must not be silent
        # (the default-path probe, by contrast, is best-effort)
        print(f"warning: --xplane {args.xplane} is not a directory", file=sys.stderr)
    if os.path.isdir(xplane_dir):
        try:
            from determined_tpu.utils import xplane as xplane_mod

            ops = xplane_mod.hlo_op_table(xplane_dir)
            coll, other = xplane_mod.split_collectives(ops)
            xplane_summary = {
                "top_ops": ops[:10],
                "category_totals": xplane_mod.category_totals(ops),
                "collective_us": coll,
                "compute_us": other,
            }
        except Exception as e:  # noqa: BLE001 - best effort
            xplane_summary = {"error": str(e)}

    if args.json:
        out = {"ledger": ledger}
        if xplane_summary is not None:
            out["xplane"] = xplane_summary
        _print_json(out)
        return 0
    print(format_ledger_text(ledger))
    if xplane_summary and "category_totals" in xplane_summary:
        print("\nxplane device-time categories (us):")
        for cat, us in list(xplane_summary["category_totals"].items())[:8]:
            print(f"  {cat:<24} {us:>12.1f}")
    return 0


def exp_resume_local(args) -> int:
    """Resume a crashed/preempted driver experiment from its journal.

    The journal records the experiment config and trial entrypoint, so the
    directory alone is enough; ``--entrypoint`` overrides (e.g. after a
    module rename).  A journal with a ``cluster_attached`` record resumes
    as a ClusterExperiment — the driver re-attaches to its master
    experiment (``-m`` overrides the journaled master url).  Exits 75
    (EX_TEMPFAIL) if the resumed run is itself preempted — still
    resumable.
    """
    from determined_tpu.config.experiment import ExperimentConfig
    from determined_tpu.experiment import (
        PREEMPTED_EXIT_CODE,
        ClusterExperiment,
        ExperimentJournalError,
        LocalExperiment,
        journal_path,
        read_journal,
    )

    try:
        replay = read_journal(journal_path(args.checkpoint_dir))
    except ExperimentJournalError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if replay.status == "completed":
        print("experiment already completed; nothing to resume")
        return 0
    started = replay.started or {}
    entrypoint = args.entrypoint or started.get("entrypoint")
    if not entrypoint:
        print(
            "error: journal records no trial entrypoint; pass --entrypoint "
            "pkg.module:TrialClass",
            file=sys.stderr,
        )
        return 2
    if not started.get("config"):
        print("error: journal records no experiment config", file=sys.stderr)
        return 2
    cfg = ExperimentConfig.parse(started["config"])
    try:
        if replay.cluster is not None:
            # cluster-driven search: re-attach to the journaled master
            ns = argparse.Namespace(
                master=args.master or replay.cluster.get("master_url"),
                user=getattr(args, "user", None),
                cert=getattr(args, "cert", None),
            )
            exp = ClusterExperiment(
                cfg,
                entrypoint,
                session=_client(ns).session,
                checkpoint_dir=args.checkpoint_dir,
                seed=started.get("seed"),
            )
            summary = exp.resume()
        else:
            module_name, _, class_name = entrypoint.partition(":")
            sys.path.insert(0, os.getcwd())
            trial_cls = getattr(importlib.import_module(module_name), class_name)
            lexp = LocalExperiment(
                cfg,
                trial_cls,
                checkpoint_dir=args.checkpoint_dir,
                seed=started.get("seed"),
            )
            summary = lexp.resume(serial=args.serial)
    except ExperimentJournalError as e:
        # e.g. the original driver is still alive and owns the journal
        print(f"error: {e}", file=sys.stderr)
        return 2
    _print_json(summary)
    return PREEMPTED_EXIT_CODE if summary.get("status") == "preempted" else 0


# ---- trial -----------------------------------------------------------------


def trial_describe(args) -> int:
    _print_json(_client(args).get_trial(args.id).to_dict())
    return 0


def trial_logs(args) -> int:
    for line in _client(args).get_trial(args.id).logs(follow=args.follow):
        print(line)
    return 0


def trial_metrics(args) -> int:
    _print_json(list(_client(args).get_trial(args.id).iter_metrics(group=args.group)))
    return 0


# ---- agents / checkpoints / models / master --------------------------------


def agent_list(args) -> int:
    _table(_client(args).list_agents(), ["id", "host", "slots", "used_slots"])
    return 0


def pool_list(args) -> int:
    _table(
        _client(args).list_resource_pools(),
        ["name", "type", "agents", "slots", "used_slots", "provisioned"],
    )
    return 0


def checkpoint_list(args) -> int:
    _table(
        [
            {
                "uuid": c.uuid,
                "trial_id": c.trial_id,
                "steps": c.metadata.get("steps_completed"),
            }
            for c in _client(args).list_checkpoints()
        ],
        ["uuid", "trial_id", "steps"],
    )
    return 0


def checkpoint_download(args) -> int:
    path = _client(args).get_checkpoint(args.uuid).download(args.output)
    print(path)
    return 0


def model_create(args) -> int:
    m = _client(args).create_model(args.name, description=args.description or "")
    print(f"created model {m.name}")
    return 0


def model_list(args) -> int:
    rows = []
    for m in _client(args).get_models():
        versions = m.get("versions") or []
        latest = max((int(v.get("version") or 0) for v in versions), default=0)
        rows.append(
            {
                "name": m.name,
                "versions": len(versions),
                "latest": f"v{latest}" if latest else "-",
            }
        )
    _table(rows, ["name", "versions", "latest"])
    return 0


def model_show(args) -> int:
    model = _client(args).get_model(args.name).to_dict()
    if args.json:
        _print_json(model)
        return 0
    print(f"model {model['name']}")
    if model.get("labels"):
        print(f"  labels: {', '.join(model['labels'])}")
    for v in model.get("versions") or []:
        lineage = []
        if v.get("source_trial_id"):
            lineage.append(f"trial {v['source_trial_id']}")
        if v.get("source_experiment_id"):
            lineage.append(f"experiment {v['source_experiment_id']}")
        print(
            f"  v{v['version']}: checkpoint {v.get('checkpoint_uuid')}"
            + (f" ({', '.join(lineage)})" if lineage else "")
        )
        if v.get("storage_path"):
            print(f"      path: {v['storage_path']}")
        if v.get("metrics"):
            print(f"      metrics: {json.dumps(v['metrics'], sort_keys=True)}")
    return 0


def model_register(args) -> int:
    from determined_tpu.experiment import registry as registry_mod

    metrics = {}
    for kv in args.metric or []:
        key, _, val = kv.partition("=")
        try:
            metrics[key] = float(val)
        except ValueError:
            metrics[key] = val
    v = registry_mod.register_version(
        _client(args).session,
        args.name,
        checkpoint_uuid=args.checkpoint_uuid,
        storage_path=args.storage_path,
        source_trial_id=args.trial_id,
        source_experiment_id=args.experiment_id,
        metrics=metrics or None,
        labels=args.label or None,
        version=args.version,
    )
    print(f"registered {args.name}@v{v['version']} "
          f"(checkpoint {v['checkpoint_uuid']})")
    return 0


def model_promote(args) -> int:
    from determined_tpu.experiment import registry as registry_mod

    session = _client(args).session
    registry_mod.ensure_model(session, args.name)
    v = session.post(
        f"/api/v1/models/{args.name}/promote", json={"trial_id": args.trial_id}
    ).json()
    print(f"promoted trial {args.trial_id} -> {args.name}@v{v['version']} "
          f"(checkpoint {v['checkpoint_uuid']})")
    return 0


def model_pull(args) -> int:
    """Materialize a registry version's checkpoint locally: copy from its
    shared-storage path when this host can see it, else download through
    the master's checkpoint route."""
    import shutil as _shutil

    from determined_tpu.experiment import registry as registry_mod

    client = _client(args)
    ver = registry_mod.resolve_version(client.session, args.ref)
    target = args.output or f"{ver['model']}-v{ver['version']}"
    src = ver.get("storage_path") or ""
    if os.path.isdir(src):
        if os.path.exists(target):
            print(f"error: {target} already exists", file=sys.stderr)
            return 2
        _shutil.copytree(src, target)
        print(target)
        return 0
    path = client.get_checkpoint(ver["checkpoint_uuid"]).download(target)
    print(path)
    return 0


def model_deploy(args) -> int:
    """Rolling deploy: walk the serving fleet one replica at a time onto
    a registry version (drain -> relaunch -> next; docs/registry.md).
    ``--canary F`` rolls only that cohort first and bakes it against the
    pre-roll error-rate/latency baseline before finishing the roll."""
    import time as _time

    from determined_tpu.experiment import registry as registry_mod

    session = _client(args).session
    name, version = registry_mod.parse_model_ref(args.ref)
    body = {"model": name, "version": version}
    if args.canary is not None:
        body["canary_fraction"] = args.canary
        body["bake_seconds"] = args.bake_seconds
        body["min_requests"] = args.canary_min_requests
        if args.rollback_on_regression:
            body["rollback_on_regression"] = True
    state = session.post("/api/v1/serving/deploy", json=body).json()
    mode = ""
    canary = state.get("canary") or {}
    if canary.get("count"):
        mode = f" (canary cohort: {canary['count']})"
    print(f"deploy {state['id']}: rolling {state['target']} "
          f"over {len(state.get('pending') or [])} replica(s){mode}")
    if not args.wait:
        print(state["status"])
        return 0
    deadline = _time.time() + args.timeout
    phase = state.get("phase")
    while _time.time() < deadline:
        state = session.get("/api/v1/serving/deploy").json()
        if state.get("phase") != phase:
            phase = state.get("phase")
            print(f"deploy {state['id']}: phase {phase}")
        if state["status"] != "rolling":
            break
        _time.sleep(1.0)
    detail = f" ({state['detail']})" if state.get("detail") else ""
    print(f"deploy {state['id']}: {state['status']}{detail}")
    canary = state.get("canary") or {}
    if canary.get("verdict"):
        stat = f" — regressed stat: {canary['offending_stat']}" \
            if canary.get("offending_stat") else ""
        print(f"canary verdict: {canary['verdict']}{stat}")
    return 0 if state["status"] == "completed" else 1


# ---- serving fleet (master-side replica supervisor) -------------------------


def fleet_set(args) -> int:
    """Declare the fleet spec: the master's supervisor launches replicas
    as agent tasks and relaunches any that die (docs/serving.md)."""
    config = {}
    if args.slots is not None:
        config["resources"] = {"slots": args.slots}
    for kv in args.env or []:
        key, _, val = kv.partition("=")
        config.setdefault("env", {})[key] = val
    fleet = _client(args).set_serving_fleet(
        args.ref, args.target, pool=args.pool, config=config or None
    )
    print(f"fleet: {fleet['model']}@v{fleet['version']} "
          f"target {fleet['target']} ({fleet['status']})")
    return 0


def fleet_status(args) -> int:
    from determined_tpu.api.session import NotFoundError

    try:
        fleet = _client(args).get_serving_fleet()
    except NotFoundError:
        print("no fleet spec declared", file=sys.stderr)
        return 1
    if args.json:
        _print_json(fleet)
        return 0
    detail = f" — {fleet['detail']}" if fleet.get("detail") else ""
    print(f"{fleet['model']}@v{fleet['version']} target {fleet['target']} "
          f"status {fleet['status']}{detail}")
    for slot in fleet.get("slots") or []:
        state = "gave-up" if slot.get("gave_up") else (
            "live" if slot.get("replica_id") else "launching")
        err = f" last_error={slot['last_error']!r}" if slot.get("last_error") else ""
        print(f"  slot {slot['index']}: {state} task={slot.get('task_id') or '-'} "
              f"replica={slot.get('replica_id') or '-'} "
              f"launches={slot.get('launches', 0)} "
              f"failures={slot.get('failures', 0)}{err}")
    return 0 if fleet["status"] != "degraded" else 1


def model_register_version(args) -> int:
    v = _client(args).get_model(args.name).register_version(args.checkpoint_uuid)
    print(f"registered {args.name} version {v.version}")
    return 0


def master_info(args) -> int:
    _print_json(_client(args).master_info())
    return 0


# ---- templates --------------------------------------------------------------


def template_set(args) -> int:
    import yaml

    with open(args.config) as f:
        _client(args).set_template(args.name, yaml.safe_load(f))
    print(f"template {args.name} set")
    return 0


def template_list(args) -> int:
    _table(_client(args).list_templates(), ["name"])
    return 0


def template_describe(args) -> int:
    _print_json(_client(args).get_template(args.name))
    return 0


def template_remove(args) -> int:
    _client(args).delete_template(args.name)
    print(f"template {args.name} removed")
    return 0


# ---- tensorboard / tasks ---------------------------------------------------


def tensorboard_start(args) -> int:
    d = _client(args)
    info = d.start_tensorboard(experiment_ids=args.experiment_ids or [])
    info = d.wait_task_ready(info["id"], timeout=args.timeout)
    url = f"{d.master}{info['proxy_url']}?dtpu_token={d.session.token}"
    print(f"tensorboard {info['id']} ready: {url}")
    return 0


def notebook_start(args) -> int:
    d = _client(args)
    info = d.start_notebook(work_dir=args.work_dir)
    info = d.wait_task_ready(info["id"], timeout=args.timeout)
    url = (f"{d.master}{info['proxy_url']}?dtpu_token={d.session.token}"
           f"&token={info.get('token', '')}")
    print(f"notebook {info['id']} ready: {url}")
    return 0


def workspace_create(args) -> int:
    info = _client(args).create_workspace(args.name)
    print(f"workspace {info['name']} created (owner {info['owner']})")
    return 0


def workspace_list(args) -> int:
    _table(
        _client(args).list_workspaces(),
        ["name", "experiments", "registered", "archived", "owner"],
    )
    return 0


def workspace_archive(args) -> int:
    _client(args).archive_workspace(args.name, archived=not args.undo)
    print(f"workspace {args.name} {'unarchived' if args.undo else 'archived'}")
    return 0


def workspace_delete(args) -> int:
    _client(args).delete_workspace(args.name)
    print(f"workspace {args.name} deleted")
    return 0


def workspace_assign(args) -> int:
    _client(args).assign_workspace_role(args.name, args.username, args.role)
    print(f"workspace {args.name}: {args.username} -> {args.role}")
    return 0


def events_cmd(args) -> int:
    """Stream the cluster event feed (reference `det` streams client)."""
    d = _client(args)
    try:
        for ev in d.events(
            since=args.since, follow=args.follow, types=args.type or None
        ):
            print(json.dumps(ev), flush=True)
    except KeyboardInterrupt:
        pass
    return 0


def shell_start(args) -> int:
    d = _client(args)
    info = d.start_shell(shell=args.shell)
    info = d.wait_task_ready(info["id"], timeout=args.timeout)
    print(f"shell {info['id']} ready")
    if getattr(args, "no_open", False):
        print(f"attach with: dtpu shell open {info['id']}")
        return 0
    args.id = info["id"]
    return shell_open(args)


def shell_open(args) -> int:
    """Attach the local terminal to the task PTY over the proxied websocket
    (reference: ``det shell open`` over an sshd tunnel)."""
    import json as _json
    import select as _select
    import shutil
    import termios
    import tty

    from determined_tpu.common import ws as wslib

    d = _client(args)
    ws = d.open_shell_ws(args.id)
    size = shutil.get_terminal_size()
    ws.send_text(_json.dumps({"type": "resize", "rows": size.lines, "cols": size.columns}))

    stdin_fd = sys.stdin.fileno()
    interactive = sys.stdin.isatty()
    saved = termios.tcgetattr(stdin_fd) if interactive else None
    if interactive:
        tty.setraw(stdin_fd)
    try:
        print("connected; exit the shell (or ctrl-d) to detach\r", flush=True)
        stdin_open = True
        while True:
            if ws.has_buffered_frame():
                r = [ws.sock]  # complete frame already read past select's view
            else:
                fds = [ws.sock] + ([stdin_fd] if stdin_open else [])
                r, _, _ = _select.select(fds, [], [])
            if ws.sock in r:
                op, data = ws.recv_message()
                if op == wslib.OP_CLOSE or ws.closed:
                    break
                if data:
                    os.write(sys.stdout.fileno(), data)
            if stdin_open and stdin_fd in r:
                data = os.read(stdin_fd, 65536)
                if not data:
                    # piped input exhausted: keep draining shell output
                    # until the remote side closes (the typical pipe ends
                    # with `exit`, which closes the PTY server-side)
                    stdin_open = False
                    continue
                ws.send_binary(data)
    except (ConnectionError, OSError):
        pass
    finally:
        if saved is not None:
            termios.tcsetattr(stdin_fd, termios.TCSADRAIN, saved)
        ws.close()
    print("\ndetached")
    return 0


def cmd_run(args) -> int:
    """Run an arbitrary command as a scheduler-placed task and stream its
    logs until it finishes (reference ``det cmd run``)."""
    argv = list(args.cmd or [])
    if argv and argv[0] == "--":
        argv = argv[1:]
    if not argv:
        print("usage: dtpu cmd run [--pool P] [--slots N] -- <command...>")
        return 2
    d = _client(args)
    info = d.run_command(
        argv if len(argv) > 1 else argv[0],
        resource_pool=args.pool,
        slots=args.slots,
    )
    tid = info["id"]
    print(f"command {tid} submitted to pool {info.get('resource_pool', 'default')}"
          + (" (queued)" if info.get("queued") else f" on {info.get('agent_id')}"))
    if args.detach:
        return 0
    import time as _time

    shown = 0
    while True:
        state = d.get_task(tid).get("state")
        logs = d.task_logs(tid)
        for rec in logs[shown:]:
            line = rec.get("line", "") if isinstance(rec, dict) else str(rec)
            print(line, flush=True)
        shown = len(logs)
        if state == "TERMINATED":
            return 0
        _time.sleep(0.5)


def token_create(args) -> int:
    info = _client(args).create_token(args.name, ttl_days=args.ttl_days,
                                      username=args.username)
    print(f"token {info['id']} ({info['name']}) for {info['username']} — "
          f"save the secret now, it is not shown again:")
    print(info["token"])
    return 0


def token_list(args) -> int:
    _table(_client(args).list_tokens(),
           ["id", "name", "username", "created_ms", "expires_ms"])
    return 0


def token_revoke(args) -> int:
    _client(args).revoke_token(args.id)
    print(f"revoked {args.id}")
    return 0


def task_list(args) -> int:
    _table(
        _client(args).list_tasks(),
        ["id", "type", "state", "ready", "queued", "resource_pool", "slots", "agent_id"],
    )
    return 0


def task_kill(args) -> int:
    _client(args).kill_task(args.id)
    print(f"killed {args.id}")
    return 0


# ---- devcluster (det deploy local analog) ----------------------------------


def _find_binary(name: str) -> str:
    import shutil

    env = os.environ.get(f"DTPU_{name.upper().replace('-', '_')}_BIN")
    if env and os.path.exists(env):
        return env
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    candidate = os.path.join(here, "native", "build", name)
    if os.path.exists(candidate):
        return candidate
    found = shutil.which(name)
    if found:
        return found
    raise SystemExit(
        f"{name} not found: build with `cmake -S native -B native/build && "
        f"cmake --build native/build` or set DTPU_{name.upper().replace('-', '_')}_BIN"
    )


def cluster_up(args) -> int:
    """Start a local master + N agents (reference: `det deploy local
    cluster-up`, minus docker — TPU VMs run processes directly)."""
    import signal as _signal
    import subprocess

    master_bin = _find_binary("dtpu-master")
    agent_bin = _find_binary("dtpu-agent")
    os.makedirs(args.state_dir, exist_ok=True)
    os.makedirs(args.checkpoint_dir, exist_ok=True)
    procs = [
        subprocess.Popen(
            [
                master_bin,
                "--host", "127.0.0.1",
                "--port", str(args.port),
                "--state-dir", args.state_dir,
                "--checkpoint-dir", args.checkpoint_dir,
                "--scheduler", args.scheduler,
            ]
        )
    ]
    import time as _time

    url = f"http://127.0.0.1:{args.port}"
    for i in range(args.agents):
        procs.append(
            subprocess.Popen(
                [
                    agent_bin,
                    "--master-host", "127.0.0.1",
                    "--master-port", str(args.port),
                    "--id", f"agent-{i}",
                    "--slots", str(args.slots),
                ]
            )
        )
    print(f"devcluster up: master {url}, {args.agents} agent(s) x {args.slots} slots")
    print("Ctrl-C to tear down")
    try:
        while all(p.poll() is None for p in procs):
            _time.sleep(1)
        print("a devcluster process exited; tearing down", file=sys.stderr)
        return 1
    except KeyboardInterrupt:
        return 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(_signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=5)
            except Exception:  # noqa: BLE001
                p.kill()


# ---- serve (online inference replica; docs/serving.md) ---------------------


class _ServeSignalFlag:
    """Signal-handler-safe drain flag: a plain attribute write holds no
    lock (the PR-7 signal-handler-unsafe rule; same pattern as
    ``experiment/local.py _PreemptFlag``).  The serve main loop polls it
    and runs the actual drain — which touches Events — on the main
    thread, never in handler context."""

    __slots__ = ("_flag",)

    def __init__(self) -> None:
        self._flag = False

    def set(self) -> None:
        self._flag = True

    def is_set(self) -> bool:
        return self._flag


def serve_cmd(args) -> int:
    """Run one online-serving replica from a trial checkpoint.

    Loads the checkpoint (``train.load_trial_from_checkpoint``), compiles
    the KV-cache prefill/decode steps, and serves ``POST /v1/generate``
    (+ ``/healthz``, ``/stats``).  With ``--master`` the replica registers
    under ``/api/v1/serving`` and heartbeats until shutdown.  SIGTERM or
    SIGINT drains: new requests are rejected (503), queued + in-flight
    requests finish, and the process exits 75 (EX_TEMPFAIL) so a
    supervisor knows the stop was orderly, not a crash.

    ``--model name[@version|@latest]`` serves a registry version instead
    of a raw path: the checkpoint is resolved through the master
    (``docs/registry.md``), the replica's listing label becomes
    ``name@vN``, and the resolved version rides registration — which is
    also what lets a rolling deploy (``dtpu model deploy``) find and
    drain replicas on older versions.  A master-requested drain exits 75
    exactly like a signal drain.
    """
    _log_to_stderr()  # before the harness imports: the first root handler wins
    import signal as _signal
    import time as _time

    from determined_tpu.experiment import PREEMPTED_EXIT_CODE
    from determined_tpu.serve import ServeConfig, ServeEngine, ServeWorker
    from determined_tpu.serve.tracing import finish_tracing, start_tracing

    try:
        serve_cfg = ServeConfig(
            block_size=args.block_size,
            num_blocks=args.num_blocks,
            max_batch=args.max_batch,
            max_prompt_len=args.max_prompt_len,
            max_new_tokens=args.max_new_tokens,
            queue_depth=args.queue_depth,
            prefix_cache=args.prefix_cache,
            decode_chunk_blocks=args.decode_chunk_blocks,
            host=args.host,
            port=args.port,
            trace_dir=args.trace_dir,
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    session = None
    if args.master or os.environ.get("DTPU_MASTER"):
        session = _client(args).session
    checkpoint = args.checkpoint
    model_name, model_version = "", 0
    if args.model:
        from determined_tpu.experiment import registry as registry_mod

        if checkpoint:
            print("error: pass a checkpoint path OR --model, not both",
                  file=sys.stderr)
            return 2
        if session is None:
            print("error: --model resolves through the master "
                  "(pass -m/--master or set DTPU_MASTER)", file=sys.stderr)
            return 2
        try:
            ver = registry_mod.resolve_version(session, args.model)
        except Exception as e:  # noqa: BLE001 - CLI boundary
            print(f"error: {e}", file=sys.stderr)
            return 2
        model_name = ver["model"]
        model_version = int(ver["version"])
        checkpoint = ver.get("storage_path") or ""
        if not checkpoint or not os.path.isdir(checkpoint):
            print(f"error: {model_name}@v{model_version} resolves to "
                  f"storage path {checkpoint!r}, which is not a directory "
                  "on this host (serve replicas load via shared storage)",
                  file=sys.stderr)
            return 2
        print(f"resolved {args.model} -> {model_name}@v{model_version} "
              f"({checkpoint})", flush=True)
    elif not checkpoint:
        print("error: pass a checkpoint path or --model name@version",
              file=sys.stderr)
        return 2
    print(f"loading checkpoint {checkpoint} ...", flush=True)
    # the tracer is this process's to own: off, or shipped into trace_dir
    start_tracing(serve_cfg.trace_dir)
    engine = ServeEngine.from_checkpoint(checkpoint, serve_cfg)
    # listing label precedence: explicit --model-name, then the registry
    # ref (name@vN), then the trial class name for raw-path launches
    if model_name:
        label = f"{model_name}@v{model_version}"
    else:
        label = args.model_name or engine.model_label
    worker = ServeWorker(
        engine,
        host=serve_cfg.host,
        port=serve_cfg.port,
        session=session,
        model=args.model_name or label,
        checkpoint=checkpoint,
        model_name=model_name,
        model_version=model_version,
    )
    url = worker.start()
    # the parseable contract scripts/tests rely on: one line, stable prefix
    print(f"serving on {url}", flush=True)

    drain_flag = _ServeSignalFlag()
    prev = {}

    def _on_signal(signum, frame):  # noqa: ARG001 - signal handler shape
        drain_flag.set()  # plain write: safe at any bytecode boundary

    for sig in (_signal.SIGTERM, _signal.SIGINT):
        prev[sig] = _signal.signal(sig, _on_signal)
    try:
        while not drain_flag.is_set() and not worker.master_drain_requested():
            _time.sleep(0.2)
        if worker.master_drain_requested() and not drain_flag.is_set():
            target = worker.master_drain_info.get("target") or "?"
            print(f"deploy drain requested by master (target {target})",
                  flush=True)
        print("drain requested: rejecting new requests, finishing in-flight",
              flush=True)
        worker.request_drain()
        clean = worker.wait_drained(timeout=serve_cfg.drain_grace_s)
        worker.shutdown()
        print(f"drained ({'clean' if clean else 'grace expired'}); exiting",
              flush=True)
        return PREEMPTED_EXIT_CODE
    finally:
        finish_tracing(serve_cfg.trace_dir)
        for sig, handler in prev.items():
            _signal.signal(sig, handler)


# ---- lint ------------------------------------------------------------------


def lint_cmd(args) -> int:
    """Static preflight analysis of trial code — no master required.

    Targets are .py files, directories (recursive), or
    ``pkg.module:TrialClass`` entrypoints.  ``--config`` additionally
    preflights an experiment YAML: parse-time validation plus the
    cross-field pipeline checks (schedule vs mesh pipe axis, n_layers
    divisibility into pipe x virtual_stages chunks, batch vs
    pipe_microbatches) that otherwise surface at trainer setup or the
    first step.  Exit status: 0 clean, 1 on error-severity findings (any
    finding with ``--strict``) or config problems, 2 on usage /
    unloadable target.
    """
    from determined_tpu import lint as lint_mod

    sys.path.insert(0, os.getcwd())
    if not args.target and not args.config and not args.native:
        print("error: nothing to lint (pass targets, --config, and/or --native)",
              file=sys.stderr)
        return 2
    config_problems = []
    for cfg_path in args.config or []:
        import yaml

        from determined_tpu.config.experiment import (
            ExperimentConfig,
            InvalidExperimentConfig,
            preflight_experiment_config,
        )

        try:
            with open(cfg_path, encoding="utf-8") as f:
                raw = yaml.safe_load(f) or {}
        except (OSError, yaml.YAMLError) as e:
            print(f"error: cannot read config {cfg_path}: {e}", file=sys.stderr)
            return 2
        try:
            cfg = ExperimentConfig.parse(raw)
        except InvalidExperimentConfig as e:
            config_problems.append(f"{cfg_path}: {e}")
            continue
        config_problems.extend(
            f"{cfg_path}: {p}" for p in preflight_experiment_config(cfg)
        )
    diags = []
    # path targets lint together as ONE program: the concurrency pass
    # builds a single cross-module lock graph spanning every target, so a
    # script taking package locks in the wrong order still forms a cycle
    path_targets = []
    for target in args.target:
        try:
            if os.path.exists(target):
                path_targets.append(target)
            elif ":" in target or "." in target:
                diags.extend(
                    lint_mod.analyze_entrypoint(
                        target, rules=args.rule or None, disabled=args.suppress or None
                    )
                )
            else:
                print(f"error: no such file, directory, or module: {target}",
                      file=sys.stderr)
                return 2
        except Exception as e:  # noqa: BLE001 - the entrypoint import runs
            # arbitrary user module code; ANY failure there is "target
            # unloadable" (exit 2), never "findings present" (exit 1)
            print(f"error: cannot lint {target}: {e}", file=sys.stderr)
            return 2
    if path_targets:
        try:
            diags.extend(
                lint_mod.analyze_paths(
                    path_targets, rules=args.rule or None,
                    disabled=args.suppress or None,
                    exclude=args.exclude or None,
                )
            )
        except Exception as e:  # noqa: BLE001 - unreadable file, bad rule id
            print(f"error: cannot lint {' '.join(path_targets)}: {e}",
                  file=sys.stderr)
            return 2
    if args.native:
        # control-plane contract pass: cross-reference the native
        # master/agent sources against the Python bindings, docs, and the
        # test suite's fake masters (docs/lint.md "Control-plane contract")
        from determined_tpu.lint.rules import build_rules

        root = None
        for cand in path_targets or [os.getcwd()]:
            root = lint_mod.find_native_root(os.path.abspath(cand))
            if root:
                break
        if not root:
            print("error: --native: no native/master/master.cpp above the "
                  "lint target (run from the repo)", file=sys.stderr)
            return 2
        try:
            diags.extend(
                lint_mod.lint_native(
                    root,
                    build_rules(args.rule or None, args.suppress or None),
                )
            )
        except Exception as e:  # noqa: BLE001 - unreadable source, bad rule id
            print(f"error: cannot run native pass over {root}: {e}",
                  file=sys.stderr)
            return 2
        diags.sort(key=lambda d: (d.file, d.line, d.col, d.rule))
    if args.json:
        payload = lint_mod.to_json_payload(diags)
        if args.config:
            payload["config_findings"] = config_problems
        _print_json(payload)
    else:
        for p in config_problems:
            print(f"config error: {p}")
        for d in diags:
            print(d.format())
        lint_errors = sum(1 for d in diags if d.severity == lint_mod.ERROR)
        errors = lint_errors + len(config_problems)
        warnings = len(diags) - lint_errors
        total = len(diags) + len(config_problems)
        print(
            f"{total} finding(s): {errors} error(s), {warnings} warning(s)"
            if total
            else "clean: no findings"
        )
    failing = [
        d for d in diags if d.severity == lint_mod.ERROR or args.strict
    ]
    return 1 if failing or config_problems else 0


# ---- search preview + local run -------------------------------------------


def preview_search(args) -> int:
    import yaml

    from determined_tpu.config.experiment import ExperimentConfig
    from determined_tpu.searcher import simulate

    with open(args.config) as f:
        raw = yaml.safe_load(f)
        cfg = ExperimentConfig.parse(raw)

    if getattr(args, "native", False):
        # drive the MASTER's C++ searcher (the parity twin of the Python
        # simulate below; see tests/test_searcher_parity.py)
        import subprocess
        import tempfile

        master_bin = _find_binary("dtpu-master")
        with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
            json.dump(raw, f)
            cfg_path = f.name
        try:
            sim = subprocess.run(
                [master_bin, "--simulate", cfg_path],
                capture_output=True,
                text=True,
                timeout=120,
            )
        finally:
            os.unlink(cfg_path)
        if sim.returncode != 0:
            print(sim.stderr, file=sys.stderr)
            return 1
        native = json.loads(sim.stdout)
        out = {
            "trials_created": native["trials_created"],
            "total_units": native["total_units"],
            "trial_units": native["trial_units"],
        }
    else:
        # synthetic smooth trial: improves with budget, hp-independent
        out = simulate(cfg, lambda hp, step: 1.0 / (1 + step), seed=0)
    smaller = cfg.searcher.smaller_is_better
    print(f"searcher: {cfg.searcher.name} (metric {cfg.searcher.metric}, "
          f"{'min' if smaller else 'max'})")
    print(f"trials created:   {out['trials_created']}")
    print(f"total units:      {out['total_units']}")
    units = sorted(out["trial_units"].values())
    print(f"units per trial:  min {units[0]}, median {units[len(units)//2]}, "
          f"max {units[-1]}")
    return 0


def exp_run(args) -> int:
    """Drive a search from this process.

    Default: the in-process ``LocalExperiment`` over ``jax.devices()``
    (exactly ``dtpu run-local``).  ``--cluster``: the search loop still
    runs HERE (journaled under ``--checkpoint-dir``), but every trial the
    searcher creates is submitted to the master, which gang-fits its slots
    across agents and launches one ``run_trial`` process per rank with
    ``jax.distributed`` rendezvous env (docs/cluster.md).
    """
    _log_to_stderr()  # before the harness imports: the first root handler wins
    import yaml

    from determined_tpu.config.experiment import ExperimentConfig
    from determined_tpu.experiment import PREEMPTED_EXIT_CODE

    with open(args.config) as f:
        cfg = ExperimentConfig.parse(yaml.safe_load(f))
    entrypoint = getattr(args, "entrypoint", None) or cfg.entrypoint
    if not entrypoint:
        print(
            "error: no entrypoint (pass pkg.module:TrialClass or set "
            "`entrypoint:` in the config)",
            file=sys.stderr,
        )
        return 2
    if getattr(args, "cluster", False):
        from determined_tpu.experiment import ClusterExperiment

        exp = ClusterExperiment(
            cfg,
            entrypoint,
            session=_client(args).session,
            checkpoint_dir=args.checkpoint_dir,
        )
        summary = exp.run()
    else:
        from determined_tpu.experiment import LocalExperiment

        module_name, _, class_name = entrypoint.partition(":")
        sys.path.insert(0, os.getcwd())
        trial_cls = getattr(importlib.import_module(module_name), class_name)
        lexp = LocalExperiment(cfg, trial_cls, checkpoint_dir=args.checkpoint_dir)
        summary = lexp.run()
    _print_json(summary)
    if summary.get("status") == "preempted":
        # EX_TEMPFAIL: the search drained to checkpoints (local) or
        # detached from its running gangs (cluster); rerun with
        # `dtpu experiment resume <checkpoint_dir>` to finish it
        return PREEMPTED_EXIT_CODE
    return 0


# back-compat alias: `dtpu run-local` predates `dtpu experiment run`
run_local = exp_run


# ---- parser ----------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dtpu", description="determined-tpu CLI")
    p.add_argument("-m", "--master", help="master url (default $DTPU_MASTER)")
    p.add_argument(
        "--cert",
        help="CA bundle for an https master (default $DTPU_MASTER_CERT)",
    )
    p.add_argument("-u", "--user", help="username (default: cached or 'determined')")
    sub = p.add_subparsers(dest="noun", required=True)

    lg = sub.add_parser("login")
    lg.add_argument("-p", "--password")
    lg.set_defaults(fn=do_login)
    sub.add_parser("whoami").set_defaults(fn=do_whoami)

    user = sub.add_parser("user").add_subparsers(dest="verb", required=True)
    uc = user.add_parser("create")
    uc.add_argument("username")
    uc.add_argument("-p", "--password")
    uc.add_argument("--admin", action="store_true")
    uc.add_argument("--role", choices=["admin", "user", "viewer"])
    uc.set_defaults(fn=user_create)
    user.add_parser("list").set_defaults(fn=user_list)

    exp = sub.add_parser("experiment", aliases=["e"]).add_subparsers(
        dest="verb", required=True
    )
    c = exp.add_parser("create")
    c.add_argument("config")
    c.add_argument(
        "context_dir",
        nargs="?",
        help="model-code directory shipped to the cluster (.detignore honored)",
    )
    c.add_argument("-f", "--follow", action="store_true")
    c.add_argument("--template", help="master-stored config template to merge under")
    c.set_defaults(fn=exp_create)
    el = exp.add_parser("list")
    el.add_argument("--workspace")
    el.add_argument("--project")
    el.set_defaults(fn=exp_list)
    for verb in ("fork", "continue"):
        fk = exp.add_parser(verb)
        fk.add_argument("id", type=int)
        fk.add_argument("--config-overrides", help="yaml file merged over the source config")
        fk.add_argument("-f", "--follow", action="store_true")
        fk.set_defaults(fn=exp_fork, verb=verb)
    d = exp.add_parser("describe")
    d.add_argument("id", type=int)
    d.set_defaults(fn=exp_describe)
    for verb in ("pause", "activate", "cancel", "kill"):
        v = exp.add_parser(verb)
        v.add_argument("id", type=int)
        v.set_defaults(fn=exp_signal, verb=verb)
    dl = exp.add_parser("delete")
    dl.add_argument("id", type=int)
    dl.set_defaults(fn=exp_delete)
    rn = exp.add_parser(
        "run",
        help="drive a search from this process: in-process by default, "
        "--cluster dispatches trials through the master (docs/cluster.md)",
    )
    rn.add_argument("config")
    rn.add_argument(
        "entrypoint",
        nargs="?",
        help="pkg.module:TrialClass (default: `entrypoint:` in the config)",
    )
    rn.add_argument(
        "--cluster",
        action="store_true",
        help="submit searcher-created trials to the master for gang "
        "dispatch across agents instead of running them in-process",
    )
    rn.add_argument(
        "--checkpoint-dir",
        default=None,
        help="driver directory (journal + traces; default: ./local_… or "
        "./cluster_experiment_driver)",
    )
    rn.set_defaults(fn=exp_run)
    st = exp.add_parser(
        "status",
        help="journal-backed status of a LOCAL experiment directory",
    )
    st.add_argument("checkpoint_dir")
    st.add_argument("--json", action="store_true", help="machine-readable output")
    st.set_defaults(fn=exp_status_local)
    rs = exp.add_parser(
        "resume",
        help="resume a crashed/preempted LOCAL experiment from its journal",
    )
    rs.add_argument("checkpoint_dir")
    rs.add_argument(
        "--entrypoint",
        help="pkg.module:TrialClass (default: recorded in the journal)",
    )
    rs.add_argument("--serial", action="store_true", help="force the sequential loop")
    rs.set_defaults(fn=exp_resume_local)
    pf = exp.add_parser(
        "profile",
        help="goodput ledger + phase breakdown from a LOCAL experiment's traces",
    )
    pf.add_argument("checkpoint_dir")
    pf.add_argument("--json", action="store_true", help="machine-readable output")
    pf.add_argument(
        "--xplane",
        help="directory holding a sampled jax.profiler window "
        "(default: <dir>/traces/xplane)",
    )
    pf.set_defaults(fn=exp_profile_local)

    trial = sub.add_parser("trial", aliases=["t"]).add_subparsers(
        dest="verb", required=True
    )
    d = trial.add_parser("describe")
    d.add_argument("id", type=int)
    d.set_defaults(fn=trial_describe)
    lg = trial.add_parser("logs")
    lg.add_argument("id", type=int)
    lg.add_argument("-f", "--follow", action="store_true")
    lg.set_defaults(fn=trial_logs)
    mt = trial.add_parser("metrics")
    mt.add_argument("id", type=int)
    mt.add_argument("--group")
    mt.set_defaults(fn=trial_metrics)

    srch = sub.add_parser("searcher").add_subparsers(dest="verb", required=True)
    sim = srch.add_parser(
        "simulate",
        help="replay search methods against a learning-curve model "
        "(trial-free, deterministic; docs/searchers.md)",
    )
    sim.add_argument("-c", "--config", help="experiment config yaml "
                     "(default: a built-in lr search space)")
    sim.add_argument(
        "--methods",
        default="random,asha,hyperband,pbt",
        help="comma-separated method names to compare",
    )
    sim.add_argument("--seed", type=int, default=0)
    sim.add_argument("--period", type=int, default=0,
                     help="validation period in budget units (0 = per-method default)")
    sim.add_argument(
        "--journal",
        help="replay recorded curves from an experiment journal "
        "(file or checkpoint dir) instead of the synthetic model",
    )
    sim.add_argument("--json", action="store_true")
    sim.set_defaults(fn=searcher_simulate)

    agent = sub.add_parser("agent", aliases=["a"]).add_subparsers(
        dest="verb", required=True
    )
    agent.add_parser("list").set_defaults(fn=agent_list)

    pool = sub.add_parser("pool").add_subparsers(dest="verb", required=True)
    pool.add_parser("list").set_defaults(fn=pool_list)

    ckpt = sub.add_parser("checkpoint", aliases=["c"]).add_subparsers(
        dest="verb", required=True
    )
    ckpt.add_parser("list").set_defaults(fn=checkpoint_list)
    cd = ckpt.add_parser("download")
    cd.add_argument("uuid")
    cd.add_argument("--output", help="target directory (default: temp dir)")
    cd.set_defaults(fn=checkpoint_download)

    model = sub.add_parser(
        "model", help="model registry: versioned checkpoints promoted from "
        "trials, served and rolled onto the fleet (docs/registry.md)"
    ).add_subparsers(dest="verb", required=True)
    mc = model.add_parser("create")
    mc.add_argument("name")
    mc.add_argument("--description")
    mc.set_defaults(fn=model_create)
    model.add_parser("list").set_defaults(fn=model_list)
    ms = model.add_parser("show", help="model + every version with lineage")
    ms.add_argument("name")
    ms.add_argument("--json", action="store_true")
    ms.set_defaults(fn=model_show)
    mg = model.add_parser(
        "register", help="register a checkpoint as the model's next version"
    )
    mg.add_argument("name")
    mg.add_argument("checkpoint_uuid")
    mg.add_argument("--storage-path",
                    help="checkpoint directory (required when the master "
                         "does not track this checkpoint)")
    mg.add_argument("--trial-id", type=int, help="source trial lineage")
    mg.add_argument("--experiment-id", type=int, help="source experiment lineage")
    mg.add_argument("--metric", action="append", metavar="KEY=VALUE",
                    help="metrics snapshot entry (repeatable)")
    mg.add_argument("--label", action="append", help="version label (repeatable)")
    mg.add_argument("--version", type=int,
                    help="pin an explicit version number (409 if taken)")
    mg.set_defaults(fn=model_register)
    mp = model.add_parser(
        "promote", help="promote a trial's latest checkpoint to the next "
        "version (the master resolves lineage + metrics)"
    )
    mp.add_argument("name")
    mp.add_argument("trial_id", type=int)
    mp.set_defaults(fn=model_promote)
    mpl = model.add_parser("pull", help="materialize a version's checkpoint locally")
    mpl.add_argument("ref", metavar="NAME[@VERSION]")
    mpl.add_argument("--output", help="target directory (default: NAME-vN)")
    mpl.set_defaults(fn=model_pull)
    md = model.add_parser(
        "deploy", help="rolling-deploy a version onto the serving fleet "
        "(drain one replica at a time; supervisors relaunch on the target)"
    )
    md.add_argument("ref", metavar="NAME[@VERSION]")
    md.add_argument("--no-wait", dest="wait", action="store_false",
                    help="start the roll and return immediately")
    md.add_argument("--timeout", type=float, default=600.0,
                    help="seconds to wait for the roll to finish")
    md.add_argument("--canary", type=float, metavar="FRACTION",
                    help="roll this fraction of the fleet first and bake "
                         "it against the pre-roll error-rate/latency "
                         "baseline before finishing the roll")
    md.add_argument("--bake-seconds", type=float, default=30.0,
                    help="canary bake window (default: 30)")
    md.add_argument("--canary-min-requests", type=int, default=1,
                    help="minimum canary-cohort requests before the bake "
                         "verdict counts (default: 1)")
    md.add_argument("--rollback-on-regression", action="store_true",
                    help="on a canary regression, roll the cohort back to "
                         "the prior version instead of holding")
    md.set_defaults(fn=model_deploy, wait=True)

    fleet = sub.add_parser(
        "fleet", help="supervised serving fleet: the master relaunches "
        "replicas that die to hold the declared target (docs/serving.md)"
    ).add_subparsers(dest="verb", required=True)
    fs = fleet.add_parser(
        "set", help="declare the fleet spec (model version + replica count)"
    )
    fs.add_argument("ref", metavar="NAME[@VERSION]")
    fs.add_argument("--target", type=int, required=True,
                    help="replica count the supervisor holds")
    fs.add_argument("--pool", help="resource pool for replica tasks")
    fs.add_argument("--slots", type=int, help="slots per replica task")
    fs.add_argument("--env", action="append", metavar="KEY=VALUE",
                    help="environment override for replica tasks (repeatable)")
    fs.set_defaults(fn=fleet_set)
    fst = fleet.add_parser("status", help="fleet spec + per-slot health")
    fst.add_argument("--json", action="store_true")
    fst.set_defaults(fn=fleet_status)
    mr = model.add_parser("register-version")
    mr.add_argument("name")
    mr.add_argument("checkpoint_uuid")
    mr.set_defaults(fn=model_register_version)

    master = sub.add_parser("master").add_subparsers(dest="verb", required=True)
    master.add_parser("info").set_defaults(fn=master_info)

    tpl = sub.add_parser("template").add_subparsers(dest="verb", required=True)
    tset = tpl.add_parser("set")
    tset.add_argument("name")
    tset.add_argument("config")
    tset.set_defaults(fn=template_set)
    tpl.add_parser("list").set_defaults(fn=template_list)
    td = tpl.add_parser("describe")
    td.add_argument("name")
    td.set_defaults(fn=template_describe)
    tr = tpl.add_parser("remove")
    tr.add_argument("name")
    tr.set_defaults(fn=template_remove)

    tb = sub.add_parser("tensorboard").add_subparsers(dest="verb", required=True)
    ts = tb.add_parser("start")
    ts.add_argument("experiment_ids", nargs="*", type=int)
    ts.add_argument("--timeout", type=float, default=60.0)
    ts.set_defaults(fn=tensorboard_start)

    nb = sub.add_parser("notebook").add_subparsers(dest="verb", required=True)
    ns = nb.add_parser("start")
    ns.add_argument("--work-dir")
    ns.add_argument("--timeout", type=float, default=150.0)
    ns.set_defaults(fn=notebook_start)

    ws = sub.add_parser("workspace", aliases=["w"]).add_subparsers(
        dest="verb", required=True
    )
    wc = ws.add_parser("create")
    wc.add_argument("name")
    wc.set_defaults(fn=workspace_create)
    ws.add_parser("list").set_defaults(fn=workspace_list)
    wa = ws.add_parser("archive")
    wa.add_argument("name")
    wa.add_argument("--undo", action="store_true")
    wa.set_defaults(fn=workspace_archive)
    wd = ws.add_parser("delete")
    wd.add_argument("name")
    wd.set_defaults(fn=workspace_delete)
    wr = ws.add_parser("assign")
    wr.add_argument("name")
    wr.add_argument("username")
    wr.add_argument("role", choices=["viewer", "user", "admin", "none"])
    wr.set_defaults(fn=workspace_assign)

    ev = sub.add_parser("events")
    ev.add_argument("-f", "--follow", action="store_true")
    ev.add_argument("--since", type=int, default=0)
    ev.add_argument("--type", action="append", help="filter by event type (repeatable)")
    ev.set_defaults(fn=events_cmd)

    sh = sub.add_parser("shell").add_subparsers(dest="verb", required=True)
    ss = sh.add_parser("start")
    ss.add_argument("--shell", default="/bin/sh")
    ss.add_argument("--timeout", type=float, default=60.0)
    ss.add_argument("--no-open", action="store_true",
                    help="start only; do not attach a terminal")
    ss.set_defaults(fn=shell_start)
    so = sh.add_parser("open")
    so.add_argument("id")
    so.set_defaults(fn=shell_open)

    task = sub.add_parser("task").add_subparsers(dest="verb", required=True)
    task.add_parser("list").set_defaults(fn=task_list)
    tk = task.add_parser("kill")
    tk.add_argument("id")
    tk.set_defaults(fn=task_kill)

    tok = sub.add_parser("token").add_subparsers(dest="verb", required=True)
    tc = tok.add_parser("create")
    tc.add_argument("name")
    tc.add_argument("--ttl-days", type=int, default=30)
    tc.add_argument("--username", default=None, help="admin: issue for another user")
    tc.set_defaults(fn=token_create)
    tok.add_parser("list").set_defaults(fn=token_list)
    tr = tok.add_parser("revoke")
    tr.add_argument("id")
    tr.set_defaults(fn=token_revoke)

    cmd = sub.add_parser("cmd").add_subparsers(dest="verb", required=True)
    cr = cmd.add_parser("run")
    cr.add_argument("--pool", default=None, help="resource pool (incl. k8s/slurm pools)")
    cr.add_argument("--slots", type=int, default=0)
    cr.add_argument("--detach", action="store_true")
    cr.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="command to run (prefix with --)")
    cr.set_defaults(fn=cmd_run)

    cl = sub.add_parser("cluster").add_subparsers(dest="verb", required=True)
    cu = cl.add_parser("up")
    cu.add_argument("--port", type=int, default=8080)
    cu.add_argument("--agents", type=int, default=1)
    cu.add_argument("--slots", type=int, default=4)
    cu.add_argument("--scheduler", default="priority",
                    choices=["priority", "fair_share"])
    cu.add_argument("--state-dir", default="/tmp/dtpu-master")
    cu.add_argument("--checkpoint-dir", default="/tmp/dtpu-checkpoints")
    cu.set_defaults(fn=cluster_up)

    sv = sub.add_parser(
        "serve",
        help="run an online-serving replica from a trial checkpoint "
        "(docs/serving.md)",
    )
    sv.add_argument("checkpoint", nargs="?", default=None,
                    help="trial checkpoint directory to serve "
                         "(or use --model to resolve one via the registry)")
    sv.add_argument("--model", default=None, metavar="NAME[@VERSION]",
                    help="serve a registry model version resolved through "
                         "the master, e.g. lm@latest or lm@v3 "
                         "(docs/registry.md)")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument(
        "--port", type=int, default=0,
        help="HTTP port (default 0: OS-assigned, printed at startup)",
    )
    sv.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV-cache block")
    sv.add_argument("--num-blocks", type=int, default=256,
                    help="KV-cache pool size in blocks")
    sv.add_argument("--max-batch", type=int, default=8,
                    help="decode lanes (max sequences in flight)")
    sv.add_argument("--max-prompt-len", type=int, default=128)
    sv.add_argument("--max-new-tokens", type=int, default=64)
    sv.add_argument("--queue-depth", type=int, default=16,
                    help="admission queue depth (full -> 429)")
    sv.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=True,
                    help="share KV blocks across requests with a common "
                         "prompt prefix (default on; docs/serving.md)")
    sv.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="disable prefix sharing: every request prefills "
                         "private blocks")
    sv.add_argument("--decode-chunk-blocks", type=int, default=1,
                    help="paged decode attention: any value above 0 reads "
                         "each lane's live KV blocks straight from the pool "
                         "(the tile width is chosen from the shapes); 0 = "
                         "full-table gather every step (must divide the "
                         "table width)")
    sv.add_argument("--model-name", default=None,
                    help="label shown in the master's replica listing")
    sv.add_argument("--trace-dir", default=None,
                    help="record the replica's serve.* spans: events.jsonl "
                         "there while it runs, trace.json once drained "
                         "(default: tracer off; docs/serving.md)")
    sv.set_defaults(fn=serve_cmd)

    ln = sub.add_parser(
        "lint",
        help="static preflight analysis of trial code (docs/lint.md)",
    )
    ln.add_argument(
        "target",
        nargs="*",
        help=".py file, directory, or pkg.module:TrialClass entrypoint",
    )
    ln.add_argument(
        "--config", action="append", metavar="YAML",
        help="experiment config to preflight (repeatable): parse "
             "validation + cross-field pipeline-schedule checks "
             "(n_layers vs pipe x virtual_stages, batch vs "
             "pipe_microbatches) before any device work",
    )
    ln.add_argument("--json", action="store_true", help="machine-readable output")
    ln.add_argument(
        "--strict", action="store_true",
        help="exit non-zero on ANY finding (default: errors only)",
    )
    ln.add_argument(
        "--rule", action="append",
        help="restrict to specific rule ids (repeatable)",
    )
    ln.add_argument(
        "--suppress", action="append",
        help="disable specific rule ids (repeatable)",
    )
    ln.add_argument(
        "--native", action="store_true",
        help="also run the control-plane contract pass: cross-reference "
             "native/master + native/agent (routes, WAL record types, "
             "/metrics names, wire payloads) against api/spec.py, API.md, "
             "docs/operations.md, the devcluster fuzz fixtures, and the "
             "test suite's fake masters",
    )
    ln.add_argument(
        "--exclude", action="append", metavar="GLOB",
        help="skip files/dirs matching this glob in dir-mode targets "
             "(repeatable; matched against basenames and target-relative "
             "paths — excluded directories are pruned, so a live "
             "experiment's checkpoint/journal/trace artifacts are never "
             "walked)",
    )
    ln.set_defaults(fn=lint_cmd)

    ps = sub.add_parser("preview-search")
    ps.add_argument("config")
    ps.add_argument("--native", action="store_true",
                    help="simulate with the master's C++ searcher")
    ps.set_defaults(fn=preview_search)

    rl = sub.add_parser("run-local")
    rl.add_argument("config")
    rl.add_argument("entrypoint", help="pkg.module:TrialClass")
    rl.add_argument("--checkpoint-dir", default=None)
    rl.set_defaults(fn=run_local)

    from determined_tpu.cli import deploy

    deploy.register(sub)

    return p


def main(argv: Optional[List[str]] = None) -> int:
    from determined_tpu.api.session import APIError

    args = build_parser().parse_args(argv)
    try:
        return args.fn(args)
    except KeyboardInterrupt:
        return 130
    except APIError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
