"""Trainer: the boundary-driven training loop engine.

Reference: ``_PyTorchTrialController`` (``harness/determined/pytorch/
_pytorch_trial.py:398-1088``) + ``Trainer``/``init`` (``_trainer.py:18-386``).
Same contract — fit(max_length, periods, latest_checkpoint) with
TRAIN/VALIDATE/CHECKPOINT/REPORT boundaries, preemption-safe, resumable —
redesigned for XLA:

- ONE jitted train step (forward+backward+update+metric-accumulate) with
  buffer donation; gradients are globally correct because the batch is a
  mesh-sharded global array (no DDP/allreduce calls to orchestrate).
- the hot loop never syncs the host: boundary arithmetic is pure Python on
  step counters; metrics are fetched once per REPORT boundary.
- checkpoints write each process's addressable array shards (orbax) inside
  a CheckpointContext-managed directory; loader/callback state rides along.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Any, Callable as TCallable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from flax.core import meta as flax_meta

from determined_tpu.config.experiment import ExperimentConfig, Length
from determined_tpu.core import _context as core_context_mod
from determined_tpu.data._loader import DataLoader, to_global
from determined_tpu.data._prefetch import EpochFeed, InputPipeline
from determined_tpu.observability import chip_peak_flops, get_tracer, log_setup_line
from determined_tpu.parallel.mesh import MeshAxes, MeshConfig, make_mesh
from determined_tpu.parallel.sharding import (
    DEFAULT_RULES,
    param_shardings,
)
from determined_tpu.train._state import TrainState
from determined_tpu.train._trial import Callback, JaxTrial, TrialContext
from determined_tpu.train import serialization
from determined_tpu.utils import faults
from determined_tpu.utils.chip import device_facts
from determined_tpu.utils.errors import CheckpointCorruptError, CheckpointNotFoundError

logger = logging.getLogger("determined_tpu.train")


@dataclasses.dataclass
class _PendingSave:
    """An in-flight background checkpoint: the writer thread serializes the
    on-device snapshot; ``finish`` (collective merge/upload/report) runs on
    the main thread at the next drain point."""

    thread: threading.Thread
    finish: TCallable[[], None]
    storage_id: str
    step: int
    errors: list


def init(
    *,
    hparams: Optional[Dict[str, Any]] = None,
    mesh_config: Optional[MeshConfig] = None,
    exp_config: Optional[ExperimentConfig] = None,
    core_context: Optional[Any] = None,
    seed: Optional[int] = None,
    rules: Optional[Dict[str, Any]] = None,
    devices: Optional[List[Any]] = None,
) -> TrialContext:
    """Build a TrialContext — reference ``pytorch.init`` (``_trainer.py:282``).

    Off-cluster this produces a fully local context (dummy core services);
    on-cluster the same call picks up rendezvous + master connection.
    ``devices`` restricts the trial's mesh to an explicit device subset —
    the concurrent scheduler passes each trial its gang-allocated submesh
    (default: all of ``jax.devices()``).
    """
    if exp_config is not None:
        if hparams is None:
            hparams = {
                k: getattr(v, "val", v)
                for k, v in exp_config.hyperparameters.items()
                if not isinstance(v, dict)
            }
            # nested hp dicts pass through with Consts collapsed
            for k, v in exp_config.hyperparameters.items():
                if isinstance(v, dict):
                    hparams[k] = _collapse(v)
        mesh_config = mesh_config or exp_config.resources.mesh
        if seed is None:
            seed = exp_config.reproducibility.experiment_seed
    from determined_tpu.utils.compilation_cache import setup_compilation_cache

    setup_compilation_cache(
        exp_config.optimizations.compilation_cache_dir if exp_config else None
    )
    core = core_context or core_context_mod.init()
    mesh = make_mesh(mesh_config or MeshConfig.data_parallel(-1), devices=devices)
    # every task log names the device it ran on, as jax reports it
    logger.info(
        "jax devices: platform=%(platform)s kind=%(kind)r count=%(count)d; "
        "trial mesh %(mesh)s",
        {
            **device_facts(),
            "mesh": {k: v for k, v in mesh.shape.items() if v > 1} or {"devices": 1},
        },
    )
    return TrialContext(
        core=core,
        mesh=mesh,
        hparams=hparams,
        rules=rules,
        seed=seed or 0,
        exp_config=exp_config,
    )


def _fmt(metrics: Dict[str, float]) -> str:
    """``loss=10.4 lr=3e-06``: one task-log line's worth of metrics."""
    return " ".join(f"{k}={v:.6g}" for k, v in sorted(metrics.items()))


def _collapse(tree: Dict[str, Any]) -> Dict[str, Any]:
    return {
        k: _collapse(v) if isinstance(v, dict) else getattr(v, "val", v)
        for k, v in tree.items()
    }


def _infer_fsdp_specs(params_abstract: Any, mesh) -> Any:
    """Auto-FSDP: shard each param's largest dim divisible by the fsdp axis.

    Zero-annotation data-parallel-sharded params — the analog of ZeRO-3 via
    DeepSpeed in the reference, but done by the compiler from a spec.
    """
    fsdp = mesh.shape.get(MeshAxes.FSDP, 1)

    def spec(leaf):
        shape = leaf.shape
        if fsdp <= 1 or not shape:
            return None
        divisible = [d for d in range(len(shape)) if shape[d] % fsdp == 0 and shape[d] >= fsdp]
        if not divisible:
            return None
        d = max(divisible, key=lambda i: shape[i])
        out = [None] * len(shape)
        out[d] = "fsdp_shard"
        return tuple(out)

    return jax.tree.map(spec, params_abstract)


def _specs_from_flax_metadata(abstract_boxed: Any) -> Optional[Any]:
    """Extract logical specs from flax ``with_partitioning`` metadata."""
    leaves = jax.tree.leaves(abstract_boxed, is_leaf=lambda x: isinstance(x, flax_meta.Partitioned))
    if not any(isinstance(l, flax_meta.Partitioned) for l in leaves):
        return None
    spec_tree = nn.get_partition_spec(abstract_boxed)
    return jax.tree.map(
        lambda s: tuple(s) if s is not None and len(tuple(s)) else None,
        spec_tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )


class _BoundarySchedule:
    """Next-boundary arithmetic over a step counter (host-side ints only)."""

    def __init__(self, period: Optional[int], max_steps: int) -> None:
        self.period = period if period and period > 0 else None
        self.max_steps = max_steps

    def next_after(self, step: int) -> int:
        if self.period is None:
            return self.max_steps
        return min(((step // self.period) + 1) * self.period, self.max_steps)

    def is_boundary(self, step: int) -> bool:
        return step >= self.max_steps or (
            self.period is not None and step % self.period == 0
        )


class Trainer:
    """Drives a JaxTrial — reference ``Trainer`` + controller in one."""

    def __init__(self, trial: JaxTrial, context: Optional[TrialContext] = None) -> None:
        self.trial = trial
        self.context = context or trial.context
        self.core = self.context.core
        self.mesh = self.context.mesh
        self._compiled = False
        # populated by _setup
        self.model: Any = None
        self.tx: Any = None
        self.train_loader: Optional[DataLoader] = None
        self.val_loader: Optional[DataLoader] = None
        self.state: Optional[TrainState] = None
        self.callbacks: Dict[str, Callback] = {}
        self.steps_completed = 0
        self.best_validation: Optional[float] = None
        self._searcher_metric: Optional[str] = None
        self._smaller_is_better = True
        self.agg = 1  # aggregation_frequency, set from exp config in _setup
        self._pending_save: Optional[_PendingSave] = None
        self._snapshot_jit: Any = None
        self._tokens_per_sample: Optional[int] = None  # set by _setup
        self._overlap_plan: Any = None  # train/_overlap.py GradSyncPlan
        self._comm_model: Any = None    # its CommModel (step.comm ledger rows)
        self._bubble_model: Any = None  # parallel/pipeline.py BubbleModel
        #                                 (step.bubble ledger rows)
        # Newest FINALIZED checkpoint (manifest written, master reported).
        # An async save still in flight is deliberately excluded: until its
        # drain-point finalize runs it has no manifest and must never be
        # offered as a resume point.  The supervisor reads this after a
        # crash to know where the next attempt resumes from.
        self.latest_checkpoint: Optional[str] = None

    # -- setup -------------------------------------------------------------

    def _setup(self) -> None:
        # the stages below, as children of ``trainer.setup``: .build (model,
        # optimizer, loaders, the first host batch), .shapes (the abstract
        # traces and the sharding plan), .init (the sharded initialiser,
        # optimizer state, placement on the mesh), .steps (wrapping the
        # jitted steps)
        stamps = [time.monotonic()]
        ctx = self.context
        self.model = self.trial.build_model()
        self.tx = self.trial.build_optimizer()
        self.train_loader = self.trial.build_training_data_loader()
        self.val_loader = self.trial.build_validation_data_loader()
        self.callbacks = dict(self.trial.build_callbacks())
        cfg = ctx.exp_config
        if cfg is not None:
            self._searcher_metric = cfg.searcher.metric
            self._smaller_is_better = cfg.searcher.smaller_is_better
            if cfg.optimizations.fetch_workers:
                # config-level fetch_workers applies to loaders the trial
                # built without an explicit per-loader setting
                for ld in (self.train_loader, self.val_loader):
                    if ld is not None and not ld.fetch_workers:
                        ld.fetch_workers = cfg.optimizations.fetch_workers

        rng = jax.random.key(ctx.seed)
        init_rng, state_rng = jax.random.split(rng)

        sample = next(self.train_loader.iter_epoch(0))
        self._sample_host_batch = sample

        # ---- parameter shapes + logical specs (no real init yet) --------
        stamps.append(time.monotonic())
        abstract_raw_boxed = jax.eval_shape(
            lambda r: self.trial.init_params(self.model, r, sample), init_rng
        )
        abstract_boxed = jax.eval_shape(
            self.trial.restructure_params, abstract_raw_boxed
        )
        specs = self.trial.param_logical_specs(abstract_boxed)
        if specs is None:
            specs = _specs_from_flax_metadata(abstract_boxed)
        abstract = flax_meta.unbox(abstract_boxed)
        if specs is None:
            specs = _infer_fsdp_specs(abstract, self.mesh)
        self._param_specs = specs
        shardings = param_shardings(specs, self.mesh, ctx.rules)

        # ---- metric structure from an abstract trace ---------------------
        global_sample = to_global(sample, self.mesh)
        metrics_shape = jax.eval_shape(
            lambda p, b, r: self.trial.loss(self.model, p, b, r)[1],
            abstract,
            global_sample,
            state_rng,
        )
        metric_keys = tuple(sorted(metrics_shape.keys())) + ("loss",)
        if getattr(self.trial, "lr_schedule", None) is not None:
            metric_keys = metric_keys + ("lr",)

        # ---- sharded init --------------------------------------------------
        stamps.append(time.monotonic())
        # 1. init params, then commit them to their planned mesh shardings;
        # 2. build opt_state under jit from the *committed* params so XLA
        #    propagates the param shardings into mirror leaves (adam mu/nu);
        # 3. replicate every remaining leaf (scalars, rng) over the mesh so
        #    the whole TrainState lives on one consistent device set.
        # No ``jax.set_mesh`` here or around the steps: flax applies each
        # Partitioned box's LOGICAL names as a sharding constraint whenever
        # that global mesh is set, and logical names are not mesh axes (the
        # legacy ``with mesh:`` the loops use does not set it).
        # out_shardings carry the mesh explicitly, so init still
        # materializes directly sharded (no single-device materialization
        # at FSDP scale).
        params = jax.jit(
            lambda r: flax_meta.unbox(
                self.trial.restructure_params(
                    self.trial.init_params(self.model, r, sample)
                )
            ),
            out_shardings=shardings,
        )(init_rng)

        # ---- overlapped gradient sync plan (train/_overlap.py) -----------
        # Built whenever the mesh has gradient-reduction axes: with the
        # knob ON it carries the bucket markers + sharded layouts the step
        # uses below; either way it carries the comm model feeding the
        # goodput ledger's step.comm rows (docs/performance.md).
        opt = ctx.exp_config.optimizations if ctx.exp_config is not None else None
        from determined_tpu.train import _overlap

        self._overlap_plan = _overlap.build_plan(
            abstract,
            shardings,
            self.mesh,
            enabled=bool(opt is not None and opt.overlap_grad_sync),
            bucket_bytes=(opt.overlap_bucket_mb if opt else 4) * 1024 * 1024,
            hierarchical=bool(opt is not None and opt.hierarchical_collectives),
        )
        self._comm_model = (
            self._overlap_plan.comm if self._overlap_plan is not None else None
        )
        sync_on = self._overlap_plan is not None and self._overlap_plan.enabled

        # ---- pipeline schedule selection (parallel/pipeline.py) ----------
        # The trial declares the microbatch schedule it traces (gpipe /
        # 1f1b / interleaved); the Trainer folds it into the jit-cache key
        # below and into the goodput ledger's step.bubble rows — the
        # analytic tick model that attributes pipe-axis idle time the way
        # the CommModel attributes gradient-collective exposure.
        spec_fn = getattr(self.trial, "pipeline_schedule_spec", None)
        pipe_sched = spec_fn() if spec_fn is not None else None
        if pipe_sched is not None:
            from determined_tpu.parallel.pipeline import BubbleModel

            self._bubble_model = BubbleModel(schedule=pipe_sched)

        if opt is not None and opt.quantized_matmul != "none":
            # fail fast with a clear config error on unsupported platforms
            # (e.g. fp8 off TPU v5p/v6+), before any compile is attempted
            from determined_tpu.train._quant import require_platform

            dev0 = self.mesh.devices.flat[0]
            require_platform(
                opt.quantized_matmul,
                backend=getattr(dev0, "platform", None),
                device_kind=getattr(dev0, "device_kind", None),
            )

        if sync_on:
            # ZeRO-style memory win: the adam mirror leaves (mu/nu) live
            # SHARDED over the sync axes, matching the reduce-scattered
            # grads the update consumes — each device owns 1/n of the
            # optimizer state instead of a full replica
            abstract_opt = jax.eval_shape(self.tx.init, params)
            opt_state = jax.jit(
                self.tx.init,
                out_shardings=self._overlap_plan.opt_shardings(abstract_opt),
            )(params)
        else:
            opt_state = jax.jit(self.tx.init)(params)
        self.state = TrainState.create(params, opt_state, state_rng, metric_keys)
        self.state = self._place_on_mesh(self.state)
        # what a checkpoint restores into (_restore_tail)
        self._restore_template = serialization.abstract_like(self._array_state())

        # ---- jitted steps -------------------------------------------------
        stamps.append(time.monotonic())
        trial, model, tx = self.trial, self.model, self.tx
        agg = opt.aggregation_frequency if opt else 1
        average_grads = opt.average_aggregated_gradients if opt else True
        self.agg = agg
        overlap = self._overlap_plan if sync_on else None
        # the layout the optimizer update runs in, for optimizers that run
        # a per-device kernel (ops/fused_adamw.py) and so must know it
        update_shardings = overlap.update_shardings() if sync_on else shardings

        def train_step(state: TrainState, batch):
            step_rng = jax.random.fold_in(state.rng, state.step)

            def loss_fn(p, mb):
                if overlap is not None and agg == 1:
                    # bucket markers: identity forward; backward pins each
                    # bucket's grads to the reduce-scattered layout at its
                    # production point (train/_overlap.py).  Under grad
                    # accumulation the sync moves AFTER the scan instead —
                    # one reduction per OPTIMIZER step, not per microbatch
                    p = overlap.mark(p)
                loss, m = trial.loss(model, p, mb, step_rng)
                return loss, m

            if agg == 1:
                (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    state.params, batch
                )
            else:
                # gradient accumulation: scan over stacked microbatches
                # [agg, batch, ...] accumulating grads on device — the
                # reference's aggregation_frequency loop
                # (_pytorch_context.py:708-914) without host round-trips
                def micro(carry, mb):
                    gacc, lacc, macc = carry
                    (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                        state.params, mb
                    )
                    gacc = jax.tree.map(jnp.add, gacc, g)
                    macc = {k: macc[k] + m[k].astype(jnp.float32) for k in macc}
                    return (gacc, lacc + l, macc), None

                g0 = jax.tree.map(jnp.zeros_like, state.params)
                m0 = {
                    k: jnp.zeros((), jnp.float32)
                    for k in state.metric_acc
                    if k not in ("loss", "lr")  # synthesized post-scan
                }
                (grads, loss, metrics), _ = jax.lax.scan(
                    micro, (g0, jnp.zeros((), jnp.float32), m0), batch
                )
                loss = loss / agg
                metrics = {k: v / agg for k, v in metrics.items()}
                if average_grads:
                    grads = jax.tree.map(lambda g: g / agg, grads)
                if overlap is not None:
                    # sync the ACCUMULATED grads once — inside the scan the
                    # markers would issue agg collectives per optimizer step
                    grads = overlap.apply_grad_sync(grads)
            # named for the device trace (``jit.scopes``): the optimizer's
            # share of a step, whichever optimizer the trial built
            with jax.named_scope("optim.update"):
                if hasattr(tx, "apply_step"):
                    # fused full-step optimizer (ops/fused_adamw.py): produces
                    # new params directly — materializing an updates tree would
                    # cost two extra HBM passes on a bandwidth-bound step
                    new_params, new_opt = tx.apply_step(
                        grads, state.opt_state, state.params, shardings=update_shardings
                    )
                else:
                    updates, new_opt = tx.update(grads, state.opt_state, state.params)
                    new_params = optax.apply_updates(state.params, updates)
            if overlap is not None:
                # the closing all-gather: sharded update back to the
                # params' own layout; opt state pinned so donated buffers
                # round-trip with stable shardings step over step
                new_params = overlap.restore_params(new_params)
                new_opt = overlap.pin_opt_state(new_opt)
            metrics = dict(metrics)
            metrics["loss"] = loss
            # schedule-state surfacing (reference LRScheduler wrapper): a
            # trial exposing `lr_schedule` (an optax schedule callable)
            # gets its current learning rate reported with every batch
            schedule = getattr(trial, "lr_schedule", None)
            if schedule is not None:
                with jax.named_scope("optim.update"):
                    metrics["lr"] = schedule(state.step).astype(jnp.float32)
            with jax.named_scope("train.metrics"):  # the step's bookkeeping: scalars
                acc = {
                    k: state.metric_acc[k] + metrics[k].astype(jnp.float32)
                    for k in state.metric_acc
                }
                return state.replace(
                    step=state.step + 1,
                    params=new_params,
                    opt_state=new_opt,
                    metric_acc=acc,
                    metric_count=state.metric_count + 1.0,
                )

        from determined_tpu.train._reducer import MEAN, get_reducer

        reducers = {k: get_reducer(v) for k, v in trial.evaluation_reducers().items()}
        self._reducers = reducers

        def eval_step(params, batch, acc, count):
            metrics = trial.evaluate_batch(model, params, batch)
            new_acc = {}
            for k, v in metrics.items():
                red = reducers.get(k, MEAN)
                carry = acc.get(k, jnp.asarray(red.init, jnp.float32))
                new_acc[k] = red.accumulate(carry, v.astype(jnp.float32))
            return new_acc, count + 1.0

        # ---- retrace sentinel (lint/_runtime.py) -------------------------
        # Wrapping happens BEFORE jit: jax then calls the wrapper once per
        # trace, so the count is the compile count for this callable.  A
        # train step traces once; an eval step twice (first validation
        # batch sees an empty metric accumulator, later ones a populated
        # one).  Anything beyond that is a silent recompile the sentinel
        # logs — exactly what the jit-reuse cache exists to prevent.
        from determined_tpu.lint._runtime import get_retrace_sentinel

        sentinel = get_retrace_sentinel()
        use_sentinel = sentinel.enabled or (
            ctx.exp_config is not None
            and getattr(ctx.exp_config, "lint", None) is not None
            and ctx.exp_config.lint.retrace_sentinel
        )
        if use_sentinel:
            label = f"{type(trial).__module__}:{type(trial).__qualname__}"
            train_step = sentinel.wrap(f"{label}.train_step", train_step, allowed=1)
            eval_step = sentinel.wrap(f"{label}.eval_step", eval_step, allowed=2)

        # ---- cross-trial jit reuse ---------------------------------------
        # Same-architecture trials in one process (the concurrent search
        # scheduler, sequential ASHA backfills) share ONE jitted callable
        # per step signature instead of re-tracing/re-compiling identical
        # programs — see train/_jit_cache.py for exactly what keys the
        # signature and why sharing is sound.
        from determined_tpu.train import _jit_cache
        from determined_tpu.utils.compilation_cache import timed_first_call

        use_cache = opt.jit_cache if opt is not None else True
        if use_cache:
            key = _jit_cache.step_cache_key(
                trial=trial,
                hparams=ctx.hparams,
                mesh=self.mesh,
                agg=agg,
                average_grads=average_grads,
                sample_batch=sample,
                metric_keys=metric_keys,
                rules=ctx.rules,
                # both knobs reshape the traced program (collective
                # structure / matmul arithmetic): toggling either must
                # never serve a stale trace
                overlap=(
                    self._overlap_plan.fingerprint()
                    if self._overlap_plan is not None
                    else "overlap:none"
                ),
                quant=opt.quantized_matmul if opt else "none",
                # the microbatch schedule + virtual-stage count reshape
                # the traced program (trip counts, param layout, custom
                # backward): toggling must never serve a stale trace
                pipeline=(
                    pipe_sched.fingerprint()
                    if pipe_sched is not None
                    else "pipe:none"
                ),
            )
            cache = _jit_cache.get_step_cache()
            entry = cache.lookup(key)
            if entry is None:
                train_jit = jax.jit(train_step, donate_argnums=0)
                entry = cache.insert(
                    key,
                    _jit_cache.CachedSteps(
                        train_step=timed_first_call(
                            train_jit, "jit.compile.train"
                        ),
                        eval_step=timed_first_call(
                            jax.jit(eval_step, donate_argnums=2), "jit.compile.eval"
                        ),
                        trial_class=f"{type(trial).__module__}:{type(trial).__qualname__}",
                        train_jit=train_jit,
                    ),
                )
            else:
                logger.info(
                    "jit-reuse cache hit for %s (key %s…): sharing compiled "
                    "train/eval steps",
                    type(trial).__qualname__,
                    key[:12],
                )
            self._train_step = entry.train_step
            self._eval_step = entry.eval_step
            self._train_step_jit = entry.train_jit
        else:
            self._train_step_jit = jax.jit(train_step, donate_argnums=0)
            self._train_step = timed_first_call(
                self._train_step_jit, "jit.compile.train"
            )
            self._eval_step = timed_first_call(
                jax.jit(eval_step, donate_argnums=2), "jit.compile.eval"
            )

        # ---- goodput-ledger context (observability/_goodput.py) ----------
        # tokens/MFU in the ledger need per-step token counts and the
        # device roofline; both are best-effort — a trial without a known
        # tokens-per-sample simply reports samples/s only
        self._tokens_per_sample = getattr(trial, "tokens_per_sample", None) or (
            (ctx.hparams or {}).get("seq_len")
            if isinstance((ctx.hparams or {}).get("seq_len"), int)
            else None
        )
        tracer = get_tracer()
        stamps.append(time.monotonic())
        for stage, t0, t1 in zip(("build", "shapes", "init", "steps"), stamps, stamps[1:]):
            tracer.record_span("trainer.setup." + stage, "setup", t0, t1)
        if tracer.enabled:
            dev = self.mesh.devices.flat[0]
            # default=0: an unknown chip (CPU tests) reports no roofline
            # rather than a bogus MFU against a TPU peak
            peak = chip_peak_flops(getattr(dev, "device_kind", ""), default=0.0)
            if peak:
                tracer.gauge(
                    "device.peak_flops_total", peak * float(self.mesh.devices.size)
                )
            fpt = getattr(trial, "flops_per_token", None)
            if fpt:
                tracer.gauge("train.flops_per_token", float(fpt))
            if self._bubble_model is not None:
                # static schedule facts for the ledger: the modeled idle
                # fraction and the tick counts behind it
                tracer.gauge(
                    "step.bubble.fraction", float(self._bubble_model.fraction)
                )
                tracer.gauge(
                    "step.bubble.ticks_total",
                    float(self._bubble_model.schedule.total_ticks),
                )
                tracer.gauge(
                    "step.bubble.ticks_idle",
                    float(self._bubble_model.schedule.bubble_ticks),
                )

    def _place_on_mesh(self, tree: Any) -> Any:
        """Replicate any leaf not already sharded over THIS mesh.

        Multi-process: ``device_put`` refuses non-addressable shardings, so
        replication goes through ``make_array_from_callback`` (every process
        supplies its addressable replicas from the host value).
        """
        from jax.sharding import NamedSharding, PartitionSpec

        repl = NamedSharding(self.mesh, PartitionSpec())
        multiprocess = jax.process_count() > 1

        def fix(x):
            if not isinstance(x, jax.Array):
                return x
            s = x.sharding
            if isinstance(s, NamedSharding) and s.mesh.devices.size == self.mesh.devices.size \
                    and set(d.id for d in s.mesh.devices.flat) == set(d.id for d in self.mesh.devices.flat):
                return x
            if multiprocess:
                if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
                    data = np.asarray(jax.random.key_data(x))
                    garr = jax.make_array_from_callback(
                        data.shape, repl, lambda idx: data[idx]
                    )
                    return jax.random.wrap_key_data(garr, impl=jax.random.key_impl(x))
                host = np.asarray(x)
                return jax.make_array_from_callback(host.shape, repl, lambda idx: host[idx])
            return jax.device_put(x, repl)

        return jax.tree.map(fix, tree)

    # -- input pipeline ----------------------------------------------------

    def _input_opts(self) -> Tuple[int, int]:
        """(prefetch_depth, device_prefetch) from config, defaulting to the
        overlapped pipeline (2/2 = background fetch + double buffering)."""
        opt = self.context.exp_config.optimizations if self.context.exp_config else None
        if opt is None:
            return 2, 2
        return opt.prefetch_depth, opt.device_prefetch

    # -- length arithmetic -------------------------------------------------

    def _to_batches(self, length: Optional[Length]) -> Optional[int]:
        """Convert a Length to OPTIMIZER steps.  With gradient accumulation
        each step consumes ``agg`` loader batches, so epoch/record lengths
        divide by it (a 1-epoch run is one data pass regardless of agg)."""
        if length is None:
            return None
        length = Length.parse(length)
        if length.unit == "batches":
            return length.units
        if length.unit == "epochs":
            return max(
                1, length.units * self.train_loader.batches_per_epoch // self.agg
            )
        # records
        gbs = self.train_loader.sampler.global_batch * self.agg
        return max(1, length.units // gbs)

    # -- checkpoint --------------------------------------------------------

    def _async_checkpointing(self) -> bool:
        opt = self.context.exp_config.optimizations if self.context.exp_config else None
        enabled = opt.async_checkpointing if opt is not None else True
        # Multi-process CPU gangs (devcluster) run collectives over gloo,
        # whose TCP pairs cannot carry two in-flight collectives from
        # different threads: the background writer's sync_global_devices
        # barrier interleaves with the training step's psum and aborts the
        # process (gloo EnforceNotMet preamble.length mismatch).  TPU/GPU
        # runtimes order concurrent collectives, so only CPU downgrades.
        # This is the collective-SEQUENCE hazard class the lint package's
        # CollectiveSequenceSentinel polices at runtime: every rank must
        # issue the same ops in the same order, and a second thread
        # injecting collectives breaks that contract on transports that
        # don't serialize them (docs/lint.md, "SPMD correctness").
        if enabled and jax.process_count() > 1 and jax.default_backend() == "cpu":
            return False
        return enabled

    def _snapshot_fits(self, tree: Any) -> bool:
        """Whether an on-device copy of ``tree`` fits beside what each
        device already holds AND the scratch the step programs reserve
        while they run.  An overlapped save holds that copy while training
        goes on; for a state over half the device's memory (the const.yaml
        LM on one v5e: 7.5 of 15.75 GiB held, 5.0 GiB of step scratch) the
        copy is the out-of-memory error of the next step — measured on the
        chip: "Error loading program 'jit_train_step': Attempting to
        reserve 5.04G" — and the save has to block instead.  The scratch is
        not in the allocator's statistics; it is read from the compiled
        programs.  A backend that reports no limit (the CPU) is taken to
        fit.  The answer is agreed across ranks: the two save paths run
        different collectives."""
        need: Dict[Any, int] = {}
        for leaf in jax.tree.leaves(tree):
            if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
                continue  # a few words
            for shard in leaf.addressable_shards:
                need[shard.device] = need.get(shard.device, 0) + shard.data.nbytes
        scratch = max(
            getattr(self._train_step, "temp_bytes", 0),
            getattr(self._eval_step, "temp_bytes", 0),
        )
        fits = True
        for dev, nbytes in need.items():
            stats = dev.memory_stats() or {}
            limit = stats.get("bytes_limit")
            held = stats.get("bytes_in_use", 0)
            reserve = max(scratch, stats.get("peak_bytes_reserved", 0))
            if limit and held + nbytes + reserve > limit:
                logger.info(
                    "checkpoint: a %.1f GiB on-device snapshot does not fit on %s "
                    "beside %.1f GiB held and %.1f GiB of step scratch (limit "
                    "%.1f GiB); saving synchronously",
                    nbytes / 2**30, dev, held / 2**30, reserve / 2**30, limit / 2**30,
                )
                fits = False
                break
        dist = self.core.distributed
        return all(dist.allgather(fits)) if dist.size > 1 else fits

    def _snapshot_arrays(self, tree: Any) -> Any:
        """On-device copy of the array state.  The train step donates its
        input state (``donate_argnums=0``), so the buffers a background
        writer reads would be invalidated by the NEXT step — the copy
        (one HBM pass, ~ms) decouples them."""

        def copy_one(x):
            if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
                return jax.random.wrap_key_data(
                    jnp.copy(jax.random.key_data(x)), impl=jax.random.key_impl(x)
                )
            return jnp.copy(x)

        if self._snapshot_jit is None:
            self._snapshot_jit = jax.jit(lambda t: jax.tree.map(copy_one, t))
        return self._snapshot_jit(tree)

    def _drain_pending_save(self) -> Optional[str]:
        """Wait for the in-flight background save (if any) and run its
        collective finalize.  Must be called from the main thread at a
        point every rank reaches identically (next save / preempt / exit).

        Multi-rank failure semantics: before entering the collective
        finalize, every rank allgathers its writer's error flag.  A failed
        background writer on ONE rank therefore fails ALL ranks here, fast
        and together — without the exchange, the healthy ranks would enter
        the finalize collective and hang on the dead rank until the 600s
        collective timeout.  This is the canonical exchange-then-escape
        idiom the ``conditional-collective-escape`` lint rule encodes: the
        raise below is guarded by ``failed_ranks``, which is derived from
        the allgather result and therefore rank-uniform — the pass
        recognizes that and stays quiet, where a raise on the LOCAL flag
        would be flagged.  The ``checkpoint.stall`` span records how long
        training sat blocked on the drain either way.
        """
        p = self._pending_save
        if p is None:
            return None
        self._pending_save = None
        tracer = get_tracer()
        stall_t0 = time.monotonic()
        p.thread.join()
        failed = bool(p.errors)
        dist = self.core.distributed
        if dist.size > 1:
            flags = dist.allgather(failed)
            failed_ranks = [r for r, f in enumerate(flags) if f]
        else:
            failed_ranks = [0] if failed else []
        tracer.record_span(
            "checkpoint.stall",
            "checkpoint",
            stall_t0,
            time.monotonic(),
            {"storage_id": p.storage_id, "failed_ranks": failed_ranks},
        )
        if failed_ranks:
            if p.errors:
                raise RuntimeError(
                    f"async checkpoint {p.storage_id} failed "
                    f"(ranks {failed_ranks})"
                ) from p.errors[0]
            raise RuntimeError(
                f"async checkpoint {p.storage_id} failed on rank(s) "
                f"{failed_ranks}; failing fast before the collective finalize"
            )
        with tracer.span("checkpoint.finalize", cat="checkpoint", storage_id=p.storage_id):
            p.finish()
        self.latest_checkpoint = p.storage_id
        for cb in self.callbacks.values():
            cb.on_checkpoint_write_end(p.storage_id)
        logger.info("checkpoint %s at step %d", p.storage_id, p.step)
        return p.storage_id

    def _array_state(self) -> Dict[str, Any]:
        """The arrays a checkpoint holds."""
        return {
            "step": self.state.step,
            "params": self.state.params,
            "opt_state": self.state.opt_state,
            "rng": self.state.rng,
        }

    def _save_checkpoint(self, asynchronous: bool = True) -> str:
        self._drain_pending_save()  # at most one save in flight
        dist = self.core.distributed
        shard = dist.size > 1
        array_state = self._array_state()
        trainer_state = {
            "steps_completed": self.steps_completed,
            "train_loader": self.train_loader.state_dict(),
            "callbacks": {k: cb.state_dict() for k, cb in self.callbacks.items()},
            "best_validation": self.best_validation,
            # rebuild-from-checkpoint info (reference pytorch/_load.py):
            # enough to reconstruct the Trial without the experiment
            "trial_class": f"{type(self.trial).__module__}:{type(self.trial).__qualname__}",
            "hparams": dict(self.context.hparams),
            "exp_config": self.context.exp_config.raw if self.context.exp_config else None,
            "seed": self.context.seed,
            # mesh the arrays were sharded over when written — a restore onto
            # a different mesh (elastic reshard) is detected by comparing this
            # against the live mesh and recorded as a ``trial.resize`` span
            "mesh_axes": {k: int(v) for k, v in self.mesh.shape.items()},
        }
        metadata = {
            "steps_completed": self.steps_completed,
            "framework": "determined_tpu",
            # lineage pointer: lets a resume that finds THIS checkpoint
            # corrupt fall back to the previous good one (the manifest
            # carries a copy; this survives a kill before the manifest)
            "parent_storage_id": self.latest_checkpoint,
        }
        if not (
            asynchronous
            and self._async_checkpointing()
            and self._snapshot_fits(array_state)
        ):
            with get_tracer().span(
                "checkpoint.save", cat="checkpoint", mode="sync", step=self.steps_completed
            ):
                with self.core.checkpoint.store_path(metadata, shard=shard) as (path, sid):
                    for cb in self.callbacks.values():
                        cb.on_checkpoint_write_start(path)
                    serialization.save_arrays(path, array_state)
                    if dist.is_chief:
                        serialization.save_trainer_state(path, trainer_state)
            self.latest_checkpoint = sid
            for cb in self.callbacks.values():
                cb.on_checkpoint_write_end(sid)
            logger.info("checkpoint %s at step %d", sid, self.steps_completed)
            return sid

        # overlapped save: snapshot on device, serialize on a background
        # thread, collective finalize at the next drain point (SURVEY §7(b))
        with get_tracer().span(
            "checkpoint.dispatch", cat="checkpoint", step=self.steps_completed
        ):
            path, sid, finish = self.core.checkpoint.store_path_async(metadata, shard=shard)
            for cb in self.callbacks.values():
                cb.on_checkpoint_write_start(path)
            snapshot = self._snapshot_arrays(array_state)
        is_chief = dist.is_chief
        errors: list = []

        def work() -> None:
            try:
                with get_tracer().span(
                    "checkpoint.write", cat="checkpoint", storage_id=sid
                ):
                    serialization.save_arrays(path, snapshot)
                    if is_chief:
                        serialization.save_trainer_state(path, trainer_state)
            except BaseException as e:  # surfaced at the drain point
                # single background writer; the drain point joins this
                # thread BEFORE reading errors (happens-before via join)
                errors.append(e)  # dtpu: lint-ok[unlocked-shared-state]

        thread = threading.Thread(target=work, name="dtpu-ckpt-writer", daemon=True)
        thread.start()
        self._pending_save = _PendingSave(
            thread=thread, finish=finish, storage_id=sid,
            step=self.steps_completed, errors=errors,
        )
        logger.info("async checkpoint %s started at step %d", sid, self.steps_completed)
        return sid

    def _verify_on_restore(self) -> bool:
        cfg = self.context.exp_config
        ft = getattr(cfg, "fault_tolerance", None) if cfg is not None else None
        return ft.verify_checkpoints if ft is not None else True

    def _restore_checkpoint(self, storage_id: str) -> None:
        """Restore with manifest verification, walking the parent lineage
        on corruption.

        Trainer-written checkpoints always end finalize with a manifest,
        so resume requires one (``require_manifest=True``): a checkpoint
        whose writer died mid-upload has no manifest and is rejected, and
        a truncated/bit-flipped file fails the size/md5 check — either way
        the restore falls back to the checkpoint's recorded parent instead
        of silently resuming from poison (reference: the master only ever
        resumes from checkpoints it recorded as COMPLETED).
        """
        verify = self._verify_on_restore()
        sid: Optional[str] = storage_id
        tried = []
        while sid:
            try:
                with self.core.checkpoint.restore_path(
                    sid, verify=verify, require_manifest=verify
                ) as path:
                    self.restore_from_path(path)
                self.latest_checkpoint = sid
                if tried:
                    logger.warning(
                        "resumed from fallback checkpoint %s (rejected: %s)",
                        sid,
                        ", ".join(tried),
                    )
                logger.info("restored checkpoint %s at step %d", sid, self.steps_completed)
                return
            except (CheckpointCorruptError, CheckpointNotFoundError) as e:
                logger.warning("checkpoint %s unusable for resume: %s", sid, e)
                tried.append(sid)
                parent = self.core.checkpoint.get_checkpoint_parent(sid)
                if parent in tried:
                    break  # defensive: a lineage cycle must not loop forever
                sid = parent
        raise CheckpointCorruptError(
            f"no usable checkpoint in lineage of {storage_id} "
            f"(tried: {', '.join(tried)}); checkpoints written before the "
            "manifest era can be resumed by setting "
            "fault_tolerance.verify_checkpoints: false"
        )

    def _restore_checkpoint_traced(self, storage_id: str) -> None:
        """Resume replay, recorded as a ``restore`` span — the goodput
        ledger's "time spent re-reaching the pre-crash state" bucket."""
        with get_tracer().span("checkpoint.restore", cat="restore", storage_id=storage_id):
            self._restore_checkpoint(storage_id)

    def restore_from_path(self, path: str) -> None:
        """Load arrays + trainer state from an already-local checkpoint dir
        (``_restore_checkpoint`` handles storage download; this is the shared
        tail, also used by ``train.load_trial_from_checkpoint``).

        The checkpoint may have been written on a DIFFERENT mesh (elastic
        reshard): ``abstract_like`` targets the *current* state's shardings,
        so orbax re-lays every array — params and the sharded optimizer
        mirrors alike — onto the live mesh, and the loader rescales its
        consumed-sample position if the global batch changed.  A cross-mesh
        restore is wrapped in a ``trial.resize`` span so the profile
        attributes the reshard window."""
        tstate = serialization.load_trainer_state(path)
        stored_axes = tstate.get("mesh_axes")
        cur_axes = {k: int(v) for k, v in self.mesh.shape.items()}
        resizing = stored_axes is not None and (
            {k: int(v) for k, v in stored_axes.items()} != cur_axes
        )
        if not resizing:
            self._restore_tail(path, tstate)
            return
        fmt = lambda ax: ",".join(f"{k}={v}" for k, v in ax.items())  # noqa: E731
        logger.info(
            "elastic reshard: restoring checkpoint written on mesh (%s) "
            "onto mesh (%s)", fmt(stored_axes), fmt(cur_axes),
        )
        with get_tracer().span(
            "trial.resize",
            cat="restore",
            from_mesh=fmt(stored_axes),
            to_mesh=fmt(cur_axes),
        ):
            self._restore_tail(path, tstate)

    def _restore_tail(self, path: str, tstate: Dict[str, Any]) -> None:
        # A restore needs the fresh init's shapes and shardings (kept by
        # _setup), not its arrays.  Let go of those BEFORE the restore
        # allocates: holding both, a state over half the device's memory
        # (the const.yaml LM: 7.5 of 15.75 GiB on a v5e) cannot be resumed
        # or served at all.  Only a trial with runtime hparams still needs
        # the fresh optimizer state (below).
        runtime = getattr(self.trial, "compile_cache_runtime_hparams", tuple)() or ()
        fresh_opt_state = self.state.opt_state if runtime else None
        self.state = self.state.replace(params=None, opt_state=None)
        restored = serialization.restore_arrays(path, self._restore_template)
        self.state = self.state.replace(**restored).reset_metrics()
        # declared-runtime hyperparameters (compile_cache_runtime_hparams,
        # e.g. an inject_hyperparams lr) live in opt_state, so a restore
        # would resurrect the CHECKPOINT's values — correct for a crash
        # resume (same hparams), wrong for a PBT clone whose explore step
        # just perturbed them.  The trial's own hparams are authoritative:
        # graft the freshly-built hyperparams back over the restored tree.
        if runtime:
            self.state = self.state.replace(
                opt_state=self._reinject_runtime_hparams(
                    fresh_opt_state, self.state.opt_state
                )
            )
        self.steps_completed = int(tstate["steps_completed"])
        self.train_loader.load_state_dict(tstate["train_loader"])
        for k, cb in self.callbacks.items():
            cb.load_state_dict(tstate.get("callbacks", {}).get(k, {}))
        self.best_validation = tstate.get("best_validation")
        for cb in self.callbacks.values():
            cb.on_checkpoint_load(path)

    def _reinject_runtime_hparams(self, fresh: Any, restored: Any) -> Any:
        """Replace ``hyperparams`` nodes (optax ``InjectHyperparamsState``)
        in a restored opt_state with the freshly-initialized ones, which
        were built from THIS trial's hparams.  Only called for a trial
        that declares runtime hparams."""

        def graft(f: Any, r: Any) -> Any:
            if type(f) is not type(r):
                return r
            if hasattr(r, "hyperparams") and hasattr(r, "_replace"):
                out = r._replace(hyperparams=f.hyperparams)
                if hasattr(r, "inner_state"):
                    out = out._replace(inner_state=graft(f.inner_state, r.inner_state))
                return out
            if isinstance(r, (tuple, list)) and len(f) == len(r):
                parts = [graft(a, b) for a, b in zip(f, r)]
                if hasattr(r, "_fields"):  # other namedtuple states
                    return type(r)(*parts)
                return type(r)(parts) if isinstance(r, list) else tuple(parts)
            return r

        return graft(fresh, restored)

    # -- validation --------------------------------------------------------

    def _validate(self) -> Dict[str, float]:
        for cb in self.callbacks.values():
            cb.on_validation_start()
        acc: Dict[str, jax.Array] = {}
        count = jnp.zeros((), jnp.float32)
        # the validation sweep gets the same overlap as training: host fetch
        # on a worker, eager to_global one batch ahead of the eval step
        prefetch_depth, device_buffer = self._input_opts()
        with EpochFeed(
            self.val_loader.iter_epoch(0),
            self.mesh,
            prefetch_depth=prefetch_depth,
            device_buffer=device_buffer,
        ) as feed:
            with self.mesh:
                for batch in feed:
                    acc, count = self._eval_step(self.state.params, batch, acc, count)
        from determined_tpu.train._reducer import MEAN

        acc_host, n = jax.device_get((acc, count))
        metrics = (
            {
                k: float(self._reducers.get(k, MEAN).finalize(float(v), float(n)))
                for k, v in acc_host.items()
            }
            if n
            else {}
        )
        if self.core.distributed.is_chief:
            self.core.train.report_validation_metrics(self.steps_completed, metrics)
            logger.info(
                "validation at step %d: %s", self.steps_completed, _fmt(metrics)
            )
        for cb in self.callbacks.values():
            cb.on_validation_end(metrics)
        return metrics

    def _is_best(self, metrics: Dict[str, float]) -> bool:
        name = self._searcher_metric or "validation_loss"
        if name not in metrics:
            return True  # nothing to compare on; treat as best
        val = metrics[name]
        if self.best_validation is None:
            self.best_validation = val
            return True
        better = val < self.best_validation if self._smaller_is_better else val > self.best_validation
        if better:
            self.best_validation = val
        return better

    # -- the loop ----------------------------------------------------------

    def fit(
        self,
        max_length: Any,
        *,
        validation_period: Optional[Any] = None,
        checkpoint_period: Optional[Any] = None,
        report_period: Optional[Any] = None,
        latest_checkpoint: Optional[str] = None,
        checkpoint_policy: Optional[str] = None,
    ) -> Dict[str, Any]:
        """Train until ``max_length``; returns a summary dict."""
        tracer = get_tracer()
        if checkpoint_policy is None:
            cfg = self.context.exp_config
            checkpoint_policy = cfg.checkpoint_policy if cfg is not None else "best"
        if checkpoint_policy != "none" or latest_checkpoint:
            # this run will touch a checkpoint: the backend's import runs
            # beside set-up and compile, not in front of the first save
            serialization.prefetch_backend()
        with tracer.span("trainer.setup", cat="setup"):
            self._setup()

        max_steps = self._to_batches(Length.parse(max_length))
        val_sched = _BoundarySchedule(self._to_batches(validation_period), max_steps)
        ckpt_sched = _BoundarySchedule(self._to_batches(checkpoint_period), max_steps)
        rep_period = self._to_batches(report_period)
        if rep_period is None:
            rep_period = min(100, max(1, max_steps // 10))
        rep_sched = _BoundarySchedule(rep_period, max_steps)

        if latest_checkpoint:
            self._restore_checkpoint_traced(latest_checkpoint)

        # bounded trace window: a whole-run xplane capture grows without
        # limit, so tracing stops after profiling.end_after_batch steps —
        # counted from the RESUME point (computed after checkpoint restore
        # so restarts trace their own window, not an already-expired one)
        prof_cfg = (self.context.exp_config.profiling
                    if self.context.exp_config else None) or {}
        self._trace_stop_step = (
            self.steps_completed + int(prof_cfg.get("end_after_batch", 10))
            if prof_cfg.get("trace")
            else None
        )

        for cb in self.callbacks.values():
            cb.on_training_start(self)

        # overlapped input feed (docs/input-pipeline.md): host fetch runs on
        # a background worker, to_global on batch N+1 dispatches while step N
        # executes; __next__ commits the loader's CONSUMED position, so the
        # state_dict a checkpoint captures is exact regardless of how far
        # ahead the worker fetched
        prefetch_depth, device_buffer = self._input_opts()
        pipeline = InputPipeline(
            self.train_loader,
            self.mesh,
            agg=self.agg,
            prefetch_depth=prefetch_depth,
            device_buffer=device_buffer,
        )
        gbs = self.train_loader.sampler.global_batch * self.agg

        try:
            self._fit_loop(
                pipeline, max_steps, val_sched, ckpt_sched, rep_sched,
                checkpoint_policy, gbs,
            )
        finally:
            # the worker must die with the loop: on clean exit, preemption,
            # AND a crash unwinding toward the supervisor restart path —
            # restarts build fresh Trainers, so anything left running here
            # would accumulate across attempts
            pipeline.close()
            for ld in (self.train_loader, self.val_loader):
                if ld is not None:
                    ld.close()

        # a save still in flight must land before we exit or report completion
        self._drain_pending_save()

        # final: always leave at least one checkpoint unless policy is none
        if checkpoint_policy != "none" and self._last_ckpt_sid is None:
            self._last_ckpt_sid = self._save_checkpoint(asynchronous=False)

        for cb in self.callbacks.values():
            cb.on_trial_shutdown()

        return {
            "steps_completed": self.steps_completed,
            "latest_checkpoint": self._last_ckpt_sid,
            "validation_metrics": self._last_val_metrics,
            "stopped_early": self._stopped_early,
            "best_validation": self.best_validation,
        }

    def _fit_loop(
        self,
        pipeline: InputPipeline,
        max_steps: int,
        val_sched: _BoundarySchedule,
        ckpt_sched: _BoundarySchedule,
        rep_sched: _BoundarySchedule,
        checkpoint_policy: str,
        gbs: int,
    ) -> None:
        # lazy import, same convention as the retrace sentinel in _setup:
        # the trainer must not pull the lint analyzer package in at module
        # import time just for the (usually disabled) runtime hook
        from determined_tpu.lint._runtime import get_collective_sentinel

        cseq = get_collective_sentinel()
        tracer = get_tracer()
        hot_time = 0.0  # train-segment wall time since last report (excludes
        # validation/checkpoint so samples_per_second tracks training only)
        steps_since_report = 0
        self._last_ckpt_sid = None
        self._last_val_metrics = {}
        self._stopped_early = False
        epoch_seen = self.train_loader.epoch

        while self.steps_completed < max_steps:
            next_stop = min(
                val_sched.next_after(self.steps_completed),
                ckpt_sched.next_after(self.steps_completed),
                rep_sched.next_after(self.steps_completed),
                max_steps,
            )
            if (
                self._trace_stop_step is not None
                and self.core.profiler.tracing
                and self._trace_stop_step > self.steps_completed
            ):
                # break the hot segment at the trace boundary so the
                # capture window is end_after_batch steps, not
                # end_after_batch rounded up to the next report period
                next_stop = min(next_stop, self._trace_stop_step)
            # ---- hot segment: no host syncs ------------------------------
            seg_t0 = time.monotonic()
            seg_start_step = self.steps_completed
            # the mesh context makes trace-time sharding constraints resolve
            # for models that annotate activations without an explicit mesh
            with self.mesh:
                if tracer.enabled:
                    # traced twin of the loop below: two extra clock reads
                    # + two lock-free ring pushes per step attribute the
                    # step's wall-clock to input wait vs. step dispatch;
                    # the untraced branch stays byte-identical to before
                    while self.steps_completed < next_stop:
                        faults.fire("train.step", step=self.steps_completed)
                        t0 = time.monotonic()
                        batch = next(pipeline)
                        t1 = time.monotonic()
                        self.state = self._train_step(self.state, batch)
                        t2 = time.monotonic()
                        tracer.record_span("data.wait", "data", t0, t1)
                        tracer.record_span("step.dispatch", "step", t1, t2)
                        self.steps_completed += 1
                        steps_since_report += 1
                else:
                    while self.steps_completed < next_stop:
                        # fault-injection hook: tests crash a step here to
                        # exercise the supervised-restart path (no-op in prod)
                        faults.fire("train.step", step=self.steps_completed)
                        # already a device-global array; the pipeline stacked
                        # microbatches (agg > 1) and committed consumed state
                        batch = next(pipeline)
                        self.state = self._train_step(self.state, batch)
                        self.steps_completed += 1
                        steps_since_report += 1
            hot_time += time.monotonic() - seg_t0
            # collective-sequence sentinel: each dispatched step carries the
            # tensor-plane psums, so the SEGMENT boundary (which steps this
            # rank dispatched) is the dispatch-site signature — folded into
            # the rolling digest here, once per boundary (not per step),
            # and verified at the next control-plane exchange.  One attr
            # check when the sentinel is not installed.
            if cseq.installed:
                cseq.record(
                    self.core.distributed,
                    "step.segment",
                    f"{seg_start_step}-{self.steps_completed}",
                )
            if self.train_loader.epoch != epoch_seen:
                for e in range(epoch_seen, self.train_loader.epoch):
                    for cb in self.callbacks.values():
                        cb.on_epoch_end(e)
                epoch_seen = self.train_loader.epoch

            at_end = self.steps_completed >= max_steps
            if (
                self._trace_stop_step is not None
                and self.core.profiler.tracing
                and self.steps_completed >= self._trace_stop_step
            ):
                self.core.profiler.stop_trace()

            # ---- REPORT ---------------------------------------------------
            if rep_sched.is_boundary(self.steps_completed) or at_end:
                sync_t0 = time.monotonic()
                metrics = self.state.fetch_metrics()  # one host sync
                sync_t1 = time.monotonic()
                hot_time += sync_t1 - sync_t0
                # the boundary fetch is where the host finally waits for
                # every dispatched step — the device-compute proxy on the
                # host timeline (cat "step": productive in the ledger)
                tracer.record_span("step.boundary_block", "step", sync_t0, sync_t1)
                if steps_since_report:
                    tracer.counter("train.steps", float(steps_since_report))
                    tracer.counter("train.samples", float(steps_since_report * gbs))
                    if self._tokens_per_sample:
                        tracer.counter(
                            "train.tokens",
                            float(steps_since_report * gbs * self._tokens_per_sample),
                        )
                    # step metrics the trial wants on the trace's timeline
                    # (an expert layer's load): the period's mean x its steps,
                    # so that sums over any stretch divide by train.steps.
                    # From the metrics just fetched: no fetch of their own.
                    for name in getattr(self.trial, "step_counters", ()):
                        if name in metrics:
                            tracer.counter(name, metrics[name] * steps_since_report)
                    if self._comm_model is not None:
                        # step.comm ledger rows (observability/_goodput.py):
                        # measured payload bytes, exposed/hidden split from
                        # the bucket-schedule model against the segment's
                        # average step time (counters, not spans — they
                        # must not perturb the span-nesting attribution)
                        hops = self._comm_model.split_hops(
                            hot_time / steps_since_report
                        )
                        n = float(steps_since_report)
                        tracer.counter(
                            "step.comm.bytes",
                            float(self._comm_model.total_bytes_per_step) * n,
                        )
                        exposed_s = sum(e for e, _ in hops.values())
                        hidden_s = sum(h for _, h in hops.values())
                        tracer.counter("step.comm.exposed_us", exposed_s * 1e6 * n)
                        tracer.counter("step.comm.hidden_us", hidden_s * 1e6 * n)
                        # per-hop rows: the DCN hop only exists on a
                        # multi-slice mesh; zero rows are suppressed so
                        # single-slice ledgers look exactly as before
                        hop_bytes = {
                            "ici": self._comm_model.bytes_per_step,
                            "dcn": self._comm_model.dcn_bytes_per_step,
                        }
                        for hop, (he, hh) in hops.items():
                            if not hop_bytes[hop]:
                                continue
                            tracer.counter(
                                f"step.comm.{hop}.bytes", float(hop_bytes[hop]) * n
                            )
                            tracer.counter(f"step.comm.{hop}.exposed_us", he * 1e6 * n)
                            tracer.counter(f"step.comm.{hop}.hidden_us", hh * 1e6 * n)
                    if self._bubble_model is not None:
                        # step.bubble ledger rows: pipe-axis idle time per
                        # the schedule's analytic tick model applied to
                        # the segment's average step time (counters, like
                        # step.comm, so span-nesting attribution stays
                        # intact)
                        bubble_s, _ = self._bubble_model.split(
                            hot_time / steps_since_report
                        )
                        tracer.counter(
                            "step.bubble.exposed_us",
                            bubble_s * 1e6 * float(steps_since_report),
                        )
                self.state = self.state.reset_metrics()
                metrics["samples_per_second"] = steps_since_report * gbs / max(hot_time, 1e-9)
                hot_time = 0.0
                steps_since_report = 0
                # metrics are identical on every rank (global-array math);
                # only the chief reports (reference: chief-only report_*)
                if self.core.distributed.is_chief:
                    self.core.train.report_training_metrics(self.steps_completed, metrics)
                    self.core.train.report_progress(self.steps_completed / max_steps)
                    logger.info(
                        "step %d/%d: %s", self.steps_completed, max_steps, _fmt(metrics)
                    )
                    # once a process: how its start went, from the tracer's events
                    log_setup_line(logger, "first report")
                for cb in self.callbacks.values():
                    cb.on_training_workload_end(self.steps_completed, metrics)

            # ---- VALIDATE -------------------------------------------------
            validated = False
            if val_sched.period is not None and (
                val_sched.is_boundary(self.steps_completed) or at_end
            ):
                with tracer.span("validate", cat="validate", step=self.steps_completed):
                    self._last_val_metrics = self._validate()
                validated = True

            # ---- CHECKPOINT ----------------------------------------------
            want_ckpt = ckpt_sched.period is not None and ckpt_sched.is_boundary(
                self.steps_completed
            )
            if validated and checkpoint_policy == "all":
                want_ckpt = True
            if validated and checkpoint_policy == "best" and self._is_best(self._last_val_metrics):
                want_ckpt = True
            # ---- PREEMPT --------------------------------------------------
            preempted = self.core.preempt.should_preempt()
            if preempted:
                want_ckpt = True
            if want_ckpt:
                pending = self._pending_save
                if (
                    preempted
                    and pending is not None
                    and pending.step == self.steps_completed
                    and not pending.errors
                ):
                    # a save of this exact step is already in flight:
                    # wait for it instead of writing a duplicate
                    self._last_ckpt_sid = self._drain_pending_save()
                else:
                    # on preemption the save must be durable before exit,
                    # so skip the overlap and write synchronously
                    self._last_ckpt_sid = self._save_checkpoint(asynchronous=not preempted)
            if preempted:
                logger.info("preempted at step %d; exiting cleanly", self.steps_completed)
                self._stopped_early = True
                # should_preempt() IS the exchange: under WorkersAskChief it
                # allgathers every rank's flag, so `preempted` is identical
                # on all ranks and the whole gang breaks on the same step
                break  # dtpu: lint-ok[conditional-collective-escape]
