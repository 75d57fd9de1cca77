"""JaxTrial: the user-facing trial ABC + TrialContext.

Reference: ``PyTorchTrial`` (``harness/determined/pytorch/_pytorch_trial.py:
1192-1449``) — users subclass, implement data/model/optimizer builders and a
per-batch loss; the framework owns the loop, distribution, checkpointing.

TPU-first divergences:
- ``loss``/``evaluate_batch`` are **pure functions** traced once by XLA; no
  imperative ``backward()``/``step_optimizer()`` calls (reference
  ``_pytorch_context.py:708,814``) — the Trainer differentiates and applies
  updates inside one jitted step.
- parallelism comes from the context's mesh + logical sharding rules, not
  from wrapping (no ``wrap_model``/``wrap_optimizer``).
"""

from __future__ import annotations

import abc
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np
import optax
from jax.sharding import Mesh

from determined_tpu.core._context import Context as CoreContext
from determined_tpu.data._loader import DataLoader
from determined_tpu.parallel.mesh import MeshAxes
from determined_tpu.parallel.sharding import DEFAULT_RULES, LogicalAxisRules

Metrics = Dict[str, jax.Array]


class TrialContext:
    """Per-trial handle: hyperparameters + mesh + core services.

    The analog of ``PyTorchTrialContext`` minus all the wrapping methods —
    on TPU the mesh IS the distribution strategy.
    """

    def __init__(
        self,
        core: CoreContext,
        mesh: Mesh,
        hparams: Optional[Dict[str, Any]] = None,
        rules: Optional[LogicalAxisRules] = None,
        seed: int = 0,
        exp_config: Optional[Any] = None,
    ) -> None:
        self.core = core
        self.mesh = mesh
        self.hparams = dict(hparams or {})
        self.rules = dict(rules if rules is not None else DEFAULT_RULES)
        self.seed = seed
        self.exp_config = exp_config

    # -- hyperparameters ---------------------------------------------------

    def get_hparam(self, name: str, default: Any = ...) -> Any:
        if name in self.hparams:
            v = self.hparams[name]
            # collapsed Const from the config system
            return getattr(v, "val", v)
        if default is ...:
            raise KeyError(f"hyperparameter {name!r} not set and no default given")
        return default

    # -- topology ----------------------------------------------------------

    @property
    def distributed(self):
        return self.core.distributed

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    @property
    def batch_axis_size(self) -> int:
        """Product of batch-carrying mesh axes (dp * fsdp)."""
        n = 1
        for a in MeshAxes.BATCH_AXES:
            n *= self.mesh.shape.get(a, 1)
        return n

    def get_global_batch_size(self) -> int:
        return int(self.get_hparam("global_batch_size", 32))

    def get_per_slot_batch_size(self) -> int:
        gbs = self.get_global_batch_size()
        if gbs % self.batch_axis_size:
            raise ValueError(
                f"global_batch_size {gbs} not divisible by batch mesh axes "
                f"({self.batch_axis_size})"
            )
        return gbs // self.batch_axis_size


class Callback:
    """Training lifecycle hooks — reference ``PyTorchCallback``
    (``harness/determined/pytorch/_callback.py``).  All hooks are host-side
    and run at boundaries, never inside the jitted step."""

    def on_training_start(self, trainer: Any) -> None: ...

    def on_epoch_start(self, epoch: int) -> None: ...

    def on_epoch_end(self, epoch: int) -> None: ...

    def on_validation_start(self) -> None: ...

    def on_validation_end(self, metrics: Dict[str, float]) -> None: ...

    def on_checkpoint_write_start(self, path: str) -> None: ...

    def on_checkpoint_write_end(self, storage_id: str) -> None: ...

    def on_checkpoint_load(self, path: str) -> None: ...

    def on_training_workload_end(
        self, steps_completed: int, metrics: Dict[str, float]
    ) -> None: ...

    def on_trial_shutdown(self) -> None: ...

    # extra state carried through checkpoints
    def state_dict(self) -> Dict[str, Any]:
        return {}

    def load_state_dict(self, state: Dict[str, Any]) -> None: ...


class JaxTrial(abc.ABC):
    """Subclass this; the Trainer drives everything else."""

    def __init__(self, context: TrialContext) -> None:
        self.context = context

    # -- builders ----------------------------------------------------------

    @abc.abstractmethod
    def build_model(self) -> Any:
        """A flax Module (or any object passed through to loss/evaluate)."""

    @abc.abstractmethod
    def build_optimizer(self) -> optax.GradientTransformation:
        ...

    @abc.abstractmethod
    def build_training_data_loader(self) -> DataLoader:
        ...

    @abc.abstractmethod
    def build_validation_data_loader(self) -> DataLoader:
        ...

    def build_callbacks(self) -> Dict[str, Callback]:
        return {}

    # -- pure compute (traced under jit over the mesh) ---------------------

    @abc.abstractmethod
    def loss(
        self,
        model: Any,
        params: Any,
        batch: Dict[str, jax.Array],
        rng: jax.Array,
    ) -> Tuple[jax.Array, Metrics]:
        """Scalar loss + auxiliary metric dict for one training batch."""

    def evaluate_batch(
        self, model: Any, params: Any, batch: Dict[str, jax.Array]
    ) -> Metrics:
        """Validation metrics for one batch; defaults to eval-mode loss."""
        loss, metrics = self.loss(model, params, batch, jax.random.key(0))
        return {"validation_loss": loss, **{f"val_{k}": v for k, v in metrics.items()}}

    def evaluation_reducers(self) -> Dict[str, Any]:
        """Per-metric across-batch reducers (reference
        ``evaluation_reducer``, ``pytorch/_reducer.py``).  Keys are metric
        names from ``evaluate_batch``; values are builtin names
        ("mean"/"sum"/"min"/"max"/"last") or ``train.MetricReducer``
        instances.  Unlisted metrics reduce by mean."""
        return {}

    # -- initialization ----------------------------------------------------

    def init_params(self, model: Any, rng: jax.Array, sample_batch: Dict[str, Any]) -> Any:
        """Build the (unsharded, possibly abstract) parameter pytree.

        Default: flax ``model.init`` on the model's input columns.  Override
        for non-flax models or custom signatures.
        """
        inputs = self.model_inputs(sample_batch)
        return model.init(rng, *inputs)

    def model_inputs(self, batch: Dict[str, Any]) -> Tuple[Any, ...]:
        """Which batch columns feed ``model.init``; default: column 'x' or
        the first column."""
        if "x" in batch:
            return (batch["x"],)
        return (next(iter(batch.values())),)

    def restructure_params(self, params: Any) -> Any:
        """Value-preserving post-init restructure of the raw param tree
        (e.g. restacking per-layer blocks into pipeline stages — see
        ``models/transformer.py`` ``split_pipeline_params``).

        Runs under jit right after ``init_params``, inside the same
        sharded-init program.  Default: identity.
        """
        return params

    def compile_cache_runtime_hparams(self) -> Tuple[str, ...]:
        """Hyperparameters that do NOT shape the compiled step.

        The cross-trial jit-reuse cache (``train/_jit_cache.py``) keys the
        shared train/eval steps on every hyperparameter by default, because
        a Python scalar closed over by ``loss``/``build_optimizer`` bakes
        into the HLO as a constant.  A trial that routes an hparam through
        runtime state instead — e.g. a learning rate via
        ``optax.inject_hyperparams`` (it then lives in ``opt_state`` and is
        read by the traced step at run time) — can name it here so trials
        differing only in that hparam share one compiled step.  Naming an
        hparam that actually IS baked into the trace silently reuses the
        first trial's value; only declare hparams you know are runtime.
        """
        return ()

    def param_logical_specs(self, params: Any) -> Optional[Any]:
        """Logical sharding spec pytree for params; None -> infer.

        Inference order: flax ``nn.Partitioned`` metadata if the model
        annotates with ``with_partitioning``; otherwise automatic FSDP
        (largest divisible dim) when the mesh has an fsdp axis.
        """
        return None

    def pipeline_schedule_spec(self) -> Optional[Any]:
        """The trial's pipeline microbatch schedule, as a
        ``parallel/pipeline.py`` ``PipelineSchedule`` — or None when the
        trial does not pipeline (no pipe mesh axis, or a model that does
        not ride ``pipeline_apply``).

        A trial that pipelines should return the schedule it actually
        traces: the Trainer folds it into the jit-reuse cache key (the
        schedule and virtual-stage count reshape the traced program, so
        toggling must never serve a stale trace) and into the goodput
        ledger's ``step.bubble`` rows via the schedule's analytic tick
        model.  Default: no pipeline.
        """
        return None
