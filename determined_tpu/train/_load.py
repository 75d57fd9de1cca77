"""Rebuild a Trial + Trainer from a stored checkpoint, no cluster needed.

Reference: ``harness/determined/pytorch/_load.py``
(``load_trial_from_checkpoint_path``) — there the checkpoint carries the
experiment config and code; here the trainer writes ``trial_class``,
``hparams``, ``exp_config`` and ``seed`` into its state file
(``_trainer.py _save_checkpoint``), so inference/fine-tune scripts can do::

    trial, trainer = train.load_trial_from_checkpoint("/ckpts/<uuid>")
    logits = trainer.predict(batch)
"""

from __future__ import annotations

import importlib
from typing import Any, Optional, Tuple

from determined_tpu.train import serialization
from determined_tpu.train._trainer import Trainer, init as train_init
from determined_tpu.train._trial import JaxTrial


def load_trial_from_checkpoint(
    path: str,
    trial_class: Optional[type] = None,
    mesh_config: Any = None,
    core_context: Any = None,
) -> Tuple[JaxTrial, Trainer]:
    """Reconstruct the Trial and a ready Trainer from a local checkpoint dir.

    ``trial_class`` overrides the recorded class (use when the original
    module isn't importable).  The returned trainer has params/opt state/rng
    restored at the checkpoint's step; call ``trainer.fit`` to continue
    training or use the restored ``trainer.state.params`` directly.
    """
    serialization.prefetch_backend()  # imports beside the trial's build and set-up
    tstate = serialization.load_trainer_state(path)
    if trial_class is None:
        ref = tstate.get("trial_class")
        if not ref or ":" not in ref:
            raise ValueError(
                "checkpoint does not record its trial class; pass trial_class="
            )
        module_name, _, qualname = ref.partition(":")
        obj: Any = importlib.import_module(module_name)
        for part in qualname.split("."):
            obj = getattr(obj, part)
        trial_class = obj

    from determined_tpu import core
    from determined_tpu.config.experiment import ExperimentConfig

    exp_config = (
        ExperimentConfig.parse(tstate["exp_config"])
        if tstate.get("exp_config")
        else None
    )
    ctx = train_init(
        hparams=tstate.get("hparams") or {},
        exp_config=exp_config,
        mesh_config=mesh_config,
        core_context=core_context or core._dummy_init(),
        seed=int(tstate.get("seed") or 0),
    )
    trial = trial_class(ctx)
    trainer = Trainer(trial)
    trainer._setup()
    trainer.restore_from_path(path)
    return trial, trainer
