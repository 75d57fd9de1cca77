"""Training engine: JaxTrial + Trainer boundary loop + serialization."""

import time as _time

_IMPORT_T0 = _time.monotonic()  # the span ``import.determined_tpu.train``: from here to this file's last line

from determined_tpu.train._jit_cache import (
    clear_step_cache,
    get_step_cache,
    step_cache_stats,
)
from determined_tpu.train._load import load_trial_from_checkpoint
from determined_tpu.train._reducer import MetricReducer, get_reducer
from determined_tpu.train._restart import Attempt, RestartPolicy, run_with_restarts
from determined_tpu.train._state import TrainState
from determined_tpu.train._trainer import Trainer, init
from determined_tpu.train._trial import Callback, JaxTrial, TrialContext
from determined_tpu.train import serialization

__all__ = [
    "Attempt",
    "Callback",
    "JaxTrial",
    "MetricReducer",
    "RestartPolicy",
    "TrainState",
    "Trainer",
    "TrialContext",
    "clear_step_cache",
    "get_reducer",
    "get_step_cache",
    "init",
    "load_trial_from_checkpoint",
    "step_cache_stats",
    "run_with_restarts",
    "serialization",
]

from determined_tpu.observability import get_tracer as _get_tracer  # noqa: E402

_get_tracer().record_span("import.determined_tpu.train", "setup", _IMPORT_T0, _time.monotonic())
