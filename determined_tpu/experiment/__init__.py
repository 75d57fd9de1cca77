"""Experiment orchestration: local searcher-driven runner, cluster-driven
runner (trials dispatched through the master), gang scheduler, and the
crash-recovery journal."""

import time as _time

_IMPORT_T0 = _time.monotonic()  # the span ``import.determined_tpu.experiment``: from here to this file's last line

from determined_tpu.experiment.cluster import (
    ClusterExperiment,
    run_cluster_experiment,
)
from determined_tpu.experiment.journal import (
    ExperimentJournal,
    ExperimentJournalError,
    JournaledSearcher,
    experiment_status,
    journal_path,
    read_journal,
)
from determined_tpu.experiment.local import (
    PREEMPTED_EXIT_CODE,
    LocalExperiment,
    TrialResult,
    run_experiment,
)
from determined_tpu.experiment.scheduler import (
    SchedulerOutcome,
    SlotAllocation,
    SlotPool,
    TrialScheduler,
)

__all__ = [
    "ClusterExperiment",
    "ExperimentJournal",
    "ExperimentJournalError",
    "JournaledSearcher",
    "LocalExperiment",
    "PREEMPTED_EXIT_CODE",
    "SchedulerOutcome",
    "SlotAllocation",
    "SlotPool",
    "TrialResult",
    "TrialScheduler",
    "experiment_status",
    "journal_path",
    "read_journal",
    "run_cluster_experiment",
    "run_experiment",
]

from determined_tpu.observability import get_tracer as _get_tracer  # noqa: E402

_get_tracer().record_span("import.determined_tpu.experiment", "setup", _IMPORT_T0, _time.monotonic())
