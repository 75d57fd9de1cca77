"""The process tracer's life in a serving process.

``dtpu serve`` and ``exec/serve_replica`` own the tracer the way
``exec/run_trial.py`` does for a trial: off unless
``ServeConfig.trace_dir`` is set — a default replica records nothing into
rings nobody drains — and otherwise enabled, shipped as JSONL into that
directory while the replica runs, and stopped and written out as
``trace.json`` (Perfetto / chrome://tracing) once it has drained.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

from determined_tpu.observability import get_tracer

logger = logging.getLogger("determined_tpu.serve")


def start_tracing(trace_dir: Optional[str]) -> None:
    """Call before the engine is built, so that ``serve.setup`` is in."""
    tracer = get_tracer()
    tracer.configure(enabled=trace_dir is not None, out_dir=trace_dir)
    if trace_dir is not None:
        tracer.start()
        logger.info("tracing the replica into %s", trace_dir)


def finish_tracing(trace_dir: Optional[str]) -> None:
    """Call once the engine has stopped: last drain, ``trace.json``."""
    if trace_dir is None:
        return
    tracer = get_tracer()
    tracer.close()  # stops the shipper, drains once more, closes events.jsonl
    try:
        tracer.export_chrome_trace(os.path.join(trace_dir, "trace.json"))
    except Exception:  # noqa: BLE001 - an export must not mask the drain's exit code
        logger.exception("trace export failed")
