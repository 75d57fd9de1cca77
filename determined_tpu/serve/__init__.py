"""Online serving tier: continuous-batching inference over a paged KV cache.

``determined_tpu/inference.py`` is the OFFLINE path (checkpointed batch
processing of a finite dataset); this package is the ONLINE one — a
``ServeWorker`` loads a trial checkpoint, compiles prefill/decode step
functions for the decoder-only transformer (``models/serving.py``, the
serving forward), and serves ``POST /v1/generate`` with:

- **continuous batching** (``engine.ServeEngine``): requests join the
  running decode batch between any two steps and retire the moment they
  finish — Orca-style iteration-level scheduling;
- a **paged KV cache** (``kv_cache.BlockAllocator`` over the block pool
  in ``models/serving.py``): fixed-size blocks, free-list allocation,
  per-sequence block tables baked into a single decode trace;
- **bounded admission** (``scheduler.AdmissionQueue``): a full queue
  answers 429, a draining worker 503 — overload degrades into fast
  rejections, not latency collapse;
- **replica registration** (``replica.ReplicaRegistration``): workers
  register with the C++ master (``/api/v1/serving``), heartbeat, and are
  pruned on heartbeat loss, so replicas scale and discover like NTSC
  tasks.

See ``docs/serving.md`` for the architecture and request lifecycle; the
cells ``serve-internlm2-decode`` and ``serve-internlm2-chat`` of
``BENCHMARK.json`` measure it.
"""

from __future__ import annotations

import time as _time

_IMPORT_T0 = _time.monotonic()  # the span ``import.determined_tpu.serve``: from here to this file's last line

import logging
from typing import Any, Dict, Optional

from determined_tpu.observability import get_tracer, log_setup_line
from determined_tpu.serve.config import ServeConfig
from determined_tpu.serve.engine import (
    DecodeKernels,
    ServeEngine,
    sample_token,
)
from determined_tpu.serve.http import ServeHTTPServer
from determined_tpu.serve.kv_cache import (
    BlockAllocator,
    CacheOOM,
    prefix_block_hashes,
)
from determined_tpu.serve.replica import ReplicaRegistration
from determined_tpu.serve.scheduler import (
    AdmissionQueue,
    AdmissionRejected,
    GenRequest,
    LaneTable,
)

logger = logging.getLogger("determined_tpu.serve")

__all__ = [
    "AdmissionQueue",
    "AdmissionRejected",
    "BlockAllocator",
    "CacheOOM",
    "DecodeKernels",
    "GenRequest",
    "LaneTable",
    "ReplicaRegistration",
    "ServeConfig",
    "prefix_block_hashes",
    "ServeEngine",
    "ServeHTTPServer",
    "ServeWorker",
    "sample_token",
]


class ServeWorker:
    """One serving replica: engine + HTTP server + optional registration.

    The CLI (``dtpu serve``) builds one of these; tests drive it
    in-process.  ``request_drain`` is idempotent and safe to call from the
    main thread after a signal flag flips (never call it FROM a signal
    handler — it touches Events; see ``cli/main.py serve_cmd`` for the
    flag-poll pattern the handler uses instead).
    """

    def __init__(
        self,
        engine: ServeEngine,
        *,
        host: str = "127.0.0.1",
        port: int = 0,
        session: Optional[Any] = None,
        model: str = "",
        checkpoint: str = "",
        model_name: str = "",
        model_version: int = 0,
        task_id: str = "",
    ) -> None:
        self.engine = engine
        self.http = ServeHTTPServer(engine, host=host, port=port)
        self._session = session
        self._model = model
        self._checkpoint = checkpoint
        self._model_name = model_name
        self._model_version = model_version
        self._task_id = task_id
        self.replica: Optional[ReplicaRegistration] = None
        # set from the heartbeat thread when the master asks this replica
        # to drain (rolling deploy); plain attribute writes so the serve
        # main loop can poll it next to its signal flag
        self._master_drain = False
        self.master_drain_info: Dict[str, Any] = {}

    def start(self) -> str:
        """Start engine + HTTP (+ master registration when a session was
        given); returns the URL the replica serves on."""
        self.engine.start()
        self.http.start()
        if self._session is not None:
            self.replica = ReplicaRegistration(
                self._session,
                url=self.http.url,
                model=self._model,
                checkpoint=self._checkpoint,
                model_name=self._model_name,
                model_version=self._model_version,
                task_id=self._task_id,
                heartbeat_interval_s=self.engine.cfg.heartbeat_interval_s,
                stats_fn=self.engine.stats,
                on_drain=self._on_master_drain,
            ).start()
        logger.info("serving replica up at %s", self.http.url)
        # where the tracer is on (``trace_dir``): how the start went, once a process
        log_setup_line(logger, "replica ready")
        return self.http.url

    def _on_master_drain(self, info: Dict[str, Any]) -> None:
        # heartbeat-thread context: attribute writes only (the main loop
        # polls master_drain_requested and runs the actual drain)
        self.master_drain_info = dict(info)
        self._master_drain = True

    def master_drain_requested(self) -> bool:
        """True once the master's heartbeat response asked for a drain
        (rolling deploy walking this replica)."""
        return self._master_drain

    def request_drain(self) -> None:
        """Close admission: /healthz flips to draining, new generations
        get 503, queued + in-flight requests run to completion."""
        self.http.start_drain()
        self.engine.queue.start_drain()
        self.engine._wake.set()

    def wait_drained(self, timeout: Optional[float] = None) -> bool:
        """Block until the engine finished its queued + in-flight work."""
        return self.engine.drain(timeout=timeout)

    def shutdown(self, deregister: bool = True) -> None:
        if self.replica is not None:
            self.replica.close(deregister=deregister)
            self.replica = None
        self.engine.stop()
        self.http.stop()

    def stats(self) -> Dict[str, Any]:
        out = self.engine.stats()
        out["url"] = self.http.url if self.http.running else None
        if self.replica is not None:
            out["replica_id"] = self.replica.replica_id
        return out


get_tracer().record_span("import.determined_tpu.serve", "setup", _IMPORT_T0, _time.monotonic())
