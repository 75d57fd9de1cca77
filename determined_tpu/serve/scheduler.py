"""Continuous-batching scheduler: requests, the bounded admission queue,
the decode-lane table, and the clock that says which phase of the step loop
the engine's thread is in (what a request waits through between its tokens).

Iteration-level (continuous) batching as in Orca (Yu et al., OSDI '22):
the unit of scheduling is one decode STEP, not one request.  New sequences
join the running batch between steps the moment a lane and cache blocks
are free, and finished sequences retire immediately — a short completion
never waits for a long neighbor the way static batching forces it to.

The admission queue is the bounded-queue backpressure pattern of
``data/_prefetch.py`` turned outward: when the queue is full the HTTP
layer answers 429 instead of buffering unboundedly, so overload degrades
into fast rejections rather than latency collapse.  FIFO order through
the queue is the fairness contract — the engine never reorders admissions,
it only delays them when the cache cannot fit the head request yet.
"""

from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

_req_ids = itertools.count(1)

#: what the engine's thread can be doing: a closed set, every moment of the
#: thread's life in exactly one of them (``PhaseClock``; ``docs/serving.md``
#: "Observability" has a line for each).  The index is the phase's place in a
#: reading of the clock
PHASES = (
    "idle",
    "admission.kv_alloc", "admission.prefill", "admission.first_sample", "admission.rest",
    "decode.dispatch", "decode.wait",
    "sample.launch", "sample.wait", "d2h", "lanes",
    "retire", "rest",
)
(
    IDLE,
    ADMISSION_KV_ALLOC, ADMISSION_PREFILL, ADMISSION_FIRST_SAMPLE, ADMISSION_REST,
    DECODE_DISPATCH, DECODE_WAIT,
    SAMPLE_LAUNCH, SAMPLE_WAIT, D2H, LANES,
    RETIRE, REST,
) = range(len(PHASES))

#: the four parts of a request's time a token, each a sum of phases, together
#: all of them: waiting for the device's step (the sampler is queued inside
#: that wait), what of the step's sampling follows the decode call (its return
#: to the last lane's stamp; the launch too where it could not lie in the
#: wait), admissions (other requests': its own ended with its first token),
#: and what is left of the host's loop
TPOT_PARTS: Dict[str, Tuple[int, ...]] = {
    "decode_wait": (DECODE_WAIT,),
    "sample": (SAMPLE_LAUNCH, SAMPLE_WAIT, D2H, LANES),
    "prefill_stall": (ADMISSION_KV_ALLOC, ADMISSION_PREFILL, ADMISSION_FIRST_SAMPLE, ADMISSION_REST),
    "host": (DECODE_DISPATCH, RETIRE, REST, IDLE),
}


class PhaseClock:
    """Cumulative seconds of the engine thread by phase.

    The thread is always in one phase; ``to(phase, at)`` closes the running
    one at the stamp ``at`` and opens the next, so the totals add up to the
    time from ``started_at`` to the newest stamp, whatever the stamps are.
    The engine takes a step's stamps as it always did and feeds them in
    afterwards, in order: a stamp is never earlier than the one before it.
    One writer (the engine's thread) and no lock: another thread reads the
    reading the engine publishes (``ServeEngine.stats``), not the clock.
    """

    __slots__ = ("totals", "phase", "at", "started_at")

    def __init__(self) -> None:
        self.started_at = self.at = time.monotonic()
        self.totals = [0.0] * len(PHASES)
        self.phase = IDLE

    def to(self, phase: int, at: float) -> None:
        self.totals[self.phase] += at - self.at
        self.phase = phase
        self.at = at

    def read(self, at: float) -> Tuple[float, ...]:
        """The totals as of ``at``, the running phase counted up to it."""
        totals = list(self.totals)
        totals[self.phase] += at - self.at
        return tuple(totals)

    def reading(self) -> Tuple[Tuple[float, ...], float]:
        """The totals as of the newest stamp, and the seconds they cover."""
        return self.read(self.at), self.at - self.started_at


@dataclasses.dataclass
class GenRequest:
    """One generation request riding through admission -> decode -> retire.

    The HTTP handler thread blocks on ``done``; the engine thread fills the
    result fields before setting it.  No lock: each field has exactly one
    writer (the engine) and readers only look after ``done`` is set.
    """

    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    seed: Optional[int] = None
    stop_token: Optional[int] = None  # generation ends early on this token
    id: int = dataclasses.field(default_factory=lambda: next(_req_ids))
    arrival: float = dataclasses.field(default_factory=time.monotonic)
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    # -- results (engine-written) -------------------------------------------
    output: List[int] = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    #: taken off the queue for the admission attempt that succeeded (one
    #: requeued for want of cache blocks keeps waiting until then)
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None  # monotonic, for TTFT
    #: one monotonic stamp per emitted token, appended with ``output``
    token_at: List[float] = dataclasses.field(default_factory=list)
    finished_at: Optional[float] = None
    #: the engine's phase clock (``PhaseClock.read``) as of ``first_token_at``
    #: and as of ``finished_at``: what the thread did in between, by phase
    phases_at_first: Optional[Tuple[float, ...]] = None
    phases_at_finish: Optional[Tuple[float, ...]] = None

    @property
    def queue_wait_s(self) -> Optional[float]:
        if self.admitted_at is None:
            return None
        return self.admitted_at - self.arrival

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.arrival

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean time per output token after the first; None under two tokens."""
        if self.finished_at is None or self.first_token_at is None or len(self.output) < 2:
            return None
        return (self.finished_at - self.first_token_at) / (len(self.output) - 1)

    @property
    def tpot_split_s(self) -> Optional[Dict[str, float]]:
        """``tpot_s`` by what the engine's thread was doing between the first
        token and the finish (``TPOT_PARTS``); the parts add up to it."""
        if self.tpot_s is None or self.phases_at_first is None or self.phases_at_finish is None:
            return None
        spent = [b - a for a, b in zip(self.phases_at_first, self.phases_at_finish)]
        gaps = len(self.output) - 1
        return {part: sum(spent[i] for i in phases) / gaps for part, phases in TPOT_PARTS.items()}

    @property
    def itl_max_s(self) -> Optional[float]:
        """Largest gap between two consecutive tokens: the stall an
        admission's prefill (or anything else) put into this request."""
        if len(self.token_at) < 2:
            return None
        return max(b - a for a, b in zip(self.token_at, self.token_at[1:]))

    @property
    def latency_s(self) -> Optional[float]:
        if self.finished_at is None:
            return None
        return self.finished_at - self.arrival

    def finish(self, error: Optional[str] = None) -> None:
        self.error = error
        self.finished_at = time.monotonic()
        self.done.set()


class AdmissionRejected(Exception):
    """Request refused at the door; ``status`` is the HTTP code to answer."""

    def __init__(self, status: int, reason: str) -> None:
        super().__init__(reason)
        self.status = status
        self.reason = reason


class AdmissionQueue:
    """Bounded FIFO between the HTTP threads and the engine loop.

    ``submit`` never blocks: a full queue raises :class:`AdmissionRejected`
    (429), a draining queue rejects everything new (503).  The engine side
    uses ``get``/``requeue_head``; ``requeue_head`` preserves FIFO when the
    head request could not be admitted yet (cache full) — it goes back to
    the FRONT, so later arrivals cannot starve it.
    """

    def __init__(self, depth: int) -> None:
        self._q: "queue.Queue[GenRequest]" = queue.Queue(maxsize=depth)
        self._head_lock = threading.Lock()
        self._head: Optional[GenRequest] = None  # requeued front-of-line item
        self._draining = False  # plain-bool flag; set once, GIL-atomic

    # -- producer side (HTTP threads) ---------------------------------------

    def submit(self, req: GenRequest) -> None:
        if self._draining:
            raise AdmissionRejected(503, "draining")
        try:
            self._q.put(req, block=False)
        except queue.Full:
            raise AdmissionRejected(429, "admission queue full") from None

    # -- consumer side (engine thread) --------------------------------------

    def get(self, timeout: float = 0.0) -> Optional[GenRequest]:
        with self._head_lock:
            if self._head is not None:
                head, self._head = self._head, None
                return head
        try:
            if timeout <= 0:
                return self._q.get(block=False)
            return self._q.get(timeout=timeout)
        except queue.Empty:
            return None

    def requeue_head(self, req: GenRequest) -> None:
        with self._head_lock:
            if self._head is not None:
                raise RuntimeError("only one head request may be parked")
            self._head = req

    # -- drain / inspection --------------------------------------------------

    def start_drain(self) -> None:
        self._draining = True

    @property
    def draining(self) -> bool:
        return self._draining

    def depth(self) -> int:
        with self._head_lock:
            head = 1 if self._head is not None else 0
        return self._q.qsize() + head

    def empty(self) -> bool:
        return self.depth() == 0


@dataclasses.dataclass
class ActiveSeq:
    """One decode lane's state: request + cache bookkeeping."""

    request: GenRequest
    blocks: List[int]               # physical block ids owned by this seq
    block_table: Any                # int32 [blocks_per_seq], made once at admission: `blocks`, then scratch 0
    pos: int                        # position of the NEXT token to feed
    next_token: int                 # token to feed at `pos`
    lane: int                       # the decode lane it was prefilled for
    rng: Any = None                 # np.random.Generator for sampling

    @property
    def generated(self) -> int:
        return len(self.request.output)


class LaneTable:
    """The fixed array of decode lanes the jitted step batches over.

    Mutated only by the engine thread; the lock exists for the ``/stats``
    reader and for tests, not for engine-vs-engine races.
    """

    def __init__(self, max_batch: int) -> None:
        self._lock = threading.Lock()
        self._lanes: List[Optional[ActiveSeq]] = [None] * max_batch
        self.joined = 0
        self.retired = 0

    def free_lane(self) -> Optional[int]:
        """The lowest free lane: the one the next ``join`` takes (only the
        engine thread joins, so it can be told to a prefill beforehand)."""
        with self._lock:
            return next((i for i, lane in enumerate(self._lanes) if lane is None), None)

    def join(self, seq: ActiveSeq, lane: int) -> int:
        """Place ``seq`` into ``lane``; raises if it is taken (the engine
        asks ``free_lane`` before it prefills)."""
        with self._lock:
            if self._lanes[lane] is not None:
                raise RuntimeError(f"decode lane {lane} is not free")
            self._lanes[lane] = seq
            self.joined += 1
            return lane

    def retire(self, lane: int) -> ActiveSeq:
        with self._lock:
            seq = self._lanes[lane]
            if seq is None:
                raise RuntimeError(f"lane {lane} already empty")
            self._lanes[lane] = None
            self.retired += 1
            return seq

    def has_free_lane(self) -> bool:
        with self._lock:
            return any(lane is None for lane in self._lanes)

    def active(self) -> List[int]:
        """Indices of occupied lanes."""
        with self._lock:
            return [i for i, lane in enumerate(self._lanes) if lane is not None]

    def get(self, lane: int) -> Optional[ActiveSeq]:
        with self._lock:
            return self._lanes[lane]

    def snapshot(self) -> Sequence[Optional[ActiveSeq]]:
        with self._lock:
            return list(self._lanes)

    def active_count(self) -> int:
        with self._lock:
            return sum(1 for lane in self._lanes if lane is not None)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "lanes": len(self._lanes),
                "active": sum(1 for lane in self._lanes if lane is not None),
                "joined": self.joined,
                "retired": self.retired,
            }
