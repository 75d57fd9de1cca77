"""Runtime sentinels: retrace detection, thread-leak checking, lock-order
tracking.

The static pass (``_ast.py``/``_concurrency.py``) catches what it can
read; these catch what only shows up live:

- **RetraceSentinel** — wraps the pre-jit step functions the Trainer
  installs (``train/_trainer.py`` / ``train/_jit_cache.py``).  jax calls
  the wrapped Python function once per TRACE, so the call count IS the
  compile count for that jitted callable: more than ``allowed`` traces of
  one logical step means the step is retrace-prone (shape-unstable
  batches, python branching on traced values, weak cache keying) and every
  extra trace is a silent full XLA compile eaten by the benchmark.  With
  the jit-reuse cache on, a healthy search stays at one trace per step
  signature — which is exactly what the sentinel asserts.
- **ThreadLeakChecker** — a context manager that snapshots live threads on
  entry and reports threads (matching ``watch`` patterns, default the
  harness's own ``dtpu-*`` workers) still alive on exit.  Tests use it to
  assert scheduler/prefetch workers die with their owners; the supervisor
  (``exec/run_trial.py``) runs trials under it in warn mode when
  ``lint.thread_sentinel`` is set.
- **LockOrderSentinel** — a test-time monkeypatch of ``threading.Lock`` /
  ``threading.RLock`` (and therefore every ``Condition``/``Event`` built
  on them afterwards) that records the process's ACTUAL lock-acquisition
  DAG and reports an inversion the moment an edge closes a cycle — the
  dynamic complement of the static ``lock-order-cycle`` rule, catching the
  dispatch the AST cannot resolve.  ``tests/conftest.py`` exposes it as
  the opt-in ``lock_order`` marker (scheduler, journal/recovery, GC, and
  observability suites run under it).
"""

from __future__ import annotations

import dataclasses
import fnmatch
import functools
import gc
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

logger = logging.getLogger("determined_tpu.lint.runtime")


# ---------------------------------------------------------------------------
# retrace sentinel
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TraceRecord:
    """Compile accounting for one wrapped step callable."""

    label: str
    allowed: int
    traces: int = 0
    violations: int = 0


class RetraceSentinel:
    """Registry of wrapped step functions and their trace counts.

    ``wrap`` must be applied to the function BEFORE ``jax.jit``: jit then
    invokes the wrapper exactly once per trace/compile of that callable.
    Thread-safe (concurrent trials trace in parallel).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._records: Dict[int, TraceRecord] = {}
        self._seq = 0
        self._enabled = False

    # -- enablement (config-driven; tests flip it directly) ----------------

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    @property
    def enabled(self) -> bool:
        return self._enabled

    # -- wrapping ----------------------------------------------------------

    def wrap(
        self, label: str, fn: Callable[..., Any], *, allowed: int = 1
    ) -> Callable[..., Any]:
        """Count executions of ``fn`` (= traces once jitted) under ``label``.

        ``allowed``: traces that are expected for this callable.  One for a
        train step; an eval step legitimately traces twice (the metric
        accumulator starts empty on the first validation batch, populated
        after).
        """
        with self._lock:
            self._seq += 1
            rec = TraceRecord(label=label, allowed=allowed)
            self._records[self._seq] = rec

        @functools.wraps(fn)
        def traced(*args: Any, **kwargs: Any) -> Any:
            with self._lock:
                rec.traces += 1
                over = rec.traces > rec.allowed
                if over:
                    rec.violations += 1
            if over:
                logger.warning(
                    "retrace sentinel: %s traced %d times (allowed %d) — the "
                    "step is recompiling; look for shape-unstable batches, "
                    "python branching on traced values, or hparams that "
                    "should key the jit cache (docs/lint.md)",
                    rec.label,
                    rec.traces,
                    rec.allowed,
                )
            return fn(*args, **kwargs)

        return traced

    # -- queries -----------------------------------------------------------

    def records(self) -> List[TraceRecord]:
        with self._lock:
            return [dataclasses.replace(r) for r in self._records.values()]

    def violations(self) -> Dict[str, int]:
        """label -> excess trace count, only for offenders."""
        with self._lock:
            out: Dict[str, int] = {}
            for r in self._records.values():
                if r.violations:
                    out[r.label] = out.get(r.label, 0) + r.violations
            return out

    def reset(self) -> None:
        with self._lock:
            self._records.clear()
            self._seq = 0


_retrace_sentinel = RetraceSentinel()


def get_retrace_sentinel() -> RetraceSentinel:
    """The process-global sentinel (one process = one jit cache = one
    compile ledger)."""
    return _retrace_sentinel


# ---------------------------------------------------------------------------
# thread-leak checker
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# lock-order sentinel
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LockOrderViolation:
    """An observed acquisition-order inversion: taking ``acquired`` while
    holding ``held`` closes a cycle against the edges in ``cycle``."""

    thread: str
    held: str
    acquired: str
    cycle: List[str]

    def format(self) -> str:
        return (
            f"lock-order inversion on thread {self.thread}: acquired "
            f"{self.acquired} while holding {self.held}, but the process "
            f"already acquired them in the opposite order "
            f"(cycle: {' -> '.join(self.cycle)})"
        )


class _TrackedLock:
    """Wrapper a patched ``threading.Lock``/``RLock`` factory returns.

    Delegates everything to the real primitive; ``acquire``/``release``
    additionally maintain the sentinel's per-thread held stack and the
    global acquisition DAG.  ``__getattr__`` forwards the private
    ``_release_save``/``_acquire_restore``/``_is_owned`` trio, so
    ``Condition`` built on a tracked RLock works unchanged (its ``wait``
    then bypasses the bookkeeping — conservative: the lock stays "held"
    on our stack through the wait, which can only ADD ordering edges the
    thread really did establish before waiting).
    """

    def __init__(self, sentinel: "LockOrderSentinel", inner: Any, sid: int,
                 label: str, reentrant: bool) -> None:
        self._dtpu_sentinel = sentinel
        self._dtpu_inner = inner
        self._dtpu_sid = sid
        self._dtpu_label = label
        self._dtpu_reentrant = reentrant

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._dtpu_inner.acquire(blocking, timeout)
        if got:
            self._dtpu_sentinel._note_acquire(self)
        return got

    def release(self) -> None:
        self._dtpu_sentinel._note_release(self)
        self._dtpu_inner.release()

    def locked(self) -> bool:
        return self._dtpu_inner.locked()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc: Any) -> None:
        self.release()

    def __getattr__(self, name: str) -> Any:
        return getattr(self._dtpu_inner, name)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"<tracked {self._dtpu_label} wrapping {self._dtpu_inner!r}>"


class LockOrderSentinel:
    """Record the live acquisition DAG; flag inversions deterministically.

    ``install()`` patches ``threading.Lock`` and ``threading.RLock`` so
    every lock created AFTERWARDS is tracked (existing locks are not —
    tests construct their subjects inside the sentinel's scope, which the
    conftest ``lock_order`` marker guarantees).  On each acquire with
    other tracked locks held, the edge ``innermost-held -> acquired`` is
    added; an edge that completes a cycle records a
    ``LockOrderViolation`` carrying both directions' witnesses.  The
    check fires on the ORDER, not on an actual deadlock, so the inversion
    is caught even when the interleaving happened to get away with it —
    that is the point: the failure is deterministic where the deadlock is
    a race.

    Locks are labeled by allocation site (``file:line#serial``), which is
    what the violation message shows.  Reentrant re-acquisition of an
    RLock adds no edges.  Not re-entrant itself: one install per process
    at a time (the conftest fixture serializes naturally).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()  # guards graph + labels + handoffs
        self._edges: Dict[int, set] = {}
        self._labels: Dict[int, str] = {}
        self._violations: List[LockOrderViolation] = []
        self._seq = 0
        self._held = threading.local()
        #: sid -> count of releases by threads that never acquired it
        #: (the legal Lock handoff pattern); the acquiring thread purges
        #: its stale stack entry lazily on its next acquire
        self._foreign_releases: Dict[int, int] = {}
        self._orig_lock: Optional[Any] = None
        self._orig_rlock: Optional[Any] = None
        self._installed = False

    # -- patching ----------------------------------------------------------

    def _alloc_site(self) -> str:
        import sys

        f = sys._getframe(2)
        while f is not None and "threading" in (f.f_code.co_filename or ""):
            f = f.f_back
        if f is None:  # pragma: no cover - interpreter internals
            return "<unknown>"
        return f"{f.f_code.co_filename}:{f.f_lineno}"

    def _make_factory(self, orig: Any, reentrant: bool) -> Any:
        def factory(*args: Any, **kwargs: Any) -> _TrackedLock:
            inner = orig(*args, **kwargs)
            with self._lock:
                self._seq += 1
                sid = self._seq
            label = f"{self._alloc_site()}#{sid}"
            with self._lock:
                self._labels[sid] = label
            return _TrackedLock(self, inner, sid, label, reentrant)

        return factory

    def install(self) -> "LockOrderSentinel":
        if self._installed:
            return self
        self._orig_lock = threading.Lock
        self._orig_rlock = threading.RLock
        threading.Lock = self._make_factory(self._orig_lock, False)  # type: ignore[misc]
        threading.RLock = self._make_factory(self._orig_rlock, True)  # type: ignore[misc]
        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        threading.Lock = self._orig_lock  # type: ignore[misc]
        threading.RLock = self._orig_rlock  # type: ignore[misc]
        self._installed = False

    def __enter__(self) -> "LockOrderSentinel":
        return self.install()

    def __exit__(self, *exc: Any) -> None:
        self.uninstall()

    # -- bookkeeping -------------------------------------------------------

    def _stack(self) -> List[int]:
        stack = getattr(self._held, "stack", None)
        if stack is None:
            stack = []
            self._held.stack = stack
        return stack

    def _purge_foreign_releases(self, stack: List[int]) -> None:
        """Drop stack entries for locks some OTHER thread has since
        released (acquire-here / release-there is legal for Lock); without
        this the handed-off lock looks held forever and every later
        acquire on this thread grows a phantom ordering edge."""
        if not self._foreign_releases:  # benign unlocked read
            return
        with self._lock:
            for i in range(len(stack) - 1, -1, -1):
                n = self._foreign_releases.get(stack[i], 0)
                if n:
                    sid = stack[i]
                    del stack[i]
                    if n == 1:
                        del self._foreign_releases[sid]
                    else:
                        self._foreign_releases[sid] = n - 1

    def _note_acquire(self, lock: _TrackedLock) -> None:
        stack = self._stack()
        self._purge_foreign_releases(stack)
        sid = lock._dtpu_sid
        if sid in stack:
            # reentrant hold (RLock, or Condition re-entry): no new order
            # information; push so the matching release pops symmetrically
            stack.append(sid)
            return
        if stack:
            holder = stack[-1]
            with self._lock:
                added = sid not in self._edges.setdefault(holder, set())
                if added:
                    self._edges[holder].add(sid)
                    cycle = self._find_cycle(sid, holder)
                    if cycle is not None:
                        self._violations.append(
                            LockOrderViolation(
                                thread=threading.current_thread().name,
                                held=self._labels.get(holder, str(holder)),
                                acquired=self._labels.get(sid, str(sid)),
                                cycle=[
                                    self._labels.get(s, str(s))
                                    for s in [holder, sid] + cycle[1:]
                                ],
                            )
                        )
        stack.append(sid)

    def _note_release(self, lock: _TrackedLock) -> None:
        stack = self._stack()
        sid = lock._dtpu_sid
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] == sid:
                del stack[i]
                return
        # released by a thread that never acquired it: cross-thread
        # handoff — the acquirer's stack entry is purged on its next
        # acquire rather than mutated from here (stacks are thread-local)
        with self._lock:
            self._foreign_releases[sid] = self._foreign_releases.get(sid, 0) + 1

    def _find_cycle(self, start: int, goal: int) -> Optional[List[int]]:
        """Path start -> ... -> goal in the edge set (caller holds _lock);
        combined with the just-added goal -> start edge it is a cycle."""
        work = [(start, [start])]
        seen = {start}
        while work:
            cur, path = work.pop()
            for nxt in self._edges.get(cur, ()):
                if nxt == goal:
                    return path + [goal]
                if nxt not in seen:
                    seen.add(nxt)
                    work.append((nxt, path + [nxt]))
        return None

    # -- queries -----------------------------------------------------------

    def violations(self) -> List[LockOrderViolation]:
        with self._lock:
            return list(self._violations)

    def reset(self) -> None:
        with self._lock:
            self._edges.clear()
            self._violations.clear()


# ---------------------------------------------------------------------------
# collective-sequence sentinel
# ---------------------------------------------------------------------------


class CollectiveDivergenceError(RuntimeError):
    """Two ranks issued different collective sequences.

    This is the named, located form of the worst debugging experience in
    distributed training: without the sentinel, the divergence is a
    silent hang — every healthy rank blocks inside its collective until
    the 600-second timeout, with no indication of WHICH rank took a
    different path or WHICH op it skipped.  The error names the first
    divergent op and carries both ranks' recent traces.
    """

    def __init__(
        self,
        message: str,
        *,
        op_index: int = -1,
        ranks: Optional[Dict[int, str]] = None,
        traces: Optional[Dict[int, List[str]]] = None,
    ) -> None:
        super().__init__(message)
        #: absolute index (0-based) of the first divergent collective
        self.op_index = op_index
        #: rank -> the op it issued at the divergence point
        self.ranks = dict(ranks or {})
        #: rank -> recent (op, detail) signature trace
        self.traces = dict(traces or {})


#: first element of every enveloped payload — lets the receiving side
#: distinguish "sentinel payload" from "raw payload from a rank that does
#: not have the sentinel installed" (a misconfiguration worth naming)
_CSEQ_MAGIC = "__dtpu_cseq__"


def _payload_sig(obj: Any) -> str:
    """Cheap structural signature of a collective operand: the top-level
    TYPE (plus shape for arrays).  Deliberately shallow and deliberately
    length-free — per-rank operands legitimately differ in content and
    size (``allgather(hostname)``), but a type split (one rank sends a
    tuple, another None) is the wrong-branch signal.  The digest must
    cost nanoseconds; op identity is what diverges first."""
    if obj is None:
        return "none"
    shape = getattr(obj, "shape", None)
    if shape is not None:
        return f"{type(obj).__name__}{tuple(shape)!r}"
    return type(obj).__name__


class _CseqState:
    """Per-DistributedContext rolling digest of the collective sequence."""

    __slots__ = ("rank", "seq", "xchg", "digest", "trace", "lock")

    def __init__(self, rank: int, trace_depth: int) -> None:
        import collections

        self.rank = rank
        self.seq = 0  # collectives recorded so far (exchanged + dispatch-site)
        self.xchg = 0  # EXCHANGED collectives only (the injection counter)
        self.digest = 0  # crc32 chain over every recorded signature
        self.trace = collections.deque(maxlen=trace_depth)
        self.lock = threading.Lock()

    def record(self, sig: str) -> None:
        import zlib

        with self.lock:
            self.seq += 1
            self.digest = zlib.crc32(sig.encode(), self.digest) & 0xFFFFFFFF
            self.trace.append(sig)

    def snapshot(self) -> Dict[str, Any]:
        with self.lock:
            return {
                "rank": self.rank,
                "seq": self.seq,
                "digest": self.digest,
                # only the TAIL rides the wire: the digest covers the full
                # history, the shipped tail exists to NAME the divergence
                # point in the error; the deeper local deque stays
                # available to whoever catches the exception
                "trace": list(self.trace)[-8:],
            }


class CollectiveSequenceSentinel:
    """Digest every rank's collective sequence; name divergences.

    ``install()`` patches the control-plane collective entry points on
    ``DistributedContext`` (``allgather``/``gather``/``broadcast``/
    ``barrier`` and their ``_local`` variants) so that every call:

    1. records an ``(op, payload-structure)`` signature into a per-rank
       rolling crc32 digest (``record`` is also public, so un-exchanged
       dispatch sites — the trainer's jitted step, which carries the
       tensor-plane psums — feed the same digest);
    2. piggybacks a tiny envelope ``{rank, seq, digest, op, trace}`` on
       the payload it was going to exchange anyway;
    3. verifies, on receipt, that every participating rank agrees on
       ``(seq, op, digest)`` — raising a deterministic
       ``CollectiveDivergenceError`` naming the first divergent op and
       both ranks' traces the moment the sequences disagree, instead of
       letting the mismatch surface as a 600-second silent hang.

    The exchange rides the collective that was already happening, so the
    sentinel adds no extra round trips; overhead per collective is one
    crc32 of a short string plus a small dict
    (``scripts/bench_sentinel.py`` tracks the number).  Divergences where one rank calls
    a DIFFERENT op on a compatible transport (allgather vs barrier, the
    common wrong-branch case) are caught in-band; a rank that issues NO
    collective still parks its peers until the control-plane deadline,
    but the deadline's ``PeerLostError`` then names the silent rank.

    Enablement: ``lint.collective_sentinel: true`` in the experiment
    config (the trial entrypoint installs it before ``core.init()``), the
    ``DTPU_COLLECTIVE_SENTINEL=1`` env, or the ``collective_order``
    pytest marker (``tests/conftest.py``).  Must be installed on EVERY
    rank of a gang or none — a raw (non-enveloped) payload from a
    sentinel-less peer raises with a message saying exactly that.

    Fault injection (the devcluster acceptance test): the env
    ``DTPU_CSEQ_INJECT="<rank>:<seq>:<op>"`` makes the named rank
    advertise ``<op>`` as its ``<seq>``-th collective — simulating the
    wrong-branch divergence without hand-writing a divergent trial.
    """

    def __init__(self, *, trace_depth: int = 64) -> None:
        self.trace_depth = trace_depth
        self._installed = False
        self._orig: Dict[str, Any] = {}
        self._violations: List[CollectiveDivergenceError] = []
        self._vlock = threading.Lock()
        # parsed DTPU_CSEQ_INJECT, or None
        self._inject: Optional[Tuple[int, int, str]] = None
        import os

        spec = os.environ.get("DTPU_CSEQ_INJECT", "")
        if spec:
            try:
                r, s, op = spec.split(":", 2)
                self._inject = (int(r), int(s), op)
            except ValueError:
                logger.warning("ignoring malformed DTPU_CSEQ_INJECT=%r", spec)

    @property
    def installed(self) -> bool:
        return self._installed

    # -- state -------------------------------------------------------------

    def _state(self, dist: Any) -> _CseqState:
        st = getattr(dist, "_dtpu_cseq", None)
        if st is None:
            st = _CseqState(getattr(dist, "rank", 0), self.trace_depth)
            dist._dtpu_cseq = st
        return st

    def record(self, dist: Any, op: str, detail: str = "") -> None:
        """Public dispatch-site hook: fold an un-exchanged collective
        (e.g. the jitted train step carrying the gradient psums) into the
        rolling digest.  The mismatch surfaces at the NEXT exchanged
        collective, whose envelope carries the digest."""
        self._state(dist).record(f"{op}({detail})" if detail else op)

    def violations(self) -> List[CollectiveDivergenceError]:
        with self._vlock:
            return list(self._violations)

    def reset(self) -> None:
        with self._vlock:
            self._violations.clear()

    # -- envelope exchange -------------------------------------------------

    def _sig_for(self, st: _CseqState, op: str, obj: Any) -> str:
        # broadcast/gather payloads are one-sided BY DESIGN (chief sends,
        # or each rank contributes local data the chief merges), so only
        # the op identity is digested for them; symmetric exchanges also
        # digest the operand's structural signature
        if op.startswith(("broadcast", "gather")):
            sig = op
        else:
            sig = f"{op}({_payload_sig(obj)})"
        with st.lock:
            st.xchg += 1
            xchg = st.xchg
        if self._inject is not None:
            rank, at_xchg, fake_op = self._inject
            # counted in EXCHANGED collectives (not dispatch-site records),
            # so the injection point is stable regardless of how many step
            # segments the trainer folded in between
            if st.rank == rank and xchg == at_xchg:
                logger.warning(
                    "cseq inject: rank %d advertising %r instead of %r at "
                    "exchanged collective #%d",
                    rank, fake_op, sig, at_xchg,
                )
                return fake_op
        return sig

    def _divergence(
        self, envs: List[Dict[str, Any]]
    ) -> Optional[CollectiveDivergenceError]:
        """Compare all ranks' envelopes; build the named error or None."""
        base = envs[0]
        if all(
            e["seq"] == base["seq"]
            and e["op"] == base["op"]
            and e["digest"] == base["digest"]
            for e in envs[1:]
        ):
            return None
        # find the first divergent absolute op index from the traces
        traces = {e["rank"]: list(e["trace"]) + [e["op"]] for e in envs}
        starts = {e["rank"]: e["seq"] + 1 - len(traces[e["rank"]]) for e in envs}
        first = min(starts.values())
        last = max(e["seq"] for e in envs)
        op_index = -1
        at: Dict[int, str] = {}
        for i in range(max(first, 0), last + 1):
            ops = {
                r: traces[r][i - starts[r]]
                for r in traces
                if 0 <= i - starts[r] < len(traces[r])
            }
            if len(set(ops.values())) > 1 or (envs and len(ops) < len(envs)):
                op_index = i
                at = {r: ops.get(r, "<nothing>") for r in traces}
                break
        if op_index < 0:
            # identical visible traces but different digests: the split is
            # older than the rolling window
            op_index = first
            at = {e["rank"]: "<diverged before trace window>" for e in envs}
        who = ", ".join(f"rank {r} issued `{op}`" for r, op in sorted(at.items()))
        err = CollectiveDivergenceError(
            f"collective sequence diverged at op #{op_index + 1}: {who}. "
            "One rank took a different code path; without this sentinel "
            "every healthy rank would hang in its collective to the "
            f"timeout. Recent traces: "
            + "; ".join(
                f"rank {r}: {tr[-8:]}" for r, tr in sorted(traces.items())
            ),
            op_index=op_index,
            ranks=at,
            traces=traces,
        )
        return err

    def _raise(self, err: CollectiveDivergenceError) -> None:
        with self._vlock:
            self._violations.append(err)
        raise err

    def _unwrap(self, item: Any) -> Tuple[Dict[str, Any], Any]:
        if (
            isinstance(item, tuple)
            and len(item) == 3
            and item[0] == _CSEQ_MAGIC
            and isinstance(item[1], dict)
        ):
            return item[1], item[2]
        raise CollectiveDivergenceError(
            "collective-sequence sentinel received a raw (non-enveloped) "
            "payload: a peer rank is running WITHOUT the sentinel. Enable "
            "it on every rank of the gang (DTPU_COLLECTIVE_SENTINEL=1 / "
            "lint.collective_sentinel) or on none."
        )

    # -- patched entry points ----------------------------------------------

    def _solo(self, dist: Any, op: str) -> bool:
        """Single-participant group: record the op (the sequence ledger
        stays complete) but skip the envelope — there is no peer to
        verify against, and Dummy contexts sit on every local-experiment
        hot path."""
        size = dist.local_size if op.endswith("_local") else dist.size
        return size <= 1

    def _exchange_allgather(
        self, dist: Any, obj: Any, op: str, orig: Any
    ) -> List[Any]:
        st = self._state(dist)
        sig = self._sig_for(st, op, obj)
        env = st.snapshot()
        env["op"] = sig
        st.record(sig)
        if self._solo(dist, op):
            return orig(dist, obj)
        result = orig(dist, (_CSEQ_MAGIC, env, obj))
        pairs = [self._unwrap(r) for r in result]
        err = self._divergence([p[0] for p in pairs])
        if err is not None:
            self._raise(err)
        return [p[1] for p in pairs]

    def _exchange_gather(
        self, dist: Any, obj: Any, op: str, orig: Any
    ) -> Optional[List[Any]]:
        st = self._state(dist)
        sig = self._sig_for(st, op, obj)
        env = st.snapshot()
        env["op"] = sig
        st.record(sig)
        if self._solo(dist, op):
            return orig(dist, obj)
        result = orig(dist, (_CSEQ_MAGIC, env, obj))
        if result is None:
            return None  # worker side: the chief verifies
        pairs = [self._unwrap(r) for r in result]
        err = self._divergence([p[0] for p in pairs])
        if err is not None:
            self._raise(err)
        return [p[1] for p in pairs]

    def _exchange_broadcast(self, dist: Any, obj: Any, op: str, orig: Any) -> Any:
        st = self._state(dist)
        sig = self._sig_for(st, op, obj)
        env = st.snapshot()
        env["op"] = sig
        st.record(sig)
        if self._solo(dist, op):
            return orig(dist, obj)
        result = orig(dist, (_CSEQ_MAGIC, env, obj))
        peer_env, payload = self._unwrap(result)
        # one-sided verification: each receiver compares the chief's
        # envelope against its OWN expected position
        if (
            peer_env["seq"] != env["seq"]
            or peer_env["op"] != env["op"]
            or peer_env["digest"] != env["digest"]
        ):
            err = self._divergence([env, peer_env])
            if err is not None:
                self._raise(err)
        return payload

    def install(self) -> "CollectiveSequenceSentinel":
        if self._installed:
            return self
        from determined_tpu.core._distributed import DistributedContext

        sentinel = self
        orig = {
            "allgather": DistributedContext.allgather,
            "allgather_local": DistributedContext.allgather_local,
            "gather": DistributedContext.gather,
            "gather_local": DistributedContext.gather_local,
            "broadcast": DistributedContext.broadcast,
            "broadcast_local": DistributedContext.broadcast_local,
            "barrier": DistributedContext.barrier,
        }
        self._orig = orig

        def allgather(self, obj):
            return sentinel._exchange_allgather(self, obj, "allgather", orig["allgather"])

        def allgather_local(self, obj):
            return sentinel._exchange_allgather(
                self, obj, "allgather_local", orig["allgather_local"]
            )

        def gather(self, obj):
            return sentinel._exchange_gather(self, obj, "gather", orig["gather"])

        def gather_local(self, obj):
            return sentinel._exchange_gather(
                self, obj, "gather_local", orig["gather_local"]
            )

        def broadcast(self, obj=None):
            return sentinel._exchange_broadcast(
                self, obj, "broadcast", orig["broadcast"]
            )

        def broadcast_local(self, obj=None):
            return sentinel._exchange_broadcast(
                self, obj, "broadcast_local", orig["broadcast_local"]
            )

        def barrier(self):
            # route the barrier through the verified allgather so it gets
            # the full both-directions check (it IS an allgather(None))
            sentinel._exchange_allgather(self, None, "barrier", orig["allgather"])

        DistributedContext.allgather = allgather
        DistributedContext.allgather_local = allgather_local
        DistributedContext.gather = gather
        DistributedContext.gather_local = gather_local
        DistributedContext.broadcast = broadcast
        DistributedContext.broadcast_local = broadcast_local
        DistributedContext.barrier = barrier
        self._installed = True
        logger.info("collective-sequence sentinel installed")
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        from determined_tpu.core._distributed import DistributedContext

        for name, fn in self._orig.items():
            setattr(DistributedContext, name, fn)
        self._installed = False

    def __enter__(self) -> "CollectiveSequenceSentinel":
        return self.install()

    def __exit__(self, *exc: Any) -> None:
        self.uninstall()


_collective_sentinel: Optional[CollectiveSequenceSentinel] = None


def get_collective_sentinel() -> CollectiveSequenceSentinel:
    """Process-global sentinel (one process = one rank = one sequence)."""
    global _collective_sentinel
    if _collective_sentinel is None:
        _collective_sentinel = CollectiveSequenceSentinel()
    return _collective_sentinel


class ThreadLeakError(RuntimeError):
    """Threads outlived the scope that owned them."""

    def __init__(self, leaked: Sequence[threading.Thread], scope: str) -> None:
        self.leaked = list(leaked)
        names = ", ".join(f"{t.name} (daemon={t.daemon})" for t in self.leaked)
        super().__init__(
            f"{len(self.leaked)} thread(s) leaked from {scope}: {names}"
        )


class ThreadLeakChecker:
    """Assert that threads started inside the block die with it.

    ``watch``: fnmatch patterns of thread names that count as leaks
    (default: the harness's own worker prefix).  Unmatched new threads —
    interpreter pools, grpc/orbax internals — are ignored: they are
    process-lifetime by design and would make the check unusable.
    ``grace``: seconds to wait (joining, after a gc pass to trigger
    ``__del__``-based cleanup) before declaring a leak.
    """

    def __init__(
        self,
        *,
        watch: Sequence[str] = ("dtpu-*",),
        grace: float = 5.0,
        raise_on_leak: bool = True,
        scope: str = "scope",
    ) -> None:
        self.watch = tuple(watch)
        self.grace = grace
        self.raise_on_leak = raise_on_leak
        self.scope = scope
        self.leaked: List[threading.Thread] = []
        self._before: Optional[Tuple[threading.Thread, ...]] = None

    def _new_watched(self, before: Tuple[threading.Thread, ...]) -> List[threading.Thread]:
        return [
            t
            for t in threading.enumerate()
            if t not in before
            and t.is_alive()
            and any(fnmatch.fnmatch(t.name, p) for p in self.watch)
        ]

    def __enter__(self) -> "ThreadLeakChecker":
        self._before = tuple(threading.enumerate())
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> None:
        assert self._before is not None
        # a del-based cleanup (un-closed PrefetchingIterator) should count
        # as "died with the scope", not as a leak
        gc.collect()
        deadline = time.monotonic() + self.grace
        leaked = self._new_watched(self._before)
        while leaked and time.monotonic() < deadline:
            for t in leaked:
                t.join(timeout=max(0.0, min(0.2, deadline - time.monotonic())))
            leaked = self._new_watched(self._before)
        self.leaked = leaked
        if not leaked:
            return
        # an in-flight exception takes precedence; don't mask it
        if self.raise_on_leak and exc_type is None:
            raise ThreadLeakError(leaked, self.scope)
        logger.warning(
            "thread sentinel: %d thread(s) leaked from %s: %s",
            len(leaked),
            self.scope,
            ", ".join(t.name for t in leaked),
        )
