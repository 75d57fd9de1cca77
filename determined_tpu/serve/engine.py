"""The serving engine: jitted prefill/decode kernels + the batching loop.

:class:`ServeEngine` does iteration-level **continuous batching** over one
set of compiled kernels (:class:`DecodeKernels`): every decode step,
finished sequences retire (blocks freed, response completed) and queued
requests join the freed lanes immediately.  It is what ``dtpu serve``, a
replica and the benchmark's serving cells run.

The jitted steps are shaped entirely by :class:`ServeConfig` (lane count,
prompt padding, block-table width), so a mixed stream of request lengths
compiles exactly once per kernel — enforced by wrapping the pre-jit
callables in the PR-4 RetraceSentinel (``lint/_runtime.py``), the same
compile-count guard the Trainer runs under.

A decode step's tokens are drawn on the device: ``kernels.decode`` hands
back the logits where they lie, ONE jitted call (``sample_lanes``) draws
every lane's token from them with the uniform the host drew from the
lane's seeded generator, and what crosses to the host a step is the ids and
the step's counters.  A request's first token, one row from its prefill, is
sampled on the host (``sample_token``, which is also the oracle the tests
hold the device's sampler to; ``docs/serving.md`` has the contract of both).

Of what the host does for a step, everything that needs no ids of that step
is done while the device runs it, and what the device already holds is not
sent again (``docs/serving.md`` "A step, in order").  A lane's block table is
an int32 row made once at admission; the engine keeps one matrix of them and
the device's copy of it, sent again only after a lane joined or retired.  A
step's tokens are the ids the sampler left on the device the step before,
unless a lane joined since.  Between the decode call's two stamps
(``DecodeKernels.during_wait``, which is handed the logits the call has just
enqueued) the lanes' uniforms are drawn and sent, and the sampler's call and
the copies of its ids and counters are queued behind the decode program: the
host hears from the device ONCE a step, and by then the ids are on their way.

The engine times itself.  One set of ``time.monotonic()`` stamps — a
dozen a step, one a token — is taken always and feeds two sinks.  The first
is the phase clock (``scheduler.PhaseClock``): every moment of the engine
thread's life lies in one phase of a closed set, so the cumulative
``step_seconds`` that ``stats()`` (``/stats``, the heartbeat) reports add up
to the thread's time, and a request knows what the thread did between its
tokens (``GenRequest.tpot_split_s``, beside the window of recent requests'
latencies).  The second, only while the process tracer is enabled, is the
``serve.*`` spans, from the same stamps (``docs/serving.md`` "Observability"
names each phase and span with what reads it).  Request spans carry
``request=<id>``, step spans ``step=<n>``.
"""

from __future__ import annotations

import collections
import functools
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from determined_tpu.lint._runtime import get_retrace_sentinel
from determined_tpu.observability import get_tracer
from determined_tpu.serve.config import ServeConfig
from determined_tpu.serve.kv_cache import (
    BlockAllocator,
    CacheOOM,
    prefix_block_hashes,
)
from determined_tpu.serve.scheduler import (
    ADMISSION_FIRST_SAMPLE,
    ADMISSION_KV_ALLOC,
    ADMISSION_PREFILL,
    ADMISSION_REST,
    D2H,
    DECODE_DISPATCH,
    DECODE_WAIT,
    IDLE,
    LANES,
    PHASES,
    REST,
    RETIRE,
    SAMPLE_LAUNCH,
    SAMPLE_WAIT,
    TPOT_PARTS,
    ActiveSeq,
    AdmissionQueue,
    AdmissionRejected,
    GenRequest,
    LaneTable,
    PhaseClock,
)

logger = logging.getLogger("determined_tpu.serve")

mono = time.monotonic

#: finished requests whose latencies ``/stats`` summarizes
LATENCY_WINDOW = 512


def _percentile(ordered: List[float], q: float) -> float:
    """``q``-th percentile (0..100) of a sorted list, linear between ranks."""
    k = (len(ordered) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (k - lo)


def _summary_ms(seconds: List[float]) -> Dict[str, Any]:
    if not seconds:
        return {"p50": None, "p90": None, "n": 0}
    ordered = sorted(seconds)
    return {
        "p50": round(1000.0 * _percentile(ordered, 50), 3),
        "p90": round(1000.0 * _percentile(ordered, 90), 3),
        "n": len(ordered),
    }


def _ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else round(1000.0 * seconds, 3)


def _named(fn: Any, name: str) -> Any:
    """The name the jitted program carries in a device trace (``jit_<name>``)."""
    fn.__name__ = name
    return fn


#: entries a block sum covers in ``sample_token``
_SAMPLE_BLOCK = 128


def sample_token(logits: np.ndarray, temperature: float, rng: Any) -> int:
    """Sample one token from f32 logits [vocab]: greedy at temperature 0,
    otherwise one draw from the exact categorical distribution
    ``softmax(logits / temperature)`` over the whole vocabulary.  Shared by
    the serving engine and the full-forward oracle in the parity tests, so
    'sampling matches' reduces to 'logits match'.

    One float32 pass (a fresh array: ``logits`` is a view into the step's
    logits and is not written to), sums of ``_SAMPLE_BLOCK`` entries
    accumulated in float64, and exactly ONE ``rng.random()``, searched first
    over the blocks' cumulative sum and then inside the block it lands in:
    the inverse-CDF draw ``Generator.choice(p=...)`` makes, without its
    float64 passes over every entry."""
    if temperature <= 0.0:
        return int(np.argmax(logits))
    with np.errstate(invalid="ignore", over="ignore"):
        # the maximum comes off BEFORE the scaling: a difference of nearby
        # float32 values is exact, so the likely tokens round at the size of
        # their distance from the maximum, not at the size of the logits
        z = np.asarray(logits, dtype=np.float32)
        z = z - z.max()
        z *= np.float32(1.0 / temperature)
        np.exp(z, out=z)
    # one sum a block, the last one short where the vocabulary is no multiple
    starts = np.arange(0, z.size, _SAMPLE_BLOCK)
    cum = np.cumsum(np.add.reduceat(z, starts, dtype=np.float64))
    total = cum[-1]
    if not np.isfinite(total) or total <= 0.0:
        # NaN/inf logits (a numerically degenerate model) must degrade to
        # a bad TOKEN, not a ValueError that kills the scheduler loop
        return int(np.argmax(np.nan_to_num(logits, nan=-np.inf)))
    u = rng.random() * total
    block = _first_above(cum, u)
    if block:
        u -= cum[block - 1]
    start = block * _SAMPLE_BLOCK
    inner = np.cumsum(z[start : start + _SAMPLE_BLOCK], dtype=np.float64)
    return start + _first_above(inner, u)


def _first_above(cum: np.ndarray, u: float) -> int:
    """First index whose cumulative sum exceeds ``u``.  ``u`` is held just
    below the last sum, so one that rounding put at or past the end picks
    the last entry that adds anything, never an index out of range or an
    entry of probability zero."""
    return int(np.searchsorted(cum, min(u, np.nextafter(cum[-1], -np.inf)), side="right"))


def sample_lanes(logits: Any, temperature: Any, uniform: Any, *, counters: int = 0) -> Tuple[Any, Any]:
    """``sample_token`` for every lane of a decode step at once, as one
    program over the logits where the device holds them.

    ``logits`` f32 ``[lanes (+ 1), vocab]`` as ``DecodeKernels.decode``
    returns them (a device's array or a host's), ``temperature`` and
    ``uniform`` f32 ``[lanes]``: a lane's ``rng.random()``, drawn on the host.
    Returns the int32 token of each lane and the first ``counters`` entries
    of the row after the lanes (the step's ``serve_counters``).

    A lane at temperature <= 0 gets ``argmax``, first index on ties.  Any
    other gets the inverse-CDF draw ``sample_token`` makes, with float32
    sums: the maximum comes off before the scaling, the exponentials are
    summed by blocks of ``_SAMPLE_BLOCK`` (a short last block padded with
    ``-inf``), the draw is searched first over the blocks' cumulative sum (a
    scan of logarithmic depth) and then over the cumulative sum inside the
    block it lands in.  Both searches take the first entry whose cumulative
    sum exceeds the draw AND which adds something, and the last entry that
    adds anything where rounding put the draw past the end: an entry of
    probability zero is never returned.  A lane whose normaliser is not
    finite or not positive gets ``argmax(nan_to_num(logits))``; so does a
    greedy lane, which differs from ``np.argmax`` only in never choosing a
    NaN."""
    import jax.numpy as jnp

    lanes = temperature.shape[0]
    x = logits[:lanes]
    vocab = x.shape[1]
    blocks = -(-vocab // _SAMPLE_BLOCK)
    greedy = temperature <= 0.0
    scale = 1.0 / jnp.where(greedy, 1.0, temperature)

    def first_above(cum: Any, mass: Any, u: Any) -> Any:
        hit = (cum > u[:, None]) & (mass > 0.0)
        last = jnp.max(jnp.where(mass > 0.0, jnp.arange(mass.shape[1]), 0), axis=-1)
        return jnp.where(hit.any(axis=-1), jnp.argmax(hit, axis=-1), last)

    padded = jnp.pad(x, ((0, 0), (0, blocks * _SAMPLE_BLOCK - vocab)), constant_values=-jnp.inf)
    top = jnp.max(x, axis=-1, keepdims=True)
    z = jnp.exp((padded - top) * scale[:, None]).reshape(lanes, blocks, _SAMPLE_BLOCK)
    sums = z.sum(axis=-1)
    cum = jnp.cumsum(sums, axis=-1)
    total = cum[:, -1]
    u = uniform * total
    block = first_above(cum, sums, u)
    before = jnp.take_along_axis(cum, jnp.maximum(block - 1, 0)[:, None], axis=1)[:, 0]
    mass = jnp.take_along_axis(z, block[:, None, None], axis=1)[:, 0]
    inside = first_above(jnp.cumsum(mass, axis=-1), mass, u - jnp.where(block > 0, before, 0.0))
    sound = jnp.isfinite(total) & (total > 0.0) & ~greedy
    fallback = jnp.argmax(jnp.nan_to_num(x, nan=-jnp.inf), axis=-1)
    ids = jnp.where(sound, block * _SAMPLE_BLOCK + inside, fallback)
    return ids.astype(jnp.int32), logits[lanes:, :counters].reshape(-1)


@functools.lru_cache(maxsize=None)
def lane_sampler(counters: int = 0) -> Any:
    """The jitted :func:`sample_lanes` that returns ``counters`` counts, as
    the engine's step calls it: ``(logits, draws)`` with ``draws`` f32
    ``[2, lanes]``, the lanes' temperatures over their uniforms (ONE array
    to the device a step: each costs a third of a millisecond on a v5e's
    host).  One a process, so that the engine's thread finds the program the
    kernels' builder loaded (``jit_serve_sample`` in a device trace)."""
    import jax

    def serve_sample(logits: Any, draws: Any) -> Tuple[Any, Any]:
        return sample_lanes(logits, draws[0], draws[1], counters=counters)

    return jax.jit(serve_sample)


class DecodeKernels:
    """Compiled prefill/decode for one (model cfg, params) pair.

    ``prefill`` / ``prefill_suffix`` run one request at a time through ONE
    program, the chunked walk (single trace; its device time follows the
    chunks the prompt asks for); ``decode`` steps all ``max_batch`` lanes at
    once and leaves their logits on the device.  The cache argument is
    donated: each step writes into the buffers of the previous one instead
    of copying the pool.
    """

    def __init__(self, model_cfg: Any, params: Any, serve_cfg: ServeConfig) -> None:
        import jax

        from determined_tpu.models.cache_kinds import BLOCKS, LANE, cache_kinds
        from determined_tpu.models.serving import (
            _check_decodable,
            init_kv_cache,
            serve_counters,
            serve_gauges,
            transformer_decode,
            transformer_prefill_chunked,
        )
        from determined_tpu.models.transformer import kv_bytes_per_token
        from determined_tpu.utils.compilation_cache import (
            setup_compilation_cache,
            timed_first_call,
        )

        _check_decodable(model_cfg)
        #: the kinds of cache this model's layers keep (``models/cache_kinds.py``):
        #: what the engine derives admission, its refusals and ``/stats`` from
        self.kinds = cache_kinds(model_cfg)
        #: some kind is held by the decode lane: no block holds what it keeps, so
        #: no prefix is shared and a prefill takes its lane and starts at 0
        lane_held = [kind for kind in self.kinds if kind.holds == LANE]
        self._lane_held = bool(lane_held)
        if lane_held and serve_cfg.prefix_cache:
            raise ValueError(lane_held[0].no_prefix_cache)
        tracer = get_tracer()
        t_setup = mono()
        # a relaunched replica loads its two kernels from disk; keeps the
        # directory ``train.init`` applied when the engine came from a
        # checkpoint (``from_checkpoint``)
        setup_compilation_cache()
        if "params" in params:  # accept the full TrainState tree or its inner dict
            params = params["params"]
        self.model_cfg = model_cfg
        self.serve_cfg = serve_cfg
        # both are waited for so that each span holds its own transfer; the
        # first kernel call would have waited for them anyway
        t_params = mono()
        self.params = jax.block_until_ready(jax.device_put(params))
        t_pool = mono()
        self.cache = jax.block_until_ready(
            init_kv_cache(
                model_cfg, serve_cfg.num_blocks, serve_cfg.block_size, serve_cfg.max_batch, serve_cfg.prefill_chunk
            )
        )
        t_pooled = mono()
        param_bytes = sum(int(x.nbytes) for x in jax.tree.leaves(self.params))
        pool_bytes = sum(int(x.nbytes) for x in jax.tree.leaves(self.cache))
        tracer.record_span(
            "serve.setup.params_to_device", "serve", t_params, t_pool,
            {"bytes": param_bytes},
        )
        # bytes_per_token: what attention reads of the cache for one cached
        # token over all layers that cache tokens (K and V rows, or one latent
        # row a layer); then what each kind says of its own store
        setup: Dict[str, Any] = {"bytes": pool_bytes, "bytes_per_token": kv_bytes_per_token(model_cfg)}
        for kind in self.kinds:
            setup.update(kind.setup(model_cfg, serve_cfg))
        tracer.record_span("serve.setup.kv_pool", "serve", t_pool, t_pooled, setup)
        if not any(kind.holds == BLOCKS for kind in self.kinds):
            logger.info(
                "no layer of this model reads the paged pool: the allocator's %d block ids address nothing and no "
                "array was made for them; a request holds the store of its decode lane (one of %d) whatever its length",
                serve_cfg.num_blocks, serve_cfg.max_batch,
            )
        #: (call, jitted call returned, logits ready on the device) of the
        #: newest ``decode``: the engine, which knows the step, turns them
        #: into ``serve.decode.dispatch`` and ``serve.decode.wait``
        self.last_decode_stamps: Optional[Tuple[float, float, float]] = None
        #: what the engine left for the next ``decode`` to run on this thread
        #: while the device runs the step: called once between the call's two
        #: stamps with the logits the call has just enqueued (a pending array:
        #: to be handed to another program, never read on the host there), and
        #: cleared by the call (an attribute and no parameter: whatever wraps
        #: ``decode`` from outside passes its three arrays on)
        self.during_wait: Optional[Callable[[Any], None]] = None
        #: a model with expert layers or a cache kind that counts: the decode
        #: program returns one more row of logits, whose first entries are these
        #: counts of the step (``transformer_decode``), in this order; the
        #: engine's sampler hands them back beside the tokens
        self.counters: Tuple[str, ...] = serve_counters(model_cfg)
        #: after them in that row, what the step read off the cache it left (a
        #: kind's gauges): the newest step's values, for ``/stats`` alone
        self.gauges: Tuple[str, ...] = serve_gauges(model_cfg)
        #: the prefill's token width: the longest prompt in whole chunks
        #: (one trace; the walk's trip count follows each prompt)
        self._prompt_pad = serve_cfg.prefill_chunks(serve_cfg.max_prompt_len) * serve_cfg.prefill_chunk
        #: narrow chunks a wide iteration of the walk takes at once (1:
        #: its program has no wide loop): what the engine counts an admission's sweeps by
        self.prefill_wide = serve_cfg.prefill_wide
        sentinel = get_retrace_sentinel()
        # cold requests run it with start=0, warm requests from the chunk
        # of their first un-cached block; either way it is the SAME trace
        # (dynamic trip count inside the program)
        # the walk is told its chunk: a cache without a pool states no block size
        prefill = sentinel.wrap(
            "serve.prefill_step",
            functools.partial(transformer_prefill_chunked, model_cfg, chunk_tokens=serve_cfg.prefill_chunk),
            allowed=1,
        )
        decode = sentinel.wrap(
            "serve.decode_step",
            functools.partial(
                transformer_decode,
                model_cfg,
                chunk_blocks=serve_cfg.decode_chunk_blocks,
                counters=bool(self.counters + self.gauges),
            ),
            allowed=1,
        )
        self._prefill = timed_first_call(
            jax.jit(_named(prefill, "serve_prefill"), donate_argnums=(5,)),
            "jit.compile.serve.prefill",
        )
        self._decode = timed_first_call(
            jax.jit(_named(decode, "serve_decode"), donate_argnums=(4,)),
            "jit.compile.serve.decode",
        )
        tracer.record_span(
            "serve.setup", "serve", t_setup, mono(),
            {"param_bytes": param_bytes, "kv_pool_bytes": pool_bytes},
        )
        # The first call of each program compiles it or loads it from the
        # cache.  It is made here, by the thread that builds the kernels, and
        # not by the engine's thread at the first request: taking the prefill
        # program out of the cache costs a worker thread 7.3 s where it costs
        # the main thread 0.6 (InternLM2-1.8B on a v5e; PERF.md, PR 35), and
        # a replica that says it is ready has its programs on the device.
        # The first two write the scratch block alone: a one-token prompt
        # under a table of block 0, and a step with every lane idle, whose
        # logits the engine's sampler (``lane_sampler``) then takes as it
        # will take a step's, with draws the device holds.
        self._prefill_from([0], [0] * serve_cfg.blocks_per_seq, 0)
        lanes = serve_cfg.max_batch
        logits, self.cache = self._decode(
            self.params, np.zeros(lanes, np.int32), np.full(lanes, -1, np.int32),
            np.zeros((lanes, serve_cfg.blocks_per_seq), np.int32), self.cache,
        )
        sample = timed_first_call(lane_sampler(len(self.counters + self.gauges)), "jit.compile.serve.sample")
        jax.block_until_ready(sample(logits, jax.device_put(np.zeros((2, lanes), np.float32))))

    # -- kernel entry points (device round trips happen HERE) ---------------

    def prefill(self, prompt: List[int], block_table: Any, lane: int = 0) -> np.ndarray:
        """Prefill one sequence from its first token, writing its K/V into
        the paged cache; returns the f32 logits at the last prompt token.
        ``lane``: the decode lane (the row of ``decode``'s batch) the sequence
        will run in, whose store a kind of cache that a request holds by its
        lane writes; a model without one takes no notice of it."""
        return self._prefill_from(prompt, block_table, 0, lane)

    def prefill_suffix(self, prompt: List[int], block_table: Any, start: int, lane: int = 0) -> np.ndarray:
        """Prefill only ``prompt[start:]`` (the un-cached suffix; ``start``
        is block-aligned — the cached prefix already sits in the mapped
        blocks).  Returns the f32 logits at the last prompt token."""
        return self._prefill_from(prompt, block_table, start, lane)

    def _prefill_from(self, prompt: List[int], block_table: Any, start: int, lane: int = 0) -> np.ndarray:
        # under both entry points, which a caller may wrap one by one; the
        # engine's table is a sequence's own int32 row, taken as it is
        tokens = np.zeros((1, self._prompt_pad), np.int32)
        tokens[0, : len(prompt)] = prompt
        table = np.asarray(block_table, np.int32)[None, :]
        starts = np.asarray([start], np.int32)
        lens = np.asarray([len(prompt)], np.int32)
        args = (self.params, tokens, starts, lens, table, self.cache)
        if self._lane_held:
            if start:
                raise ValueError(
                    f"a prompt of a model that keeps part of its cache by the decode lane (a ring, a state slot) is prefilled from 0, not from {start}"
                )
            args += (np.asarray([lane], np.int32),)
        logits, self.cache = self._prefill(*args)
        return np.asarray(logits[0])

    def decode(self, tokens: Any, positions: np.ndarray, tables: Any) -> Any:
        """One decode step over every lane; returns the f32 logits
        ``[B, vocab]`` as the device holds them, ready, and with one more row
        after the lanes' where the model counts its steps (``counters``).
        ``tokens`` and ``tables`` are the host's arrays or the device's (the
        engine hands over the sampler's ids and its own copy of the table).

        Nothing of them is copied to the host here: the engine draws the
        step's tokens from them on the device (``sample_lanes``), and a
        caller that wants a row asks for it (``np.asarray(logits[lane])``).
        The call is stamped where its two parts end — the jitted call returns
        (enqueued), the logits are ready on the device — and the stamps left
        in ``last_decode_stamps``: whatever times this call from outside
        holds the device's whole step.  Between the two, while the device
        runs the step, ``during_wait`` is run, once, where the engine left
        one, with the logits as they are then: enqueued and not yet ready.
        The engine queues its sampler behind them there, so the sampler's
        operations close the step's burst on the device and may start
        before this call returns."""
        t0 = mono()
        logits, self.cache = self._decode(
            self.params, tokens, positions, tables, self.cache
        )
        t1 = mono()
        work, self.during_wait = self.during_wait, None
        if work is not None:
            work(logits)
        logits.block_until_ready()
        self.last_decode_stamps = (t0, t1, mono())
        return logits


class LaneRow:
    """What a decode step hands a sampler of the caller's own
    (``ServeEngine._advance_lane``) for one lane: the token the device drew
    for it, and the lane's float32 logits for whoever asks for them as an
    array (one row's copy to the host).  The engine's own bookkeeping reads
    the token where it lies and makes none."""

    __slots__ = ("token", "_logits", "_lane")

    def __init__(self, token: int, logits: Any, lane: int) -> None:
        self.token = token
        self._logits = logits
        self._lane = lane

    def __array__(self, dtype: Any = None, copy: Any = None) -> np.ndarray:
        return np.asarray(self._logits[self._lane], dtype=dtype)


class ServeEngine:
    """Continuous batching: join between any two steps, retire instantly."""

    #: a sampler of the caller's own, ``(seq, row: LaneRow) -> finished``, laid
    #: over an engine as a bound method: called for each live lane of a step in
    #: place of the engine's bookkeeping (which gives the lane the device's
    #: token), it appends the token and its stamp, advances ``pos`` and
    #: ``next_token`` and counts what it emitted itself
    _advance_lane: Optional[Callable[[ActiveSeq, LaneRow], bool]] = None

    def __init__(self, kernels: DecodeKernels) -> None:
        self.kernels = kernels
        self.cfg = kernels.serve_cfg
        self.lanes = LaneTable(self.cfg.max_batch)
        # THE STEP'S OWN STATE: ``_tables``, ``_tables_on_device``,
        # ``_positions`` and ``_ids_on_device`` belong to whichever thread runs
        # ``step_once``: the engine's own after ``start()``, a test's before
        # it, never both.  Every read and write of the four is under
        # ``step_once`` (``_admit_one``, ``_decode_and_sample`` over
        # ``_decode_batch``, ``_retire_lane``); ``stats()``, ``submit()``,
        # ``stop()`` and ``drain()`` read none of them.  One writer, no lock:
        # each write below is marked ``lint-ok[unlocked-shared-state]`` on this
        # argument, and a use of one of them outside the step breaks it.
        #: every lane's block table, for the engine's life: a lane that joins
        #: writes its row, a lane that retires gets the scratch block 0 again
        #: (an idle lane's row never names a block the allocator may have
        #: handed to someone else)
        self._tables = np.zeros((self.cfg.max_batch, self.cfg.blocks_per_seq), np.int32)
        #: the device's copy of it; None once a row changed: the next step
        #: sends the matrix again
        self._tables_on_device: Any = None
        #: the lanes' positions at the next step (-1: idle).  A step hands
        #: the array over and makes the next one while the device runs
        self._positions = np.full(self.cfg.max_batch, -1, np.int32)
        #: the ids the newest step's sampler left on the device: the next
        #: step's tokens as they lie.  None once a lane joined (its first
        #: token is the host's draw) and where the caller's own sampler chose
        self._ids_on_device: Any = None
        #: decode steps, those whose table was sent again, those whose tokens
        #: were the device's ids, those whose sampler was queued inside the
        #: decode call's wait, and what the paged decode kernels' walks had to
        #: read and what their copies brought, in tokens over every walked row
        #: of the cache (``/stats`` ``step_inputs``)
        self._step_inputs = {
            "decode_steps": 0, "table_sent": 0, "tokens_from_device": 0, "sampler_in_wait": 0,
            "paged_live_tokens": 0, "paged_copied_tokens": 0,
        }
        from determined_tpu.models.cache_kinds import BLOCKS

        #: (rows, window) of each kind a paged decode kernel walks a step
        self._walked = [kind.walked(kernels.model_cfg) for kind in kernels.kinds if kind.walked is not None]

        #: whether a request holds blocks of some kind of the kernels' cache
        #: (``models/cache_kinds.py``).  Where none does, a request holds the
        #: store of its lane and no block, and admission never asks the allocator
        self._holds_blocks = any(kind.holds == BLOCKS for kind in kernels.kinds)
        #: trial/model label surfaced in the master's replica listing
        self.model_label = type(kernels.model_cfg).__name__
        self.allocator = BlockAllocator(
            self.cfg.num_blocks,
            self.cfg.block_size,
            prefix_cache=self.cfg.prefix_cache,
        )
        self.queue = AdmissionQueue(self.cfg.queue_depth)
        self._tracer = get_tracer()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._finished = threading.Event()
        #: set when the loop died on an unexpected exception; /healthz
        #: reports it so a crashed engine never keeps serving 'ok'
        self.failed: Optional[str] = None
        self._thread = threading.Thread(
            target=self._run_guarded, name="dtpu-serve-engine", daemon=True
        )
        self._stats_lock = threading.Lock()
        self._submitted = 0
        self._completed = 0
        self._rejected = 0
        self._tokens_generated = 0
        #: of those, the tokens a decode step's one call drew on the device
        #: (the rest: each request's first, sampled on the host at admission)
        self._tokens_sampled_on_device = 0
        #: the counts the kernels' decode program returns in the row after
        #: the lanes' logits, by name and in its order (a stand-in has none)
        self._counters: Tuple[str, ...] = tuple(getattr(kernels, "counters", ()))
        #: what follows them: the kinds' gauges, whose newest values ``stats()`` hands the kinds' reports
        self._gauges: Tuple[str, ...] = tuple(getattr(kernels, "gauges", ()))
        self._step_gauges: Dict[str, float] = {}
        #: requests that finished with an error (prefill crash, engine
        #: stop/crash, drain abandonment) — the error-rate numerator the
        #: master's canary bake compares against its pre-roll baseline
        self._errored = 0
        #: 5xx responses counted by the HTTP layer (note_http_response);
        #: catches handler-level failures the engine never sees
        self._http_5xx = 0
        self._latency_ms_total = 0.0
        #: (ttft_s, tpot_s, queue_wait_s, tpot_split_s) of the newest finished
        #: requests; appended at retire, summarized by stats() on the caller's thread
        self._recent: "collections.deque[Tuple[Any, ...]]" = (
            collections.deque(maxlen=LATENCY_WINDOW)
        )
        #: (``_completed`` when summarized, the summary): stats()'s cache
        self._latency_summary: Tuple[int, Dict[str, Any]] = (-1, {})
        #: where this thread's time goes, by phase: fed from a step's stamps
        #: by the engine's thread alone, which starts here, idle
        self._clock = PhaseClock()
        #: a reading of the clock and the seconds it covers, as the engine's
        #: thread last published it (a step's one hold of the stats lock,
        #: an idle wait's end): what ``stats()`` reports as ``step_seconds``
        self._step_seconds: Tuple[Tuple[float, ...], float] = self._clock.reading()
        #: prompt tokens the prefills were asked for (past what the prefix
        #: cache held) and tokens they computed (whole chunks), cumulative
        self._prefill_tokens_asked = 0
        self._prefill_tokens_computed = 0
        #: of those, the tokens computed in wide iterations of the walk
        self._prefill_wide_tokens = 0
        #: what the decode steps of a model with expert layers counted,
        #: cumulative, by the counter's name (``/stats`` ``step_counters``)
        self._step_counters: Dict[str, float] = {}
        #: the engine's own step counter: decode steps (and admit-only
        #: iterations) that did work
        self._steps = 0
        #: true while THIS engine runs the tracer's shipper (it started it)
        self._owns_shipper = False
        self._started_at = self._clock.started_at

    @classmethod
    def from_checkpoint(
        cls,
        path: str,
        serve_cfg: Optional[ServeConfig] = None,
        trial_class: Optional[type] = None,
    ) -> "ServeEngine":
        """Load a trial checkpoint (``train.load_trial_from_checkpoint``)
        and serve its model.  The trial's ``build_model()`` must return a
        module exposing ``cfg`` (a TransformerConfig) — the LMTrial
        contract."""
        from determined_tpu import train

        trial, trainer = train.load_trial_from_checkpoint(path, trial_class=trial_class)
        model_cfg = getattr(trainer.model, "cfg", None)
        if model_cfg is None:
            raise ValueError(
                "checkpointed trial does not build a decoder-only transformer "
                "(model has no .cfg); only TransformerLM-style trials serve"
            )
        params = trainer.state.params
        if "params" not in params:
            raise ValueError(
                "checkpoint params are in a pipeline-stage layout; serving "
                "loads single-host (pipe=1) checkpoints only"
            )
        engine = cls(DecodeKernels(model_cfg, params, serve_cfg or ServeConfig()))
        engine.model_label = type(trial).__name__  # e.g. "LMTrial"
        return engine

    # -- admission (HTTP threads) -------------------------------------------

    def submit(
        self,
        prompt: List[int],
        *,
        max_new_tokens: Optional[int] = None,
        temperature: float = 0.0,
        seed: Optional[int] = None,
        stop_token: Optional[int] = None,
    ) -> GenRequest:
        """Admit one request or raise :class:`AdmissionRejected` — 413 for
        requests no drained replica could ever serve, 429 under queue
        backpressure, 503 while draining."""
        if not prompt:
            raise AdmissionRejected(400, "empty prompt")
        if len(prompt) > self.cfg.max_prompt_len:
            raise AdmissionRejected(
                413,
                f"prompt of {len(prompt)} tokens exceeds max_prompt_len="
                f"{self.cfg.max_prompt_len}",
            )
        new = (
            self.cfg.max_new_tokens
            if max_new_tokens is None
            else min(int(max_new_tokens), self.cfg.max_new_tokens)
        )
        if new < 1:  # 0 is a client error, not "use the default"
            raise AdmissionRejected(400, "max_new_tokens must be >= 1")
        if self._holds_blocks and self.allocator.blocks_for(len(prompt) + new) > self.allocator.capacity:
            # permanent: this request can NEVER fit this replica's cache
            raise AdmissionRejected(
                413, "request exceeds kv cache capacity (kv_cache_oom)"
            )
        req = GenRequest(
            prompt=list(prompt),
            max_new_tokens=new,
            temperature=float(temperature),
            seed=seed,
            stop_token=stop_token,
        )
        try:
            self.queue.submit(req)
        except AdmissionRejected:
            with self._stats_lock:
                self._rejected += 1
            raise
        with self._stats_lock:
            self._submitted += 1
        self._wake.set()
        return req

    def generate(self, prompt: List[int], timeout: float = 120.0, **kw: Any) -> GenRequest:
        """submit + wait: the in-process convenience the bench/tests use."""
        req = self.submit(prompt, **kw)
        if not req.done.wait(timeout):
            raise TimeoutError(f"request {req.id} did not finish in {timeout}s")
        return req

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "ServeEngine":
        if not self._thread.is_alive() and not self._finished.is_set():
            t0 = mono()
            if self._tracer.enabled and not self._tracer.shipping:
                # a step writes ten spans: without the shipper the
                # engine thread's ring fills within minutes and every later
                # event is dropped.  An entry point that runs the shipper
                # itself (dtpu serve with trace_dir, a trial) keeps it.
                self._tracer.start()
                self._owns_shipper = True
            self._thread.start()  # returns once the step thread is up
            self._tracer.record_span("serve.engine.start", "serve", t0, mono())
        return self

    def _release_shipper(self) -> None:
        if self._owns_shipper:
            self._owns_shipper = False
            self._tracer.stop()  # joins the shipper, then drains once more

    @property
    def healthy(self) -> bool:
        """False once the loop died (crash or stop) — the liveness the
        HTTP layer and heartbeats must report, NOT thread aliveness alone
        (an unstarted engine in tests is fine)."""
        return self.failed is None and not (
            self._finished.is_set() and not self.queue.draining
        )

    def _run_guarded(self) -> None:
        """The thread target: one unexpected exception must not strand
        parked HTTP handlers on a silently dead loop — fail everything
        loudly and flip `failed` so /healthz stops claiming 'ok'."""
        try:
            self._run()
        except BaseException as e:  # noqa: BLE001 - last line of defense
            logger.exception("serving engine loop died")
            # Safe: the engine thread is the ONLY writer (exactly once, on
            # death); HTTP threads only read the GIL-atomic reference.
            self.failed = f"{type(e).__name__}: {e}"  # dtpu: lint-ok[unlocked-shared-state]
            reason = f"engine crashed: {self.failed}"
            self._fail_outstanding(reason)
            self._abort_active(reason)
            self._finished.set()

    def _abort_active(self, reason: str) -> None:
        """Fail in-flight sequences on a crash."""
        for i in self.lanes.active():
            seq = self.lanes.retire(i)
            self._finish_error(seq.request, reason)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Stop admitting, finish queued + in-flight work, stop the loop.
        Returns True when everything completed inside ``timeout``."""
        self.queue.start_drain()
        self._wake.set()
        if not self._thread.is_alive():
            return True
        self._thread.join(timeout if timeout is not None else self.cfg.drain_grace_s)
        if self._thread.is_alive():
            self.stop()
            return False
        self._release_shipper()
        return True

    def stop(self) -> None:
        """Hard stop: abandon in-flight work, fail outstanding requests."""
        self._stop.set()
        self._wake.set()
        if self._thread.is_alive():
            self._thread.join(timeout=10.0)
        self._fail_outstanding("engine stopped")
        self._release_shipper()

    def _finish_error(self, req: GenRequest, reason: str) -> None:
        """Fail one request AND count it: every error-finish goes through
        here so the `errored` stat the heartbeat ships stays truthful."""
        self._finish(req, reason)
        with self._stats_lock:
            self._errored += 1
        self._record_request(req)

    def _finish(self, req: GenRequest, error: Optional[str] = None) -> None:
        """Finish a request and, where it has a first token, read the phase
        clock at its last stamp.  Only the engine's thread finishes such a
        request (another thread fails what is still queued), so only it
        reads the clock."""
        req.finish(error)
        if req.phases_at_first is not None:
            req.phases_at_finish = self._clock.read(req.finished_at)

    def note_http_response(self, status: int) -> None:
        """HTTP layer callback: count 5xx responses (handler failures the
        engine's own error path never sees)."""
        if status >= 500:
            with self._stats_lock:
                self._http_5xx += 1

    def _record_request(self, req: GenRequest) -> None:
        """One ``serve.request`` span a finished request, arrival to finish:
        the line an operator's export holds for it."""
        if self._tracer.enabled:
            split = req.tpot_split_s
            self._tracer.record_span(
                "serve.request", "serve", req.arrival, req.finished_at,
                {
                    "request": req.id,
                    "prompt_tokens": len(req.prompt),
                    "output_tokens": len(req.output),
                    "queue_wait_ms": _ms(req.queue_wait_s),
                    "ttft_ms": _ms(req.ttft_s),
                    "tpot_ms": _ms(req.tpot_s),
                    # its parts, to 0.1 us so that the four add up to it within 1 us
                    **{f"tpot_{part}_ms": None if split is None else round(1000.0 * split[part], 4) for part in TPOT_PARTS},
                    "itl_max_ms": _ms(req.itl_max_s),
                    "error": req.error,
                },
            )

    def _fail_outstanding(self, reason: str) -> None:
        while True:
            req = self.queue.get()
            if req is None:
                break
            self._finish_error(req, reason)

    # -- stats ---------------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._stats_lock:
            counters = {
                "submitted": self._submitted,
                "completed": self._completed,
                "rejected": self._rejected,
                "tokens_generated": self._tokens_generated,
                "tokens_sampled_on_device": self._tokens_sampled_on_device,
                # computed over asked is what the walk's whole chunks cost
                # beyond the prompts (1.0: every prompt ended on a chunk's edge)
                "prefill_tokens_asked": self._prefill_tokens_asked,
                "prefill_tokens_computed": self._prefill_tokens_computed,
                # of those, the tokens computed under each of the walk's two widths
                "prefill_wide_tokens": self._prefill_wide_tokens,
                "prefill_narrow_tokens": self._prefill_tokens_computed - self._prefill_wide_tokens,
                "errored": self._errored,
                "http_5xx": self._http_5xx,
                "latency_ms_avg": round(
                    self._latency_ms_total / self._completed, 3
                )
                if self._completed
                else 0.0,
            }
            # summarized again only after another request finished: the
            # sorts cost ~0.15 ms, and a caller may poll every step
            at, latency = self._latency_summary
            completed = self._completed
            recent = list(self._recent) if at != completed else None
            phases, covered = self._step_seconds
            steps = self._steps
            step_counters = dict(self._step_counters)
            gauges = dict(self._step_gauges)
            step_inputs = dict(self._step_inputs)
        if recent is not None:
            latency = {
                name: _summary_ms([r[i] for r in recent if r[i] is not None])
                for i, name in enumerate(("ttft_ms", "tpot_ms", "queue_wait_ms"))
            }
            splits = [r[3] for r in recent if r[3] is not None]
            latency["tpot_split_ms"] = {part: _summary_ms([s[part] for s in splits]) for part in TPOT_PARTS}
            with self._stats_lock:
                self._latency_summary = (completed, latency)
        latency = {name: dict(v) for name, v in latency.items()}
        latency["tpot_split_ms"] = {part: dict(v) for part, v in latency["tpot_split_ms"].items()}
        from determined_tpu.models.cache_kinds import CACHE_KINDS

        kv = self.allocator.stats()
        live = self.lanes.stats()["active"]
        return {
            **counters,
            # what a caller feels, over the newest LATENCY_WINDOW finished
            # requests (tpot, and its split by what the engine's thread was
            # doing between a request's tokens: those with two tokens or more)
            "latency": latency,
            # where the engine thread's time went, cumulative since the
            # engine was made: every phase of the clock, which add up to
            # ``uptime`` (the seconds the reading covers), and the four sums
            # that were here before it: waiting for the decode program (the
            # lanes' uniforms are drawn and sent and the sampler is queued
            # inside that wait), sampling (the decode call's return to the
            # last lane's stamp), of which copying the ids and the counters
            # to the host, admitting (prefill and first sample)
            "step_seconds": {
                "decode_wait": round(phases[DECODE_WAIT], 6),
                "d2h": round(phases[D2H], 6),
                "sample": round(sum(phases[i] for i in TPOT_PARTS["sample"]), 6),
                "admission": round(sum(phases[i] for i in TPOT_PARTS["prefill_stall"]), 6),
                "steps": steps,
                "uptime": round(covered, 6),
                "phases": {name: round(v, 6) for name, v in zip(PHASES, phases)},
            },
            # cumulative counts of the decode steps, where the model has
            # expert layers: picks that landed on the experts held here and
            # held experts that got a row, each summed over layers and steps
            "step_counters": step_counters,
            # how often a decode step had to send its inputs: the block table
            # goes to the device again only after a lane joined or retired,
            # the tokens are the ids the sampler left there unless a lane
            # joined since, and the sampler is queued inside the decode call's
            # wait unless the kernels are a stand-in that never runs the hook
            "step_inputs": step_inputs,
            "queue_depth": self.queue.depth(),
            # static queue bound: the router's saturation signal — at
            # queue_depth >= queue_capacity the next submit would 429
            "queue_capacity": self.cfg.queue_depth,
            "draining": self.queue.draining,
            # truthy once the loop died: the heartbeat ships this and the
            # master reaps the replica immediately instead of waiting out
            # the TTL behind a 500 /healthz
            "failed": self.failed,
            "kv_cache": kv,
            # what each kind of cache says of its own store (``window_store``,
            # ``state`` with ``block_ids_address_nothing``, ``attn_products``:
            # ``models/cache_kinds.py``; one the model has no layer of says so
            # itself); ``kv_cache`` counts the allocator's blocks alone
            **{k: v for kind in CACHE_KINDS for k, v in kind.report(self.kernels.model_cfg, self.cfg, live, gauges).items()},
            # live-block fraction, shared (ref>1) blocks counted ONCE so
            # prefix sharing never inflates the router's load signal
            "kv_utilization": round(kv["used"] / max(1, kv["capacity"]), 4),
            "prefix_hits": kv["prefix_hits"],
            "prefix_tokens_saved": kv["prefix_tokens_saved"],
            "prefix_hit_rate": round(
                kv["prefix_hits"] / max(1, kv["prefix_lookups"]), 4
            ),
            "uptime_s": round(time.monotonic() - self._started_at, 3),
            "lanes": self.lanes.stats(),
        }

    # -- the engine thread's work ---------------------------------------------

    def _padded_table(self, blocks: List[int]) -> np.ndarray:
        """A sequence's row of the block table, made once: its blocks, then the scratch block 0."""
        table = np.zeros(self.cfg.blocks_per_seq, np.int32)
        table[: len(blocks)] = blocks
        return table

    def _start_sequence(self, req: GenRequest, step: int) -> Optional[ActiveSeq]:
        """Allocate + prefill + sample the first token.  Returns the live
        sequence, or None when the request finished at prefill (wanted a
        single token).  Raises CacheOOM without side effects.

        With the prefix cache on, admission first walks the allocator's
        hash trie for the longest run of cached full blocks (capped at
        ``len(prompt) - 1`` tokens, so the block the first decode write
        lands in is never aliased — the partial tail is copy-on-write by
        re-prefilling it into a private block), maps the shared physical
        blocks into this sequence's table with a reference each, and
        prefills only the un-cached suffix.  Afterwards every full prompt
        block is registered as cached content for future admissions.

        ``step`` is the engine step this admission belongs to.  The stamps
        taken here are the request's own (``admitted_at``, the first
        ``token_at``) and the edges of its ``serve.queue_wait`` and
        ``serve.admission`` spans: the wait ends where the admission
        starts, the admission where the first token is out.  The phase
        clock is fed from them once the blocks are held: an attempt that has
        to wait for blocks is not an admission, and its time stays the loop's.
        """
        tracer = self._tracer
        clock = self._clock
        t_admit = mono()  # just off the queue
        # a request that holds no block of any kind: the free lane is the admission
        total = self.allocator.blocks_for(len(req.prompt) + req.max_new_tokens) if self._holds_blocks else 0
        shared: List[int] = []
        cached_tokens = 0
        chain: List[Any] = []
        if self.cfg.prefix_cache:
            chain = prefix_block_hashes(
                req.prompt, self.cfg.block_size, limit_tokens=len(req.prompt) - 1
            )
            shared = self.allocator.match_prefix(chain)
            cached_tokens = len(shared) * self.cfg.block_size
        needed = total - len(shared)
        t_alloc = mono()
        try:
            private = self.allocator.alloc(needed) if needed else []
        except CacheOOM:
            if shared:
                self.allocator.free(shared)
            raise
        finally:
            t_alloced = mono()
            tracer.record_span(
                "serve.kv_alloc", "serve", t_alloc, t_alloced, {"request": req.id, "step": step, "blocks": needed}
            )
        # this attempt holds its blocks: the wait in the queue is over
        req.admitted_at = t_admit
        clock.to(ADMISSION_REST, t_admit)
        clock.to(ADMISSION_KV_ALLOC, t_alloc)
        clock.to(ADMISSION_REST, t_alloced)
        tracer.record_span(
            "serve.queue_wait", "serve", req.arrival, t_admit, {"request": req.id}
        )
        blocks = shared + private
        table = self._padded_table(blocks)
        # the lane is known before the prefill: a kind held by the lane writes its store
        lane = self.lanes.free_lane()
        # the walk's trip counts: whole narrow chunks, from the one the first
        # un-cached token lies in (0 cached when nothing matched), each whole
        # aligned group of them one wide iteration where the walk has a wide loop
        # (kernels wrapped from outside may not pass the attribute on: such a walk is counted narrow)
        per_wide = getattr(self.kernels, "prefill_wide", 1)
        wide, narrow = self.cfg.prefill_walk(per_wide, len(req.prompt), cached_tokens)
        narrow_tokens = narrow * self.cfg.prefill_chunk
        wide_tokens = wide * per_wide * self.cfg.prefill_chunk
        chunks, computed = wide + narrow, wide_tokens + narrow_tokens
        t_prefill = mono()
        try:
            logits = self.kernels.prefill_suffix(req.prompt, table, cached_tokens, lane)
        except BaseException:
            self.allocator.free(blocks)
            raise
        finally:
            t_prefilled = mono()
            tracer.record_span(
                "serve.prefill", "serve", t_prefill, t_prefilled,
                {
                    "request": req.id, "step": step,
                    "cached_tokens": cached_tokens, "chunks": chunks, "computed_tokens": computed,
                    "wide_tokens": wide_tokens, "narrow_tokens": narrow_tokens,
                },
            )
        clock.to(ADMISSION_PREFILL, t_prefill)
        clock.to(ADMISSION_REST, t_prefilled)
        if chain:
            # the suffix just materialized this prompt's remaining full
            # blocks; make them matchable (shared prefix entries are
            # already in the trie — first writer wins)
            self.allocator.register_prefix(chain, blocks[: len(chain)])
        rng = np.random.default_rng(req.seed)
        t_sample = mono()
        tok = sample_token(logits, req.temperature, rng)
        t_first = mono()
        clock.to(ADMISSION_FIRST_SAMPLE, t_sample)
        clock.to(REST, t_first)
        req.first_token_at = t_first
        # its own admission is counted up to here, so what the clock gains
        # between this reading and the one at the finish is the time between
        # the request's first token and its last
        req.phases_at_first = clock.read(t_first)
        req.output.append(tok)
        req.token_at.append(t_first)
        with self._stats_lock:
            self._tokens_generated += 1
            self._prefill_tokens_asked += len(req.prompt) - cached_tokens
            self._prefill_tokens_computed += computed
            self._prefill_wide_tokens += wide_tokens
        if tracer.enabled:
            tracer.record_span(
                "serve.first_sample", "serve", t_sample, t_first, {"request": req.id}
            )
            tracer.record_span(
                "serve.admission", "serve", t_admit, t_first,
                {
                    "request": req.id, "step": step,
                    "prompt_tokens": len(req.prompt), "cached_tokens": cached_tokens,
                },
            )
        seq = ActiveSeq(
            request=req,
            blocks=blocks,
            block_table=table,
            pos=len(req.prompt),
            next_token=tok,
            rng=rng,
            lane=lane,
        )
        if self._sequence_finished(seq, tok):
            self._retire_seq(seq)
            return None
        return seq

    def _sequence_finished(self, seq: ActiveSeq, last_token: int) -> bool:
        req = seq.request
        return len(req.output) >= req.max_new_tokens or (
            req.stop_token is not None and last_token == req.stop_token
        )

    def _retire_seq(self, seq: ActiveSeq) -> None:
        self.allocator.free(seq.blocks)
        req = seq.request
        self._finish(req)
        latency = req.latency_s
        with self._stats_lock:
            self._completed += 1
            if latency is not None:
                self._latency_ms_total += latency * 1000.0
            self._recent.append((req.ttft_s, req.tpot_s, req.queue_wait_s, req.tpot_split_s))
        self._record_request(req)

    def _launch_sampler(self, logits: Any, draws: Any) -> Tuple[Any, Any, Tuple[float, float]]:
        """Queue the step's ONE sampler call (``sample_lanes``) on the logits,
        ready or not, and the copies of its ids and counters to the host
        behind it.  The ids and the counted row as the device holds them, and
        the launch's two stamps."""
        t0 = mono()
        n_counted = len(self._counters) + len(self._gauges)
        ids, counted = lane_sampler(n_counted)(logits, draws)
        # queued behind the program at once: waiting for the ids first and
        # only then asking for them costs a host round trip
        ids.copy_to_host_async()
        if n_counted:
            counted.copy_to_host_async()
        return ids, counted, (t0, mono())

    def _decode_batch(
        self, lanes: List[Optional[ActiveSeq]]
    ) -> Tuple[Any, np.ndarray, Any, Dict[str, int], Tuple[float, float], Tuple[float, float], Optional[Tuple[Any, Any, Tuple[float, float]]]]:
        """One jitted decode step over the full (static) lane table, and
        inside its wait what the step's sampler needs that no id decides AND
        the sampler's launch.  Returns the logits as the kernels handed them
        back (on the device, ready), the lanes' positions (-1: idle), the
        lanes' temperatures over their uniforms as the device holds them,
        which of the step's inputs had to be sent (``step_inputs``), the
        call's two ends, the prepared work's, and what the sampler's launch
        inside the wait left (``_launch_sampler``; None where it was not
        launched there).

        The table goes to the device only where a row changed since it last
        went; the tokens are the ids the last step's sampler left there,
        unless a lane joined since (then the host's, as each lane's
        ``next_token`` has them).  The positions are the host's array.

        The prepared work runs on this thread between the kernels' two
        stamps (``DecodeKernels.during_wait``): a sampled lane's uniform is
        ONE ``rng.random()`` of its request's generator, drawn in lane order
        (a greedy lane draws nothing), the ``[2, lanes]`` draws go to the
        device, the positions of the step after are made (a NEW array:
        whoever wraps the call reads this step's after it returns), and the
        sampler is queued on the logits the kernels hand the hook, which are
        not ready yet: the device runs it the moment the decode program ends.
        Under a stand-in for the kernels that never runs the hook the draws
        are made here, after the call, and the launch is left to the caller.
        There too the step's walks are counted from the positions (``walk_counts``
        of ``ops/paged_attention.py``, each walked row of the cache alike):
        ``paged_live_tokens`` the kernels' queries see, ``paged_copied_tokens``
        their copies bring."""
        import jax

        from determined_tpu.ops.paged_attention import walk_counts

        b = self.cfg.max_batch
        positions = self._positions
        sent = {
            "table_sent": int(self._tables_on_device is None), "tokens_from_device": int(self._ids_on_device is not None),
            "paged_live_tokens": 0, "paged_copied_tokens": 0,
        }
        if self._tables_on_device is None:
            # a copy: the matrix is written again while the device's buffer is still held
            self._tables_on_device = jax.device_put(self._tables.copy())  # dtpu: lint-ok[unlocked-shared-state] the step's own (__init__)
        tokens = self._ids_on_device
        if tokens is None:
            tokens = np.zeros(b, np.int32)
            for i, seq in enumerate(lanes):
                if seq is not None:
                    tokens[i] = seq.next_token
        prepared: List[Any] = []

        def prepare(pending: Any = None) -> None:
            t_prepare = mono()
            draws = np.zeros((2, b), np.float32)  # temperatures over uniforms; an idle lane: argmax, ignored
            for i, seq in enumerate(lanes):
                if seq is not None and seq.request.temperature > 0.0:
                    draws[0, i] = seq.request.temperature
                    draws[1, i] = seq.rng.random()
            on_device = jax.device_put(draws)
            self._positions = np.where(positions >= 0, positions + 1, positions)  # dtpu: lint-ok[unlocked-shared-state] the step's own (__init__)
            for rows, window in self._walked:
                walk = walk_counts(positions, self.cfg.block_size, window)
                sent["paged_live_tokens"] += rows * walk.live_tokens
                sent["paged_copied_tokens"] += rows * walk.copied_tokens
            prepared.extend((on_device, (t_prepare, mono()), None if pending is None else self._launch_sampler(pending, on_device)))

        kernels = self.kernels
        kernels.during_wait = prepare
        t0 = mono()
        logits = kernels.decode(tokens, positions, self._tables_on_device)
        t1 = mono()
        if not prepared:
            kernels.during_wait = None
            prepare()
        draws, t_prepared, launched = prepared
        return logits, positions, draws, sent, (t0, t1), t_prepared, launched

    def _decode_and_sample(self, lanes: List[Optional[ActiveSeq]], step: int) -> int:
        """One decode step over the lane table, whose wait also queues the
        one call that draws every lane's token on the device
        (``sample_lanes``) from the logits where they lie, with the draws the
        device already holds; what comes to the host is the ids and the
        step's counters.  Each live lane then takes its token, in lane order,
        the step's counts go into the stats under ONE hold of their lock, and
        the sequences that finished are retired, in this step (a response's
        counts are in ``stats()`` before it is complete).  Returns how many
        finished.

        The step's order: the decode program is launched
        (``serve.decode.dispatch``); inside ``serve.decode.wait`` the draws
        are made and sent (``serve.step.prepare``) and the sampler's call and
        its two copies are queued behind the decode program
        (``serve.sample.launch``); the call returns when the logits are ready.
        ``serve.sample`` runs from that return to the last lane's token stamp,
        which is what of the sampling is still in series with the device, in
        three parts end to end: ``serve.sample.wait`` (the ids are ready on
        the device: at once or nearly, the sampler ran while the host was
        being told of the logits), ``serve.decode.d2h`` (they are on the
        host) and ``serve.lanes`` (each lane has its token).  The sampler's
        operations close the step's burst on the device and may start inside
        ``serve.decode``: a reader that takes the device's step from that span
        or from the burst counts them in it.  ``serve.retire`` follows where a
        sequence finished.  The phase clock is fed from the same stamps,
        after the lanes' loop; the launch inside the wait is the device's
        busy time and stays under ``decode.wait``.

        Where nothing launched the sampler inside the wait (a stand-in for
        the kernels that never runs the hook, a ``_decode_batch`` of a
        caller's own) it is launched here, after the call, on the same inputs:
        ``serve.sample`` then starts at the launch, which is its first part
        and the clock's ``sample.launch``.  ``sampler_in_wait`` (1 or 0) on
        ``serve.decode`` and in ``step_inputs`` says which a step took."""
        logits, positions, draws, sent, (t_call, t_back), prepare, launched = self._decode_batch(lanes)
        in_wait = launched is not None
        if not in_wait:
            launched = self._launch_sampler(logits, draws)
        ids, counted, (t_launch, t_launched) = launched
        sent["sampler_in_wait"] = int(in_wait)
        # the sampling that is still in series with the device: from the
        # call's return, or from a launch that had to follow it
        t0, t_wait = (t_back, t_back) if in_wait else (t_launch, t_launched)
        names = self._counters + self._gauges
        ids.block_until_ready()
        t_ready = mono()
        tokens = np.asarray(ids).tolist()
        counts = dict(zip(names, np.asarray(counted).tolist())) if names else {}
        gauges = {name: counts.pop(name) for name in self._gauges}
        t1 = t_host = mono()
        # the kernels stamp the parts of their own call (whatever wraps
        # ``kernels.decode`` from outside); a stand-in that leaves no stamps,
        # or stale ones, leaves the step with its whole ``serve.decode`` only
        stamps = getattr(self.kernels, "last_decode_stamps", None)
        if stamps is not None and stamps[0] < t_call:
            stamps = None
        own = self._advance_lane
        self._ids_on_device = ids if own is None else None  # dtpu: lint-ok[unlocked-shared-state] the step's own (__init__)
        finished: List[Tuple[int, ActiveSeq]] = []
        live = 0
        for i, seq in enumerate(lanes):
            if seq is None:
                continue
            live += 1
            tok = tokens[i]
            if own is None:
                req = seq.request
                req.output.append(tok)
                t1 = mono()
                req.token_at.append(t1)
                seq.pos += 1
                seq.next_token = tok
                done = self._sequence_finished(seq, tok)
            else:
                done = own(seq, LaneRow(tok, logits, i))
                t1 = seq.request.token_at[-1]
            if done:
                finished.append((i, seq))
        on_device = live if own is None else 0
        # what lies in ``serve.decode`` outside the kernels' own wait (a
        # wrapper's work round the call, the launch) is the dispatch
        clock = self._clock
        clock.to(DECODE_DISPATCH, t_call)
        if stamps is not None:
            clock.to(DECODE_WAIT, stamps[1])
            clock.to(DECODE_DISPATCH, stamps[2])
        clock.to(SAMPLE_LAUNCH, t0)  # no time of it where the launch lay inside the wait
        clock.to(SAMPLE_WAIT, t_wait)
        clock.to(D2H, t_ready)
        clock.to(LANES, t_host)
        clock.to(REST, t1)
        with self._stats_lock:
            self._tokens_generated += on_device
            self._tokens_sampled_on_device += on_device
            self._step_seconds = clock.reading()
            for name, value in counts.items():
                self._step_counters[name] = self._step_counters.get(name, 0.0) + value
            self._step_gauges = gauges
            self._step_inputs["decode_steps"] += 1
            for name, value in sent.items():
                self._step_inputs[name] += value
            self._steps = step
        if finished:
            t_retire = mono()
            clock.to(RETIRE, t_retire)
            for i, seq in finished:
                self._retire_lane(i, seq)
            t_retired = mono()
            clock.to(REST, t_retired)
        tracer = self._tracer
        if tracer.enabled:
            at = {"step": step}
            # what the step's attention had to read: with the two a trace
            # says whether device time follows what is live (the kernel
            # walks each lane's own blocks) or the longest lane
            tracer.record_span(
                "serve.decode", "serve", t_call, t_back,
                {
                    **at,
                    "active": live,
                    "live_kv_tokens": int((positions + 1).sum()),
                    "max_context": int(positions.max()) + 1,
                    **sent,
                    **counts,
                },
            )
            if stamps is not None:
                tracer.record_span("serve.decode.dispatch", "serve", stamps[0], stamps[1], at)
                tracer.record_span("serve.decode.wait", "serve", stamps[1], stamps[2], at)
            tracer.record_span("serve.step.prepare", "serve", *prepare, at)
            tracer.record_span("serve.sample.launch", "serve", t_launch, t_launched, at)
            tracer.record_span("serve.sample.wait", "serve", t_wait, t_ready, at)
            tracer.record_span("serve.decode.d2h", "serve", t_ready, t_host, at)
            tracer.record_span("serve.lanes", "serve", t_host, t1, at)
            tracer.record_span(
                "serve.sample", "serve", t0, t1, {**at, "lanes": live, "device_lanes": on_device}
            )
            if finished:
                tracer.record_span("serve.retire", "serve", t_retire, t_retired, {**at, "retired": len(finished)})
        return len(finished)

    def _admit_one(self, step: int) -> bool:
        """Try to move one queued request into a lane.  False when nothing
        was admitted (empty queue, or the head request must wait for cache
        blocks — it is parked at the front so FIFO order holds)."""
        req = self.queue.get()
        if req is None:
            return False
        try:
            seq = self._start_sequence(req, step)
        except CacheOOM:
            self.queue.requeue_head(req)
            return False
        except Exception as e:  # noqa: BLE001 - a poisoned request must not kill the loop
            self._clock.to(REST, mono())  # wherever the attempt broke off
            logger.exception("request %d failed at prefill", req.id)
            self._finish_error(req, f"prefill failed: {e}")
            return True
        if seq is not None:
            self.lanes.join(seq, seq.lane)
            self._positions[seq.lane] = seq.pos  # dtpu: lint-ok[unlocked-shared-state] the step's own (__init__)
            self._ids_on_device = None  # dtpu: lint-ok[unlocked-shared-state] the step's own (__init__): its first token is the host's draw
            if seq.blocks:
                self._tables[seq.lane] = seq.block_table  # dtpu: lint-ok[unlocked-shared-state] the step's own (__init__)
                self._tables_on_device = None  # dtpu: lint-ok[unlocked-shared-state] the step's own (__init__)
        return True

    def _retire_lane(self, lane: int, seq: ActiveSeq) -> None:
        self.lanes.retire(lane)
        self._positions[lane] = -1  # dtpu: lint-ok[unlocked-shared-state] the step's own (__init__)
        if seq.blocks:
            # before the blocks are free again: an idle lane names the scratch block alone
            self._tables[lane] = 0  # dtpu: lint-ok[unlocked-shared-state] the step's own (__init__)
            self._tables_on_device = None  # dtpu: lint-ok[unlocked-shared-state] the step's own (__init__)
        self._retire_seq(seq)

    def step_once(self) -> bool:
        """One scheduler iteration: admit whatever fits, run one decode
        step, retire what finished.  Returns True when any work happened.
        The engine thread loops this; tests drive it directly for
        deterministic join/retire assertions (no wall-clock races).

        What of an iteration lies under none of its parts (this loop, the
        lanes' snapshot, the stats lock, the tracer's pushes) is the phase
        ``rest``; ``serve.step`` carries the iteration's own split by phase
        (``phase_ms``: the phases that took any time), which adds up to it."""
        t0 = mono()
        clock = self._clock
        clock.to(REST, t0)
        traced = self._tracer.enabled
        before = clock.totals.copy() if traced else None
        step = self._steps + 1  # this iteration's number, kept if it works
        admitted = 0
        while self.lanes.has_free_lane() and not self._stop.is_set():
            if not self._admit_one(step):
                break
            admitted += 1
        snapshot = self.lanes.snapshot()
        active = sum(1 for seq in snapshot if seq is not None)
        retired = 0
        if active:
            retired = self._decode_and_sample(snapshot, step)
        elif admitted:
            with self._stats_lock:
                self._steps = step  # admitted, and all finished at prefill
                self._step_seconds = clock.reading()
        else:
            return False
        if traced:
            # the step's own line: what it did, where its time went, and the
            # queue and the pool as it left them (the two gauges nobody read
            # rode here)
            t1 = mono()
            clock.to(REST, t1)
            self._tracer.record_span(
                "serve.step", "serve", t0, t1,
                {
                    "step": step, "active": active, "admitted": admitted,
                    "retired": retired, "queued": self.queue.depth(),
                    "kv_used_blocks": self.allocator.used_blocks,
                    "phase_ms": {
                        name: round(1000.0 * (now - was), 3)
                        for name, was, now in zip(PHASES, before, clock.totals) if now != was
                    },
                },
            )
        return True

    def _idle_wait(self) -> None:
        """Nothing to do: wait to be woken, as the phase ``idle``, and publish
        the clock (no step does while none works)."""
        clock = self._clock
        clock.to(IDLE, mono())
        self._wake.wait(timeout=0.05)
        self._wake.clear()
        clock.to(REST, mono())
        with self._stats_lock:
            self._step_seconds = clock.reading()

    def _run(self) -> None:
        while not self._stop.is_set():
            if self.step_once():
                continue
            # idle: no active lanes, nothing admitted
            if self.queue.draining and self.queue.empty():
                break
            self._idle_wait()
        if self._stop.is_set():
            for i in self.lanes.active():
                seq = self.lanes.retire(i)
                self.allocator.free(seq.blocks)
                self._finish_error(seq.request, "engine stopped")
        # the thread's last reading: from here on the engine is idle for good
        self._clock.to(IDLE, mono())
        with self._stats_lock:
            self._step_seconds = self._clock.reading()
        self._finished.set()
