"""Online serving tier (determined_tpu/serve): allocator invariants,
continuous-batching semantics, backpressure, drain, and the devcluster
replica-registration e2e.

Runs under the lock_order + no_thread_leaks sentinels: the serve package
has real lock structure (allocator free-list, admission queue, lane table,
replica heartbeat thread) and its workers are dtpu-* named, so an
inversion or a leaked engine thread fails deterministically here.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.models.transformer import TransformerConfig, TransformerLM
from determined_tpu.serve import (
    AdmissionRejected,
    BlockAllocator,
    CacheOOM,
    prefix_block_hashes,
    DecodeKernels,
    LaneTable,
    ServeConfig,
    ServeEngine,
    ServeWorker,
)
from determined_tpu.serve.scheduler import PHASES, TPOT_PARTS, ActiveSeq, GenRequest
from tests.model_cases import causal_forward

pytestmark = [pytest.mark.lock_order, pytest.mark.no_thread_leaks]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# kv block allocator
# ---------------------------------------------------------------------------


def test_allocator_alloc_free_roundtrip():
    a = BlockAllocator(num_blocks=9, block_size=4)
    assert a.capacity == 8
    got = a.alloc(5)
    assert len(got) == 5 and len(set(got)) == 5
    assert 0 not in got  # scratch block never handed out
    assert a.used_blocks == 5 and a.free_blocks == 3
    a.free(got)
    assert a.used_blocks == 0 and a.free_blocks == 8


def test_allocator_oom_is_all_or_nothing():
    a = BlockAllocator(num_blocks=5, block_size=4)
    a.alloc(3)
    with pytest.raises(CacheOOM):
        a.alloc(2)  # only 1 free
    # the failed alloc took nothing
    assert a.free_blocks == 1
    a.alloc(1)


def test_allocator_double_free_raises():
    a = BlockAllocator(num_blocks=4, block_size=2)
    got = a.alloc(2)
    a.free(got)
    with pytest.raises(ValueError):
        a.free(got)
    with pytest.raises(ValueError):
        a.free([0])  # scratch block was never allocated


def test_allocator_block_reuse_is_lifo():
    """Freed blocks are handed out again first (hot working set)."""
    a = BlockAllocator(num_blocks=16, block_size=4)
    first = a.alloc(4)
    a.free(first)
    second = a.alloc(4)
    assert set(second) == set(first)


def test_allocator_no_fragmentation_under_interleaving():
    """A free list has no contiguity requirement: any interleaving of
    alloc/free with total <= capacity must succeed, and no id may be live
    twice."""
    a = BlockAllocator(num_blocks=17, block_size=4)  # capacity 16
    rng = np.random.default_rng(0)
    live = []
    for _ in range(200):
        if live and (len(live) >= 4 or rng.random() < 0.4):
            a.free(live.pop(rng.integers(len(live))))
        else:
            n = int(rng.integers(1, 5))
            if a.free_blocks >= n:
                blocks = a.alloc(n)
                flat = [b for g in live for b in g]
                assert not set(blocks) & set(flat), "id allocated twice"
                live.append(blocks)
    for g in live:
        a.free(g)
    assert a.free_blocks == 16


def test_allocator_utilization_and_stats():
    a = BlockAllocator(num_blocks=11, block_size=2)
    a.alloc(5)
    assert a.utilization() == pytest.approx(0.5)
    st = a.stats()
    assert st["used"] == 5 and st["free"] == 5 and st["peak"] == 5


# ---------------------------------------------------------------------------
# prefix cache: refcounts, CoW-by-recompute boundary, LRU eviction
# ---------------------------------------------------------------------------


def test_prefix_match_shares_and_registered_blocks_park_on_free():
    a = BlockAllocator(num_blocks=17, block_size=4, prefix_cache=True)
    chain = prefix_block_hashes(list(range(12)), 4)
    assert len(chain) == 3
    blocks = a.alloc(3)
    a.register_prefix(chain, blocks)
    # a second sequence matching the chain shares the SAME physical blocks
    shared = a.match_prefix(chain)
    assert shared == blocks
    assert all(a.refcount(b) == 2 for b in blocks)
    assert a.used_blocks == 3  # shared blocks count once
    a.free(shared)
    assert all(a.refcount(b) == 1 for b in blocks)
    # refcount 0 parks registered blocks in the cache, not the free list
    a.free(blocks)
    assert a.used_blocks == 0 and a.cached_blocks == 3
    again = a.match_prefix(chain)
    assert again == blocks and a.cached_blocks == 0
    a.free(again)
    st = a.stats()
    assert st["prefix_hits"] == 2 and st["prefix_tokens_saved"] == 24


def test_prefix_hash_chain_is_a_trie_not_a_bag():
    """Matching stops at the first miss: a chain whose FIRST block differs
    shares nothing even if a later block's tokens coincide, because each
    hash covers its whole prefix."""
    a = BlockAllocator(num_blocks=9, block_size=2, prefix_cache=True)
    chain = prefix_block_hashes([1, 2, 3, 4], 2)
    blocks = a.alloc(2)
    a.register_prefix(chain, blocks)
    other = prefix_block_hashes([9, 9, 3, 4], 2)  # same 2nd block tokens
    assert a.match_prefix(other) == []
    # a shorter prompt sharing only the first block matches exactly it
    head = prefix_block_hashes([1, 2], 2)
    hit = a.match_prefix(head)
    assert hit == blocks[:1]
    a.free(hit)
    a.free(blocks)


def test_prefix_limit_tokens_never_covers_the_tail():
    """Admission caps the chain at len(prompt)-1, so the block holding the
    final prompt token is never shared — that is the copy-on-write policy
    (the tail is re-prefilled privately, shared blocks stay read-only)."""
    bs = 4
    # 8 tokens = exactly 2 full blocks, but the cap must drop the last one
    chain = prefix_block_hashes(list(range(8)), bs, limit_tokens=7)
    assert len(chain) == 1
    # partial tails never participate even uncapped
    assert len(prefix_block_hashes(list(range(7)), bs)) == 1
    assert prefix_block_hashes([1], bs, limit_tokens=0) == []


def test_prefix_shared_double_free_raises():
    """Over-freeing a shared block raises instead of silently recycling a
    block another sequence is still reading."""
    a = BlockAllocator(num_blocks=9, block_size=4, prefix_cache=True)
    chain = prefix_block_hashes(list(range(8)), 4)
    mine = a.alloc(2)
    a.register_prefix(chain, mine)
    theirs = a.match_prefix(chain)
    a.free(mine)
    a.free(theirs)  # the co-owner's single release is fine
    with pytest.raises(ValueError):
        a.free(theirs)  # a third free would corrupt the cached content


def test_prefix_eviction_is_lru_and_never_touches_live_refs():
    a = BlockAllocator(num_blocks=5, block_size=2, prefix_cache=True)
    c1 = prefix_block_hashes([1, 2], 2)
    c2 = prefix_block_hashes([3, 4], 2)
    b1 = a.alloc(1)
    a.register_prefix(c1, b1)
    b2 = a.alloc(1)
    a.register_prefix(c2, b2)
    live = a.alloc(2)  # free list is now empty
    a.free(b1)  # released first -> evicted first
    a.free(b2)
    got = a.alloc(2)  # must reclaim BOTH cached blocks, never `live`
    assert set(got) == {b1[0], b2[0]}
    assert all(a.refcount(b) == 1 for b in live)
    assert a.match_prefix(c1) == [] and a.match_prefix(c2) == []
    assert a.stats()["evictions"] == 2


def test_prefix_eviction_order_is_least_recently_released():
    a = BlockAllocator(num_blocks=4, block_size=2, prefix_cache=True)
    c1 = prefix_block_hashes([1, 2], 2)
    c2 = prefix_block_hashes([3, 4], 2)
    b1 = a.alloc(1)
    a.register_prefix(c1, b1)
    b2 = a.alloc(1)
    a.register_prefix(c2, b2)
    a.alloc(1)  # drain the free list
    a.free(b2)  # release the NEWER registration first
    a.free(b1)
    a.alloc(1)  # evicts b2: least recently released, not lowest id
    assert a.match_prefix(c2) == []
    assert a.match_prefix(c1) == b1


def test_prefix_interleaved_share_release_no_fragmentation():
    """Random interleaving of prefix-matched admissions and retirements
    keeps every block exactly one of live / cached / free — capacity is
    never lost to double-parking or leaked references."""
    bs = 4
    a = BlockAllocator(num_blocks=33, block_size=bs, prefix_cache=True)
    rng = np.random.default_rng(7)
    prompts = [list(range(100 + p, 112 + p)) for p in range(5)]
    live = []
    for _ in range(300):
        if live and (rng.random() < 0.45 or a.free_blocks + a.cached_blocks < 4):
            a.free(live.pop(rng.integers(len(live))))
        else:
            toks = prompts[rng.integers(len(prompts))]
            chain = prefix_block_hashes(toks, bs, limit_tokens=len(toks) - 1)
            shared = a.match_prefix(chain)
            private = a.alloc(a.blocks_for(len(toks)) - len(shared))
            a.register_prefix(chain, shared + private)
            live.append(shared + private)
        st = a.stats()
        assert st["used"] + st["free"] + st["cached"] == st["capacity"]
    for g in live:
        a.free(g)
    assert a.used_blocks == 0
    assert a.free_blocks + a.cached_blocks == a.capacity


# ---------------------------------------------------------------------------
# lane table
# ---------------------------------------------------------------------------


def _dummy_seq(lane=0):
    return ActiveSeq(
        request=GenRequest(prompt=[1], max_new_tokens=1),
        blocks=[1],
        block_table=[1, 0],
        pos=1,
        next_token=0,
        lane=lane,
    )


def test_lane_table_join_retire():
    lanes = LaneTable(2)
    assert lanes.free_lane() == 0
    i0 = lanes.join(_dummy_seq(0), 0)
    assert lanes.free_lane() == 1
    i1 = lanes.join(_dummy_seq(1), 1)
    assert {i0, i1} == {0, 1}
    assert not lanes.has_free_lane() and lanes.free_lane() is None
    with pytest.raises(RuntimeError):
        lanes.join(_dummy_seq(1), 1)
    lanes.retire(i0)
    assert lanes.has_free_lane()
    with pytest.raises(RuntimeError):
        lanes.retire(i0)  # already empty
    assert lanes.stats() == {"lanes": 2, "active": 1, "joined": 2, "retired": 1}


# ---------------------------------------------------------------------------
# engine fixtures: one compiled kernel set for the whole module
# ---------------------------------------------------------------------------

SERVE_CFG = ServeConfig(
    block_size=4,
    num_blocks=64,
    max_batch=4,
    max_prompt_len=16,
    max_new_tokens=32,
    queue_depth=4,
)


@pytest.fixture(scope="module")
def lm_setup():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        max_seq_len=64, dtype=jnp.float32, attention_impl="reference",
    )
    from flax.core import meta as flax_meta

    model = TransformerLM(cfg)
    variables = flax_meta.unbox(
        jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )
    return cfg, model, variables


@pytest.fixture(scope="module")
def kernels(lm_setup):
    cfg, _model, variables = lm_setup
    return DecodeKernels(cfg, variables, SERVE_CFG)


@pytest.fixture()
def engine(kernels):
    eng = ServeEngine(kernels).start()
    yield eng
    eng.stop()


def _submit_retry(eng, prompt, deadline_s=60.0, **kw):
    """Engine-level submit with 429 backoff (tests race the compile)."""
    deadline = time.monotonic() + deadline_s
    while True:
        try:
            return eng.submit(prompt, **kw)
        except AdmissionRejected as e:
            assert e.status == 429
            assert time.monotonic() < deadline, "queue never drained"
            time.sleep(0.05)


# ---------------------------------------------------------------------------
# continuous-batching semantics
# ---------------------------------------------------------------------------


def test_generate_greedy_matches_full_forward(engine, lm_setup):
    _cfg, model, variables = lm_setup
    prompt = [3, 14, 15, 9, 2, 6]
    req = engine.generate(prompt, max_new_tokens=6)
    assert req.error is None and len(req.output) == 6
    seq = list(prompt)
    forward = causal_forward(model, 16)
    for tok in req.output:
        assert tok == int(np.argmax(np.asarray(forward(variables, seq)[-1])))
        seq.append(tok)


def test_join_mid_flight_and_retire_immediately(kernels):
    """A short request submitted while a long one decodes joins the
    running batch and completes long before the long one finishes.
    Step-driven: a threaded engine decodes a 32-token request faster
    than the wall clock can interleave a second submission."""
    eng = ServeEngine(kernels)  # not started: the test drives step_once()
    try:
        long_req = eng.submit([1, 2, 3], max_new_tokens=32)
        assert eng.step_once()  # admit + first decode step
        assert long_req.first_token_at is not None
        assert not long_req.done.is_set()
        short_req = eng.submit([4, 5], max_new_tokens=2)
        steps = 0
        while not short_req.done.is_set():
            assert eng.step_once(), "scheduler stalled"
            steps += 1
            assert steps < 8, "short request starved behind the long one"
        assert short_req.error is None and len(short_req.output) == 2
        # retire-immediately: the short one finished while the long one runs
        assert not long_req.done.is_set()
        while not long_req.done.is_set():
            assert eng.step_once(), "long request starved"
        assert short_req.finished_at <= long_req.finished_at
        st = eng.stats()
        assert st["completed"] == 2
        assert st["lanes"]["joined"] >= 2  # short joined a running batch
    finally:
        eng.stop()


def test_fairness_under_mixed_prompt_lengths(kernels):
    """FIFO admission with immediate retirement, driven step by step: a
    long sequence monopolizes one lane for 32 steps while SIX short
    requests (more than the remaining lanes) flow through the other
    three — none of them waits for the long one."""
    eng = ServeEngine(kernels)  # not started: the test drives step_once()
    try:
        long_req = eng.submit(list(range(14)), max_new_tokens=32)
        shorts = [eng.submit([i, i + 1], max_new_tokens=2) for i in range(3)]
        eng.step_once()  # admits long + shorts 0-2 (4 lanes), one decode
        late = [eng.submit([9, i], max_new_tokens=2) for i in range(3)]
        steps = 1
        while not all(r.done.is_set() for r in shorts + late):
            assert eng.step_once(), "scheduler stalled"
            steps += 1
            assert steps < 16, "shorts starved behind the long request"
        # every short flowed through while the long one still decodes
        assert not long_req.done.is_set()
        assert len(long_req.output) < 16
        # FIFO: the late batch was admitted in submission order
        firsts = [r.first_token_at for r in late]
        assert firsts == sorted(firsts)
        while not long_req.done.is_set():
            assert eng.step_once(), "long request starved"
        assert long_req.error is None and len(long_req.output) == 32
        assert eng.allocator.used_blocks == 0  # everything reclaimed
    finally:
        eng.stop()


def test_backpressure_429_when_queue_saturated(kernels):
    """An engine that is not consuming fills its queue and answers 429."""
    eng = ServeEngine(kernels)  # never started: nothing drains the queue
    try:
        for _ in range(SERVE_CFG.queue_depth):
            eng.submit([1, 2], max_new_tokens=1)
        with pytest.raises(AdmissionRejected) as exc:
            eng.submit([1, 2], max_new_tokens=1)
        assert exc.value.status == 429
        assert eng.stats()["rejected"] == 1
    finally:
        eng.stop()


def test_oversized_request_rejected_413(kernels):
    eng = ServeEngine(kernels)
    try:
        with pytest.raises(AdmissionRejected) as exc:
            eng.submit(list(range(17)), max_new_tokens=1)  # > max_prompt_len
        assert exc.value.status == 413
    finally:
        eng.stop()


def test_cache_oom_delays_admission_not_correctness(lm_setup):
    """A cache sized for ~one worst-case sequence serializes admission:
    the second request parks at the queue head until the first frees its
    blocks, and both complete."""
    cfg, _model, variables = lm_setup
    tight = ServeConfig(
        block_size=4, num_blocks=14, max_batch=2, max_prompt_len=16,
        max_new_tokens=32, queue_depth=4,
    )  # capacity 13 blocks; a 16+32 request needs 12
    eng = ServeEngine(DecodeKernels(cfg, variables, tight)).start()
    try:
        a = eng.submit(list(range(16)), max_new_tokens=32)
        b = eng.submit(list(range(16)), max_new_tokens=32)
        assert a.done.wait(120) and a.error is None
        assert b.done.wait(120) and b.error is None
        assert b.finished_at >= a.finished_at  # serialized by the cache
        assert eng.allocator.stats()["peak"] <= 13
    finally:
        eng.stop()


def test_prefix_cached_generation_matches_cold(kernels):
    """Warm admission — shared prefix blocks mapped, suffix-only prefill —
    is token-for-token identical to the cold run under a fixed seed, and
    the shared blocks inflate neither kv_utilization nor correctness."""
    prompt = list(range(3, 12))  # 9 tokens: chain covers 2 full blocks
    eng = ServeEngine(kernels)
    try:
        cold = eng.submit(prompt, max_new_tokens=4, temperature=0.7, seed=42)
        eng.step_once()  # admit + prefill the cold run before warm submit
        warm = eng.submit(prompt, max_new_tokens=4, temperature=0.7, seed=42)
        for _ in range(12):
            eng.step_once()
            if cold.done.is_set() and warm.done.is_set():
                break
        assert cold.error is None and warm.error is None
        assert cold.output == warm.output and len(cold.output) == 4
        st = eng.stats()
        assert st["prefix_hits"] == 1 and st["prefix_tokens_saved"] == 8
        assert st["prefix_hit_rate"] == pytest.approx(0.5)
    finally:
        eng.stop()


def test_kv_utilization_counts_shared_blocks_once(kernels):
    """Regression for the router's load signal: two in-flight sequences
    sharing 2 prefix blocks occupy 2*total - 2 distinct blocks, and
    ``kv_utilization`` reports exactly that (shared counted once)."""
    prompt = list(range(20, 29))  # 9 tokens -> 2 shareable blocks
    total = SERVE_CFG.blocks_for(len(prompt) + 4)
    eng = ServeEngine(kernels)
    try:
        a = eng.submit(prompt, max_new_tokens=4)
        eng.step_once()
        b = eng.submit(prompt, max_new_tokens=4)
        eng.step_once()  # admits b: both sequences now hold blocks
        assert not (a.done.is_set() and b.done.is_set())
        distinct = 2 * total - 2
        assert eng.allocator.used_blocks == distinct
        st = eng.stats()
        assert st["kv_utilization"] == pytest.approx(
            distinct / SERVE_CFG.usable_blocks, abs=1e-4
        )
        assert st["queue_capacity"] == SERVE_CFG.queue_depth
        while not (a.done.is_set() and b.done.is_set()):
            eng.step_once()
    finally:
        eng.stop()


def test_prefix_cache_off_restores_private_blocks(lm_setup):
    """--no-prefix-cache: identical prompts never share physical blocks
    and the hit counters stay zero (the PR-9 data path)."""
    cfg, _model, variables = lm_setup
    off = ServeConfig(
        block_size=4, num_blocks=64, max_batch=4, max_prompt_len=16,
        max_new_tokens=32, queue_depth=4, prefix_cache=False,
    )
    eng = ServeEngine(DecodeKernels(cfg, variables, off))
    try:
        prompt = list(range(3, 12))
        a = eng.submit(prompt, max_new_tokens=4)
        eng.step_once()
        b = eng.submit(prompt, max_new_tokens=4)
        eng.step_once()
        assert eng.allocator.used_blocks == 2 * off.blocks_for(len(prompt) + 4)
        while not (a.done.is_set() and b.done.is_set()):
            eng.step_once()
        st = eng.stats()
        assert st["prefix_hits"] == 0 and st["prefix_hit_rate"] == 0.0
        assert a.output == b.output  # greedy: sharing was never load-bearing
    finally:
        eng.stop()


def test_drain_finishes_inflight_rejects_new(engine):
    long_req = engine.submit([7, 8, 9], max_new_tokens=32)
    deadline = time.monotonic() + 60
    while long_req.first_token_at is None:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    engine.queue.start_drain()
    engine._wake.set()
    with pytest.raises(AdmissionRejected) as exc:
        engine.submit([1], max_new_tokens=1)
    assert exc.value.status == 503
    assert engine.drain(timeout=60)
    assert long_req.done.is_set() and long_req.error is None
    assert len(long_req.output) == 32  # finished, not truncated


def test_stop_token_ends_generation_early(engine, lm_setup):
    """A request whose greedy first token IS its stop token retires after
    one token, well under its max_new_tokens budget."""
    _cfg, model, variables = lm_setup
    prompt = [3, 14, 15]
    logits = model.apply(variables, jnp.asarray(prompt, jnp.int32)[None, :])
    first = int(np.argmax(np.asarray(logits[0, -1])))
    req = engine.generate(prompt, max_new_tokens=8, stop_token=first)
    assert req.error is None and req.output == [first]


def test_max_new_tokens_zero_is_rejected_not_defaulted(kernels):
    """Regression: 0 used to be falsy-coerced to the server default."""
    eng = ServeEngine(kernels)
    try:
        with pytest.raises(AdmissionRejected) as exc:
            eng.submit([1, 2], max_new_tokens=0)
        assert exc.value.status == 400
    finally:
        eng.stop()


class _CrashingKernels:
    """Shared-kernel shim whose decode step blows up (an XLA error, a NaN
    cascade): the loop guard must fail requests loudly, not strand them."""

    def __init__(self, kernels):
        self._kernels = kernels
        self.serve_cfg = kernels.serve_cfg
        self.model_cfg = kernels.model_cfg
        self.kinds = kernels.kinds  # what a request holds of the cache: the engine derives admission from it
        self.prefill = kernels.prefill
        self.prefill_suffix = kernels.prefill_suffix

    def decode(self, *a, **kw):
        raise RuntimeError("synthetic decode explosion")


def test_engine_crash_fails_requests_and_flips_health(kernels):
    """Regression: an unexpected engine-loop exception used to kill the
    thread silently while /healthz kept answering ok and parked handlers
    waited out their 600s timeout."""
    requests = pytest.importorskip("requests")
    eng = ServeEngine(_CrashingKernels(kernels))
    worker = ServeWorker(eng)
    url = worker.start()
    try:
        # needs >1 token so the request survives prefill and hits decode
        req = eng.submit([1, 2, 3], max_new_tokens=4)
        assert req.done.wait(30), "crash did not fail the in-flight request"
        assert req.error and "engine crashed" in req.error
        assert not eng.healthy
        h = requests.get(url + "/healthz", timeout=5)
        assert h.status_code == 500 and h.json()["status"] == "failed"
    finally:
        worker.shutdown()


def test_http_malformed_fields_return_400(kernels):
    requests = pytest.importorskip("requests")
    worker = ServeWorker(ServeEngine(kernels))
    url = worker.start()
    try:
        for body in (
            {"prompt_tokens": [1], "temperature": "hot"},
            {"prompt_tokens": [1], "max_new_tokens": "many"},
            {"prompt_tokens": [1], "seed": "x"},
            {"prompt_tokens": [1], "max_new_tokens": 0},
        ):
            r = requests.post(url + "/v1/generate", json=body, timeout=30)
            assert r.status_code == 400, (body, r.status_code, r.text)
    finally:
        worker.shutdown()


def test_there_is_one_engine_class():
    """The static-batch baseline and the base class that was split off to
    carry it are gone (PR 29): the package exports one engine, the module defines one, and it stands on
    nothing but ``object``."""
    import determined_tpu.serve as serve
    from determined_tpu.serve import engine as engine_mod

    assert [n for n in serve.__all__ if n.endswith("Engine")] == ["ServeEngine"]
    defined = [
        n for n, v in vars(engine_mod).items()
        if isinstance(v, type) and v.__module__ == engine_mod.__name__ and "Engine" in n
    ]
    assert defined == ["ServeEngine"]
    assert ServeEngine.__bases__ == (object,)


def test_engine_keeps_every_name_the_benchmark_reads(kernels):
    """What ``benchmark/benchlib/serve_run.py`` and the replicas reach for on
    a built engine, so that a later fold cannot take one away unseen: the
    attributes, the methods, the keys of ``stats()``, the module-level
    ``sample_token`` and the kernels' three entry points as instance
    attributes that can be replaced and put back."""
    from determined_tpu.serve import engine as engine_mod

    eng = ServeEngine(kernels)
    assert eng.kernels is kernels and eng.cfg is kernels.serve_cfg
    for name in ("allocator", "queue", "lanes", "healthy", "failed", "model_label"):
        assert hasattr(eng, name), name
    for name in (
        "submit", "generate", "start", "stop", "drain", "step_once", "stats",
        "note_http_response", "from_checkpoint",
    ):
        assert callable(getattr(ServeEngine, name)), name
    for name in ("alloc", "blocks_for", "stats", "free"):
        assert callable(getattr(eng.allocator, name)), name
    assert set(eng.stats()) >= {
        "submitted", "completed", "rejected", "tokens_generated", "tokens_sampled_on_device", "errored", "http_5xx",
        "prefill_tokens_asked", "prefill_tokens_computed",
        "latency_ms_avg", "latency", "step_seconds", "queue_depth", "queue_capacity",
        "draining", "failed", "kv_cache", "kv_utilization", "prefix_hits",
        "prefix_tokens_saved", "prefix_hit_rate", "uptime_s", "lanes",
    }
    assert callable(engine_mod.sample_token)
    for name in ("decode", "prefill", "prefill_suffix"):
        original = getattr(kernels, name)
        setattr(kernels, name, lambda *a: None)
        assert name in vars(kernels)  # an instance attribute shadows the method
        delattr(kernels, name)
        assert getattr(kernels, name) == original


def test_retrace_sentinel_one_decode_trace(lm_setup):
    """Acceptance: a mixed-length request stream compiles the decode step
    exactly once (and prefill exactly once) — the paged layout keeps every
    shape static."""
    from determined_tpu.lint._runtime import get_retrace_sentinel

    cfg, _model, variables = lm_setup
    sentinel = get_retrace_sentinel()
    sentinel.reset()
    eng = ServeEngine(DecodeKernels(cfg, variables, SERVE_CFG)).start()
    try:
        rng = np.random.default_rng(2)
        reqs = []
        for i in range(5):
            prompt = [int(t) for t in rng.integers(0, 64, size=int(rng.integers(1, 16)))]
            reqs.append(
                _submit_retry(eng, prompt, max_new_tokens=1 + i * 3,
                              temperature=0.5 * (i % 2), seed=i)
            )
        # a repeated long prompt forces a WARM admission too: the suffix
        # kernel must also hold one trace across varying (start, len)
        shared = [int(t) for t in rng.integers(0, 64, size=13)]
        for i in range(3):
            reqs.append(
                _submit_retry(eng, shared + [i], max_new_tokens=2, seed=9 + i)
            )
        for r in reqs:
            assert r.done.wait(120) and r.error is None
    finally:
        eng.stop()
    by_label = {r.label: r for r in sentinel.records()}
    assert by_label["serve.decode_step"].traces == 1
    # cold and warm admissions run ONE program, the chunked walk, from
    # start 0 or from the first un-cached block: one trace across every
    # mix of lengths and starts, and no second prefill program
    assert by_label["serve.prefill_step"].traces == 1
    assert set(by_label) == {"serve.decode_step", "serve.prefill_step"}
    assert sentinel.violations() == {}
    sentinel.reset()


#: three chunks of 256 to the longest prompt: lengths on both sides of a
#: chunk's edges, and the longest prompt admitted
CHUNKED_CFG = ServeConfig(
    block_size=16, num_blocks=96, max_batch=2, max_prompt_len=600, max_new_tokens=8, queue_depth=8,
)


def test_a_prefill_costs_its_prompts_chunks_and_says_so(lm_setup, tracer):
    """``serve.prefill`` carries the walk's trip count and what it computed,
    ``/stats`` the two cumulative counts whose ratio is the padding share;
    every length and a warm start run the one trace; the first token is the
    full forward's."""
    import dataclasses

    from determined_tpu.lint._runtime import get_retrace_sentinel

    cfg, _model, variables = lm_setup
    cfg = dataclasses.replace(cfg, max_seq_len=CHUNKED_CFG.max_seq_len)
    chunk = CHUNKED_CFG.prefill_chunk
    assert chunk == 256 and ServeConfig(block_size=4, max_prompt_len=16, num_blocks=64).prefill_chunk == 16
    sentinel = get_retrace_sentinel()
    sentinel.reset()
    kernels = DecodeKernels(cfg, variables, CHUNKED_CFG)
    assert kernels._prompt_pad == 768
    eng = ServeEngine(kernels)
    rng = np.random.default_rng(3)
    lengths = [chunk - 1, chunk, chunk + 1, 2 * chunk + 17, CHUNKED_CFG.max_prompt_len]
    prompts = [[int(t) for t in rng.integers(1, 64, size=n)] for n in lengths]
    # a warm admission whose first un-cached token lies inside chunk 1
    prompts.append(prompts[3][:400] + [int(t) for t in rng.integers(1, 64, size=150)])
    reqs = []
    try:
        for prompt in prompts:
            reqs.append(eng.submit(prompt, max_new_tokens=1, temperature=0.0))
            while not reqs[-1].done.is_set():
                assert eng.step_once()
    finally:
        eng.stop()
    forward = causal_forward(TransformerLM(cfg), CHUNKED_CFG.max_prompt_len)
    for prompt, req in zip(prompts, reqs):
        assert req.error is None
        assert req.output == [int(np.argmax(np.asarray(forward(variables, prompt)[-1])))]
    spans = {e["args"]["request"]: e["args"] for e in _spans(tracer, "serve.prefill")}
    asked = computed = 0
    for prompt, req in zip(prompts, reqs):
        args = spans[req.id]
        cached = 400 // 16 * 16 if prompt is prompts[-1] else 0
        assert args["cached_tokens"] == cached
        assert args["chunks"] == -(-len(prompt) // chunk) - cached // chunk
        assert args["computed_tokens"] == args["chunks"] * chunk
        asked += len(prompt) - cached
        computed += args["computed_tokens"]
    assert [spans[r.id]["chunks"] for r in reqs] == [1, 1, 2, 3, 3, 2]
    st = eng.stats()
    assert (st["prefill_tokens_asked"], st["prefill_tokens_computed"]) == (asked, computed)
    assert computed == 12 * chunk and asked == sum(lengths) + 150
    by_label = {r.label: r for r in sentinel.records()}
    assert by_label["serve.prefill_step"].traces == 1 and sentinel.violations() == {}
    sentinel.reset()


@pytest.fixture()
def tracer():
    """The process tracer, empty and on; left as a fresh process has it."""
    from determined_tpu.observability import _tracer as tracer_mod
    from determined_tpu.observability import get_tracer

    t = get_tracer()
    t.reset()
    t.configure(enabled=True)
    yield t
    t.close()  # stops a shipper a test left running, closes an export
    t.configure(enabled=True, flush_interval=tracer_mod.DEFAULT_FLUSH_INTERVAL)
    t.reset()


def _spans(tracer, name=None):
    return [
        e for e in tracer.chrome_events()
        if e.get("ph") == "X" and (name is None or e["name"] == name)
    ]


def _inside(inner, outer, slack_us=0.2):
    """Both ends of ``inner`` within ``outer`` (events round to 0.1 us)."""
    return (
        inner["ts"] >= outer["ts"] - slack_us
        and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + slack_us
    )


def test_serve_spans_reach_tracer(lm_setup, tracer):
    """The engine's own spans (docs/serving.md "Observability") land in the
    process tracer; the span and the two gauges nothing read are gone."""
    cfg, _model, variables = lm_setup
    eng = ServeEngine(DecodeKernels(cfg, variables, SERVE_CFG)).start()
    try:
        req = eng.generate([1, 2, 3], max_new_tokens=3)
        assert req.error is None
    finally:
        eng.stop()
    names = {e["name"] for e in tracer.chrome_events()}
    for expected in (
        "serve.setup", "serve.setup.params_to_device", "serve.setup.kv_pool",
        "serve.queue_wait", "serve.step", "serve.admission", "serve.kv_alloc",
        "serve.prefill", "serve.first_sample", "serve.decode",
        "serve.decode.dispatch", "serve.decode.wait", "serve.decode.d2h",
        "serve.sample", "serve.request",
    ):
        assert expected in names, f"missing {expected} in {sorted(names)}"
    for gone in ("serve.admit", "serve.queue_depth", "serve.kv_utilization"):
        assert gone not in names
    assert {e["cat"] for e in _spans(tracer) if e["name"].startswith("serve.")} == {"serve"}
    sizes = _spans(tracer, "serve.setup")[0]["args"]
    assert sizes["param_bytes"] > 0 and sizes["kv_pool_bytes"] > 0


def test_building_the_kernels_makes_each_programs_first_call(lm_setup, tracer):
    """The three programs (the walk, the decode step, the step's sampler)
    compile (or load) where ``DecodeKernels`` is built, on the builder's
    thread, before any request: their first-call spans are there, the model's
    two each traced once, and the calls wrote the scratch block alone."""
    from determined_tpu.lint._runtime import get_retrace_sentinel

    cfg, _model, variables = lm_setup
    sentinel = get_retrace_sentinel()
    sentinel.reset()
    kernels = DecodeKernels(cfg, variables, SERVE_CFG)
    programs = ["jit.compile.serve.decode", "jit.compile.serve.prefill", "jit.compile.serve.sample"]
    first = [e["name"] for e in _spans(tracer) if e["name"].startswith("jit.compile.")]
    # each first call with its two children: the inspection of the program's text, and the run
    assert sorted(first) == sorted(p + tail for p in programs for tail in ("", ".inspect", ".first_run"))
    assert {r.label: r.traces for r in sentinel.records()} == {"serve.prefill_step": 1, "serve.decode_step": 1}
    for pool in kernels.cache.values():
        assert not np.asarray(pool[:, 1:]).any()
    assert kernels.last_decode_stamps is None and kernels.counters == ()
    # a request then compiles nothing more
    eng = ServeEngine(kernels)
    req = eng.submit([1, 2, 3], max_new_tokens=2)
    while not req.done.is_set():
        assert eng.step_once()
    eng.stop()
    assert len([e for e in _spans(tracer) if e["name"] in programs]) == 3
    assert len([e for e in _spans(tracer) if e["name"].startswith("jit.compile.")]) == 9
    assert {r.label: r.traces for r in sentinel.records()} == {"serve.prefill_step": 1, "serve.decode_step": 1}
    sentinel.reset()


def test_request_spans_share_an_id_and_add_up(kernels, tracer):
    """One request's timeline can be read back by its id, and its parts are
    the whole: the queue wait ends where the admission starts, the
    admission where the first token is out, and the rest is decoding."""
    eng = ServeEngine(kernels).start()
    try:
        reqs = [
            _submit_retry(eng, [5, 6, 7, 8 + i], max_new_tokens=6 + i, seed=i,
                          temperature=0.5)
            for i in range(3)
        ]
        for r in reqs:
            assert r.done.wait(60) and r.error is None
    finally:
        eng.stop()
    for r in reqs:
        mine = {
            e["name"]: e for e in _spans(tracer)
            if (e.get("args") or {}).get("request") == r.id
        }
        assert set(mine) == {
            "serve.queue_wait", "serve.admission", "serve.kv_alloc",
            "serve.prefill", "serve.first_sample", "serve.request",
        }
        wait, adm, whole = (
            mine["serve.queue_wait"], mine["serve.admission"], mine["serve.request"]
        )
        args = whole["args"]
        assert args["output_tokens"] == len(r.output) and args["error"] is None
        assert args["prompt_tokens"] == 4
        # the same stamps on both sides of each joint (0.1 us rounding)
        assert wait["ts"] == pytest.approx(whole["ts"], abs=0.2)
        assert adm["ts"] == pytest.approx(wait["ts"] + wait["dur"], abs=0.2)
        for child in ("serve.kv_alloc", "serve.prefill", "serve.first_sample"):
            assert _inside(mine[child], adm), child
        decode_ms = args["tpot_ms"] * (args["output_tokens"] - 1)
        assert (wait["dur"] + adm["dur"]) / 1e3 + decode_ms == pytest.approx(
            whole["dur"] / 1e3, abs=0.02
        )
        assert args["queue_wait_ms"] == pytest.approx(wait["dur"] / 1e3, abs=0.002)
        assert args["ttft_ms"] == pytest.approx((wait["dur"] + adm["dur"]) / 1e3, abs=0.002)
        assert args["itl_max_ms"] == pytest.approx(1000.0 * r.itl_max_s, abs=0.002)


def test_step_spans_nest_and_carry_their_step(kernels, tracer):
    """A step's anatomy lies inside its ``serve.step`` and names it."""
    eng = ServeEngine(kernels)
    for i in range(3):
        eng.submit([1 + i, 2, 3], max_new_tokens=4)
    while eng.step_once():  # no engine thread: deterministic
        pass
    steps = {e["args"]["step"]: e for e in _spans(tracer, "serve.step")}
    assert sorted(steps) == list(range(1, len(steps) + 1))
    assert eng.stats()["step_seconds"]["steps"] == len(steps)
    assert sum(s["args"]["admitted"] for s in steps.values()) == 3
    assert sum(s["args"]["retired"] for s in steps.values()) == 3
    last = steps[len(steps)]["args"]
    assert last["queued"] == 0 and last["kv_used_blocks"] == 0
    seen = set()
    for e in _spans(tracer):
        if e["name"] in ("serve.sample", "serve.decode", "serve.decode.dispatch",
                         "serve.decode.wait", "serve.decode.d2h", "serve.admission",
                         "serve.prefill", "serve.kv_alloc"):
            assert _inside(e, steps[e["args"]["step"]]), e
            seen.add(e["name"])
    assert len(seen) == 8
    decodes = {e["args"]["step"]: e for e in _spans(tracer, "serve.decode")}
    samples = {e["args"]["step"]: e for e in _spans(tracer, "serve.sample")}
    assert sorted(decodes) == sorted(samples) and len(decodes) >= 3
    for part in ("dispatch", "wait"):
        for e in _spans(tracer, "serve.decode." + part):
            assert _inside(e, decodes[e["args"]["step"]]) and e["dur"] > 0
    # the copy that is left (ids, counters) lies in the sampling, which is
    # what follows the decode call (the sampler was queued inside its wait)
    copies = _spans(tracer, "serve.decode.d2h")
    assert len(copies) == len(decodes)
    for e in copies:
        assert _inside(e, samples[e["args"]["step"]]) and e["dur"] > 0
    for step, e in samples.items():
        assert e["ts"] >= decodes[step]["ts"] + decodes[step]["dur"] - 0.2
        assert e["args"]["lanes"] == e["args"]["device_lanes"] == steps[step]["args"]["active"]


def test_a_steps_tokens_are_the_host_samplers_on_the_same_logits_and_uniforms(kernels):
    """The step's one call on the device against the host's ``sample_token``
    (the oracle): the logits each call returned (kept here row by row) and a
    twin of each request's seeded generator give the request's own tokens.
    ``decode`` hands back the device's array and copies nothing of it."""
    from determined_tpu.serve.engine import sample_token

    eng = ServeEngine(kernels)
    decode, prefill = kernels.decode, kernels.prefill_suffix
    steps, firsts = [], []
    kernels.decode = lambda *a: steps.append(decode(*a)) or steps[-1]
    kernels.prefill_suffix = lambda *a: firsts.append(prefill(*a)) or firsts[-1]
    try:
        temperatures = [0.8, 0.0, 1.3]
        reqs = [
            eng.submit([2 + i, 3, 5], max_new_tokens=9 - 2 * i, temperature=t, seed=40 + i)
            for i, t in enumerate(temperatures)
        ]
        while eng.step_once():
            pass
    finally:
        del kernels.decode, kernels.prefill_suffix
    assert all(isinstance(x, jax.Array) and x.shape == (4, 64) for x in steps)
    assert len(kernels.last_decode_stamps) == 3  # call, enqueued, ready: no copy to time
    for lane, (req, t) in enumerate(zip(reqs, temperatures)):
        twin = np.random.default_rng(req.seed)
        want = [sample_token(firsts[lane], t, twin)]
        want += [sample_token(np.asarray(x[lane]), t, twin) for x in steps[: req.max_new_tokens - 1]]
        assert req.error is None and req.output == want
    stats = eng.stats()
    assert stats["tokens_generated"] == 9 + 7 + 5
    assert stats["tokens_sampled_on_device"] == stats["tokens_generated"] - 3  # a first token is admission's


def test_a_sampler_of_the_callers_own_is_handed_each_live_lanes_float32_row(kernels, tracer):
    """``_advance_lane(seq, row)`` stays the seam a lane's token goes through
    (``tests/benchmark/test_bench_other_sampler.py`` lays a sampler of its
    own there): what it is handed turns into the lane's own row of the
    step's logits for whoever asks, and the device's token is not counted
    where it was not taken."""
    import types

    eng = ServeEngine(kernels)
    decode = kernels.decode
    steps, rows = [], []
    kernels.decode = lambda *a: steps.append(decode(*a)) or steps[-1]

    def advance(self, seq, row):
        got = np.asarray(row)
        rows.append((len(steps) - 1, seq.lane, got))
        tok = int(np.argmin(got))  # no sampler of the engine's yields this
        seq.request.output.append(tok)
        seq.request.token_at.append(time.monotonic())
        seq.pos += 1
        seq.next_token = tok
        return self._sequence_finished(seq, tok)

    eng._advance_lane = types.MethodType(advance, eng)
    try:
        reqs = [eng.submit([7 + i, 8], max_new_tokens=4, temperature=0.9, seed=i) for i in range(2)]
        while eng.step_once():
            pass
    finally:
        del kernels.decode
    assert len(rows) == 2 * 3 and {lane for _, lane, _ in rows} == {0, 1}
    for step, lane, got in rows:
        assert got.dtype == np.float32 and got.shape == (64,)
        np.testing.assert_array_equal(got, np.asarray(steps[step])[lane])
    for req in reqs:
        assert req.error is None and len(req.output) == 4
    assert [r[2].argmin() for r in rows if r[1] == 0] == reqs[0].output[1:]
    assert {e["args"]["device_lanes"] for e in _spans(tracer, "serve.sample")} == {0}
    assert eng.stats()["tokens_sampled_on_device"] == 0


# ---------------------------------------------------------------------------
# a step's inputs: the table and the tokens stay on the device, the draws are
# made inside the decode call's wait (PR 49)
# ---------------------------------------------------------------------------


class _HostRebuiltEngine(ServeEngine):
    """The path before PR 49, as the reference: every step makes the table
    and the tokens anew on the host from each lane's own state, hands the
    kernels three numpy arrays, draws the uniforms after the call and leaves
    the sampler's launch to the engine (nothing is handed to the call's wait)."""

    def _decode_batch(self, lanes):
        b, t = self.cfg.max_batch, self.cfg.blocks_per_seq
        tokens, positions = np.zeros(b, np.int32), np.full(b, -1, np.int32)
        tables = np.zeros((b, t), np.int32)
        for i, seq in enumerate(lanes):
            if seq is not None:
                tokens[i], positions[i], tables[i] = seq.next_token, seq.pos, seq.block_table
        t0 = time.monotonic()
        logits = self.kernels.decode(tokens, positions, tables)
        t1 = time.monotonic()
        draws = np.zeros((2, b), np.float32)
        for i, seq in enumerate(lanes):
            if seq is not None and seq.request.temperature > 0.0:
                draws[:, i] = seq.request.temperature, seq.rng.random()
        return logits, positions, draws, {"table_sent": 1, "tokens_from_device": 0}, (t0, t1), (t1, t1), None


def _mixed_traffic(seed=11, n=12):
    """(step it arrives at, prompt, keywords): sampled and greedy requests of
    unequal lengths, half of them behind one of two shared prefixes of two
    blocks, so that lanes join and retire all through the run."""
    rng = np.random.default_rng(seed)
    shared = [[int(t) for t in rng.integers(1, 64, size=8)] for _ in range(2)]
    plan = []
    for i in range(n):
        own = [int(t) for t in rng.integers(1, 64, size=int(rng.integers(1, 8)))]
        prompt = (shared[i % 2] + own) if i % 2 else own
        plan.append((
            int(rng.integers(0, 14)), prompt,
            dict(max_new_tokens=int(rng.integers(1, 12)), temperature=float(rng.choice([0.0, 0.7, 1.2])), seed=100 + i),
        ))
    return sorted(plan, key=lambda entry: entry[0])


def _drive(eng, plan):
    """Submit each request at its step, step the engine by hand to the end;
    the requests in the plan's order."""
    reqs, waiting, step = [], list(plan), 0
    while waiting or eng.lanes.active_count() or not eng.queue.empty():
        while waiting and waiting[0][0] <= step and eng.queue.depth() < eng.cfg.queue_depth:
            _, prompt, kw = waiting.pop(0)
            reqs.append(eng.submit(prompt, **kw))
        eng.step_once()
        step += 1
    assert all(r.done.is_set() and r.error is None for r in reqs)
    return reqs


class _SeenDecodes:
    """Wraps ``kernels.decode`` as the benchmark's recorder does (three
    positional arrays) and keeps what each call was handed."""

    def __init__(self, kernels):
        self.kernels, self.calls = kernels, []
        self._decode = kernels.decode

    def __enter__(self):
        def decode(tokens, positions, tables):
            out = self._decode(tokens, positions, tables)
            self.calls.append((tokens, positions.copy(), tables, np.asarray(tables).copy()))
            return out

        self.kernels.decode = decode
        return self

    def __exit__(self, *exc):
        del self.kernels.decode


def test_every_requests_tokens_are_those_of_the_host_rebuilt_step(kernels):
    """(a) Over a seeded mix of sampled and greedy requests with joins,
    retirements and the prefix cache on, every request gets the tokens of
    the path that makes the table and the tokens anew every step and draws
    after the call; and the table the device holds is, at every step, the
    live lanes' own rows over the scratch block for every idle lane."""
    plan = _mixed_traffic()
    want = [r.output for r in _drive(_HostRebuiltEngine(kernels), plan)]
    eng = ServeEngine(kernels)
    with _SeenDecodes(kernels) as seen:
        got = _drive(eng, plan)
    assert [r.output for r in got] == want
    assert any(r.temperature > 0 and len(r.output) > 3 for r in got) and any(r.temperature == 0 for r in got)
    stats = eng.stats()
    assert stats["prefix_hits"] > 0 and stats["lanes"]["joined"] == stats["lanes"]["retired"] > SERVE_CFG.max_batch
    assert 0 < stats["step_inputs"]["tokens_from_device"] < stats["step_inputs"]["decode_steps"] == len(seen.calls)
    for _tokens, positions, _tables, table in seen.calls:
        assert table.dtype == np.int32 and not table[positions < 0].any()
        assert (table[positions >= 0][:, 0] > 0).all()  # a live lane's first block is its own, never the scratch block
    assert not eng._tables.any() and (eng._positions == -1).all()  # all retired: nothing but the scratch block is named


def test_a_retired_lanes_row_names_the_scratch_block_before_its_blocks_are_anyones(lm_setup):
    """(b) A lane that retires has its row set to block 0 in the step that
    retired it, and a request admitted next, into ANOTHER lane and onto the
    blocks just freed, runs as it runs alone."""
    cfg, _model, variables = lm_setup
    serve_cfg = ServeConfig(
        block_size=4, num_blocks=64, max_batch=4, max_prompt_len=16, max_new_tokens=32, queue_depth=4, prefix_cache=False,
    )
    kernels = DecodeKernels(cfg, variables, serve_cfg)
    late = dict(max_new_tokens=9, temperature=0.9, seed=5)
    alone = ServeEngine(kernels)
    want = alone.submit([9, 8, 7, 6, 5], **late)
    while alone.step_once():
        pass
    eng = ServeEngine(kernels)
    first = eng.submit([1, 2, 3], max_new_tokens=2)        # lane 0, gone after one step
    second = eng.submit([4, 5, 6, 7, 8], max_new_tokens=4)  # lane 1, gone two steps later
    eng.step_once()
    assert first.done.is_set() and not eng._tables[0].any() and eng._tables[1].any()
    held = list(eng.lanes.get(1).blocks)
    assert eng._tables[1].tolist() == held + [0] * (serve_cfg.blocks_per_seq - len(held))
    while not second.done.is_set():
        eng.step_once()
    assert not eng._tables.any() and eng._tables_on_device is None  # before the next step, and marked to be sent
    third = eng.submit([9, 8, 7, 6, 5], **late)
    with _SeenDecodes(kernels) as seen:
        eng.step_once()
        seq = eng.lanes.get(0)
        assert seq.request is third and set(seq.blocks) & set(held)  # the blocks lane 1 just let go, in lane 0
        while eng.step_once():
            pass
    assert all(not table[1:].any() and table[0].any() for *_, table in seen.calls)
    assert third.error is None and third.output == want.output and len(want.output) == 9


class _KernelsThatNeverPrepare:
    """A stand-in that hands the decode call on and knows nothing of
    ``during_wait`` (the engine sets it on THIS object, which never runs it)
    nor of stamps; ``host`` makes it return the host's array."""

    def __init__(self, kernels, host):
        self._kernels, self._host = kernels, host
        self.serve_cfg, self.model_cfg, self.kinds = kernels.serve_cfg, kernels.model_cfg, kernels.kinds
        self.prefill, self.prefill_suffix = kernels.prefill, kernels.prefill_suffix

    def decode(self, tokens, positions, tables):
        assert self._kernels.during_wait is None  # nothing reached the kernels underneath
        out = self._kernels.decode(tokens, positions, tables)
        return np.asarray(out) if self._host else out


@pytest.mark.parametrize("host", [False, True], ids=["device_logits", "host_logits"])
def test_kernels_that_never_run_the_prepared_work_give_the_same_tokens(kernels, tracer, host):
    """(c) Under a stand-in for the kernels that never runs what the engine
    left for the call's wait, the engine runs it itself after the call: the
    same tokens, whether the stand-in returns the device's logits or the
    host's, and the span says that nothing overlapped."""
    plan = _mixed_traffic(seed=12, n=8)
    want = [r.output for r in _drive(ServeEngine(kernels), plan)]
    tracer.reset()
    shim = _KernelsThatNeverPrepare(kernels, host)
    got = _drive(ServeEngine(shim), plan)
    assert [r.output for r in got] == want and shim.during_wait is None
    decodes = {e["args"]["step"]: e for e in _spans(tracer, "serve.decode")}
    prepared = _spans(tracer, "serve.step.prepare")
    assert len(prepared) == len(decodes) > 5 and not _spans(tracer, "serve.decode.wait")
    for e in prepared:
        assert e["ts"] >= decodes[e["args"]["step"]]["ts"] + decodes[e["args"]["step"]]["dur"] - 0.2


def test_the_table_is_sent_after_a_join_or_a_retirement_and_the_devices_ids_go_in_otherwise(kernels, tracer):
    """(d) The two counters, a step at a time: the table goes to the device
    again on exactly the steps after one in which a lane joined or retired
    (and on the first), the tokens are the device's ids, the array the
    sampler returned, on exactly the steps no lane joined before."""
    eng = ServeEngine(kernels)
    moved = []  # (lanes joined, lanes joined or retired) before each decode call, since the call before
    with _SeenDecodes(kernels) as seen:
        inner = kernels.decode
        last = [0, 0]

        def decode(tokens, positions, tables):
            lanes = eng.lanes.stats()
            moved.append((lanes["joined"] - last[0], lanes["joined"] + lanes["retired"] - sum(last)))
            last[:] = lanes["joined"], lanes["retired"]
            return inner(tokens, positions, tables)

        kernels.decode = decode
        _drive(eng, _mixed_traffic(seed=13, n=10))
    assert len(moved) == len(seen.calls) > 10
    sent = from_device = 0
    for n, ((joined, changed), (tokens, _positions, tables, _table)) in enumerate(zip(moved, seen.calls)):
        assert isinstance(tables, jax.Array)
        again = n == 0 or tables is not seen.calls[n - 1][2]
        assert again == (n == 0 or changed > 0), n
        assert isinstance(tokens, jax.Array) == (joined == 0), n
        assert isinstance(tokens, (jax.Array, np.ndarray)) and tokens.dtype == np.int32
        sent += again
        from_device += joined == 0
    assert 0 < sent < len(moved) and 0 < from_device < len(moved)
    # what the paged kernel's walks had to read and what their copies brought (whole blocks), over every layer
    layers, block = kernels.model_cfg.n_layers, SERVE_CFG.block_size
    lengths = [positions[positions >= 0] + 1 for _, positions, _, _ in seen.calls]
    live = [layers * int(n.sum()) for n in lengths]
    copied = [layers * int((-(-n // block) * block).sum()) for n in lengths]
    assert sum(live) < sum(copied)
    assert eng.stats()["step_inputs"] == {
        "decode_steps": len(moved), "table_sent": sent, "tokens_from_device": from_device, "sampler_in_wait": len(moved),
        "paged_live_tokens": sum(live), "paged_copied_tokens": sum(copied),
    }
    spans = sorted(_spans(tracer, "serve.decode"), key=lambda e: e["args"]["step"])
    assert [(e["args"]["paged_live_tokens"], e["args"]["paged_copied_tokens"]) for e in spans] == list(zip(live, copied))
    assert [e["args"]["table_sent"] for e in spans] == [int(n == 0 or changed > 0) for n, (_, changed) in enumerate(moved)]
    assert [e["args"]["tokens_from_device"] for e in spans] == [int(joined == 0) for joined, _ in moved]


def test_the_prepared_work_lies_inside_the_decode_calls_wait(kernels, tracer):
    """(e) ``serve.sample`` starts after ``serve.decode`` has ended, and the
    prepared work's span lies inside ``serve.decode.wait`` of its step, the
    draws of every sampled lane in it: the lanes' generators have moved by
    the time the call returns."""
    eng = ServeEngine(kernels)
    reqs = [eng.submit([3 + i, 4], max_new_tokens=5, temperature=0.8 * (i % 2), seed=i) for i in range(3)]
    decode, drawn = kernels.decode, []

    def watched(*a):
        before = [seq.rng.bit_generator.state["state"]["state"] for seq in eng.lanes.snapshot() if seq is not None]
        out = decode(*a)
        after = [seq.rng.bit_generator.state["state"]["state"] for seq in eng.lanes.snapshot() if seq is not None]
        drawn.append([x != y for x, y in zip(before, after)])
        return out

    kernels.decode = watched
    try:
        while eng.step_once():
            pass
    finally:
        del kernels.decode
    assert all(r.error is None and len(r.output) == 5 for r in reqs)
    assert drawn and all(step == [False, True, False] for step in drawn)  # inside the call, the sampled lane alone
    by_step = lambda name: {e["args"]["step"]: e for e in _spans(tracer, name)}  # noqa: E731
    decodes, waits, samples, prepared = (by_step(n) for n in ("serve.decode", "serve.decode.wait", "serve.sample", "serve.step.prepare"))
    assert sorted(decodes) == sorted(waits) == sorted(samples) == sorted(prepared) and len(decodes) == 4
    for step, e in prepared.items():
        assert _inside(e, waits[step]) and _inside(waits[step], decodes[step]) and e["dur"] > 0
        assert samples[step]["ts"] >= decodes[step]["ts"] + decodes[step]["dur"] - 0.2


# ---------------------------------------------------------------------------
# the sampler is queued behind the decode program inside the call's wait (PR 56)
# ---------------------------------------------------------------------------


class _KernelsThatSwallowTheHook(_KernelsThatNeverPrepare):
    """The same stand-in with the kernels' stamps handed on: the engine's
    phases and spans are whole, only the hook is never run."""

    def __init__(self, kernels):
        super().__init__(kernels, host=False)

    @property
    def last_decode_stamps(self):
        return self._kernels.last_decode_stamps


def _engine_on(path, kernels):
    """An engine on each path a step can take: the sampler queued inside the
    decode call's wait (the real kernels), or launched after the call
    because nothing ran the hook."""
    if path == "in_wait":
        return ServeEngine(kernels)
    if path == "host_rebuilt":
        return _HostRebuiltEngine(kernels)
    if path == "swallowed":
        return ServeEngine(_KernelsThatSwallowTheHook(kernels))
    return ServeEngine(_KernelsThatNeverPrepare(kernels, host=path == "swallowed_host_logits"))


def _traffic_at(temperature, seed=15, n=8):
    """``_mixed_traffic`` with every request at one temperature, seeds kept."""
    return [(at, prompt, {**kw, "temperature": temperature}) for at, prompt, kw in _mixed_traffic(seed=seed, n=n)]


def test_the_sampler_is_launched_on_the_pending_logits_before_decode_returns(kernels):
    """(a) With the real kernels the hook is handed the logits the call has
    just enqueued (the very array the call then returns), the sampler's
    launch lies between the call's second and third stamp, and every step is
    counted as one whose sampler was queued inside the wait."""
    eng = ServeEngine(kernels)
    inner, launch = kernels.decode, eng._launch_sampler
    handed, returned, stamps, launches = [], [], [], []

    def decode(tokens, positions, tables):
        hook = kernels.during_wait
        assert hook is not None

        def recording(pending):
            handed.append(pending)
            hook(pending)
            assert len(launches) == len(handed)  # launched by the hook itself, inside the call

        kernels.during_wait = recording
        out = inner(tokens, positions, tables)
        assert len(launches) == len(handed) == len(returned) + 1  # before decode returned
        returned.append(out)
        stamps.append(kernels.last_decode_stamps)
        return out

    def recorded_launch(logits, draws):
        launches.append((logits, launch(logits, draws)))
        return launches[-1][1]

    kernels.decode, eng._launch_sampler = decode, recorded_launch
    try:
        got = _drive(eng, _traffic_at(0.8))
    finally:
        del kernels.decode
    assert len(handed) == len(returned) == len(launches) > 10 and kernels.during_wait is None
    for pending, out, (_call, enqueued, ready), (logits, (ids, _counted, (t_launch, t_launched))) in zip(handed, returned, stamps, launches):
        assert isinstance(pending, jax.Array) and pending.shape == (4, 64) and pending is out and logits is pending
        assert enqueued <= t_launch <= t_launched <= ready
        assert isinstance(ids, jax.Array) and ids.shape == (4,) and ids.dtype == np.int32
    inputs = eng.stats()["step_inputs"]
    assert inputs["sampler_in_wait"] == inputs["decode_steps"] == len(handed)
    assert all(len(r.output) == r.max_new_tokens for r in got)


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "t0.8"])
@pytest.mark.parametrize("path", ["swallowed_device_logits", "swallowed_host_logits", "host_rebuilt"])
def test_a_sampler_launched_after_the_call_draws_the_same_tokens(kernels, path, temperature):
    """(b) Where nothing runs the hook, the engine sees that the sampler was
    not launched and launches it after the call returns, on the same inputs
    in the same order: no step counts as ``sampler_in_wait`` and every
    request's tokens are those of the launch inside the wait, bit for bit,
    greedy and sampled (one ``rng.random()`` a sampled lane in lane order)."""
    plan = _traffic_at(temperature)
    first = ServeEngine(kernels)
    want = [r.output for r in _drive(first, plan)]
    inputs = first.stats()["step_inputs"]
    assert inputs["sampler_in_wait"] == inputs["decode_steps"] > 10
    eng = _engine_on(path, kernels)
    got = _drive(eng, plan)
    assert [r.output for r in got] == want and any(len(r.output) > 5 for r in got)
    inputs = eng.stats()["step_inputs"]
    assert inputs["sampler_in_wait"] == 0 and inputs["decode_steps"] > 10
    assert kernels.during_wait is None


@pytest.mark.parametrize("path", ["in_wait", "swallowed", "host_rebuilt"])
def test_a_requests_phases_are_its_time_a_token_wherever_the_sampler_was_launched(kernels, path):
    """(c) The phase clock on both paths: each finished request's four parts
    are its ``tpot_s`` (the clock's gain between its first token's stamp and
    its last), the phases are the thread's time, and ``sample.launch`` holds
    time only where the launch had to follow the call."""
    eng = _engine_on(path, kernels)
    reqs = _drive(eng, _traffic_at(0.8, seed=16))
    now = time.monotonic()
    clock = eng._clock
    totals = dict(zip(PHASES, clock.read(now)))
    assert sum(totals.values()) == pytest.approx(now - clock.started_at, abs=1e-6)
    several = [r for r in reqs if len(r.output) >= 2]
    assert len(several) >= 4
    for r in several:
        split = r.tpot_split_s
        assert sum(split.values()) == pytest.approx(r.tpot_s, abs=1e-9) and all(v >= 0.0 for v in split.values())
        assert (r.token_at[-1] - r.token_at[0]) / (len(r.output) - 1) <= r.tpot_s
        assert split["sample"] > 0 and split["host"] > 0
    assert (totals["sample.launch"] == 0.0) == (path == "in_wait")
    assert all(totals[k] > 0 for k in ("decode.wait", "sample.wait", "d2h", "lanes", "decode.dispatch"))
    inputs = eng.stats()["step_inputs"]
    assert inputs["sampler_in_wait"] == (inputs["decode_steps"] if path == "in_wait" else 0)


@pytest.mark.parametrize("path", ["in_wait", "swallowed"])
def test_a_traced_steps_sampling_starts_where_the_decode_call_ends(kernels, tracer, path):
    """(d) A traced step's spans.  The sampler queued inside the wait:
    ``serve.sample.launch`` lies in ``serve.decode.wait`` behind the prepared
    work, ``serve.sample`` starts where ``serve.decode`` ends and is tiled by
    the wait for the ids, their copy and the lanes' loop.  Launched after the
    call: the launch is ``serve.sample``'s first part, after the decode call
    and the draws.  ``serve.decode`` says which (``sampler_in_wait``)."""
    eng = _engine_on(path, kernels)
    reqs = [eng.submit([3 + i, 4], max_new_tokens=5, temperature=0.8 * (i % 2), seed=i) for i in range(3)]
    while eng.step_once():
        pass
    assert all(r.error is None and len(r.output) == 5 for r in reqs)
    by_step = lambda name: {e["args"]["step"]: e for e in _spans(tracer, name)}  # noqa: E731
    names = ("serve.decode", "serve.decode.wait", "serve.step.prepare", "serve.sample", "serve.sample.launch",
             "serve.sample.wait", "serve.decode.d2h", "serve.lanes")
    decodes, waits, prepared, samples, launches, ready, copies, books = (by_step(n) for n in names)
    assert all(sorted(spans) == sorted(decodes) for spans in (waits, prepared, samples, launches, ready, copies, books))
    assert len(decodes) == 4
    end = lambda e: e["ts"] + e["dur"]  # noqa: E731
    for step, sample in samples.items():
        assert decodes[step]["args"]["sampler_in_wait"] == int(path == "in_wait")
        assert sample["ts"] >= end(decodes[step]) - 0.2
        parts = [ready[step], copies[step], books[step]]
        if path == "in_wait":
            assert _inside(launches[step], waits[step]) and launches[step]["ts"] >= end(prepared[step]) - 0.2
            assert sample["ts"] == pytest.approx(end(decodes[step]), abs=0.11)
        else:
            assert prepared[step]["ts"] >= end(decodes[step]) - 0.2 and launches[step]["ts"] >= end(prepared[step]) - 0.2
            parts.insert(0, launches[step])
        assert parts[0]["ts"] == pytest.approx(sample["ts"], abs=0.11)
        for before, after in zip(parts, parts[1:]):
            assert after["ts"] == pytest.approx(end(before), abs=0.21)
        assert end(parts[-1]) == pytest.approx(end(sample), abs=0.21)
        assert all(p["dur"] > 0 for p in parts + [launches[step]])


def test_no_kind_of_step_compiles_or_lowers_a_program_after_the_kernels_are_built(lm_setup):
    """Whatever kind of argument a step hands the decode program and the
    sampler (the host's tokens or the sampler's ids, the device's table and
    draws), it finds the programs the kernels' builder loaded: once the
    kernels stand, a run with joins and retirements lowers and compiles
    nothing at all, and traces neither program again."""
    from jax._src import monitoring

    from determined_tpu.lint._runtime import get_retrace_sentinel

    cfg, _model, variables = lm_setup
    sentinel = get_retrace_sentinel()
    sentinel.reset()
    kernels = DecodeKernels(cfg, variables, SERVE_CFG)
    np.asarray(kernels.prefill([1, 2], np.zeros(SERVE_CFG.blocks_per_seq, np.int32)))  # a row of logits to the host, once
    made = []  # a program lowered, or compiled (jax times its own look-up of a traced program under a third name)

    def listener(event, seconds, **kw):
        if event.endswith(("jaxpr_to_mlir_module_duration", "backend_compile_duration")):
            made.append((event, kw))

    monitoring.register_event_duration_secs_listener(listener)
    try:
        eng = ServeEngine(kernels)
        _drive(eng, _mixed_traffic(seed=14, n=8))
    finally:
        monitoring.unregister_event_duration_listener(listener)
    inputs = eng.stats()["step_inputs"]
    assert 0 < inputs["table_sent"] < inputs["decode_steps"] and 0 < inputs["tokens_from_device"] < inputs["decode_steps"]
    assert made == []
    assert {r.label: r.traces for r in sentinel.records()} == {"serve.decode_step": 1, "serve.prefill_step": 1}
    sentinel.reset()


def test_decode_span_says_what_the_step_had_to_read(kernels, tracer):
    """``serve.decode`` carries the live KV tokens (sum of pos + 1 over
    active lanes) and the longest context of its step: with the step's
    device time a trace says which of the two the attention follows."""
    eng = ServeEngine(kernels)
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10]]
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    while eng.step_once():
        pass
    decodes = sorted(_spans(tracer, "serve.decode"), key=lambda e: e["args"]["step"])
    first = decodes[0]["args"]
    # both lanes decode their first token at position len(prompt)
    assert first["active"] == 2
    assert first["live_kv_tokens"] == sum(len(p) + 1 for p in prompts)
    assert first["max_context"] == max(len(p) for p in prompts) + 1
    for before, after in zip(decodes, decodes[1:]):
        if after["args"]["active"] == before["args"]["active"]:
            assert after["args"]["live_kv_tokens"] == (
                before["args"]["live_kv_tokens"] + after["args"]["active"]
            )
            assert after["args"]["max_context"] == before["args"]["max_context"] + 1
    for e in decodes:
        assert e["args"]["max_context"] <= e["args"]["live_kv_tokens"]


def test_token_stamps_follow_the_output(engine):
    req = engine.generate([3, 1, 4, 1, 5], max_new_tokens=7, temperature=0.8, seed=3)
    assert req.error is None
    assert len(req.token_at) == len(req.output) == 7
    assert req.token_at == sorted(req.token_at)
    assert req.arrival <= req.admitted_at <= req.first_token_at == req.token_at[0]
    assert req.token_at[-1] <= req.finished_at
    assert req.queue_wait_s == req.admitted_at - req.arrival
    assert req.tpot_s == (req.finished_at - req.token_at[0]) / 6
    assert req.itl_max_s == max(b - a for a, b in zip(req.token_at, req.token_at[1:]))
    one = engine.generate([2, 7], max_new_tokens=1)
    assert len(one.token_at) == 1 and one.tpot_s is None and one.itl_max_s is None


OLD_STATS_KEYS = {
    "submitted", "completed", "rejected", "tokens_generated", "errored",
    "http_5xx", "latency_ms_avg", "queue_depth", "queue_capacity", "draining",
    "failed", "kv_cache", "kv_utilization", "prefix_hits",
    "prefix_tokens_saved", "prefix_hit_rate", "uptime_s", "lanes",
}


def test_stats_carries_latency_and_step_seconds(kernels, tracer):
    """/stats gains what an operator lacked, keeps every key it had, and
    fills whether or not anything is traced."""
    tracer.configure(enabled=False)
    eng = ServeEngine(kernels).start()
    try:
        empty = eng.stats()
        assert empty["latency"]["ttft_ms"] == {"p50": None, "p90": None, "n": 0}
        for i in range(5):
            assert eng.generate([1, 2, 3 + i], max_new_tokens=1 + i).error is None
        st = eng.stats()
    finally:
        eng.stop()
    assert OLD_STATS_KEYS <= set(st)
    lat = st["latency"]
    assert set(lat) == {"ttft_ms", "tpot_ms", "queue_wait_ms", "tpot_split_ms"}
    assert lat["ttft_ms"]["n"] == lat["queue_wait_ms"]["n"] == 5
    assert lat["tpot_ms"]["n"] == 4  # the one-token request has no gap
    split = lat.pop("tpot_split_ms")
    for v in list(lat.values()) + list(split.values()):
        assert 0 <= v["p50"] <= v["p90"]
    assert lat["queue_wait_ms"]["p50"] <= lat["ttft_ms"]["p50"]
    # a request's time a token by what the engine's thread was doing, over the same requests
    assert set(split) == set(TPOT_PARTS) == {"decode_wait", "sample", "prefill_stall", "host"}
    assert {v["n"] for v in split.values()} == {4} and split["decode_wait"]["p50"] > 0
    assert split["decode_wait"]["p50"] < lat["tpot_ms"]["p50"] <= sum(v["p90"] for v in split.values()) + 0.004
    ss = st["step_seconds"]
    assert set(ss) == {"decode_wait", "d2h", "sample", "admission", "steps", "uptime", "phases"}
    assert ss["steps"] >= 4 and all(ss[k] > 0 for k in ss if k != "phases")
    assert ss["decode_wait"] + ss["d2h"] + ss["sample"] + ss["admission"] < st["uptime_s"]
    # the four that were there are sums of the clock's phases, which are a
    # closed set: together they are the seconds the reading covers
    phases = ss["phases"]
    # (the sampler's launch lies inside the decode call's wait and is counted there)
    assert tuple(phases) == PHASES and all((v > 0) == (k != "sample.launch") for k, v in phases.items())
    assert sum(phases.values()) == pytest.approx(ss["uptime"], abs=2e-5) and ss["uptime"] <= st["uptime_s"] + 0.0005  # rounded to a millisecond
    assert ss["decode_wait"] == phases["decode.wait"] and ss["d2h"] == phases["d2h"]
    assert ss["sample"] == pytest.approx(sum(phases[k] for k in ("sample.launch", "sample.wait", "d2h", "lanes")), abs=5e-6)
    assert ss["admission"] == pytest.approx(sum(v for k, v in phases.items() if k.startswith("admission.")), abs=5e-6)
    # the disabled tracer recorded nothing, and no shipper was started for it
    assert tracer.stats()["events"] == 0 and not tracer.shipping


def test_latency_window_is_bounded(kernels):
    from determined_tpu.serve import engine as engine_mod

    eng = ServeEngine(kernels)
    for i in range(engine_mod.LATENCY_WINDOW + 40):
        eng.submit([1, 2], max_new_tokens=1)
        assert eng.step_once()
    st = eng.stats()
    assert st["completed"] == engine_mod.LATENCY_WINDOW + 40
    assert st["latency"]["ttft_ms"]["n"] == engine_mod.LATENCY_WINDOW


def test_2000_steps_drop_no_event(kernels, tracer):
    """ServeEngine.start() runs the tracer's shipper: a step's half a dozen
    spans would otherwise fill the engine thread's 8,192-slot ring within
    ~1,200 steps and every later event would be dropped."""
    # a CPU step of this toy model is far shorter than a real one: drain
    # often enough that the ring holds an interval's events here too
    tracer.configure(flush_interval=0.02)
    assert not tracer.shipping
    eng = ServeEngine(kernels).start()
    assert tracer.shipping
    try:
        pending = []
        while True:
            # read while the engine's thread runs: every reading it publishes is closed
            ss = eng.stats()["step_seconds"]
            assert abs(sum(ss["phases"].values()) - ss["uptime"]) < 1e-3, ss
            if ss["steps"] >= 2000:
                break
            pending.append(_submit_retry(eng, [1, 2, 3], max_new_tokens=32))
            pending = [r for r in pending if not r.done.is_set()]
        for r in pending:
            assert r.done.wait(60)
    finally:
        eng.stop()
    assert not tracer.shipping  # the engine started it, the engine stopped it
    # the clock lost none of the thread's time over those steps, and every
    # request's four parts are its time a token
    ss = eng.stats()["step_seconds"]
    assert ss["steps"] >= 2000 and abs(sum(ss["phases"].values()) - ss["uptime"]) < 1e-3
    requests = [e["args"] for e in _spans(tracer, "serve.request")]
    assert len(requests) >= 60 and all(a["tpot_ms"] is not None for a in requests)
    for a in requests:
        assert abs(sum(a[f"tpot_{part}_ms"] for part in TPOT_PARTS) - a["tpot_ms"]) < 0.001, a
    st = tracer.stats()
    assert st["dropped"] == 0
    assert len(_spans(tracer, "serve.step")) == eng.stats()["step_seconds"]["steps"] >= 2000
    assert st["events"] > 8192


def test_trace_dir_leaves_a_readable_timeline(kernels, tracer, tmp_path):
    """What ``dtpu serve --trace-dir`` does round the engine's life: off by
    default, and with a directory ``events.jsonl`` and ``trace.json`` whose
    ``serve.request`` spans number the requests served."""
    import json

    from determined_tpu.serve.tracing import finish_tracing, start_tracing

    start_tracing(None)
    assert not tracer.enabled and not tracer.shipping
    finish_tracing(None)
    assert ServeConfig().trace_dir is None
    out = str(tmp_path / "trace")
    cfg = ServeConfig.from_dict({"trace_dir": out})
    start_tracing(cfg.trace_dir)
    assert tracer.enabled and tracer.shipping
    eng = ServeEngine(kernels).start()
    try:
        for i in range(4):
            assert eng.generate([9, 8, 7 + i], max_new_tokens=3).error is None
        assert eng.drain(timeout=30)
    finally:
        eng.stop()
        finish_tracing(cfg.trace_dir)
    assert not tracer.shipping
    with open(os.path.join(out, "events.jsonl")) as f:
        lines = [json.loads(x) for x in f]
    assert sum(1 for e in lines if e.get("name") == "serve.request") == 4
    assert lines[0]["name"] == "clock_sync"
    with open(os.path.join(out, "trace.json")) as f:
        trace = json.load(f)
    served = [e for e in trace["traceEvents"] if e.get("name") == "serve.request"]
    assert len(served) == 4 and trace["otherData"]["dropped_events"] == 0
    assert all(e["args"]["output_tokens"] == 3 for e in served)


# ---------------------------------------------------------------------------
# HTTP worker (in-process)
# ---------------------------------------------------------------------------


def test_http_generate_healthz_stats_and_drain(kernels):
    requests = pytest.importorskip("requests")
    worker = ServeWorker(ServeEngine(kernels))
    url = worker.start()
    try:
        assert requests.get(url + "/healthz", timeout=5).json()["status"] == "ok"
        r = requests.post(
            url + "/v1/generate",
            json={"prompt_tokens": [1, 2, 3], "max_new_tokens": 3},
            timeout=60,
        )
        assert r.status_code == 200, r.text
        body = r.json()
        assert len(body["tokens"]) == 3
        assert body["usage"] == {"prompt_tokens": 3, "completion_tokens": 3}
        assert body["latency_ms"] >= body["ttft_ms"] >= 0
        st = requests.get(url + "/stats", timeout=5).json()
        assert st["completed"] >= 1
        # malformed bodies
        assert requests.post(url + "/v1/generate", json={"prompt_tokens": "x"},
                             timeout=5).status_code == 400
        assert requests.post(url + "/v1/generate", data=b"{", timeout=5).status_code == 400
        # drain: healthz flips, new generations rejected 503
        worker.request_drain()
        h = requests.get(url + "/healthz", timeout=5)
        assert h.status_code == 503 and h.json()["status"] == "draining"
        r = requests.post(url + "/v1/generate",
                          json={"prompt_tokens": [1]}, timeout=5)
        assert r.status_code == 503
        assert worker.wait_drained(timeout=30)
    finally:
        worker.shutdown()


# ---------------------------------------------------------------------------
# subprocess: dtpu serve — SIGTERM drain exits 75
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lm_checkpoint(tmp_path_factory):
    """A real trained-LMTrial checkpoint for the from_checkpoint paths."""
    from determined_tpu import core, train
    from determined_tpu.config import Length
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig

    root = tmp_path_factory.mktemp("serve-ckpt")
    ctx = train.init(
        hparams={
            "lr": 1e-3, "global_batch_size": 8, "seq_len": 8, "vocab_size": 64,
            "d_model": 32, "n_layers": 1, "n_heads": 2, "n_kv_heads": 2,
            "dataset_size": 32, "bf16": False, "attention": "reference",
            "warmup_steps": 1,
        },
        mesh_config=MeshConfig(data=1),
        core_context=core._dummy_init(checkpoint_dir=str(root)),
        seed=0,
    )
    trainer = train.Trainer(LMTrial(ctx))
    result = trainer.fit(Length.batches(2))
    assert result["latest_checkpoint"]
    return str(root / result["latest_checkpoint"])


def test_engine_from_checkpoint_serves(lm_checkpoint):
    cfg = ServeConfig(block_size=4, num_blocks=32, max_batch=2,
                      max_prompt_len=8, max_new_tokens=8, queue_depth=4)
    eng = ServeEngine.from_checkpoint(lm_checkpoint, cfg).start()
    try:
        req = eng.generate([1, 2, 3], max_new_tokens=4)
        assert req.error is None and len(req.output) == 4
        assert all(0 <= t < 64 for t in req.output)
    finally:
        eng.stop()


def _spawn_serve_worker(lm_checkpoint, extra_args=(), env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # 1 virtual device: fastest startup
    env.update(env_extra or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "determined_tpu.cli", *extra_args,
         "serve", lm_checkpoint, "--port", "0",
         "--block-size", "16", "--num-blocks", "64", "--max-batch", "2",
         "--max-prompt-len", "8", "--max-new-tokens", "512",
         "--queue-depth", "4"],
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    lines = []

    def pump():
        for line in proc.stdout:
            lines.append(line.rstrip())

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    deadline = time.time() + 180
    url = None
    while time.time() < deadline and url is None:
        for line in lines:
            if line.startswith("serving on "):
                url = line.split("serving on ", 1)[1].strip()
                break
        if proc.poll() is not None:
            raise AssertionError(
                "serve worker exited early:\n" + "\n".join(lines)
            )
        time.sleep(0.2)
    assert url, "worker never announced its url:\n" + "\n".join(lines)
    return proc, url, lines


@pytest.mark.slow
def test_sigterm_drain_exits_75(lm_checkpoint):
    """SIGTERM: in-flight requests finish (200), new ones are rejected,
    and the process exits 75 (EX_TEMPFAIL) — the orderly-preemption
    contract shared with experiment drains."""
    requests = pytest.importorskip("requests")
    proc, url, lines = _spawn_serve_worker(lm_checkpoint)
    try:
        results = {}

        def generate():
            results["resp"] = requests.post(
                url + "/v1/generate",
                json={"prompt_tokens": [1, 2, 3], "max_new_tokens": 512},
                timeout=180,
            )

        t = threading.Thread(target=generate, daemon=True)
        t.start()
        # let the request get admitted, then drain under it
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if requests.get(url + "/stats", timeout=5).json()["submitted"] >= 1:
                    break
            except Exception:
                pass
            time.sleep(0.05)
        proc.send_signal(signal.SIGTERM)
        # wait for the worker to acknowledge the drain (the signal flag is
        # polled on its main loop) before probing rejection
        deadline = time.time() + 30
        while time.time() < deadline and not any(
            line.startswith("drain requested") for line in lines
        ):
            time.sleep(0.05)
        assert any(line.startswith("drain requested") for line in lines), lines
        # new requests are rejected while draining (503), or the listener
        # is already gone (connection refused) — both are rejections
        try:
            r = requests.post(url + "/v1/generate",
                              json={"prompt_tokens": [4]}, timeout=10)
            assert r.status_code == 503, r.text
        except requests.ConnectionError:
            pass
        t.join(timeout=180)
        assert not t.is_alive(), "in-flight request never completed"
        resp = results["resp"]
        assert resp.status_code == 200, resp.text
        assert len(resp.json()["tokens"]) == 512  # finished, not truncated
        rc = proc.wait(timeout=60)
        assert rc == 75, "\n".join(lines)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# master-outage hardening: the worker serves through a master kill+restart
# ---------------------------------------------------------------------------


class _FakeServeMaster:
    """Just enough master for the replica contract: register (201),
    heartbeat (200, or 404 for ids it does not know), delete.  ``kill()``
    closes the listener (connection-refused, like a dead master);
    ``restart()`` rebinds the SAME port with the registry EMPTY — exactly
    what a real master restart looks like to a worker (replicas are
    ephemeral by design; only the auth token survives the WAL replay)."""

    def __init__(self):
        self.registrations = []
        self.known = set()
        self.heartbeats = 0
        # rid -> deploy payload: heartbeat answers {"drain": true, ...}
        # (the rolling-deploy signal channel)
        self.drain = {}
        # when set (a Retry-After value), heartbeats answer 429 with that
        # header — the admission-control shedding the backoff test drives
        self.throttle = None
        self.throttle_hits = 0
        self.lock = threading.Lock()
        self.port = 0
        self.server = None
        self.thread = None
        self._serve()

    def _serve(self):
        import json as _json
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        from urllib.parse import urlparse

        fake = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _json(self, obj, code=200):
                body = _json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                path = urlparse(self.path).path
                n = int(self.headers.get("Content-Length") or 0)
                body = _json.loads(self.rfile.read(n) or b"{}") if n else {}
                with fake.lock:
                    if path == "/api/v1/auth/login":
                        return self._json({"token": "t"})
                    if path == "/api/v1/serving/replicas":
                        rid = f"replica-{len(fake.registrations) + 1}"
                        fake.registrations.append(dict(body))
                        fake.known.add(rid)
                        return self._json(
                            {"id": rid, "heartbeat_ttl_ms": 15000}, 201
                        )
                    if path.endswith("/heartbeat"):
                        rid = path.split("/")[5]
                        if rid not in fake.known:
                            return self._json({"error": "no such replica"}, 404)
                        if fake.throttle is not None:
                            fake.throttle_hits += 1
                            shed = _json.dumps({"error": "shedding"}).encode()
                            self.send_response(429)
                            self.send_header("Content-Type", "application/json")
                            self.send_header("Retry-After", str(fake.throttle))
                            self.send_header("Content-Length", str(len(shed)))
                            self.end_headers()
                            self.wfile.write(shed)
                            return
                        fake.heartbeats += 1
                        dep = fake.drain.get(rid)
                        if dep is not None:
                            return self._json({"drain": True, "deploy": dep})
                        return self._json({})
                return self._json({"error": f"no fake route {path}"}, 404)

            def do_DELETE(self):
                return self._json({})

        self.server = ThreadingHTTPServer(("127.0.0.1", self.port), Handler)
        self.port = self.server.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True, name="fake-serve-master"
        )
        self.thread.start()

    def kill(self):
        self.server.shutdown()
        self.server.server_close()

    def restart(self):
        with self.lock:
            self.known.clear()  # a restarted master forgot every replica
        self._serve()

    def close(self):
        try:
            self.kill()
        except Exception:  # noqa: BLE001 - already down is fine
            pass


class _FastHeartbeatKernels:
    """Shared-kernel shim with a fast heartbeat interval (no recompile)."""

    def __init__(self, kernels, interval_s=0.1):
        import dataclasses

        self.serve_cfg = dataclasses.replace(
            kernels.serve_cfg, heartbeat_interval_s=interval_s
        )
        self.model_cfg = kernels.model_cfg
        self.kinds = kernels.kinds
        self.prefill = kernels.prefill
        self.prefill_suffix = kernels.prefill_suffix
        self.decode = kernels.decode


def test_worker_survives_master_kill_and_reregisters(kernels):
    """Regression (ISSUE 13 satellite): kill and restart a fake master
    under an active ServeWorker.  The heartbeat thread must survive the
    outage (connection errors logged-and-retried, never crash), the worker
    must keep serving generations throughout, and on the restarted master
    the first heartbeat's 404 must trigger a re-registration."""
    requests = pytest.importorskip("requests")
    from determined_tpu.api.session import Session

    fake = _FakeServeMaster()
    worker = ServeWorker(
        ServeEngine(_FastHeartbeatKernels(kernels)),
        session=Session(fake.url, token="t"),
        model="lm",
    )
    url = worker.start()
    try:
        assert worker.replica is not None
        assert len(fake.registrations) == 1
        deadline = time.time() + 10
        while fake.heartbeats == 0 and time.time() < deadline:
            time.sleep(0.05)
        assert fake.heartbeats > 0, "heartbeat never arrived"

        fake.kill()
        time.sleep(0.5)  # several heartbeat intervals of dead master
        # the worker keeps serving through the control-plane outage
        r = requests.post(
            url + "/v1/generate",
            json={"prompt_tokens": [1, 2, 3], "max_new_tokens": 2, "seed": 0},
            timeout=30,
        )
        assert r.status_code == 200, r.text
        hb_thread = worker.replica._thread
        assert hb_thread is not None and hb_thread.is_alive(), (
            "heartbeat thread died during the master outage"
        )

        fake.restart()
        deadline = time.time() + 10
        while len(fake.registrations) < 2 and time.time() < deadline:
            time.sleep(0.05)
        assert len(fake.registrations) >= 2, (
            "worker never re-registered after the master restart"
        )
        hb_before = fake.heartbeats
        deadline = time.time() + 10
        while fake.heartbeats == hb_before and time.time() < deadline:
            time.sleep(0.05)
        assert fake.heartbeats > hb_before, "heartbeats did not resume"
    finally:
        worker.shutdown(deregister=False)
        fake.close()


def test_registration_carries_registry_version(kernels):
    """A replica launched via ``--model`` (ISSUE 15): its listing label is
    the registry ``name@vN`` and the resolved version rides registration;
    a raw-path launch falls back to the trial class name with no
    registry fields at all."""
    from determined_tpu.api.session import Session

    fake = _FakeServeMaster()
    worker = ServeWorker(
        ServeEngine(_FastHeartbeatKernels(kernels)),
        session=Session(fake.url, token="t"),
        model="lm@v3",
        model_name="lm",
        model_version=3,
    )
    worker.start()
    try:
        reg = fake.registrations[0]
        assert reg["model"] == "lm@v3"
        assert reg["model_name"] == "lm" and reg["model_version"] == 3
    finally:
        worker.shutdown(deregister=False)

    raw = ServeWorker(
        ServeEngine(_FastHeartbeatKernels(kernels)),
        session=Session(fake.url, token="t"),
        model="LMTrial",  # class-name fallback (PR 9 review fix)
    )
    raw.start()
    try:
        reg = fake.registrations[1]
        assert reg["model"] == "LMTrial"
        assert "model_name" not in reg and "model_version" not in reg
    finally:
        raw.shutdown(deregister=False)
        fake.close()


def test_master_drain_request_reaches_worker(kernels):
    """Rolling deploy's drain channel: when the master answers a
    heartbeat with ``{"drain": true, "deploy": {...}}``, the worker's
    master-drain flag flips (the serve main loop polls it next to the
    signal flag) and the deploy target is exposed."""
    from determined_tpu.api.session import Session

    fake = _FakeServeMaster()
    worker = ServeWorker(
        ServeEngine(_FastHeartbeatKernels(kernels)),
        session=Session(fake.url, token="t"),
        model="lm@v1",
        model_name="lm",
        model_version=1,
    )
    worker.start()
    try:
        assert not worker.master_drain_requested()
        rid = worker.replica.replica_id
        with fake.lock:
            fake.drain[rid] = {"model": "lm", "version": 2, "target": "lm@v2"}
        deadline = time.time() + 10
        while not worker.master_drain_requested() and time.time() < deadline:
            time.sleep(0.05)
        assert worker.master_drain_requested(), "drain flag never flipped"
        assert worker.master_drain_info["target"] == "lm@v2"
        # the flag is drain-once: later heartbeats must not re-fire it
        assert worker.replica.drain_requested.is_set()
    finally:
        worker.shutdown(deregister=False)
        fake.close()


def test_heartbeat_backs_off_on_429_honoring_retry_after():
    """Admission-control shedding (ISSUE 16 satellite): a master answering
    heartbeats 429 + Retry-After must slow the replica's cadence to the
    advertised delay — not hammer on the fixed interval — and recover the
    normal cadence (throttle counter reset) once the master stops
    shedding.  Drives ReplicaRegistration directly: no engine needed."""
    from determined_tpu.serve.replica import ReplicaRegistration
    from determined_tpu.api.session import Session

    fake = _FakeServeMaster()
    reg = ReplicaRegistration(
        Session(fake.url, token="t"),
        url="http://127.0.0.1:1/x",
        model="lm",
        heartbeat_interval_s=0.05,
    ).start()
    try:
        deadline = time.time() + 10
        while fake.heartbeats == 0 and time.time() < deadline:
            time.sleep(0.02)
        assert fake.heartbeats > 0, "heartbeat never arrived"

        with fake.lock:
            fake.throttle = "0.6"
        time.sleep(2.0)
        with fake.lock:
            hits = fake.throttle_hits
            fake.throttle = None
        # Retry-After 0.6s over 2s allows ~4 attempts; the un-backed-off
        # 0.05s cadence would have made ~40.  The margin proves the header
        # was honored, not merely that SOME delay happened.
        assert 1 <= hits <= 8, f"429 backoff not honored: {hits} hits in 2s"
        assert reg.throttled >= 1, "throttle counter never grew"

        hb_before = fake.heartbeats
        deadline = time.time() + 10
        while fake.heartbeats < hb_before + 3 and time.time() < deadline:
            time.sleep(0.02)
        assert fake.heartbeats >= hb_before + 3, "cadence did not recover"
        assert reg.throttled == 0, "throttle counter not reset on success"
    finally:
        reg.close(deregister=False)
        fake.close()


def test_throttle_delay_is_capped_and_prefers_retry_after():
    """The computed 429 backoff must honor an explicit Retry-After, fall
    back to capped exponential growth for the HTTP-date form it cannot
    parse, and never exceed MAX_THROTTLE_S (staying under the master's
    reap horizon)."""
    from determined_tpu.serve.replica import MAX_THROTTLE_S, ReplicaRegistration

    reg = ReplicaRegistration.__new__(ReplicaRegistration)
    reg._interval = 2.0
    reg._lock = threading.Lock()
    reg.throttled = 1
    assert reg._throttle_delay("7") == 7.0
    assert reg._throttle_delay("0") == 0.0
    # unparseable HTTP-date form falls back to the computed backoff
    d = reg._throttle_delay("Wed, 21 Oct 2026 07:28:00 GMT")
    assert 0 < d <= MAX_THROTTLE_S
    reg.throttled = 50  # deep throttle: 2*2^50 without the cap
    for _ in range(10):
        assert reg._throttle_delay() <= MAX_THROTTLE_S


# ---------------------------------------------------------------------------
# devcluster e2e: registration, serving under load, heartbeat-loss pruning
# ---------------------------------------------------------------------------


@pytest.mark.devcluster
def test_failed_engine_heartbeat_reaps_immediately(tmp_path):
    """ISSUE 16 satellite: a replica whose heartbeat stats carry a truthy
    ``failed`` is reaped NOW — the crashed-engine-behind-a-live-HTTP-thread
    case must not wait out the TTL.  Registers against the REAL master
    with a 60s TTL so the immediate disappearance proves the failed-stat
    path, not the reaper; also proves healthy heartbeats (failed=None,
    the engine's normal stats shape) are NOT false-positive reaped."""
    requests = pytest.importorskip("requests")
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from devcluster import DevCluster

    cluster = DevCluster(
        tmp_path, agents=0, master_args=["--serve-replica-timeout-sec", "60"]
    )
    cluster.start_master()
    try:
        r = cluster.http.post(
            cluster.url + "/api/v1/serving/replicas",
            json={"url": "http://127.0.0.1:1/x", "model": "lm@v1"},
            timeout=5,
        )
        assert r.status_code == 201, r.text
        rid = r.json()["id"]
        hb = cluster.url + f"/api/v1/serving/replicas/{rid}/heartbeat"

        # healthy stats — including the engine's literal "failed": None —
        # keep the replica listed
        r = cluster.http.post(
            hb, json={"stats": {"requests": 3, "failed": None}}, timeout=5
        )
        assert r.status_code == 200 and "reaped" not in r.json(), r.text
        assert [x["id"] for x in cluster.serving()] == [rid]

        # a truthy failed stat reaps on the spot
        r = cluster.http.post(
            hb,
            json={"stats": {"requests": 3,
                            "failed": "RuntimeError: kernel crashed"}},
            timeout=5,
        )
        assert r.status_code == 200 and r.json().get("reaped") is True, r.text
        assert cluster.serving() == [], "failed replica still listed"

        # the dead replica's next heartbeat 404s -> the worker re-registers
        r = cluster.http.post(hb, json={}, timeout=5)
        assert r.status_code == 404
    finally:
        cluster.stop()


@pytest.mark.devcluster
def test_fleet_supervisor_adopts_replaces_and_backs_off(tmp_path):
    """The master-side replica supervisor (ISSUE 16 tentpole), driven at
    the API level with no agents: a PUT over a hand-launched fleet ADOPTS
    the live replicas instead of doubling them; a failed replica's slot is
    refilled by launching a serve task through the generic-task path; and
    a launch that dies crashing is accounted as a slot failure with
    backoff, not retried hot."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from devcluster import DevCluster

    cluster = DevCluster(
        tmp_path, agents=0,
        master_args=["--serve-replica-timeout-sec", "60",
                     "--fleet-backoff-initial-ms", "100"],
    )
    cluster.start_master()
    try:
        cluster.register_model("lm", "uuid-fleet", storage_path="/ck/fleet")
        rids = []
        for i in range(2):
            r = cluster.http.post(
                cluster.url + "/api/v1/serving/replicas",
                json={"url": f"http://127.0.0.1:1/{i}", "model": "lm@v1",
                      "model_name": "lm", "model_version": 1},
                timeout=5,
            )
            assert r.status_code == 201, r.text
            rids.append(r.json()["id"])

        # adoption: the spec binds the live replicas, launches nothing
        r = cluster.http.put(
            cluster.url + "/api/v1/serving/fleet",
            json={"model": "lm", "version": 1, "target": 2},
            timeout=5,
        )
        assert r.status_code == 200, r.text
        fleet = r.json()
        assert fleet["status"] == "ok", fleet
        assert sorted(s["replica_id"] for s in fleet["slots"]) == sorted(rids)
        assert all(s["launches"] == 0 for s in fleet["slots"]), fleet

        # a failed replica's reap triggers a replacement launch
        r = cluster.http.post(
            cluster.url + f"/api/v1/serving/replicas/{rids[0]}/heartbeat",
            json={"stats": {"failed": "boom"}}, timeout=5,
        )
        assert r.json().get("reaped") is True, r.text
        fleet = cluster.http.get(
            cluster.url + "/api/v1/serving/fleet", timeout=5).json()
        assert fleet["status"] == "reconciling", fleet
        vacant = [s for s in fleet["slots"] if not s["replica_id"]]
        assert len(vacant) == 1 and vacant[0]["task_id"], fleet
        assert vacant[0]["launches"] == 1
        task = cluster.http.get(
            cluster.url + f"/api/v1/tasks/{vacant[0]['task_id']}", timeout=5
        ).json()
        assert task["type"] == "serve"

        # the launch dying with a crash exit is a failure + backoff ...
        r = cluster.http.post(
            cluster.url + f"/api/v1/tasks/{vacant[0]['task_id']}/exit",
            json={"exit_code": 1, "detail": "bad checkpoint"}, timeout=5,
        )
        assert r.status_code == 200, r.text
        deadline = time.time() + 10
        while time.time() < deadline:
            fleet = cluster.http.get(
                cluster.url + "/api/v1/serving/fleet", timeout=5).json()
            slot = fleet["slots"][vacant[0]["index"]]
            if slot["failures"] >= 1:
                break
            time.sleep(0.2)
        assert slot["failures"] == 1, fleet
        assert "exited 1" in slot["last_error"], fleet

        # ... and the supervisor retries after the backoff (2s tick)
        deadline = time.time() + 15
        while time.time() < deadline:
            fleet = cluster.http.get(
                cluster.url + "/api/v1/serving/fleet", timeout=5).json()
            slot = fleet["slots"][vacant[0]["index"]]
            if slot["launches"] >= 2:
                break
            time.sleep(0.2)
        assert slot["launches"] >= 2, fleet
    finally:
        cluster.stop()


def _fake_replica(cluster, version, stats=None):
    """Register a fake replica on lm@v{version}; optionally ship stats."""
    r = cluster.http.post(
        cluster.url + "/api/v1/serving/replicas",
        json={"url": f"http://127.0.0.1:1/v{version}", "model": f"lm@v{version}",
              "model_name": "lm", "model_version": version},
        timeout=5,
    )
    assert r.status_code == 201, r.text
    rid = r.json()["id"]
    if stats is not None:
        r = cluster.http.post(
            cluster.url + f"/api/v1/serving/replicas/{rid}/heartbeat",
            json={"stats": stats}, timeout=5,
        )
        assert r.status_code == 200, r.text
    return rid


def _canary_cluster(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from devcluster import DevCluster

    cluster = DevCluster(
        tmp_path, agents=0,
        master_args=["--serve-replica-timeout-sec", "60",
                     "--deploy-step-timeout-sec", "60"],
    )
    cluster.start_master()
    cluster.register_model("lm", "uuid-v1", storage_path="/ck/v1")
    cluster.register_model("lm", "uuid-v2", storage_path="/ck/v2", version=2)
    return cluster


_HEALTHY = {"completed": 100, "errored": 1, "http_5xx": 0,
            "latency_ms_avg": 10.0}
# error_rate 10/100 = 0.10 > baseline (2/202 ~ 0.01) + threshold 0.05
_REGRESSED = {"completed": 90, "errored": 8, "http_5xx": 2,
              "latency_ms_avg": 11.0}


def _walk_one_drain(cluster, replace_version, stats):
    """Play the supervisor for one deploy step: wait for the master to
    name a draining replica, take it away, and register the replacement
    the walk demands (carrying ``stats`` on its first heartbeat)."""
    deadline = time.time() + 30
    while time.time() < deadline:
        state = cluster.deploy_status()
        if state.get("draining"):
            break
        time.sleep(0.2)
    assert state.get("draining"), state
    r = cluster.http.delete(
        cluster.url + f"/api/v1/serving/replicas/{state['draining']}",
        timeout=5,
    )
    assert r.status_code == 200, r.text
    return _fake_replica(cluster, replace_version, stats=stats)


@pytest.mark.devcluster
def test_canary_regression_holds_naming_the_stat(tmp_path):
    """The canary gate (ISSUE 16 tentpole): a canary deploy rolls only
    the cohort, bakes it against the journaled pre-roll baseline, and an
    error-rate regression HOLDS the roll with the offending stat named —
    the untouched half of the fleet never drains."""
    cluster = _canary_cluster(tmp_path)
    try:
        _fake_replica(cluster, 1, stats=_HEALTHY)
        keep = _fake_replica(cluster, 1, stats=_HEALTHY)

        r = cluster.http.post(
            cluster.url + "/api/v1/serving/deploy",
            json={"model": "lm", "version": 2, "canary_fraction": 0.5,
                  "bake_seconds": 2, "min_requests": 10},
            timeout=5,
        )
        assert r.status_code == 202, r.text
        state = r.json()
        assert state["phase"] == "canary", state
        assert state["canary"]["count"] == 1
        assert state["canary"]["baseline"]["requests"] == 202
        assert state["prev_version"] == 1

        _walk_one_drain(cluster, 2, stats=_REGRESSED)
        deadline = time.time() + 20
        while time.time() < deadline:
            state = cluster.deploy_status()
            if state["status"] != "rolling":
                break
            time.sleep(0.2)
        assert state["status"] == "held", state
        assert state["canary"]["verdict"] == "regression"
        assert state["canary"]["offending_stat"] == "error_rate"
        assert state["canary"]["observed"]["error_rate"] == pytest.approx(0.1)
        assert "error_rate" in state["detail"]
        # the non-canary half of the fleet was never walked
        assert [x["id"] for x in cluster.serving() if x["id"] == keep] == [keep]
    finally:
        cluster.stop()


@pytest.mark.devcluster
def test_canary_regression_rolls_back_to_prev_version(tmp_path):
    """With --rollback-on-regression the regressed canary cohort is
    drained BACK onto the previous version through the same walk
    machinery, terminal status ``rolled_back``."""
    cluster = _canary_cluster(tmp_path)
    try:
        _fake_replica(cluster, 1, stats=_HEALTHY)
        _fake_replica(cluster, 1, stats=_HEALTHY)

        r = cluster.http.post(
            cluster.url + "/api/v1/serving/deploy",
            json={"model": "lm", "version": 2, "canary_fraction": 0.5,
                  "bake_seconds": 2, "min_requests": 10,
                  "rollback_on_regression": True},
            timeout=5,
        )
        assert r.status_code == 202, r.text

        _walk_one_drain(cluster, 2, stats=_REGRESSED)
        # the regression flips the walk into rolling_back: the master now
        # drains the bad v2 canary and demands a v1 replacement
        deadline = time.time() + 20
        while time.time() < deadline:
            state = cluster.deploy_status()
            if state.get("phase") == "rolling_back" or state["status"] != "rolling":
                break
            time.sleep(0.2)
        assert state.get("phase") == "rolling_back", state
        assert state["version"] == 1 and state["target"] == "lm@v1", state

        _walk_one_drain(cluster, 1, stats=_HEALTHY)
        deadline = time.time() + 20
        while time.time() < deadline:
            state = cluster.deploy_status()
            if state["status"] != "rolling":
                break
            time.sleep(0.2)
        assert state["status"] == "rolled_back", state
        assert state["canary"]["offending_stat"] == "error_rate"
        labels = sorted(x["model"] for x in cluster.serving())
        assert labels == ["lm@v1", "lm@v1"], labels
    finally:
        cluster.stop()


@pytest.mark.devcluster
def test_canary_deploy_survives_master_sigkill_and_resumes(tmp_path):
    """WAL-durable deploys (ISSUE 16 tentpole): SIGKILL the master
    mid-canary-bake; the restarted master replays deploy_started/advanced,
    waits for re-registrations, restarts the bake window, and the roll
    completes — no operator re-POST."""
    cluster = _canary_cluster(tmp_path)
    try:
        _fake_replica(cluster, 1, stats=_HEALTHY)
        _fake_replica(cluster, 1, stats=_HEALTHY)
        r = cluster.http.post(
            cluster.url + "/api/v1/serving/deploy",
            json={"model": "lm", "version": 2, "canary_fraction": 0.5,
                  "bake_seconds": 2, "min_requests": 5},
            timeout=5,
        )
        assert r.status_code == 202, r.text
        canary_rid = _walk_one_drain(cluster, 2, stats=_HEALTHY)
        deadline = time.time() + 20
        while time.time() < deadline:
            state = cluster.deploy_status()
            if state.get("phase") == "baking":
                break
            time.sleep(0.2)
        assert state.get("phase") == "baking", state

        cluster.kill_master()
        cluster.restart_master()
        # replicas are ephemeral: play each worker's 404 -> re-register.
        # The canary re-registers on v2 (it IS running v2), the survivor
        # on v1; the rescan rebuilds the walk from these live rows.
        _fake_replica(cluster, 2, stats=_HEALTHY)
        _fake_replica(cluster, 1, stats=_HEALTHY)

        state = cluster.deploy_status()
        assert state["status"] == "rolling", state  # resumed, not lost
        # the resumed roll finishes: bake passes (healthy canary stats),
        # then the remaining v1 replica drains
        _walk_one_drain(cluster, 2, stats=_HEALTHY)
        deadline = time.time() + 30
        while time.time() < deadline:
            state = cluster.deploy_status()
            if state["status"] != "rolling":
                break
            time.sleep(0.2)
        assert state["status"] == "completed", state
        assert state["canary"]["verdict"] == "pass", state
        del canary_rid
    finally:
        cluster.stop()


@pytest.mark.devcluster
@pytest.mark.slow
def test_replica_lifecycle_against_real_master(lm_checkpoint, tmp_path):
    requests = pytest.importorskip("requests")
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from devcluster import DevCluster

    cluster = DevCluster(
        tmp_path, agents=0, master_args=["--serve-replica-timeout-sec", "3"]
    )
    cluster.start_master()
    proc = None
    try:
        proc, url, lines = _spawn_serve_worker(
            lm_checkpoint, extra_args=["-m", cluster.url]
        )
        # replica appears in the master's listing
        deadline = time.time() + 60
        replicas = []
        while time.time() < deadline:
            replicas = cluster.http.get(cluster.url + "/api/v1/serving",
                                        timeout=5).json()
            if replicas:
                break
            time.sleep(0.3)
        assert len(replicas) == 1, lines
        assert replicas[0]["url"] == url
        assert replicas[0]["checkpoint"] == lm_checkpoint

        # heartbeats carry the worker's stats into the listing
        deadline = time.time() + 30
        while time.time() < deadline:
            replicas = cluster.http.get(cluster.url + "/api/v1/serving",
                                        timeout=5).json()
            if replicas and replicas[0].get("stats"):
                break
            time.sleep(0.5)
        assert "kv_cache" in replicas[0]["stats"], replicas

        # serves under (a little) load through the registered url
        for i in range(4):
            r = requests.post(
                replicas[0]["url"] + "/v1/generate",
                json={"prompt_tokens": [i + 1, i + 2], "max_new_tokens": 3},
                timeout=120,
            )
            assert r.status_code == 200, r.text
            assert len(r.json()["tokens"]) == 3

        # heartbeat loss (SIGKILL: no deregistration) -> master prunes
        proc.kill()
        proc.wait(timeout=10)
        deadline = time.time() + 20
        while time.time() < deadline:
            replicas = cluster.http.get(cluster.url + "/api/v1/serving",
                                        timeout=5).json()
            if not replicas:
                break
            time.sleep(0.5)
        assert replicas == [], "replica not pruned after heartbeat loss"
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
        cluster.stop()

# ---------------------------------------------------------------------------
# master request routing: POST /v1/generate on the master reverse-proxies to
# the least-loaded healthy replica with prefix/session affinity (ISSUE 17)
# ---------------------------------------------------------------------------


class _FakeReplica:
    """A replica's HTTP face only: /v1/generate answers with the replica's
    own tag, so router tests can see exactly where the master sent each
    request.  ``status`` flips the replica into shedding (429/503) mode."""

    def __init__(self, tag, status=200):
        import json as _json
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.tag = tag
        self.status = status
        self.hits = 0
        self.lock = threading.Lock()
        fake = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                self.rfile.read(n)
                with fake.lock:
                    fake.hits += 1
                    code = fake.status
                body = _json.dumps({"tokens": [7], "replica": fake.tag}).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.url = f"http://127.0.0.1:{self.server.server_address[1]}/"
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True,
            name=f"dtpu-fake-replica-{tag}",
        )
        self.thread.start()

    def close(self):
        try:
            self.server.shutdown()
            self.server.server_close()
        except Exception:  # noqa: BLE001 - already down is fine
            pass


def _route_register(cluster, url, stats):
    """Register a replica url and push one heartbeat of router-visible
    stats (queue_depth/queue_capacity/kv_utilization)."""
    r = cluster.http.post(
        cluster.url + "/api/v1/serving/replicas",
        json={"url": url, "model": "lm@v1"}, timeout=5,
    )
    assert r.status_code == 201, r.text
    rid = r.json()["id"]
    if stats is not None:
        hb = cluster.http.post(
            cluster.url + f"/api/v1/serving/replicas/{rid}/heartbeat",
            json={"stats": stats}, timeout=5,
        )
        assert hb.status_code == 200, hb.text
    return rid


def _router_cluster(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from devcluster import DevCluster

    cluster = DevCluster(
        tmp_path, agents=0, master_args=["--serve-replica-timeout-sec", "60"]
    )
    cluster.start_master()
    return cluster


@pytest.mark.devcluster
def test_route_picks_least_loaded_replica(tmp_path):
    """With no affinity key the router picks by load — queue depth plus
    KV utilization from the last heartbeat — and stamps the winning
    replica id on X-DTPU-Replica."""
    cluster = _router_cluster(tmp_path)
    busy, idle = _FakeReplica("busy"), _FakeReplica("idle")
    try:
        _route_register(cluster, busy.url, {
            "queue_depth": 3, "queue_capacity": 8, "kv_utilization": 0.9})
        rid_idle = _route_register(cluster, idle.url, {
            "queue_depth": 0, "queue_capacity": 8, "kv_utilization": 0.1})
        for _ in range(3):
            r = cluster.http.post(cluster.url + "/v1/generate",
                                  json={}, timeout=10)
            assert r.status_code == 200, r.text
            assert r.json()["replica"] == "idle"
            assert r.headers["X-DTPU-Replica"] == rid_idle
        assert busy.hits == 0 and idle.hits == 3
        # inflight bookkeeping drains back to zero after each response
        listing = cluster.http.get(cluster.url + "/api/v1/serving",
                                   timeout=5).json()
        assert all(x["inflight"] == 0 for x in listing), listing
    finally:
        busy.close()
        idle.close()
        cluster.stop()


@pytest.mark.devcluster
def test_route_sticky_session_survives_replica_death(tmp_path):
    """A session key pins to one replica (consistent-hash ring); when that
    replica dies, ONLY its keys move — a key on a surviving replica stays
    put, and the moved key lands consistently on one survivor."""
    cluster = _router_cluster(tmp_path)
    reps = [_FakeReplica(f"r{i}") for i in range(3)]
    stats = {"queue_depth": 0, "queue_capacity": 8, "kv_utilization": 0.0}
    try:
        rids = [_route_register(cluster, rep.url, stats) for rep in reps]

        def route_of(session):
            r = cluster.http.post(cluster.url + "/v1/generate",
                                  json={"session": session}, timeout=10)
            assert r.status_code == 200, r.text
            return r.headers["X-DTPU-Replica"]

        # stickiness: the same key routes to the same replica every time
        first = route_of("user-0")
        assert all(route_of("user-0") == first for _ in range(4))

        # find a key owned by a DIFFERENT replica (3 replicas x 40 vnodes:
        # a handful of keys is plenty to land on two distinct owners)
        other_key, other_rid = None, None
        for i in range(1, 64):
            rid = route_of(f"user-{i}")
            if rid != first:
                other_key, other_rid = f"user-{i}", rid
                break
        assert other_key is not None, "all keys hashed to one replica"

        # kill the first key's replica (failed heartbeat -> immediate reap)
        hb = cluster.http.post(
            cluster.url + f"/api/v1/serving/replicas/{first}/heartbeat",
            json={"stats": {"failed": "SIGKILL"}}, timeout=5,
        )
        assert hb.json().get("reaped") is True, hb.text
        reps[rids.index(first)].close()

        # the surviving key did not move...
        assert route_of(other_key) == other_rid
        # ...and the orphaned key re-pins consistently to one survivor
        new_home = route_of("user-0")
        assert new_home != first and new_home in rids
        assert all(route_of("user-0") == new_home for _ in range(4))
    finally:
        for rep in reps:
            rep.close()
        cluster.stop()


@pytest.mark.devcluster
def test_route_503_when_fleet_saturated_or_empty(tmp_path):
    """No replicas, or every replica at queue capacity, answers 503 with
    Retry-After — the client backs off instead of queueing blind."""
    cluster = _router_cluster(tmp_path)
    rep = _FakeReplica("full")
    try:
        r = cluster.http.post(cluster.url + "/v1/generate", json={},
                              timeout=10)
        assert r.status_code == 503 and "Retry-After" in r.headers

        _route_register(cluster, rep.url, {
            "queue_depth": 8, "queue_capacity": 8, "kv_utilization": 0.5})
        # saturated even for the sticky path: affinity yields to capacity
        r = cluster.http.post(cluster.url + "/v1/generate",
                              json={"session": "s"}, timeout=10)
        assert r.status_code == 503 and "Retry-After" in r.headers
        assert rep.hits == 0
    finally:
        rep.close()
        cluster.stop()


@pytest.mark.devcluster
def test_route_fails_over_dead_and_shedding_replicas(tmp_path):
    """The best-ranked replica being unreachable (crash window before the
    reaper fires) or shedding 429 does not surface to the client: the
    router walks down the candidate list and returns the first success."""
    cluster = _router_cluster(tmp_path)
    shedding, healthy = _FakeReplica("shed", status=429), _FakeReplica("ok")
    try:
        # ranked first (load 0) but the port is dead: connection refused
        _route_register(cluster, "http://127.0.0.1:1/x", {
            "queue_depth": 0, "queue_capacity": 8, "kv_utilization": 0.0})
        # ranked second, answers 429
        _route_register(cluster, shedding.url, {
            "queue_depth": 1, "queue_capacity": 8, "kv_utilization": 0.0})
        rid_ok = _route_register(cluster, healthy.url, {
            "queue_depth": 2, "queue_capacity": 8, "kv_utilization": 0.0})
        r = cluster.http.post(cluster.url + "/v1/generate", json={},
                              timeout=15)
        assert r.status_code == 200, r.text
        assert r.json()["replica"] == "ok"
        assert r.headers["X-DTPU-Replica"] == rid_ok
        assert shedding.hits == 1 and healthy.hits == 1
    finally:
        shedding.close()
        healthy.close()
        cluster.stop()
