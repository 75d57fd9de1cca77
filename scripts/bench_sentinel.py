"""Collective-sequence sentinel overhead microbenchmark.

Two numbers, so the sentinel's cost stays a TRACKED quantity instead of a
belief (BASELINE.md):

- ``digest_record_us``: cost of folding one (op, detail) signature into
  the per-rank rolling digest — the path the trainer hits once per hot
  segment (``step.segment``) and every wrapped collective hits once.
  This is a crc32 of a short string plus a bounded deque append.
- ``collective_overhead_us``: added latency per control-plane collective
  from the envelope piggyback + verification, measured as (wrapped −
  bare) allgather round-trip over a REAL 2-rank localhost star — the
  same transport the devcluster gangs use.  The envelope rides the
  collective that was already happening, so this is serialization +
  verify cost only, no extra round trips.

Run directly::

    python scripts/bench_sentinel.py [--rounds 400] [--records 50000]

One-line JSON on stdout, same contract as the other bench scripts.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bench_record(n: int) -> float:
    """Microseconds per digest record."""
    from determined_tpu.core import DummyDistributedContext
    from determined_tpu.lint import CollectiveSequenceSentinel

    sentinel = CollectiveSequenceSentinel()
    dist = DummyDistributedContext()
    t0 = time.perf_counter()
    for i in range(n):
        sentinel.record(dist, "step.segment", f"{i}-{i + 50}")
    return (time.perf_counter() - t0) / n * 1e6


def _bench_allgather(rounds: int, wrapped: bool) -> float:
    """Median microseconds per 2-rank allgather round."""
    from determined_tpu.lint import CollectiveSequenceSentinel
    from tests.parallel_utils import Execution

    def body(ctx, rank):
        # warm the lazy client connection before timing
        ctx.allgather("warm")
        samples = []
        for i in range(rounds):
            t0 = time.perf_counter()
            ctx.allgather(i)
            samples.append(time.perf_counter() - t0)
        return statistics.median(samples) * 1e6

    if wrapped:
        with CollectiveSequenceSentinel():
            per_rank = Execution(2, timeout=120).run(body)
    else:
        per_rank = Execution(2, timeout=120).run(body)
    return statistics.median(per_rank)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rounds", type=int, default=400,
                    help="timed allgather rounds per rank")
    ap.add_argument("--records", type=int, default=50_000,
                    help="digest records for the record-path number")
    args = ap.parse_args()

    record_us = _bench_record(args.records)
    bare_us = _bench_allgather(args.rounds, wrapped=False)
    wrapped_us = _bench_allgather(args.rounds, wrapped=True)
    overhead_us = max(wrapped_us - bare_us, 0.0)

    print(
        json.dumps(
            {
                "metric": "collective_sentinel_overhead",
                "value": round(overhead_us, 1),
                "unit": "us/collective",
                # the bare star round-trip is the baseline
                "vs_baseline": round(wrapped_us / bare_us, 3) if bare_us else None,
                "digest_record_us": round(record_us, 3),
                "allgather_bare_us": round(bare_us, 1),
                "allgather_wrapped_us": round(wrapped_us, 1),
                "rounds": args.rounds,
                "records": args.records,
            }
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
