"""One generator for every traffic mix: a mix is a data file of parameters.

The seed PERMUTES, it never resamples.  A length distribution is turned
into a fixed list of quantile values, an arrival process into the quantiles
of its gap distribution, and ``--seed`` chooses the token contents, the
sampling seeds, which prefix is shared and, in a file with no
``balance_over_requests``, the order.  So every seed offers the same number
of requests, prompt tokens and output tokens: a run's work does not depend
on its seed (PR 22 resampled, and one cell's tokens/s then spread by 5.8 %
on one program).

Kinds (``"kind"`` in the file): ``serve-open`` (arrivals on a schedule),
``serve-closed`` (one client per lane, next request when the last one
finished) and ``train`` (packed sequences; see ``train_run.py``).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def quantile_values(dist: Dict[str, Any], n: int) -> List[int]:
    """``n`` whole numbers at the mid-quantiles ``(i + 0.5) / n`` of ``dist``.

    Shapes: ``fixed`` (value), ``uniform`` (min, max), ``lognormal``
    (median, sigma; clipped to min..max: a heavy right tail).
    """
    shape = dist["shape"]
    qs = [(i + 0.5) / n for i in range(n)]
    if shape == "fixed":
        vals = [float(dist["value"])] * n
    elif shape == "uniform":
        lo, hi = float(dist["min"]), float(dist["max"])
        vals = [lo + (hi - lo) * q for q in qs]
    elif shape == "lognormal":
        mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
        nd = NormalDist()
        vals = [math.exp(mu + sigma * nd.inv_cdf(q)) for q in qs]
        vals = [min(max(v, float(dist["min"])), float(dist["max"])) for v in vals]
    else:
        raise ValueError(f"unknown length shape {shape!r}")
    return [max(1, int(round(v))) for v in vals]


def exponential_gaps(rate: float, n: int) -> List[float]:
    """The ``n`` mid-quantiles of the exponential gap at ``rate`` a second,
    scaled so that they sum to exactly ``n / rate`` seconds."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]


def _order_key(traffic: Dict[str, Any], key: List[int]) -> List[int]:
    """What the ORDER of lengths and gaps is drawn from: the seed, or, in a
    file that balances its order (``balance_over_requests``), nothing but the
    stretch's number.  Every seed then offers ONE schedule of arrivals and
    lengths and chooses token contents, sampling seeds and which prefix is
    shared.  For an open loop near its knee the order is work: which requests
    overlap sets how many lanes are busy, and a step's time grows with the
    busy lanes.  A chat cell's median gap between tokens spread by 11-13 %
    between seeds under a free order and by 5 % under a balanced order drawn
    from the seed, while two runs of one seed agreed within 1 % (my chip
    runs, PR 23): the seed was changing the work.  Such a cell measures one
    schedule, and says so in its ``why``."""
    return [0, *key[1:]] if traffic.get("balance_over_requests") else key


def seeded_order(n: int, rng: np.random.Generator, group: int = 0) -> List[int]:
    """An order of the indices ``0..n-1`` of a sorted quantile list.

    ``group`` 0: any permutation (the closed loop: every lane is busy
    whatever the order).  ``group`` g: balanced, so that every g consecutive
    places hold one value from each of g bands of the list (``G = ceil(n /
    g)`` groups, the bands dealt to them to and fro; ``rng`` orders the
    groups and the members of each).  Sums over any g consecutive values are
    then nearly alike.
    """
    if not group or group >= n:
        return [int(i) for i in rng.permutation(n)]
    g_count = math.ceil(n / group)
    out: List[int] = []
    for g in rng.permutation(g_count):
        # band j holds indices j*G .. j*G + G - 1; the bands are dealt to the
        # groups to and fro, so that no group gets the top of every band
        members = [
            j * g_count + (int(g) if j % 2 == 0 else g_count - 1 - int(g))
            for j in range(math.ceil(n / g_count))
        ]
        members = [m for m in members if m < n]
        out += [members[int(i)] for i in rng.permutation(len(members))]
    return out


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int
    due: float = 0.0            # seconds from the schedule's start (open loop)
    seed: int = 0               # the request's own sampling seed
    shared_prefix: int = -1     # which shared prefix opens the prompt, -1 none


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab, size=n, dtype=np.int64).tolist()


def _requests(
    traffic: Dict[str, Any], n: int, seed: Any, vocab: int
) -> List[Request]:
    """``n`` requests: the quantile lists of both lengths, each in an order
    of its own drawn from the seed, with seeded contents.

    Which requests open with a shared prefix is decided on the quantile
    list, evenly over the prompt lengths, BEFORE the seed permutes it: a
    prompt shorter than the prefix grows to prefix + 16 tokens, so choosing
    them by the seed would change the prompt tokens offered.
    """
    key = [int(k) for k in np.atleast_1d(seed)]
    rng = np.random.default_rng([*key, 0x5EED])
    order_rng = np.random.default_rng([*_order_key(traffic, key), 0x0DE4])
    shared = traffic.get("shared_prefix")
    prompts = quantile_values(traffic["prompt_tokens"], n)
    flags = [False] * n
    if shared:
        share, plen = float(shared["share"]), int(shared["tokens"])
        flags = [math.floor((i + 1) * share) > math.floor(i * share) for i in range(n)]
        prompts = [max(p, plen + 16) if f else p for p, f in zip(prompts, flags)]
    outputs = quantile_values(traffic["output_tokens"], n)
    group = int(traffic.get("balance_over_requests", 0))
    order = seeded_order(n, order_rng, group)
    prompts, flags = [prompts[i] for i in order], [flags[i] for i in order]
    outputs = [outputs[i] for i in seeded_order(n, order_rng, group)]
    prefixes = (
        [_tokens(rng, int(shared["tokens"]), vocab) for _ in range(int(shared["count"]))]
        if shared
        else []
    )
    out = []
    for i in range(n):
        body = _tokens(rng, prompts[i], vocab)
        which = -1
        if flags[i]:
            which = int(rng.integers(0, len(prefixes)))
            body = prefixes[which] + body[len(prefixes[which]):]
        out.append(
            Request(
                prompt=body,
                max_new_tokens=outputs[i],
                seed=int(rng.integers(0, 2**31 - 1)),
                shared_prefix=which,
            )
        )
    return out


@dataclasses.dataclass
class OpenSchedule:
    """An open loop's three stretches, with ``due`` in seconds from the
    schedule's start: the ramp that fills the lanes before the window, the
    window itself, and a tail that keeps the load up while the window's last
    requests get their first tokens."""

    ramp: List[Request]
    window: List[Request]
    tail: List[Request]
    window_start: float
    window_end: float


def open_schedule(
    traffic: Dict[str, Any], seed: int, vocab: int, seconds: float
) -> OpenSchedule:
    """Arrivals at the file's fixed ``rate_per_s``.  Each stretch holds
    ``round(length * rate)`` requests: the whole quantile lists of the
    lengths and of the exponential gap, scaled to fill the stretch exactly
    and put in an order drawn from the seed.  So the window offers the same
    requests, prompt tokens and output tokens whatever the seed."""
    rate = float(traffic["rate_per_s"])
    parts = []
    t = 0.0
    for k, length in enumerate(
        (float(traffic["ramp_s"]), float(seconds), float(traffic["tail_s"]))
    ):
        n = max(1, int(round(length * rate)))
        rng = np.random.default_rng([*_order_key(traffic, [int(seed)]), 0xA881, k])
        gaps = [g * (length * rate / n) for g in exponential_gaps(rate, n)]
        gaps = [gaps[i] for i in seeded_order(n, rng, int(traffic.get("balance_over_requests", 0)))]
        reqs = _requests(traffic, n, [int(seed), k], vocab)
        for req, gap in zip(reqs, gaps):
            t += gap
            req.due = t
        parts.append(reqs)
    ramp_s = float(traffic["ramp_s"])
    return OpenSchedule(
        ramp=parts[0], window=parts[1], tail=parts[2],
        window_start=ramp_s, window_end=ramp_s + float(seconds),
    )


@dataclasses.dataclass
class ClosedPlan:
    """Per client: its first request (already part-way through, see
    :func:`closed_plan`) and the requests it sends after it, in a cycle."""

    first: List[Request]
    later: List[List[Request]]


def closed_plan(traffic: Dict[str, Any], seed: int, vocab: int) -> ClosedPlan:
    """A closed loop of ``clients`` clients that starts in its steady state.

    With one output length, or with every client starting a whole request at
    once, all lanes finish in the same step and the engine then runs
    ``clients`` prefills back to back: whether such a wave falls inside the
    window is worth percents of it (PR 22).  A loop that has run for long has
    each lane at another point of its request.  So client ``i`` of ``c``
    starts with a request that is already ``1 - (i + 1) / c`` done: that part
    of its output is appended to its prompt as context (so positions and
    cache contents are those of a request in flight), and only the rest is
    generated.  From the first measured step the lanes then finish one at a
    time, evenly spread, and every later request runs whole.
    """
    clients = int(traffic["clients"])
    per_client = int(traffic["requests_per_client"])
    rng = np.random.default_rng([int(seed), 0xC105ED])
    # the first wave's sizes are fixed pairs of quantiles (strides coprime
    # with the client count decorrelate prompt, output and progress), so its
    # totals do not depend on the seed; the seed deals them to the clients
    prompts = quantile_values(traffic["prompt_tokens"], clients)
    outputs = quantile_values(traffic["output_tokens"], clients)
    sp, so = _coprime_stride(clients, 7), _coprime_stride(clients, 13)
    taken: set = set()
    wave = []
    for k in range(clients):
        out = outputs[(so * k + 5) % clients]
        left = min(out, max(2, int(round(out * (k + 1) / clients))))
        while left in taken:  # no two lanes finish in the same step
            left += 1
        taken.add(left)
        wave.append((prompts[(sp * k + 3) % clients], max(out, left) - left, left))
    first = []
    for k in rng.permutation(clients):
        prompt, done, left = wave[int(k)]
        first.append(
            Request(
                prompt=_tokens(rng, prompt + done, vocab),
                max_new_tokens=left,
                seed=int(rng.integers(0, 2**31 - 1)),
            )
        )
    reqs = _requests(traffic, clients * per_client, seed, vocab)
    later = [reqs[c * per_client:(c + 1) * per_client] for c in range(clients)]
    return ClosedPlan(first=first, later=later)


def _coprime_stride(n: int, start: int) -> int:
    k = start
    while math.gcd(k, n) != 1:
        k += 1
    return k


def with_new_contents(req: Request, seed: List[int], vocab: int) -> Request:
    """The same sizes with other token contents and another sampling seed."""
    rng = np.random.default_rng([*seed, 0x1A9])
    return dataclasses.replace(
        req, prompt=_tokens(rng, len(req.prompt), vocab), seed=int(rng.integers(0, 2**31 - 1))
    )


def totals(requests: List[Request]) -> Dict[str, int]:
    return {
        "requests": len(requests),
        "prompt_tokens": sum(len(r.prompt) for r in requests),
        "output_tokens": sum(r.max_new_tokens for r in requests),
    }
