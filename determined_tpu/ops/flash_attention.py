"""Flash attention: Pallas TPU kernel, forward + custom-VJP backward.

Blockwise softmax attention (FlashAttention-2 style) tiled for the MXU:
O(seq) memory, no [Sq, Sk] materialization.  f32 accumulation in VMEM
scratch regardless of input dtype (bf16 inputs recommended).

Layout: q [b, h, Sq, d]; k, v [b, h_kv, Sk, d] (GQA: h_kv divides h —
expanded in the wrapper, gradients re-reduced over the group).

Grid: (batch, heads, q_blocks, k_blocks), k innermost; running (m, l, acc)
live in VMEM scratch across the k sweep.  A BLOCK is what one grid step
holds: ``block_q`` queries against ``block_k`` keys (1,024 x 1,024).  What a
block holds depends only on its OFFSET, its first query's position less its
first key's (``_visible``), and it is one of three kinds:

- *no visible pair* (above the diagonal, or wholly behind a window): the
  step does nothing;
- *interior*, every pair visible: one product, the scores used as they come
  (no iota, no compare, no select);
- *crossed* by the diagonal, a window's trailing edge, or both: judged in
  SUB-TILES of ``SUB_TILE x SUB_TILE`` and worked in bands of ``SUB_TILE``
  queries (keys, in the dk/dv sweep): of a band, the sub-tiles that hold a
  visible pair lie side by side and are one masked product, the others are
  not computed (``_pieces``).  A grid has few crossed offsets (0 for square
  blocks; a window's edge adds one or two), so a kernel tells them apart by
  ``pl.when(offset == d)`` and knows each one's rectangles, and its mask, as
  it is traced (``_crossed_offsets``, ``_visit``).

``block_work`` counts what that computes beside what is visible
(docs/training.md has the table of the training cells' layers).

``window`` (causal only): query i also sees no key j with ``i - j >=
window``.  The inner grid dimension then spans only the blocks a window can
touch (``_inner_span``), starting at the first live block of each outer
block (``_k_first`` / ``_q_first``): a window layer costs what its window
covers, not the sequence.  ``causal=False`` makes every block interior.

The two functions that call the kernels (``_fwd_program``, ``_bwd_program``)
are ``jax.jit(..., inline=True)`` with everything a trace depends on as a
static argument: a process traces a configuration's kernels once, a model's
layers of one kind and every pass over them share the jaxpr, and inlined it
is the program it would be without the ``jit``.

On non-TPU backends the kernel runs in interpreter mode (tests on the
8-device CPU mesh exercise the exact same code path).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from determined_tpu.ops import kernel_form

NEG_INF = -1e30
# Measured on v5e (hd=128, bf16): 1024-blocks run the fwd+bwd sweep ~3.7x
# faster than 128-blocks (36 vs 10 TFLOP/s at seq 1k, 49 vs 12 at seq 4k) —
# fewer grid steps amortize the VMEM (m,l,acc) rescale between MXU calls,
# and [1024,1024] logit tiles still fit VMEM comfortably.  Under a window of
# 1,024 at seq 8k too: 512-blocks compute 1.5 x visible where whole
# 1024-blocks compute 2, and take 8.82 ms for 8.54 (fwd + dq + dkv, [1, 32,
# 8192, 128]): the grid step costs more than the pairs it saves.
DEFAULT_BLOCK = 1024
# The grain at which a crossed block skips: the least the lanes allow, and
# the fastest measured.  fwd + dq + dkv of one call, ms, bf16, kernel alone
# on a v5e, sub-tiles of 1,024 (none) -> 512 -> 256 -> 128: [1, 32, 8192,
# 128] under a window of 1,024 8.54 -> 6.58 -> 5.92 -> 5.47; the same shape
# full 19.48 -> 18.21 -> 17.95 -> 17.71; [4, 32, 4096, 128] 22.53 -> 20.40
# -> 19.86 -> 19.33; [3, 8, 8192, 128] 14.33 -> 13.41 -> 13.19 -> 13.03
# (PERF.md section 5, "PR 52").  The smallest wins only while a band's
# sub-tiles are ONE product and the bands' phases are interleaved
# (``_fwd_kernel``): a product, a row reduction and a ``pl.when`` a sub-tile
# cost more than the pairs they save.
SUB_TILE = 128

# The softmax runs in log2 space: the qk dot is scaled by scale*log2(e)
# once (MXU output epilogue) and every exp becomes a native exp2 — on TPU
# `exp` lowers to exp2 + a per-element multiply, so log2 space deletes one
# VPU multiply per logit from the kernel's bound resource (the VPU).  The
# stored lse is base-2 (m + log2 l), consumed only by the bwd kernels.
LOG2E = 1.4426950408889634


def _pick_block(seq: int, want: int) -> int:
    block = min(want, seq)
    while seq % block:
        block //= 2
    return max(block, 1)


def _k_first(qi, block_q: int, block_k: int, window: int):
    """First k block that query block ``qi`` sees under ``window``."""
    return jnp.maximum(qi * block_q - window + 1, 0) // block_k


def _q_first(ki, block_q: int, block_k: int):
    """First q block that (causally) sees k block ``ki``."""
    return (ki * block_k) // block_q


def _inner_span(nq: int, nk: int, block_q: int, block_k: int, window: int) -> Tuple[int, int]:
    """Most k blocks one q block touches, and most q blocks one k block is
    touched by, under causal + ``window``: the inner grid sizes."""
    k_span = max(
        min((qi * block_q + block_q - 1) // block_k, nk - 1)
        - max(qi * block_q - window + 1, 0) // block_k + 1
        for qi in range(nq)
    )
    q_span = max(
        min((ki * block_k + block_k - 2 + window) // block_q, nq - 1)
        - (ki * block_k) // block_q + 1
        for ki in range(nk)
    )
    return k_span, q_span


def _visible(offset, q_n: int, k_n: int, causal: bool, window: Optional[int]):
    """``(some, every)``: whether ``q_n`` queries against ``k_n`` keys, the
    first query ``offset`` positions after the first key, hold a visible
    pair, and whether every pair of them is visible.  The one rule for
    blocks and sub-tiles alike; Python bools where ``offset`` is an int."""
    if not causal:
        return True, True
    some = offset + q_n - 1 >= 0          # the last query is not before the first key
    every = offset - (k_n - 1) >= 0       # nor the first query before the last key
    if window is not None:
        some &= offset - (k_n - 1) < window   # the first query still reaches the last key
        every &= offset + q_n - 1 < window    # and the last query the first key
    return some, every


Piece = Tuple[int, int, int, int, bool]   # row, rows, col, cols, masked


def _pieces(
    offset: int, block_q: int, block_k: int, causal: bool, window: Optional[int], tile: int,
    tall: bool = False,
) -> Sequence[Piece]:
    """What of a block is computed, the block's first query ``offset``
    positions after its first key, as rectangles ``(row, rows, col, cols,
    masked)``, one product each.  An interior block is one unmasked
    rectangle.  A crossed block is judged in sub-tiles of ``tile x tile``
    and worked in bands of ``tile`` queries: the sub-tiles of a band that
    hold a visible pair lie side by side and are one rectangle, masked if an
    edge crosses any of them; the others are left out.  A side of the block
    that ``tile`` does not divide (a sequence of 320 is one block of 320) is
    one sub-tile whole: every rectangle lies inside the block.  ``tall``:
    bands of ``tile`` keys against the query rows that see them (the dk/dv
    sweep accumulates a key).  A block with no visible pair gives nothing."""
    some, every = _visible(offset, block_q, block_k, causal, window)
    if every:
        return [(0, block_q, 0, block_k, False)]
    tq, tk = (block if block % tile else tile for block in (block_q, block_k))
    (n_band, t_band), (n_run, t_run) = ((block_k, tk), (block_q, tq)) if tall else ((block_q, tq), (block_k, tk))
    pieces = []
    for a in range(0, n_band, t_band) if some else ():
        live = []
        for c in range(0, n_run, t_run):
            row, col = (c, a) if tall else (a, c)
            t_some, t_every = _visible(offset + row - col, tq, tk, causal, window)
            if t_some:
                live.append((c, t_every))
        if live:
            c, n = live[0][0], live[-1][0] + t_run - live[0][0]
            masked = not all(t_every for _, t_every in live)
            pieces.append((c, n, a, t_band, masked) if tall else (a, t_band, c, n, masked))
    return pieces


def _crossed_offsets(nq: int, nk: int, block_q: int, block_k: int, causal: bool, window: Optional[int]) -> Tuple[int, ...]:
    """The offsets (first query - first key) of the grid's blocks that an
    edge crosses: what a block holds depends on its offset alone, so a
    kernel tells its crossed blocks apart by these few values (0 for square
    blocks; under a window the trailing edge's one or two besides) and knows
    each one's sub-tiles as it is traced."""
    def crossed(d: int) -> bool:
        some, every = _visible(d, block_q, block_k, causal, window)
        return some and not every

    return tuple(sorted(filter(crossed, {qi * block_q - ki * block_k for qi in range(nq) for ki in range(nk)})))


def _visit(
    offset, crossed: Sequence[int], block_q: int, block_k: int, causal: bool, window: Optional[int], tile: int,
    work, tall: bool = False,
) -> None:
    """``work(d, pieces)`` once for the block at ``offset`` (a scalar of the
    kernel): the interior form, or the form of the one of ``crossed`` it is
    (``d``, the offset as an int), or not at all."""
    every = _visible(offset, block_q, block_k, causal, window)[1]
    interior = [(0, block_q, 0, block_k, False)]
    if every is True:                     # no mask at all: every block is interior
        return work(0, interior)
    pl.when(every)(functools.partial(work, 0, interior))
    for d in crossed:
        pl.when(offset == d)(functools.partial(work, d, _pieces(d, block_q, block_k, causal, window, tile, tall)))


def block_work(seq: int, block_q: int, block_k: int, window: Optional[int], tile: int) -> Tuple[int, int]:
    """``(computed, visible)`` (query, key) pairs of one head of causal
    self-attention over ``seq`` tokens: what the kernels compute at these
    block and sub-tile sizes (``_pieces`` of every block), and what the
    mask lets through."""
    computed = sum(
        rows * cols
        for q_lo in range(0, seq, block_q) for k_lo in range(0, seq, block_k)
        for _, rows, _, cols, _ in _pieces(q_lo - k_lo, block_q, block_k, True, window, tile)
    )
    return computed, sum(min(i + 1, window or seq) for i in range(seq))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _scores(q, k, scale, offset: int, window: Optional[int], masked: bool, keys_first: bool = False):
    """qk dot in log2 space (scale*log2e folded into the MXU epilogue) and,
    where ``masked``, the causal (and window) mask of queries whose first is
    ``offset`` positions after the first key.  Shared by the fwd and both
    bwd kernels so the three stay bit-identical on the p they reconstruct;
    ``keys_first`` gives the same numbers as [tk, tq]."""
    a, b = (k, q) if keys_first else (q, k)
    s2 = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32) * (scale * LOG2E)  # f32, log2 units
    if masked:
        q_pos = offset + jax.lax.broadcasted_iota(jnp.int32, s2.shape, int(keys_first))
        k_pos = jax.lax.broadcasted_iota(jnp.int32, s2.shape, 1 - int(keys_first))
        seen = q_pos >= k_pos
        if window is not None:
            seen &= q_pos - k_pos < window
        s2 = jnp.where(seen, s2, NEG_INF)
    return s2


def _all_scores(q_ref, k_ref, d: int, pieces: Sequence[Piece], scale, window, keys_first: bool = False):
    """``(rs, ks, scores)`` of a block's rectangles: each one's rows of q, its
    rows of k, and its scores (the block's first query ``d`` after its first
    key), the products side by side (a phase at a time: ``_fwd_kernel``)."""
    rs = [pl.ds(a, rows) for a, rows, *_ in pieces]
    ks = [pl.ds(c, cols) for _, _, c, cols, _ in pieces]
    scores = [
        _scores(q_ref[0, 0, r, :], k_ref[0, 0, k, :], scale, d + a - c, window, masked, keys_first)
        for r, k, (a, _, c, _, masked) in zip(rs, ks, pieces)
    ]                                                 # [tq, tk] f32 each ([tk, tq] keys first)
    return rs, ks, scores


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    window: Optional[int], tile: int, crossed: Sequence[int],
):
    qi, step = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    # under a window the sweep starts at the first block the window reaches
    ki = step if window is None else _k_first(qi, block_q, block_k, window) + step

    @pl.when(step == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    def _compute(d, pieces):
        # MXU inputs stay in the INPUT dtype (bf16 in production: ~4x the
        # f32 matmul throughput on v5e) with f32 accumulation; only the
        # softmax running stats are f32.  f32 inputs (tests/debug) keep
        # full f32 matmuls, so tight-tolerance checks still hold.
        #
        # A phase at a time over ALL the block's bands, not a band at a
        # time: Mosaic keeps the order it is given, and a band's chain
        # (product, row max, exp2, product) leaves the MXU idle while the
        # VPU works and the other way round; the bands share no row, so
        # one's products stand beside another's softmax.
        rs, ks, scores = _all_scores(q_ref, k_ref, d, pieces, scale, window)
        # a row that sees no key of its FIRST computed rectangle reads
        # m = NEG_INF and p = 1 there; alpha = 0 wipes that when its first
        # visible key arrives (its own, at the latest)
        m_prev = [m_sc[r, :] for r in rs]
        m_new = [jnp.maximum(m, jnp.max(s2, axis=1, keepdims=True)) for m, s2 in zip(m_prev, scores)]  # [tq, 1]
        ps = [jnp.exp2(s2 - m) for s2, m in zip(scores, m_new)]
        alphas = [jnp.exp2(m0 - m) for m0, m in zip(m_prev, m_new)]
        pvs = [
            jax.lax.dot_general(p.astype(q_ref.dtype), v_ref[0, 0, k, :], _NN, preferred_element_type=jnp.float32)
            for p, k in zip(ps, ks)
        ]                                             # [tq, d] f32 each
        for r, m, p, alpha, pv in zip(rs, m_new, ps, alphas, pvs):
            l_sc[r, :] = alpha * l_sc[r, :] + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[r, :] = acc_sc[r, :] * alpha + pv
            m_sc[r, :] = m

    _visit(qi * block_q - ki * block_k, crossed, block_q, block_k, causal, window, tile, _compute)

    @pl.when(step == nk - 1)
    def _final():
        l = jnp.maximum(l_sc[:], 1e-30)
        o_ref[0, 0] = (acc_sc[:] / l).astype(o_ref.dtype)
        # lse is laid out [b, h, 1, sq] so the block's last dim is the
        # 128-aligned seq dim (TPU block-shape constraint)
        lse_ref[0, 0] = (m_sc[:] + jnp.log2(l))[:, 0][None, :]


def _fwd_kernel_single(
    q_ref, k_ref, v_ref, o_ref, lse_ref,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    window: Optional[int], tile: int,
):
    """One block holds the whole of q and of k: the online-softmax machinery
    (running m/l scratch, acc rescale, the init and final grid phases) is
    pure VPU overhead, and a plain one-pass softmax a band does the same
    math with none of it.  The shape of a sequence of one block
    (examples/transformer_lm/const.yaml: 1,024 tokens)."""
    pieces = _pieces(0, block_q, block_k, causal, window, tile)
    rs, ks, scores = _all_scores(q_ref, k_ref, 0, pieces, scale, window)
    ms = [jnp.max(s2, axis=1, keepdims=True) for s2 in scores]   # [tq, 1]
    ps = [jnp.exp2(s2 - m) for s2, m in zip(scores, ms)]         # [tq, tk] f32
    accs = [
        jax.lax.dot_general(p.astype(q_ref.dtype), v_ref[0, 0, k, :], _NN, preferred_element_type=jnp.float32)
        for p, k in zip(ps, ks)
    ]
    for r, m, p, acc in zip(rs, ms, ps, accs):
        l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
        o_ref[0, 0, r, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, r] = (m + jnp.log2(l))[:, 0][None, :]


def _windowed(window: Optional[int], name: str) -> dict:
    """``pallas_call`` arguments of the window kernels only: a name of their
    own in the trace.  The kernels without a window keep the name (none)
    that the benchmark's patterns know them by."""
    return {} if window is None else {"name": name}


def _k_spec(block_q: int, block_k: int, d: int, nk: int, window: Optional[int]) -> pl.BlockSpec:
    """K (or V) blocks for a (b, h, q block, step) grid: block ``step``, or
    under a window the ``step``-th from the first block the window reaches
    (clamped: a step past the diagonal does nothing and copies nothing new)."""
    if window is None:
        return pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi, ki, 0))
    return pl.BlockSpec(
        (1, 1, block_k, d),
        lambda bi, hi, qi, ki: (
            bi, hi, jnp.minimum(_k_first(qi, block_q, block_k, window) + ki, nk - 1), 0
        ),
    )


def _flash_fwd_call(
    q: jax.Array, k: jax.Array, v: jax.Array, scale: float, causal: bool,
    block_q: int, block_k: int, window: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    return _fwd_program(q, k, v, scale, causal, block_q, block_k, window, SUB_TILE, kernel_form.interpreted_off_chip())


# One trace a configuration, whoever asks and how often: a model's layers of
# one kind, the Trainer's abstract pass over the loss, the step and the
# benchmark's check all take the same jaxpr (a crossed block's bands make a
# kernel's body long to trace: seconds of every start, traced a layer a
# pass).  ``inline``: the jaxpr is copied into the caller's, so the program
# is the one it would be without the ``jit``; as a call of its own the
# Mosaic calls are named after this function and lose the scopes round
# them, which is what readers of a step's trace find them by.  What a trace
# depends on is an argument: the sub-tile and the interpreter are read by
# the caller, as each call is made.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9), inline=True)
def _fwd_program(q, k, v, scale, causal, block_q, block_k, window, tile, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    grid = (b, h, nq, nk)
    single = nq == 1 and nk == 1
    kernel = functools.partial(
        _fwd_kernel_single if single else _fwd_kernel,
        scale=scale, causal=causal, block_q=block_q, block_k=block_k, window=window, tile=tile,
        **({} if single else {"crossed": _crossed_offsets(nq, nk, block_q, block_k, causal, window)}),
    )
    if window is not None:
        # k blocks from the window's first, as many as a window can touch
        grid = (b, h, nq, _inner_span(nq, nk, block_q, block_k, window)[0])
    kspec = _k_spec(block_q, block_k, d, nk, window)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            kspec,
            kspec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
        ],
        scratch_shapes=[] if single else [
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        **_windowed(window, "flash_window_fwd"),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    window: Optional[int], tile: int, crossed: Sequence[int],
):
    qi, step = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    ki = step if window is None else _k_first(qi, block_q, block_k, window) + step

    @pl.when(step == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def _compute(d, pieces):
        # bf16 MXU inputs, f32 accumulation; a phase at a time (see _fwd_kernel)
        lse = lse_ref[0, 0].reshape(-1, 1)            # [bq, 1], log2 units
        delta = delta_ref[0, 0].reshape(-1, 1)        # [bq, 1]
        rs, ks, scores = _all_scores(q_ref, k_ref, d, pieces, scale, window)
        dps = [
            jax.lax.dot_general(do_ref[0, 0, r, :], v_ref[0, 0, k, :], _NT, preferred_element_type=jnp.float32)
            for r, k in zip(rs, ks)
        ]                                             # [tq, tk] f32 each
        # the probabilities rebuilt from the stored lse: 0 where hidden,
        # exp2(NEG_INF - lse)
        dss = [
            jnp.exp2(s2 - lse[a:a + rows]) * (dp - delta[a:a + rows]) * scale
            for s2, dp, (a, rows, *_) in zip(scores, dps, pieces)
        ]
        for r, k, ds in zip(rs, ks, dss):
            dq_sc[r, :] += jax.lax.dot_general(
                ds.astype(q_ref.dtype), k_ref[0, 0, k, :], _NN, preferred_element_type=jnp.float32
            )

    _visit(qi * block_q - ki * block_k, crossed, block_q, block_k, causal, window, tile, _compute)

    @pl.when(step == nk - 1)
    def _final():
        dq_ref[0, 0] = dq_sc[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_sc, dv_sc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    window: Optional[int], tile: int, crossed: Sequence[int], n_q_blocks: int,
):
    ki, step = pl.program_id(2), pl.program_id(3)     # NOTE: q innermost here
    nq = pl.num_programs(3)
    # under a window the sweep starts at the first q block that sees this k block
    qi = step if window is None else _q_first(ki, block_q, block_k) + step

    @pl.when(step == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def _compute(d, pieces):
        # bf16 MXU inputs, f32 accumulation; a phase at a time (see
        # _fwd_kernel).  Everything is held keys first, [tk, tq]: dk and dv
        # are then plain products (p.T @ do by a contraction over rows
        # costs a transpose of every tall rectangle), and lse and delta are
        # used as they lie, a row along the lanes.
        rs, ks, scores = _all_scores(q_ref, k_ref, d, pieces, scale, window, keys_first=True)
        dps = [
            jax.lax.dot_general(v_ref[0, 0, k, :], do_ref[0, 0, r, :], _NT, preferred_element_type=jnp.float32)
            for r, k in zip(rs, ks)
        ]
        ps = [jnp.exp2(s2 - lse_ref[0, 0, :, r]) for s2, r in zip(scores, rs)]   # 0 where hidden
        dss = [p * (dp - delta_ref[0, 0, :, r]) * scale for p, dp, r in zip(ps, dps, rs)]
        for r, k, p, ds in zip(rs, ks, ps, dss):
            dv_sc[k, :] += jax.lax.dot_general(
                p.astype(q_ref.dtype), do_ref[0, 0, r, :], _NN, preferred_element_type=jnp.float32
            )                                         # [tk, d]
            dk_sc[k, :] += jax.lax.dot_general(
                ds.astype(q_ref.dtype), q_ref[0, 0, r, :], _NN, preferred_element_type=jnp.float32
            )                                         # [tk, d]

    def _sweep():
        _visit(qi * block_q - ki * block_k, crossed, block_q, block_k, causal, window, tile, _compute, tall=True)

    if window is None:
        _sweep()
    else:
        # a step past the sequence's end (its blocks are clamped) does nothing
        pl.when(qi < n_q_blocks)(_sweep)

    @pl.when(step == nq - 1)
    def _final():
        dk_ref[0, 0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)


def _flash_bwd_call(
    q, k, v, do, out, lse, scale, causal, block_q, block_k, window=None
):
    return _bwd_program(q, k, v, do, out, lse, scale, causal, block_q, block_k, window, SUB_TILE, kernel_form.interpreted_off_chip())


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12), inline=True)   # as _fwd_program
def _bwd_program(q, k, v, do, out, lse, scale, causal, block_q, block_k, window, tile, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    # delta = rowsum(do * out): tiny elementwise+reduce, XLA fuses it
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[
        :, :, None, :
    ]  # [b, h, 1, sq] — same layout as lse

    qspec = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kspec = _k_spec(block_q, block_k, d, nk, window)
    rowq = pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi))
    k_steps, q_steps = (nk, nq) if window is None else _inner_span(nq, nk, block_q, block_k, window)
    blocks = dict(
        scale=scale, causal=causal, block_q=block_q, block_k=block_k, window=window, tile=tile,
        crossed=_crossed_offsets(nq, nk, block_q, block_k, causal, window),
    )

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **blocks),
        grid=(b, h, nq, k_steps),
        in_specs=[qspec, kspec, kspec, qspec, rowq, rowq],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        **_windowed(window, "flash_window_dq"),
    )(q, k, v, do, lse, delta)

    # dkv sweep: swap loop nest — k blocks outer, q inner
    qspec2 = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, ki, qi: (bi, hi, qi, 0))
    kspec2 = pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki, qi: (bi, hi, ki, 0))
    rowq2 = pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, ki, qi: (bi, hi, 0, qi))
    if window is not None:

        def q_block(ki, step):
            return jnp.minimum(_q_first(ki, block_q, block_k) + step, nq - 1)

        qspec2 = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, ki, qi: (bi, hi, q_block(ki, qi), 0))
        rowq2 = pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, ki, qi: (bi, hi, 0, q_block(ki, qi)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q_blocks=nq, **blocks),
        grid=(b, h, nk, q_steps),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowq2, rowq2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        **_windowed(window, "flash_window_dkv"),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, window):
    out, _ = _flash_fwd_call(q, k, v, scale, causal, block_q, block_k, window)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, window):
    out, lse = _flash_fwd_call(q, k, v, scale, causal, block_q, block_k, window)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, window, res, g):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd_call(
        q, k, v, g, out, lse, scale, causal, block_q, block_k, window
    )
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    window: Optional[int] = None,
) -> jax.Array:
    """Blockwise flash attention; differentiable; GQA-aware.  ``window``:
    query i sees keys ``i - window < j <= i`` (causal only); a window that
    covers the whole sequence is no window."""
    b, h, sq, d = q.shape
    if window is not None:
        if not causal or window < 1 or sq != k.shape[2]:
            raise ValueError("a window needs causal self-attention and window >= 1")
        if window >= sq:
            window = None
    hkv, sk = k.shape[1], k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    from determined_tpu.ops.attention import _repeat_kv

    n_rep = h // hkv
    # expand kv for the kernel; group-sum of dk/dv happens automatically
    # through the broadcast's transpose in autodiff
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    return _flash(q, k, v, scale, causal, block_q, block_k, window)
