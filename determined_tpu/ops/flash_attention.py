"""Flash attention: Pallas TPU kernel, forward + custom-VJP backward.

Blockwise softmax attention (FlashAttention-2 style) tiled for the MXU:
O(seq) memory, no [Sq, Sk] materialization.  f32 accumulation in VMEM
scratch regardless of input dtype (bf16 inputs recommended).

Layout: q [b, h, Sq, d]; k, v [b, h_kv, Sk, d] (GQA: h_kv divides h —
expanded in the wrapper, gradients re-reduced over the group).

Grid: (batch, heads, q_blocks, k_blocks), k innermost; running (m, l, acc)
live in VMEM scratch across the k sweep.  Causal blocks strictly above the
diagonal are skipped with ``pl.when`` (half the FLOPs at long seq).

``window`` (causal only): query i also sees no key j with ``i - j >=
window``.  The inner grid dimension then spans only the blocks a window can
touch (``_inner_span``), starting at the first live block of each outer
block (``_k_first`` / ``_q_first``): a window layer costs what its window
covers, not the sequence.  ``window=None`` is the program it always was.

On non-TPU backends the kernel runs in interpreter mode (tests on the
8-device CPU mesh exercise the exact same code path).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Measured on v5e (hd=128, bf16): 1024-blocks run the fwd+bwd sweep ~3.7x
# faster than 128-blocks (36 vs 10 TFLOP/s at seq 1k, 49 vs 12 at seq 4k) —
# fewer grid steps amortize the VMEM (m,l,acc) rescale between MXU calls,
# and [1024,1024] logit tiles still fit VMEM comfortably.
DEFAULT_BLOCK = 1024

# The softmax runs in log2 space: the qk dot is scaled by scale*log2(e)
# once (MXU output epilogue) and every exp becomes a native exp2 — on TPU
# `exp` lowers to exp2 + a per-element multiply, so log2 space deletes one
# VPU multiply per logit from the kernel's bound resource (the VPU).  The
# stored lse is base-2 (m + log2 l), consumed only by the bwd kernels.
LOG2E = 1.4426950408889634


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


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


def _live(qi, ki, causal: bool, window: Optional[int], block_q: int, block_k: int):
    """Whether block (qi, ki) holds any visible (query, key) pair."""
    if not causal:
        return True
    needed = ki * block_k <= qi * block_q + block_q - 1
    if window is not None:
        # not wholly before the window of the block's first query
        needed &= qi * block_q - (ki * block_k + block_k - 1) < window
    return needed


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _scores(q_ref, k_ref, qi, ki, scale, causal, block_q, block_k, window=None):
    """qk dot in log2 space (scale*log2e folded into the MXU epilogue) +
    causal (and window) mask.  Shared by the fwd and both bwd kernels so the
    three stay bit-identical on the p they reconstruct."""
    q = q_ref[0, 0]                                   # [bq, d]
    k = k_ref[0, 0]                                   # [bk, d]
    s2 = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (scale * LOG2E)                               # [bq, bk] f32, log2 units
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        seen = q_pos >= k_pos
        if window is not None:
            seen &= q_pos - k_pos < window
        s2 = jnp.where(seen, s2, NEG_INF)
    return s2


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    window: Optional[int] = None,
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

    # causal: process only blocks touching/below the diagonal (and, under a
    # window, not wholly before it)
    needed = _live(qi, ki, causal, window, block_q, block_k)

    @pl.when(needed)
    def _compute():
        # MXU inputs stay in the INPUT dtype (bf16 in production: ~4x the
        # f32 matmul throughput on v5e) with f32 accumulation; only the
        # softmax running stats are f32.  f32 inputs (tests/debug) keep
        # full f32 matmuls, so tight-tolerance checks still hold.
        q = q_ref[0, 0]                               # [bq, d]
        v = v_ref[0, 0]                               # [bk, d]
        s2 = _scores(q_ref, k_ref, qi, ki, scale, causal, block_q, block_k, window)
        m_prev, l_prev = m_sc[:], l_sc[:]
        m_cur = jnp.max(s2, axis=1, keepdims=True)    # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp2(s2 - m_new)                      # [bq, bk] f32
        alpha = jnp.exp2(m_prev - m_new)              # [bq, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[:] = acc_sc[:] * alpha + jax.lax.dot_general(
            p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[:] = m_new
        l_sc[:] = l_new

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
    window: Optional[int] = None,
):
    """nk == 1 specialization: the whole k sweep is one block, so the
    online-softmax machinery (running m/l scratch, acc rescale, the init
    and final grid phases) is pure VPU overhead — a plain one-pass softmax
    does the same math with none of it.  This is the hot shape: the
    flagship seq-1024 workload runs block 1024 (see DEFAULT_BLOCK note)."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    q = q_ref[0, 0]
    v = v_ref[0, 0]
    s2 = _scores(q_ref, k_ref, qi, ki, scale, causal, block_q, block_k, window)
    m = jnp.max(s2, axis=1, keepdims=True)            # [bq, 1]
    p = jnp.exp2(s2 - m)                              # [bq, bk] f32
    l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
    acc = jax.lax.dot_general(
        p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log2(l))[:, 0][None, :]


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
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    grid = (b, h, nq, nk)
    single = nk == 1
    kernel = functools.partial(
        _fwd_kernel_single if single else _fwd_kernel,
        scale=scale, causal=causal, block_q=block_q, block_k=block_k, window=window,
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
        interpret=_interpret(),
        **_windowed(window, "flash_window_fwd"),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    window: Optional[int] = None,
):
    qi, step = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    ki = step if window is None else _k_first(qi, block_q, block_k, window) + step

    @pl.when(step == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    needed = _live(qi, ki, causal, window, block_q, block_k)

    @pl.when(needed)
    def _compute():
        # bf16 MXU inputs, f32 accumulation (see _fwd_kernel note)
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0].reshape(-1, 1)            # [bq, 1], log2 units
        delta = delta_ref[0, 0].reshape(-1, 1)        # [bq, 1]
        s2 = _scores(q_ref, k_ref, qi, ki, scale, causal, block_q, block_k, window)
        p = jnp.exp2(s2 - lse)                        # [bq, bk] f32
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                             # [bq, bk] f32
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dq_sc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(step == nk - 1)
    def _final():
        dq_ref[0, 0] = dq_sc[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_sc, dv_sc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    window: Optional[int] = None, n_q_blocks: Optional[int] = None,
):
    ki, step = pl.program_id(2), pl.program_id(3)     # NOTE: q innermost here
    nq = pl.num_programs(3)
    # under a window the sweep starts at the first q block that sees this k block
    qi = step if window is None else _q_first(ki, block_q, block_k) + step

    @pl.when(step == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    if window is None:
        needed = True if not causal else (qi * block_q + block_q - 1 >= ki * block_k)
    else:
        # a step past the sequence's end (its blocks are clamped) does nothing
        needed = _live(qi, ki, causal, window, block_q, block_k) & (qi < n_q_blocks)

    @pl.when(needed)
    def _compute():
        # bf16 MXU inputs, f32 accumulation (see _fwd_kernel note)
        q = q_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0].reshape(-1, 1)            # log2 units
        delta = delta_ref[0, 0].reshape(-1, 1)
        s2 = _scores(q_ref, k_ref, qi, ki, scale, causal, block_q, block_k, window)
        p = jnp.exp2(s2 - lse)                        # [bq, bk] f32
        p_in = p.astype(q.dtype)
        dv_sc[:] += jax.lax.dot_general(
            p_in, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                             # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(q.dtype)  # [bq, bk]
        dk_sc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                             # [bk, d]

    @pl.when(step == nq - 1)
    def _final():
        dk_ref[0, 0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)


def _flash_bwd_call(
    q, k, v, do, out, lse, scale, causal, block_q, block_k, window=None
):
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

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            window=window,
        ),
        grid=(b, h, nq, k_steps),
        in_specs=[qspec, kspec, kspec, qspec, rowq, rowq],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
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
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            window=window, n_q_blocks=nq,
        ),
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
        interpret=_interpret(),
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
