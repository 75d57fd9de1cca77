"""Ring attention: sequence-parallel attention over the mesh "seq" axis.

Long-context machinery the reference platform lacks entirely (SURVEY.md
§2.10: "SP / CP / ring attention ... not present").  Design follows the
blockwise-parallel / ring-attention construction: q, k, v are sharded along
the sequence dim across the "seq" mesh axis; each device computes blockwise
attention of its local queries against the k/v shard it currently holds,
maintaining a running (m, l, acc) softmax state, then passes the k/v shard
to its ring neighbor with ``lax.ppermute`` (XLA lowers this to ICI
neighbor exchanges that overlap with the block compute).

Efficiency notes:
- **Causal work balancing (zigzag assignment)**: under causal masking with
  CONTIGUOUS sequence shards, rank r's queries attend to r+1 of the n k/v
  shards — the last rank does n times the work of the first and sets the
  critical path, so skipping masked blocks saves FLOPs/energy but no
  wall-clock.  The ``zigzag`` assignment (the llama3-style context-parallel
  trick) gives every rank one LOW half-chunk (chunk r) and one HIGH
  half-chunk (chunk 2n-1-r) of the sequence, so each rank executes exactly
  2 half-block computes per ring step (3 on its diagonal step) — balanced,
  and ~half the FLOPs of the dense sweep on the critical path.  The
  conversion between the contiguous layout outside and the zigzag layout
  inside is two half-chunk ``ppermute``s on entry/exit (O(S/n) bytes vs the
  ring's O(S) total, so the fix-up is amortized away).  Contiguous remains
  the path for non-causal attention, where work is already balanced.
  Per-rank executed-work counters (``ring_block_counts``) make the balance
  testable without relying on noisy CPU-emulated wall-clock.
- **Causal step skipping**: a k/v (half-)shard lying strictly after the
  local queries contributes nothing under causal masking; those computes
  are skipped with ``lax.cond`` (the rotation still happens).
- **Grouped-KV rotation**: with GQA the ring rotates the *kv* heads and
  expands to full heads only inside the local block compute, dividing
  ppermute/ICI traffic by the group size; dk/dv are group-summed back
  before they continue around the ring.  (When the tensor axis does not
  divide h_kv, k/v are pre-expanded instead so head sharding stays legal.)

Memory per device is O(S/N) in BOTH directions: the backward is a custom
VJP that re-runs the ring, rotating (k, v, dk, dv) together so no per-step
k/v residuals are stored (a plain autodiff through the scan would stash
every rotated shard = O(S) per device).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from determined_tpu.ops.attention import _repeat_kv
from determined_tpu.parallel.mesh import MeshAxes

NEG_INF = -1e30


def _block_logits(q, k, scale, causal, q_pos, k_pos):
    """Masked logits for one block; ``q_pos``/``k_pos`` are global position
    vectors (contiguous or zigzag — the mask only sees positions)."""
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    if causal:
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# contiguous assignment (non-causal path + fallback)
# ---------------------------------------------------------------------------


def _ring_fwd_local(q, k, v, *, axis_name, causal, scale, n_rep):
    """Forward ring sweep; returns (out, lse, cnt) with local seq shards.

    k/v carry ``h_kv`` heads around the ring; expansion to the full head
    count happens per step inside the block compute.  ``cnt`` counts
    executed half-block-equivalents (each full-shard compute = 4); the
    increments live inside the cond branches, so the counter reports
    what actually ran (``ring_block_counts`` surfaces it; the vjp
    wrappers drop it).
    """
    n = jax.lax.axis_size(axis_name)
    # positions (and the rank index feeding them) exist only for the causal
    # mask; on the non-causal path axis_index must not be emitted at all —
    # its dead value survives into the custom_vjp residual jaxpr and older
    # XLA then refuses to SPMD-partition the PartitionId instruction
    idx = jax.lax.axis_index(axis_name) if causal else 0
    b, h, sl, d = q.shape
    qf = q.astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    m0 = jnp.full((b, h, sl, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sl, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, sl, d), jnp.float32)
    cnt0 = jnp.zeros((), jnp.int32)

    def step_fn(carry, step):
        m, l, acc, cnt, k_cur, v_cur = carry
        src = (idx - step) % n

        def compute(m, l, acc, cnt):
            k_exp = _repeat_kv(k_cur, n_rep)
            v_exp = _repeat_kv(v_cur, n_rep)
            q_pos = (idx * sl + jnp.arange(sl)) if causal else None
            k_pos = (src * sl + jnp.arange(sl)) if causal else None
            s = _block_logits(qf, k_exp, scale, causal, q_pos, k_pos)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "bhqk,bhkd->bhqd", p, v_exp.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc_new, cnt + 4

        if causal:
            # src > idx: the shard lies strictly after every local query —
            # fully masked, skip the block compute entirely
            m, l, acc, cnt = jax.lax.cond(
                src <= idx, compute, lambda m, l, acc, cnt: (m, l, acc, cnt),
                m, l, acc, cnt,
            )
        else:
            m, l, acc, cnt = compute(m, l, acc, cnt)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m, l, acc, cnt, k_nxt, v_nxt), None

    (m, l, acc, cnt, _, _), _ = jax.lax.scan(
        step_fn, (m0, l0, acc0, cnt0, k, v), jnp.arange(n)
    )
    l = jnp.maximum(l, 1e-30)
    out = (acc / l).astype(q.dtype)
    lse = m + jnp.log(l)  # [b, h, sl, 1]
    return out, lse, cnt


def _ring_bwd_local(q, k, v, out, lse, do, *, axis_name, causal, scale, n_rep):
    """Backward ring sweep: dk/dv rotate WITH their k/v shards, arriving
    home after n steps; no per-step residuals are kept.  dk/dv travel with
    ``h_kv`` heads (group-summed from the expanded gradient each step)."""
    n = jax.lax.axis_size(axis_name)
    # see _ring_fwd_local: no dead axis_index on the non-causal path
    idx = jax.lax.axis_index(axis_name) if causal else 0
    b, h, sl, d = q.shape
    h_kv = k.shape[1]
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1, keepdims=True)
    perm = [(i, (i + 1) % n) for i in range(n)]

    dq0 = jnp.zeros((b, h, sl, d), jnp.float32)
    dk0 = jnp.zeros_like(k, dtype=jnp.float32)
    dv0 = jnp.zeros_like(v, dtype=jnp.float32)

    def step_fn(carry, step):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        src = (idx - step) % n

        def compute(dq, dk_cur, dv_cur):
            k_exp = _repeat_kv(k_cur, n_rep)
            v_exp = _repeat_kv(v_cur, n_rep)
            q_pos = (idx * sl + jnp.arange(sl)) if causal else None
            k_pos = (src * sl + jnp.arange(sl)) if causal else None
            s = _block_logits(qf, k_exp, scale, causal, q_pos, k_pos)
            p = jnp.exp(s - lse)                              # [b,h,ql,kl]
            dp = jnp.einsum(
                "bhqd,bhkd->bhqk", dof, v_exp.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta) * scale
            dq_new = dq + jnp.einsum(
                "bhqk,bhkd->bhqd", ds, k_exp.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            dk_full = jnp.einsum(
                "bhqk,bhqd->bhkd", ds, qf, preferred_element_type=jnp.float32
            )
            dv_full = jnp.einsum(
                "bhqk,bhqd->bhkd", p, dof, preferred_element_type=jnp.float32
            )
            # group-sum the expanded-head gradient back to kv heads
            dk_new = dk_cur + dk_full.reshape(b, h_kv, n_rep, sl, d).sum(axis=2)
            dv_new = dv_cur + dv_full.reshape(b, h_kv, n_rep, sl, d).sum(axis=2)
            return dq_new, dk_new, dv_new

        if causal:
            dq, dk_cur, dv_cur = jax.lax.cond(
                src <= idx,
                compute,
                lambda dq, dk_cur, dv_cur: (dq, dk_cur, dv_cur),
                dq, dk_cur, dv_cur,
            )
        else:
            dq, dk_cur, dv_cur = compute(dq, dk_cur, dv_cur)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_cur, axis_name, perm)
        return (dq, k_nxt, v_nxt, dk_nxt, dv_nxt), None

    (dq, _, _, dk, dv), _ = jax.lax.scan(
        step_fn, (dq0, k, v, dk0, dv0), jnp.arange(n)
    )
    # after n rotations dk/dv have completed a full loop and are home
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_local(q, k, v, axis_name, causal, scale, n_rep):
    out, _, _ = _ring_fwd_local(
        q, k, v, axis_name=axis_name, causal=causal, scale=scale, n_rep=n_rep
    )
    return out


def _ring_local_fwd(q, k, v, axis_name, causal, scale, n_rep):
    out, lse, _ = _ring_fwd_local(
        q, k, v, axis_name=axis_name, causal=causal, scale=scale, n_rep=n_rep
    )
    return out, (q, k, v, out, lse)


def _ring_local_bwd(axis_name, causal, scale, n_rep, res, g):
    q, k, v, out, lse = res
    return _ring_bwd_local(
        q, k, v, out, lse, g,
        axis_name=axis_name, causal=causal, scale=scale, n_rep=n_rep,
    )


_ring_local.defvjp(_ring_local_fwd, _ring_local_bwd)


# ---------------------------------------------------------------------------
# zigzag assignment (balanced causal work)
# ---------------------------------------------------------------------------


def _zz_owner(chunk: int, n: int) -> int:
    """Zigzag owner of half-chunk ``chunk`` (of 2n): rank r holds (r, 2n-1-r)."""
    return chunk if chunk < n else 2 * n - 1 - chunk


def zigzag_redistribute(x, axis_name, inverse: bool = False):
    """Exchange half-chunks between contiguous and zigzag layouts along the
    second-to-last dim (inside manual SPMD over ``axis_name``).

    Contiguous rank r holds sequence chunks (2r, 2r+1); zigzag rank r holds
    (r, 2n-1-r).  Each rank's two chunks have opposite parity, so the moves
    decompose into exactly two ``ppermute``s — one carrying the even chunks,
    one the odd — plus a parity select on arrival.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    sl = x.shape[-2]
    hc = sl // 2
    first, second = x[..., :hc, :], x[..., hc:, :]
    perm_a = [(s, _zz_owner(2 * s, n)) for s in range(n)]        # even chunks
    perm_b = [(s, _zz_owner(2 * s + 1, n)) for s in range(n)]    # odd chunks
    idx = jax.lax.axis_index(axis_name)
    even = (idx % 2) == 0
    if not inverse:
        ra = jax.lax.ppermute(first, axis_name, perm_a)
        rb = jax.lax.ppermute(second, axis_name, perm_b)
        # my zigzag chunks: (idx, 2n-1-idx) — idx shares my parity
        lo = jnp.where(even, ra, rb)
        hi = jnp.where(even, rb, ra)
        return jnp.concatenate([lo, hi], axis=-2)
    # inverse: send back what travelled each ppermute, along the inverse map
    send_a = jnp.where(even, first, second)     # the even chunk I hold
    send_b = jnp.where(even, second, first)     # the odd chunk I hold
    inv_a = [(d, s) for s, d in perm_a]
    inv_b = [(d, s) for s, d in perm_b]
    ra = jax.lax.ppermute(send_a, axis_name, inv_a)   # my chunk 2r
    rb = jax.lax.ppermute(send_b, axis_name, inv_b)   # my chunk 2r+1
    return jnp.concatenate([ra, rb], axis=-2)


def _zz_pos(rank, n, hc):
    """Global position vectors of the two half-chunks rank holds (zigzag)."""
    lo = rank * hc + jnp.arange(hc)
    hi = (2 * n - 1 - rank) * hc + jnp.arange(hc)
    return lo, hi


def _attn_update(qf, k_half, v_half, q_pos, k_pos, m, l, acc, scale, n_rep):
    """Online-softmax update of one q half against one k/v half-chunk."""
    k_exp = _repeat_kv(k_half, n_rep)
    v_exp = _repeat_kv(v_half, n_rep)
    s = _block_logits(qf, k_exp, scale, True, q_pos, k_pos)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v_exp.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _zz_fwd_local(q, k, v, *, axis_name, scale, n_rep):
    """Zigzag causal forward.  Local shards are (lo, hi) half-chunks; per
    ring step each rank runs: hi-q × lo-k (always, fully unmasked),
    lo-q × lo-k (iff src ≤ idx), hi-q × hi-k (iff src ≥ idx) — so every
    rank executes 2 half-computes per step (3 on the diagonal), vs the
    contiguous sweep's rank-(n-1) doing 4 per step."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, sl, d = q.shape
    hc = sl // 2
    q_lo = q[..., :hc, :].astype(jnp.float32)
    q_hi = q[..., hc:, :].astype(jnp.float32)
    p_lo, p_hi = _zz_pos(idx, n, hc)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def zero_state():
        return (
            jnp.full((b, h, hc, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, h, hc, 1), jnp.float32),
            jnp.zeros((b, h, hc, d), jnp.float32),
        )

    st_lo0, st_hi0 = zero_state(), zero_state()
    cnt0 = jnp.zeros((), jnp.int32)

    def step_fn(carry, step):
        st_lo, st_hi, cnt, k_cur, v_cur = carry
        src = (idx - step) % n
        k_lo, k_hi = k_cur[..., :hc, :], k_cur[..., hc:, :]
        v_lo, v_hi = v_cur[..., :hc, :], v_cur[..., hc:, :]
        kp_lo, kp_hi = _zz_pos(src, n, hc)

        # hi-q attends to every lo-k chunk: always computed, never masked
        st_hi = _attn_update(q_hi, k_lo, v_lo, p_hi, kp_lo, *st_hi, scale, n_rep)
        cnt = cnt + 1

        def lo_lo(st, cnt):
            m, l, acc = st
            return _attn_update(q_lo, k_lo, v_lo, p_lo, kp_lo, m, l, acc,
                                scale, n_rep), cnt + 1

        st_lo, cnt = jax.lax.cond(
            src <= idx, lo_lo, lambda st, cnt: (st, cnt), st_lo, cnt
        )

        def hi_hi(st, cnt):
            m, l, acc = st
            return _attn_update(q_hi, k_hi, v_hi, p_hi, kp_hi, m, l, acc,
                                scale, n_rep), cnt + 1

        st_hi, cnt = jax.lax.cond(
            src >= idx, hi_hi, lambda st, cnt: (st, cnt), st_hi, cnt
        )

        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (st_lo, st_hi, cnt, k_nxt, v_nxt), None

    (st_lo, st_hi, cnt, _, _), _ = jax.lax.scan(
        step_fn, (st_lo0, st_hi0, cnt0, k, v), jnp.arange(n)
    )

    def finish(st):
        m, l, acc = st
        l = jnp.maximum(l, 1e-30)
        return (acc / l), m + jnp.log(l)

    out_lo, lse_lo = finish(st_lo)
    out_hi, lse_hi = finish(st_hi)
    out = jnp.concatenate([out_lo, out_hi], axis=-2).astype(q.dtype)
    lse = jnp.concatenate([lse_lo, lse_hi], axis=-2)
    return out, lse, cnt


def _attn_bwd_half(qf, k_half, v_half, lse_h, do_f, delta_h, q_pos, k_pos,
                   scale, n_rep, h_kv):
    """One (q-half, k-half) backward block: returns (dq, dk_grp, dv_grp)."""
    b, h, ql, d = qf.shape
    kl = k_half.shape[-2]
    k_exp = _repeat_kv(k_half, n_rep)
    v_exp = _repeat_kv(v_half, n_rep)
    s = _block_logits(qf, k_exp, scale, True, q_pos, k_pos)
    p = jnp.exp(s - lse_h)
    dp = jnp.einsum(
        "bhqd,bhkd->bhqk", do_f, v_exp.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_h) * scale
    dq = jnp.einsum(
        "bhqk,bhkd->bhqd", ds, k_exp.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    dk_full = jnp.einsum("bhqk,bhqd->bhkd", ds, qf, preferred_element_type=jnp.float32)
    dv_full = jnp.einsum("bhqk,bhqd->bhkd", p, do_f, preferred_element_type=jnp.float32)
    dk = dk_full.reshape(b, h_kv, h // h_kv, kl, d).sum(axis=2)
    dv = dv_full.reshape(b, h_kv, h // h_kv, kl, d).sum(axis=2)
    return dq, dk, dv


def _zz_bwd_local(q, k, v, out, lse, do, *, axis_name, scale, n_rep):
    """Zigzag causal backward: same balanced pair schedule as the forward;
    dk/dv rotate with their k/v shards and are home after n steps."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, sl, d = q.shape
    hc = sl // 2
    h_kv = k.shape[1]
    q_lo = q[..., :hc, :].astype(jnp.float32)
    q_hi = q[..., hc:, :].astype(jnp.float32)
    do_f = do.astype(jnp.float32)
    delta = jnp.sum(do_f * out.astype(jnp.float32), axis=-1, keepdims=True)
    do_lo, do_hi = do_f[..., :hc, :], do_f[..., hc:, :]
    dl_lo, dl_hi = delta[..., :hc, :], delta[..., hc:, :]
    lse_lo, lse_hi = lse[..., :hc, :], lse[..., hc:, :]
    p_lo, p_hi = _zz_pos(idx, n, hc)
    perm = [(i, (i + 1) % n) for i in range(n)]

    dq_lo0 = jnp.zeros((b, h, hc, d), jnp.float32)
    dq_hi0 = jnp.zeros((b, h, hc, d), jnp.float32)
    dk0 = jnp.zeros_like(k, dtype=jnp.float32)
    dv0 = jnp.zeros_like(v, dtype=jnp.float32)

    def step_fn(carry, step):
        dq_lo, dq_hi, k_cur, v_cur, dk_cur, dv_cur = carry
        src = (idx - step) % n
        k_lo, k_hi = k_cur[..., :hc, :], k_cur[..., hc:, :]
        v_lo, v_hi = v_cur[..., :hc, :], v_cur[..., hc:, :]
        dk_lo, dk_hi = dk_cur[..., :hc, :], dk_cur[..., hc:, :]
        dv_lo, dv_hi = dv_cur[..., :hc, :], dv_cur[..., hc:, :]
        kp_lo, kp_hi = _zz_pos(src, n, hc)

        # hi-q × lo-k: always
        g = _attn_bwd_half(q_hi, k_lo, v_lo, lse_hi, do_hi, dl_hi,
                           p_hi, kp_lo, scale, n_rep, h_kv)
        dq_hi = dq_hi + g[0]
        dk_lo = dk_lo + g[1]
        dv_lo = dv_lo + g[2]

        def lo_lo(dq_lo, dk_lo, dv_lo):
            g = _attn_bwd_half(q_lo, k_lo, v_lo, lse_lo, do_lo, dl_lo,
                               p_lo, kp_lo, scale, n_rep, h_kv)
            return dq_lo + g[0], dk_lo + g[1], dv_lo + g[2]

        dq_lo, dk_lo, dv_lo = jax.lax.cond(
            src <= idx, lo_lo, lambda a, b_, c: (a, b_, c), dq_lo, dk_lo, dv_lo
        )

        def hi_hi(dq_hi, dk_hi, dv_hi):
            g = _attn_bwd_half(q_hi, k_hi, v_hi, lse_hi, do_hi, dl_hi,
                               p_hi, kp_hi, scale, n_rep, h_kv)
            return dq_hi + g[0], dk_hi + g[1], dv_hi + g[2]

        dq_hi, dk_hi, dv_hi = jax.lax.cond(
            src >= idx, hi_hi, lambda a, b_, c: (a, b_, c), dq_hi, dk_hi, dv_hi
        )

        dk_nxt = jnp.concatenate([dk_lo, dk_hi], axis=-2)
        dv_nxt = jnp.concatenate([dv_lo, dv_hi], axis=-2)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = jax.lax.ppermute(dk_nxt, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_nxt, axis_name, perm)
        return (dq_lo, dq_hi, k_nxt, v_nxt, dk_nxt, dv_nxt), None

    (dq_lo, dq_hi, _, _, dk, dv), _ = jax.lax.scan(
        step_fn, (dq_lo0, dq_hi0, k, v, dk0, dv0), jnp.arange(n)
    )
    dq = jnp.concatenate([dq_lo, dq_hi], axis=-2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_local_zz(q, k, v, axis_name, scale, n_rep):
    out, _, _ = _zz_fwd_local(q, k, v, axis_name=axis_name, scale=scale, n_rep=n_rep)
    return out


def _ring_local_zz_fwd(q, k, v, axis_name, scale, n_rep):
    out, lse, _ = _zz_fwd_local(q, k, v, axis_name=axis_name, scale=scale, n_rep=n_rep)
    return out, (q, k, v, out, lse)


def _ring_local_zz_bwd(axis_name, scale, n_rep, res, g):
    q, k, v, out, lse = res
    return _zz_bwd_local(
        q, k, v, out, lse, g, axis_name=axis_name, scale=scale, n_rep=n_rep
    )


_ring_local_zz.defvjp(_ring_local_zz_fwd, _ring_local_zz_bwd)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def _resolve_assignment(assignment: str, causal: bool, sl: int) -> str:
    """zigzag needs causal masking (the balance argument is causal-specific)
    and an even per-rank shard; everything else rides contiguous."""
    if assignment == "auto":
        return "zigzag" if (causal and sl % 2 == 0) else "contiguous"
    if assignment == "zigzag" and not causal:
        raise ValueError("zigzag assignment requires causal=True")
    if assignment == "zigzag" and sl % 2:
        raise ValueError(f"zigzag needs an even per-rank shard, got {sl}")
    if assignment not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring assignment {assignment!r}")
    return assignment


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
    assignment: str = "auto",
) -> jax.Array:
    """Ring attention on LOCAL seq shards, for callers already inside manual
    SPMD (shard_map) over ``axis_name`` — e.g. pipeline stages composing
    with the seq axis.  Inputs/outputs use the CONTIGUOUS layout (rank r
    holds rows [r·sl, (r+1)·sl)); the zigzag layout is internal."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    n_rep = q.shape[1] // k.shape[1]
    assignment = _resolve_assignment(assignment, causal, q.shape[-2])
    if assignment == "zigzag":
        q, k, v = (zigzag_redistribute(t, axis_name) for t in (q, k, v))
        out = _ring_local_zz(q, k, v, axis_name, scale, n_rep)
        return zigzag_redistribute(out, axis_name, inverse=True)
    return _ring_local(q, k, v, axis_name, causal, scale, n_rep)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    seq_axis: str = MeshAxes.SEQUENCE,
    assignment: str = "auto",
) -> jax.Array:
    """Sequence-parallel attention over global [b, h, S, d] arrays.

    Batch dim may additionally be sharded over data/fsdp axes and heads over
    the tensor axis; the seq dim is sharded over ``seq_axis``.  GQA kv heads
    stay compact around the ring (ppermute traffic is h_kv, not h); the
    gradient re-reduction over the group is explicit in the backward.  Falls
    back to single-shard blockwise attention when the mesh has no seq axis.

    ``assignment``: "auto" (zigzag for causal — balanced per-rank work),
    "contiguous", or "zigzag".
    """
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    n_rep = q.shape[1] // k.shape[1]

    n_seq = mesh.shape.get(seq_axis, 1)
    if n_seq <= 1:
        from determined_tpu.ops.attention import reference_attention

        return reference_attention(q, k, v, causal=causal, scale=scale)

    batch_axes = tuple(
        a for a in MeshAxes.BATCH_AXES if mesh.shape.get(a, 1) > 1
    )
    tensor_size = mesh.shape.get(MeshAxes.TENSOR, 1)
    head_axis = MeshAxes.TENSOR if tensor_size > 1 else None
    if head_axis is not None and k.shape[1] % tensor_size != 0:
        # kv heads can't be sharded over the tensor axis (e.g. MQA with
        # tensor>1): expand to full heads before the ring instead
        k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
        n_rep = 1
    spec = P(batch_axes or None, head_axis, seq_axis, None)
    assignment = _resolve_assignment(assignment, causal, q.shape[-2] // n_seq)

    fn = jax.shard_map(
        lambda q, k, v: ring_attention_local(
            q, k, v, axis_name=seq_axis, causal=causal, scale=scale,
            assignment=assignment,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def ring_block_counts(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    seq_axis: str = MeshAxes.SEQUENCE,
    assignment: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Run the forward ring and return (out, per_rank_counts).

    ``per_rank_counts[i]`` is the number of half-block-equivalent computes
    rank i's cond branches actually executed (a full-shard compute counts
    4); this is the balance evidence the zigzag assignment exists for —
    CPU-emulated wall-clock is too noisy to assert on."""
    d = q.shape[-1]
    scale = d ** -0.5
    n_rep = q.shape[1] // k.shape[1]
    n_seq = mesh.shape[seq_axis]
    assignment = _resolve_assignment(assignment, causal, q.shape[-2] // n_seq)
    spec = P(None, None, seq_axis, None)

    def local(q, k, v):
        if assignment == "zigzag":
            q, k, v = (zigzag_redistribute(t, seq_axis) for t in (q, k, v))
            out, _, cnt = _zz_fwd_local(
                q, k, v, axis_name=seq_axis, scale=scale, n_rep=n_rep
            )
            out = zigzag_redistribute(out, seq_axis, inverse=True)
        else:
            out, _, cnt = _ring_fwd_local(
                q, k, v, axis_name=seq_axis, causal=causal, scale=scale,
                n_rep=n_rep,
            )
        return out, cnt[None]

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, P(seq_axis)),
        check_vma=False,
    )
    return fn(q, k, v)
