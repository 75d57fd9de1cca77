"""Power retention of degree 2: attention whose cache is a fixed-size state.

For queries and keys of one head, ``A_ij = exp(G_i - G_j) (q_i . k_j)^2`` for
``j <= i`` (``G`` the running sum of the gate's logarithm) and ``o_i = sum_j
A_ij v_j / sum_j A_ij``.  Because ``(x . y)^2 = phi(x) . phi(y)`` for a fixed
feature map ``phi``, the sums over ``j`` fold into a state a KV head that does
not grow with the context::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        z_t = g_t z_{t-1} + phi(k_t)
    o_t = S_t^T phi(q_t) / (z_t . phi(q_t))

**The feature map.**  ``phi(x)[r, c] = w_r x_c x_{(c + r) mod d}`` for ``r`` in
``0 .. d/2`` with ``w_0 = w_{d/2} = 1`` and ``w_r = sqrt 2`` between: row ``r``
is the row ``x`` times itself rolled by ``r``, which the chip makes with one
lane rotation.  Row 0 holds the squares; rows ``1 .. d/2 - 1`` hold every
unordered pair at circular distance ``r`` once (weight ``sqrt 2``, squared 2:
the pair's two places in ``(x . y)^2``); row ``d/2`` holds its pairs twice, at
weight 1 each.  ``d/2 + 1`` rows of ``d``: 65 x 128 = 8,320 features at a head
of 128, where the symmetric form has 8,256 and ``x (x) x`` 16,384.

**The state pool** (``models/serving.py init_kv_cache``): ``rs [layers,
lanes, kv_heads, rows * d, d]`` float32, feature row ``r`` and value ``v`` at
row ``r * d + v``, the feature's column ``c`` along the lanes; ``rz [layers,
lanes, kv_heads, rows, d]`` the normaliser.  A decode lane owns slot ``lane``,
and beside it its **recent rows** (:func:`recent_shapes`): the keys and values
of the tokens a decode step has not folded into the slot yet, at most
``FOLD_EVERY``, the running sum of the gate's logarithm at each, and their count.

* :func:`retention_decode`: one token a lane.  The token joins the lane's
  recent rows and is answered from both halves of the one sum: the state AS IT
  LIES, decayed to the token in the answer (``e^{G_t} phi(q) . S``), plus the
  quadratic form over the rows (``sum_j e^{G_t - G_j} (q . k_j)^2 v_j``, the
  token's own among them): the same sums in another order.  The Pallas
  kernel's grid is (lane, KV head): a program reads the head's ``[rows * d,
  d]`` state once (``phi(q) [heads, d] @ S_r^T`` on the MXU, a feature row at a
  time) and WRITES NOTHING of it, unless the lane's rows have reached
  ``FOLD_EVERY``: then they all enter the state at once (the chunk kernel's
  arithmetic at ``s = FOLD_EVERY``) and the slot is written into the pool's own
  buffer (``input_output_aliases``), one write in ``FOLD_EVERY`` tokens where
  the per-token form wrote every slot every token.  A lane folds by its OWN
  count of tokens, so the lanes that are due vary from step to step: the
  output block's index comes from a scalar-prefetched map (:func:`_held_blocks`)
  that stays where it was on programs that are not due, and the pipeline
  writes a block back only when the index moves.  ``phi`` is built in the
  kernel from the 128-wide rows and never lies in HBM.  An idle lane's slot
  and rows pass through.
* :func:`retention_chunk`: a chunk of tokens (the prefill walk, and the wide
  prefill as one chunk): the chunk is answered from the state at its start,
  decayed, plus the quadratic form inside the chunk (``jax.numpy``), and
  folded into the state once.  What it reads of the state and writes to it
  is a second Pallas kernel, grid (row, KV head): ``phi`` of the chunk's
  queries and keys is built a feature row at a time in VMEM, each row of the
  state is read once, answers the queries as it comes, takes the keys and is
  written back.  The feature rows are a LOOP (``fori_loop``, five rows a
  trip) whose body is stated once: ``phi``'s row ``r`` is a lane rotation by
  ``r`` (a dynamic shift), its weight a compare on ``r``, the state's row a
  slice at ``r * d``.  Written out 65 times the same arithmetic was 293 KB of
  serialized Mosaic body and 41 s of compile a call; the loop is 30 KB and
  under 2 s, small enough to stand in both loops of the walk, and 11 % faster
  a call (PERF.md section 5 "PR 65").
  Its ``jax.numpy`` form holds ``phi`` of the queries whole.
* :func:`retention_quadratic`: the masked quadratic form over a whole
  sequence: the training forward, and the oracle of both forms above.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from determined_tpu.ops import kernel_form

_HIGHEST = jax.lax.Precision.HIGHEST
#: how the decode kernel multiplies phi(q) with the float32 state on the MXU
QUERY_PRECISION = _HIGHEST
#: VMEM the decode kernel may take: a head's state at 128 is 4.26 MB, held
#: twice coming in and twice going out
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
#: tokens a lane's recent rows hold before they are folded into its state: a
#: decode step reads a slot every token and writes it once in as many.  Chosen
#: on a v5e at Brumby's shape (32 lanes x 8 KV heads x 5 layers, ms a step):
#: 8.19 at 16, 8.00 at 32, 8.20 at 64, 8.70 at 128 (the write falls as 1 / C,
#: the rows a program copies and multiplies grow with C; PERF.md section 5)
FOLD_EVERY = 32


def phi_rows(head_dim: int) -> int:
    """Feature rows of ``phi`` at a head of ``head_dim``: rolls 0 .. d/2."""
    return head_dim // 2 + 1


def state_shapes(layers: int, lanes: int, kv_heads: int, head_dim: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """The shapes of the state pool and of its normaliser."""
    rows = phi_rows(head_dim)
    return (layers, lanes, kv_heads, rows * head_dim, head_dim), (layers, lanes, kv_heads, rows, head_dim)


def _weight(r: int, head_dim: int) -> float:
    return 1.0 if r in (0, head_dim // 2) else math.sqrt(2.0)


def phi(x: jax.Array) -> jax.Array:
    """``[..., d] -> [..., d/2 + 1, d]`` with ``phi(x) . phi(y) = (x . y)^2``."""
    d = x.shape[-1]
    return jnp.stack([_weight(r, d) * x * jnp.roll(x, -r, axis=-1) for r in range(phi_rows(d))], axis=-2)


def kernel_takes(head_dim: int, state_dtype) -> bool:
    """Whether the decode kernel runs these shapes: a head is one lane tile."""
    return head_dim == 128 and jnp.dtype(state_dtype).itemsize in (2, 4)


# ---------------------------------------------------------------------------
# the quadratic form
# ---------------------------------------------------------------------------


def retention_quadratic(q: jax.Array, k: jax.Array, v: jax.Array, log_g: jax.Array) -> jax.Array:
    """``q`` [b, heads, s, d], ``k`` / ``v`` [b, kv_heads, s, d], ``log_g`` [b,
    kv_heads, s] float32 -> [b, heads, s, d] in q's dtype.  Weights in float32."""
    b, h, s, d = q.shape
    g = k.shape[1]
    cum = jnp.cumsum(log_g.astype(jnp.float32), axis=-1)
    scores = jnp.einsum(
        "bgnsd,bgtd->bgnst", q.reshape(b, g, h // g, s, d), k, preferred_element_type=jnp.float32, precision=_HIGHEST
    )
    decay = cum[:, :, None, :, None] - cum[:, :, None, None, :]
    seen = jnp.tril(jnp.ones((s, s), bool))
    weights = jnp.where(seen, jnp.exp(jnp.where(seen, decay, 0.0)) * jnp.square(scores), 0.0)
    num = jnp.einsum("bgnst,bgtd->bgnsd", weights, v.astype(jnp.float32), precision=_HIGHEST)
    return (num / jnp.sum(weights, axis=-1, keepdims=True)).reshape(b, h, s, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# a chunk of tokens against a state, and into it
# ---------------------------------------------------------------------------


def chunk_kernel_takes(query_rows: int, tokens: int, head_dim: int, state_dtype) -> bool:
    """Whether the chunk kernel runs these shapes: a head is one lane tile, the
    chunk's rows are whole sublane tiles, and a KV head's queries and their
    answers fit VMEM beside its state (the walk's 256 tokens x 5 query heads
    are 1,280 rows; 4,096 compile for a v5e under ``_VMEM_LIMIT_BYTES``)."""
    return kernel_takes(head_dim, state_dtype) and tokens % 8 == 0 and tokens <= 512 and query_rows <= 4096


def retention_chunk(
    q: jax.Array, k: jax.Array, v: jax.Array, log_g: jax.Array, state: jax.Array, norm: jax.Array, valid: jax.Array,
    *, impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``s`` tokens a row of the batch, after the ones ``state`` / ``norm``
    ([b, kv_heads, rows * d, d] / [b, kv_heads, rows, d]) already hold.  ``valid``
    [b, s] marks the tokens that exist: the others neither decay the state nor
    enter it, and what they are answered is not read.  Returns (o [b, heads, s,
    d] float32, the state and the normaliser after the chunk).

    The quadratic form inside the chunk is ``jax.numpy``.  What the chunk
    reads of the state and folds into it is ``impl``: ``"kernel"`` (the Pallas
    kernel: one pass over a KV head's state, ``phi`` of the chunk's queries and
    keys built a feature row at a time in VMEM), ``"kernel_interpret"``
    (tests), ``"jnp"`` (``phi`` whole, in HBM), or None: the kernel on a TPU
    when :func:`chunk_kernel_takes` the shapes."""
    b, h, s, d = q.shape
    g = k.shape[1]
    n = h // g
    impl = kernel_form.resolve_impl(
        impl, chunk_kernel_takes(n * s, s, d, state.dtype),
        f"the retention chunk kernel does not take {n * s} query rows of {s} tokens at head_dim {d}, {state.dtype}",
    )
    f32 = jnp.float32
    qf, kf, vf = q.astype(f32).reshape(b, g, n, s, d), k.astype(f32), v.astype(f32)
    vf = jnp.where(valid[:, None, :, None], vf, 0.0)
    cum = jnp.cumsum(jnp.where(valid[:, None, :], log_g.astype(f32), 0.0), axis=-1)  # [b, g, s]
    # inside the chunk: the quadratic form
    scores = jnp.einsum("bgnsd,bgtd->bgnst", qf, kf, precision=_HIGHEST)
    seen = jnp.tril(jnp.ones((s, s), bool))[None, None, None] & valid[:, None, None, None, :]
    decay = cum[:, :, None, :, None] - cum[:, :, None, None, :]
    weights = jnp.where(seen, jnp.exp(jnp.where(seen, decay, 0.0)) * jnp.square(scores), 0.0)
    num = jnp.einsum("bgnst,bgtd->bgnsd", weights, vf, precision=_HIGHEST)
    den = jnp.sum(weights, axis=-1)
    # what came before it, and the chunk into it: each key decayed from its place to the chunk's end
    total = cum[..., -1]
    left = jnp.where(valid[:, None, :], jnp.exp(total[..., None] - cum), 0.0)  # [b, g, s]
    if impl == "jnp":
        num0, den0, s1, z1 = _chunk_state_jnp(qf, kf, vf, left, jnp.exp(total), state, norm)
    else:
        num0, den0, s1, z1 = _chunk_state_pallas(qf, kf, vf, left, jnp.exp(total), state, norm, interpret=impl == "kernel_interpret")
    since = jnp.exp(cum)[:, :, None, :]  # the state at the chunk's start, decayed up to each query
    num, den = num + since[..., None] * num0, den + since * den0
    out = num / jnp.where(den == 0.0, 1.0, den)[..., None]
    return out.reshape(b, h, s, d), s1, z1


def _chunk_state_jnp(qf, kf, vf, left, kept, state, norm):
    """``phi(q) . S`` and ``phi(q) . z`` for the chunk's queries ([b, g, n, s, d]
    / [b, g, n, s]) against the state as it comes, and the state and the
    normaliser after the chunk's keys ``kf`` (weighed by ``left`` [b, g, s]) and
    values ``vf`` entered them, what they held decayed by ``kept`` [b, g]."""
    b, g, n, s, d = qf.shape
    rows = phi_rows(d)
    s0, z0 = state.astype(jnp.float32).reshape(b, g, rows, d, d), norm.astype(jnp.float32)
    pq = phi(qf)  # [b, g, n, s, rows, d]
    num0 = jnp.einsum("bgnsrc,bgrvc->bgnsv", pq, s0, precision=_HIGHEST)
    den0 = jnp.einsum("bgnsrc,bgrc->bgns", pq, z0, precision=_HIGHEST)
    pk = phi(kf) * left[..., None, None]  # [b, g, s, rows, d]
    s1 = kept[..., None, None, None] * s0 + jnp.einsum("bgsrc,bgsv->bgrvc", pk, vf, precision=_HIGHEST)
    z1 = kept[..., None, None] * z0 + jnp.sum(pk, axis=2)
    return num0, den0, s1.reshape(state.shape).astype(state.dtype), z1.astype(norm.dtype)


def _two_terms(x):
    """float32 -> (hi, lo) bfloat16 with hi + lo = x to 16 bits."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _nt(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _query_state(pq, s_old):
    """``pq [m, c] . s_old [v, c]^T`` in float32 from three bfloat16 products
    (hi x hi, hi x lo, lo x hi).  Not one float32 product at
    ``Precision.HIGHEST``: with 256 to 1,280 rows on the left Mosaic's reads
    0.6-3.5 % off the ``jax.numpy`` form on a v5e where this reads 2e-6 and is
    faster; the decode kernel's 8 rows are sound (PERF.md section 7)."""
    (a, a_lo), (b, b_lo) = _two_terms(pq), _two_terms(s_old)
    return _nt(a, b) + (_nt(a, b_lo) + _nt(a_lo, b))


#: feature rows a trip of the chunk kernel's loop takes (65 = 13 x 5).  One row a trip stalls between trips: the next
#: row's loads and rotations cannot start under this row's products (0.604 ms a call at Brumby's shape on a v5e where
#: the 65 rows written out took 0.590); five rows a trip give the scheduler that room (0.525), thirteen gain no more
#: (0.536) and the body grows with the factor (13 / 30 / 64 KB serialized at 1 / 5 / 13: PERF.md section 5 "PR 65")
_CHUNK_ROWS_A_TRIP = 5


def _retention_chunk_kernel(q_ref, k_ref, left_ref, vt_ref, kept_ref, s_ref, z_ref, num_ref, den_ref, s_out, z_out, z32, *, d, block):
    """One (row of the batch, KV head): ``q_ref`` [m, d] the KV head's queries
    (query head by token), ``k_ref`` [s, d] the keys, ``left_ref`` [s, 1] each
    key's decay to the chunk's end (0: no such token), ``vt_ref`` [d, s] the
    values as columns, ``kept_ref`` [1, 1]; ``s_ref`` [rows * d, d] and
    ``z_ref`` [rows, d] the head's state; ``z32`` [rows, d] float32 scratch.
    The feature rows are a LOOP whose body is stated once: row ``r`` of the
    state answers the queries AS IT COMES, ``block`` rows at a time, then takes
    the keys and values and is written back."""
    f32 = jnp.float32
    key, vt, kept = k_ref[...], vt_ref[...], kept_ref[...]
    weighed = key * left_ref[...]
    num_ref[...] = jnp.zeros(num_ref.shape, f32)
    den_ref[...] = jnp.zeros(den_ref.shape, f32)
    # the normaliser is read whole before any row is written, and a row of it at a dynamic index comes from 32-bit
    # rows alone (a bfloat16 state's lie two a sublane)
    z32[...] = z_ref[...].astype(f32)

    def feature_row(r):
        w = jnp.where(jnp.logical_or(r == 0, r == d // 2), 1.0, math.sqrt(2.0)).astype(f32)  # ``_weight``
        shift = jax.lax.rem(d - r, d)  # rolled by it, column c holds x[:, (c + r) % d]
        rows = pl.ds(pl.multiple_of(r * d, d), d)
        s_old = s_ref[rows, :].astype(f32)  # [d (value), d (feature column)]
        z_old = z32[pl.ds(r, 1), :]
        for lo in range(0, q_ref.shape[0], block):
            q = q_ref[lo:lo + block, :]
            pq = q * pltpu.roll(q, shift, 1) * w  # phi's row r of the queries
            num_ref[lo:lo + block, :] += _query_state(pq, s_old)
            den_ref[lo:lo + block, :] += jnp.sum(pq * z_old, axis=1, keepdims=True)
        pk = weighed * pltpu.roll(key, shift, 1) * w
        # [d, s] x [s, d] at HIGHEST agrees with the jax.numpy form to 7.6e-6 on the chip (the queries' product does not: _query_state)
        entered = jax.lax.dot_general(vt, pk, (((1,), (0,)), ((), ())), precision=_HIGHEST, preferred_element_type=f32)
        s_out[rows, :] = (kept * s_old + entered).astype(s_out.dtype)
        z32[pl.ds(r, 1), :] = kept * z_old + jnp.sum(pk, axis=0, keepdims=True)

    def trip(i, carry):
        for j in range(_CHUNK_ROWS_A_TRIP):
            feature_row(i * _CHUNK_ROWS_A_TRIP + j)
        return carry

    assert phi_rows(d) % _CHUNK_ROWS_A_TRIP == 0  # ``kernel_takes``: a head of 128, 65 rows
    jax.lax.fori_loop(0, phi_rows(d) // _CHUNK_ROWS_A_TRIP, trip, 0)
    z_out[...] = z32[...].astype(z_out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_state_pallas(qf, kf, vf, left, kept, state, norm, *, interpret: bool):
    """:func:`_chunk_state_jnp` as one pass over each KV head's state."""
    b, g, n, s, d = qf.shape
    rows, m = phi_rows(d), n * s
    at = lambda bi, gi: (bi, gi, 0, 0)  # noqa: E731
    num0, den0, s1, z1 = pl.pallas_call(
        # a query head's rows at a time: a product of all 1,280 rows read slower on a v5e, its operands stream through VMEM
        functools.partial(_retention_chunk_kernel, d=d, block=s),
        grid=(b, g),
        in_specs=[
            pl.BlockSpec((None, None, m, d), at),
            pl.BlockSpec((None, None, s, d), at),
            pl.BlockSpec((None, None, s, 1), at),
            pl.BlockSpec((None, None, d, s), at),
            pl.BlockSpec((None, None, 1, 1), at),
            pl.BlockSpec((None, None, rows * d, d), at),
            pl.BlockSpec((None, None, rows, d), at),
        ],
        out_specs=[
            pl.BlockSpec((None, None, m, d), at),
            pl.BlockSpec((None, None, m, 1), at),
            pl.BlockSpec((None, None, rows * d, d), at),
            pl.BlockSpec((None, None, rows, d), at),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, g, m, d), jnp.float32),
            jax.ShapeDtypeStruct((b, g, m, 1), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct(norm.shape, norm.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
        input_output_aliases={5: 2, 6: 3},  # the lanes' slots as the walk gathered them: updated where they lie
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=kernel_form.interpret_params(interpret),
        name="retention_chunk",
    )(
        qf.reshape(b, g, m, d), kf, left[..., None], vf.transpose(0, 1, 3, 2), kept[..., None, None], state, norm,
    )
    return num0.reshape(b, g, n, s, d), den0.reshape(b, g, n, s), s1, z1


# ---------------------------------------------------------------------------
# one token a lane
# ---------------------------------------------------------------------------


def recent_shapes(layers: int, lanes: int, kv_heads: int, head_dim: int) -> Tuple[Tuple[int, ...], ...]:
    """The shapes of a lane's recent rows: the keys and the values of the tokens
    not yet folded into its state, the running sum of the gate's logarithm at
    each since the last fold (held as the state is), and how many rows are pending (int32)."""
    rows = (layers, lanes, kv_heads, FOLD_EVERY, head_dim)
    return rows, rows, rows[:-1], (layers, lanes)


def retention_decode(
    q: jax.Array, k: jax.Array, v: jax.Array, log_g: jax.Array, state: jax.Array, norm: jax.Array,
    recent: Tuple[jax.Array, jax.Array, jax.Array, jax.Array], layer, live: jax.Array, *, impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, Tuple[jax.Array, jax.Array, jax.Array, jax.Array]]:
    """One decode step of one layer over the state pool, in place.

    ``q`` [lanes, heads, d], ``k`` / ``v`` [lanes, kv_heads, d] (q and k after
    their norm and rotary), ``log_g`` [lanes, kv_heads] float32, ``state`` /
    ``norm`` the whole pools (:func:`state_shapes`), ``recent`` the lanes' whole
    recent rows (:func:`recent_shapes`), ``layer`` the layer to update,
    ``live`` [lanes] bool: an idle lane's slot and rows are left as they are
    and its output is zeros.  Returns (o [lanes, heads, d] float32, state,
    norm, recent).

    The token joins its lane's recent rows and is answered from the state AS
    IT LIES, decayed to the token, plus the quadratic form over the rows; the
    slot of a lane whose rows have reached ``FOLD_EVERY`` takes them all and
    is written, the others are not.

    ``impl``: ``"kernel"``, ``"kernel_interpret"`` (tests), ``"jnp"`` or None:
    the kernel on a TPU when :func:`kernel_takes` the shapes.
    """
    d = q.shape[-1]
    impl = kernel_form.resolve_impl(
        impl, kernel_takes(d, state.dtype), f"the retention kernel needs head_dim 128 and a 2- or 4-byte state (got {d}, {state.dtype})"
    )
    return _retention_decode(q, k, v, log_g, state, norm, tuple(recent), jnp.asarray(layer, jnp.int32), live, impl=impl)


# one jitted function, the layer an argument (``ops/kernel_form.py`` says why)
@functools.partial(jax.jit, static_argnames=("impl",))
def _retention_decode(q, k, v, log_g, state, norm, recent, layer, live, *, impl):
    recent, rows = _append(k, v, log_g, recent, layer, live)
    if impl == "jnp":
        out, state, norm = _retention_decode_jnp(q, *recent[:2], *rows, state, norm, layer, live)
    else:
        out, state, norm = _retention_decode_pallas(q, *recent[:2], *rows, state, norm, layer, live, interpret=impl == "kernel_interpret")
    return out, state, norm, recent


def _append(k, v, log_g, recent, layer, live):
    """The token into its lane's recent rows, where they lie.  Returns the rows
    and what a step reads beside them: each row's decay up to this token
    ``left`` [b, g, C] (0: no such row), the state's ``kept`` [b, g], and the
    lanes whose rows are now whole, ``due`` [b]: those the step folds into
    their slots, and whose count starts again."""
    keys, vals, sums, pending = recent
    b, every = k.shape[0], keys.shape[3]
    lanes = jnp.arange(b)
    had = pending[layer]  # [b] rows before this token: 0 .. C - 1
    at = jnp.where(live, had, every)  # an idle lane's row is dropped
    before = jnp.take_along_axis(sums[layer], jnp.maximum(had - 1, 0)[:, None, None], axis=2)[..., 0].astype(jnp.float32)
    total = jnp.where(had[:, None] > 0, before, 0.0) + log_g.astype(jnp.float32)  # [b, g] the gate's logarithm since the last fold
    total = total.astype(sums.dtype)  # as the rows keep it: a running sum is state, held as the state is
    # a select over the layer's rows, not a scatter of one: XLA keeps a scatter's operand with the scattered
    # dimension outermost, and would copy the rows to that order and back around every step
    here = (jnp.arange(every)[None, :] == at[:, None])[:, None, :, None]  # [b, 1, C, 1]; an idle lane: nowhere
    keys, vals = (
        jax.lax.dynamic_update_index_in_dim(rows, jnp.where(here, new[:, :, None, :].astype(rows.dtype), rows[layer]), layer, 0)
        for rows, new in ((keys, k), (vals, v))
    )
    sums = sums.at[layer, lanes, :, at].set(total, mode="drop")
    total = total.astype(jnp.float32)
    due = live & (had + 1 == every)
    pending = pending.at[layer].set(jnp.where(live, jnp.where(due, 0, had + 1), had))
    held = jnp.arange(every)[None, None, :] <= had[:, None, None]
    left = jnp.where(held, jnp.exp(jnp.where(held, total[..., None] - sums[layer].astype(jnp.float32), 0.0)), 0.0)
    return (keys, vals, sums, pending), (left, jnp.exp(total), due)


def _retention_decode_jnp(q, keys, vals, left, kept, due, state, norm, layer, live):
    b, h, d = q.shape
    g = keys.shape[2]
    f32 = jnp.float32
    s0 = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    z0 = jax.lax.dynamic_index_in_dim(norm, layer, 0, keepdims=False)
    qf, kf, vf = q.astype(f32).reshape(b, g, h // g, d), keys[layer].astype(f32), vals[layer].astype(f32)
    # the tokens the state does not hold yet: the quadratic form; the others: the state as it lies, decayed; and the
    # state a fold leaves: every row into it, each decayed from its place to this token (a chunk of C keys, one query)
    weights = left[:, :, None, :] * jnp.square(jnp.einsum("bgnd,bgjd->bgnj", qf, kf, precision=_HIGHEST))
    num0, den0, s1, z1 = _chunk_state_jnp(qf[:, :, :, None, :], kf, vf, left, kept, s0, z0)
    num = jnp.einsum("bgnj,bgjd->bgnd", weights, vf, precision=_HIGHEST) + kept[..., None, None] * num0[:, :, :, 0]
    den = jnp.sum(weights, axis=-1) + kept[..., None] * den0[:, :, :, 0]
    out = jnp.where(live[:, None, None], (num / jnp.where(den == 0.0, 1.0, den)[..., None]).reshape(b, h, d), 0.0)
    fold = due[:, None, None, None]  # the lanes whose rows are whole; the others' slots stay bit for bit
    return (
        out,
        jax.lax.dynamic_update_index_in_dim(state, jnp.where(fold, s1, s0), layer, 0),
        jax.lax.dynamic_update_index_in_dim(norm, jnp.where(fold, z1, z0), layer, 0),
    )


def _retention_kernel(
    layer_ref, live_ref, due_ref, read_lane, read_head, write_lane, write_head, q_ref, k_ref, v_ref, row_ref, col_ref, kept_ref,
    s_ref, z_ref, o_ref, s_out, z_out, *, d,
):
    """One (lane, KV head): ``q_ref`` [rows8, d] float32 the KV head's queries,
    ``k_ref`` / ``v_ref`` [C, d] the lane's recent keys and values with this
    token's, ``row_ref`` [1, C] / ``col_ref`` [C, 1] each row's
    decay up to this token (0: no such row), ``kept_ref`` [1, 1] the state's;
    ``s_ref`` [rows * d, d] the head's state and ``z_ref`` [rows, kv_heads, d]
    the LANE's normalisers (this head's at ``[:, head]``) where the lane is live (else the block the pipeline already holds: nothing is
    fetched for an idle lane), READ; ``s_out`` / ``z_out`` the block the
    pipeline last wrote or will write next (:func:`_held_blocks`): this
    program's own only where the lane is due, and written only then."""
    lane, head = pl.program_id(0), pl.ds(pl.program_id(1), 1)
    f32 = jnp.float32
    alive, due = live_ref[lane] > 0, due_ref[lane] > 0
    features = [(r, _weight(r, d), pl.ds(r * d, d)) for r in range(phi_rows(d))]

    def rolled(x, r):  # column c holds x[:, (c + r) % d]
        return x if r == 0 else pltpu.roll(x, d - r, 1)

    @pl.when(alive)
    def _answer():
        q, key, kept = q_ref[...], k_ref[...].astype(f32), kept_ref[...]
        weights = row_ref[...] * jnp.square(jax.lax.dot_general(q, key, (((1,), (1,)), ((), ())), precision=_HIGHEST, preferred_element_type=f32))
        num = jax.lax.dot_general(weights, v_ref[...].astype(f32), (((1,), (0,)), ((), ())), precision=_HIGHEST, preferred_element_type=f32)
        den = jnp.sum(weights, axis=1, keepdims=True)
        num0, den0 = jnp.zeros(q.shape, f32), jnp.zeros((q.shape[0], 1), f32)
        for r, w, rows in features:
            pq = q * rolled(q, r) * w  # phi's row r of the queries
            # ONE float32 product: sound at these 8 left rows where ``_query_state``'s shapes are not (PERF.md section 7)
            num0 = num0 + jax.lax.dot_general(
                pq, s_ref[rows, :].astype(f32), (((1,), (1,)), ((), ())), precision=QUERY_PRECISION, preferred_element_type=f32
            )
            den0 = den0 + jnp.sum(pq * z_ref[r, head, :].astype(f32), axis=1, keepdims=True)
        num, den = num + kept * num0, den + kept * den0
        o_ref[...] = num / jnp.where(den == 0.0, 1.0, den)  # rows past n_rep are not read

    @pl.when(jnp.logical_not(alive))
    def _idle():
        o_ref[...] = jnp.zeros(o_ref.shape, f32)

    @pl.when(due)
    def _fold():
        key, vt, kept = k_ref[...].astype(f32), v_ref[...].astype(f32).T, kept_ref[...]  # the values as columns [d, C]
        weighed = key * col_ref[...]
        for r, w, rows in features:
            pk = weighed * rolled(key, r) * w  # [C, d]
            entered = jax.lax.dot_general(vt, pk, (((1,), (0,)), ((), ())), precision=_HIGHEST, preferred_element_type=f32)
            s_out[rows, :] = (kept * s_ref[rows, :].astype(f32) + entered).astype(s_out.dtype)  # [d (value), d (feature column)]
            z_out[r, head, :] = (kept * z_ref[r, head, :].astype(f32) + jnp.sum(pk, axis=0, keepdims=True)).astype(z_out.dtype)

    # no lane is due: every program's block is the one the first program read, written once as it was
    @pl.when(jnp.logical_and(write_lane[0] < 0, jnp.logical_and(lane == 0, pl.program_id(1) == 0)))
    def _through():
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]


def _held_blocks(mine, heads: int):
    """The block of a pool that the pipeline holds at each program of the grid
    (lane, KV head) when only the lanes ``mine`` [b] ask for their own: a lane
    that does not names the block of the program BEFORE it (the last head of
    the last lane that did), or the first head of the first lane that will where
    none came before.  Pallas copies a block when the index MOVES between one
    program and the next: so nothing is fetched for a lane that is not live,
    no slot is written that is not due, and each due slot once.  Returns (lane
    [b] int32, -1 throughout where no lane asks; head [b] int32)."""
    b = mine.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(mine, idx, -1))
    first = jnp.min(jnp.where(mine, idx, b))
    lane = jnp.where(last >= 0, last, jnp.where(first < b, first, -1))
    return lane.astype(jnp.int32), jnp.where(last >= 0, heads - 1, 0).astype(jnp.int32)


def _retention_decode_pallas(q, keys, vals, left, kept, due, state, norm, layer, live, *, interpret: bool):
    b, h, d = q.shape
    g, rows, every = keys.shape[2], phi_rows(d), keys.shape[3]
    n_rep = h // g
    rows8 = -(-n_rep // 8) * 8  # whole sublane tiles: the queries, zeros
    f32 = jnp.float32
    qf = jnp.pad(q.astype(f32).reshape(b, g, n_rep, d), ((0, 0), (0, 0), (0, rows8 - n_rep), (0, 0)))
    at_head = lambda bi, gi, *_: (bi, gi, 0, 0)  # noqa: E731
    at_rows = lambda bi, gi, lay, *_: (lay[0], bi, gi, 0, 0)  # noqa: E731

    def held(bi, gi, mine, lane, head):
        return jnp.where(mine[bi] > 0, bi, jnp.maximum(lane[bi], 0)), jnp.where(mine[bi] > 0, gi, head[bi])

    def read(bi, gi, lay, live, due, read_lane, read_head, *_):
        return lay[0], *held(bi, gi, live, read_lane, read_head), 0, 0

    def written(bi, gi, lay, live, due, read_lane, read_head, write_lane, write_head):
        # no lane is due: the block the first program read (its own, or the first live lane's: head 0 either way)
        first = jnp.where(live[0] > 0, 0, jnp.maximum(read_lane[0], 0))
        lane, head = held(bi, gi, due, write_lane, write_head)
        return lay[0], jnp.where(write_lane[0] < 0, first, lane), head, 0, 0

    # the normaliser a LANE at a time, [rows, kv_heads, d]: the order the device keeps that pool in (65 rows are no
    # whole sublane tiles, so its heads lie inside its rows), where the transposes are free and XLA copies nothing
    lane_of = lambda at: lambda *a: (*at(*a)[:2], 0, 0, 0)  # noqa: E731

    by_lane = norm.transpose(0, 1, 3, 2, 4)
    out, state, by_lane = pl.pallas_call(
        functools.partial(_retention_kernel, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(b, g),
            in_specs=[
                pl.BlockSpec((None, None, rows8, d), at_head),
                pl.BlockSpec((None, None, None, every, d), at_rows),
                pl.BlockSpec((None, None, None, every, d), at_rows),
                pl.BlockSpec((None, None, 1, every), at_head),
                pl.BlockSpec((None, None, every, 1), at_head),
                pl.BlockSpec((None, None, 1, 1), at_head),
                pl.BlockSpec((None, None, None, rows * d, d), read),
                pl.BlockSpec((None, None, rows, g, d), lane_of(read)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, rows8, d), at_head),
                pl.BlockSpec((None, None, None, rows * d, d), written),
                pl.BlockSpec((None, None, rows, g, d), lane_of(written)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, g, rows8, d), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct(by_lane.shape, norm.dtype),
        ],
        # the pools are updated where they lie (inputs count the scalar-prefetch arguments)
        input_output_aliases={13: 1, 14: 2},
        # in the grid's order: what ``_held_blocks`` says of a block holds between one program and the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=kernel_form.interpret_params(interpret),
        name="retention_decode",
    )(
        layer.reshape(1), live.astype(jnp.int32), due.astype(jnp.int32), *_held_blocks(live, g), *_held_blocks(due, g),
        qf, keys, vals, left[:, :, None, :], left[..., None], kept[..., None, None], state, by_lane,
    )
    return out[:, :, :n_rep, :].reshape(b, h, d), state, by_lane.transpose(0, 1, 3, 2, 4)
