"""Gated DeltaNet's delta rule (Yang, Kautz and Hatamizadeh, arXiv:2412.06464):
a state a head that a token decays, CORRECTS by what it already holds, and
reads.

For one value head whose key is ``k_t`` (unit length), query ``q_t``, value
``v_t``, write strength ``beta_t`` in (0, 1) and decay ``exp(g_t)``, ``g_t <=
0``, the state ``S`` is ``[K (key), V (value)]`` float32, zero at the start::

    S <- exp(g_t) S;   r = S^T k_t;   S <- S + k_t (x) beta_t (v_t - r);   o_t = S^T q_t

Unlike a Mamba-2 or a retention state (``ops/ssm.py``, ``ops/retention.py``),
which only ADD a rank-one term to a decayed state, the update reads the state
before it writes it: per token it is ``(I - beta k k^T) exp(g) S + beta k v^T``.

**The state pool** (``models/cache_kinds.py``, the kind ``delta_slot``):
``[layers, lanes + 1, heads, K, V]``: a head's state lies ``[K, V]``, the values
along the chip's lanes, so that both reads of a decode step (``S^T k``, ``S^T
q``) are sums down the sublanes, plain vector adds; decode lane ``l`` owns slot
``l``, the slot past the last lane is scratch (what an idle lane's program is
sent to, as ``ops/ssm.py``'s).

* :func:`gdn_decode`: one token a lane, in place: the state read once and
  written once.  The Pallas kernel's grid is (blocks of heads, lane); a program
  decays each of its heads' states, takes ``r`` off it, adds the corrected
  rank-one term, reads the new state out and writes it back into the pool's own
  buffer (``input_output_aliases``).  No product goes to the MXU: a head's two
  reads are 2 x 16 K multiply-adds, and a float32 product at full precision
  would cost six passes each to save them.
* :func:`gdn_chunk`: ``s`` tokens after a state (the prefill walk's chunk, and a
  whole sequence): the chunked WY / UT-transform form of the paper's section 3.
  Inside a sub-chunk of ``chunk`` tokens, with ``G_i`` the running sum of ``g``
  and ``A[i, j] = beta_i (k_i . k_j) exp(G_i - G_j)`` for ``i > j``, the
  pseudo-values ``U`` and the keys ``W`` the carried state is corrected by solve
  the unit lower-triangular system ``(I + A) [U | W] = [beta v | beta exp(G)
  k]`` (one blocked solve for all sub-chunks, heads and rows at once: it
  depends on no state; :func:`_unit_lower_inverse`: diagonal blocks of 16 rows
  by forward substitution, the rest by products, as the published kernels do,
  where XLA's own triangular solve is a loop over every row that re-reads the
  whole batch of systems a row); then, sub-chunk after sub-chunk under a ``scan``,
  ``V' = U - W S``, ``O = (exp(G) q) S + tril(q k^T exp(G_i - G_j)) V'`` and
  ``S <- exp(G_last) S + (exp(G_last - G) k)^T V'``.  ``jax.numpy`` on every
  backend, float32 at the highest matmul precision.

**A decay a CHANNEL** (Kimi Delta Attention, "Kimi Linear", arXiv:2510.26692
section 3): ``g`` of one more axis, ``[..., heads, K]``, and ``exp(g_t)`` a vector
down the state's key axis in the scalar's place, ``S <- Diag(exp(g_t)) S``.  The
rule, the pool, the decode kernel (whose decay already lies ``[K, heads]`` down a
column) and the solve are the same; what changes is the chunk form's pairwise
decay: ``A[i, j] = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` no longer takes a
scalar out of the dot product, so both sides are scaled round a reference row
before it (:func:`_pairwise_channel`), in blocks of ``_DECAY_BLOCK`` rows whose
exponents stay inside float32 while ``g >= DECAY_FLOOR`` a token (the bounded
gate's ``kda_lower_bound`` -5 is inside it).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from determined_tpu.ops import kernel_form

_HIGHEST = jax.lax.Precision.HIGHEST
#: VMEM the decode kernel may take: a program's 2 MB of states, held twice coming in and twice going out
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
#: the state one program of the decode kernel aims to move each way (``ops/ssm.py`` says why)
_PROGRAM_BYTES = 2 * 1024 * 1024


def state_shape(layers: int, lanes: int, heads: int, key_dim: int, value_dim: int) -> Tuple[int, ...]:
    """The state pool: a slot a decode lane a layer, and one scratch slot after them."""
    return (layers, lanes + 1, heads, key_dim, value_dim)


def kernel_takes(heads: int, key_dim: int, value_dim: int, state_dtype) -> bool:
    """Whether the decode kernel runs these shapes: a head's state is whole
    tiles (its keys whole sublane tiles of either dtype, its values whole lane
    tiles) and the heads whole sublane tiles."""
    return key_dim % 16 == 0 and value_dim % 128 == 0 and heads % 8 == 0 and jnp.dtype(state_dtype).itemsize in (2, 4)


def heads_a_program(heads: int, key_dim: int, value_dim: int, state_dtype) -> int:
    """How many heads one program of the decode kernel takes, from the shapes:
    the most whole sublane tiles of heads that divide ``heads`` and whose states
    stay within ``_PROGRAM_BYTES`` (at least one tile)."""
    head_bytes = key_dim * value_dim * jnp.dtype(state_dtype).itemsize
    return max(n for n in range(8, heads + 1, 8) if heads % n == 0 and (n == 8 or n * head_bytes <= _PROGRAM_BYTES))


def l2_heads(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Each head of ``x`` [..., width] at unit Euclidean length, float32: ``x / sqrt(sum x^2 + eps)``."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


# ---------------------------------------------------------------------------
# a chunk of tokens against a state, and into it
# ---------------------------------------------------------------------------


def gdn_chunk(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, state: jax.Array, live: jax.Array,
    *, chunk: int = 64,
) -> Tuple[jax.Array, jax.Array]:
    """``s`` tokens a row of the batch after the ones ``state`` [b, heads, K, V]
    already holds.  ``q`` / ``k`` [b, s, heads, K] (a head's own, normalised and
    scaled by the caller), ``v`` [b, s, heads, V], ``g`` (the decay's logarithm)
    [b, s, heads], or [b, s, heads, K] a decay a channel (no smaller than
    ``DECAY_FLOOR`` a token), and ``beta`` [b, s, heads] float32, ``live`` [b, s]
    marks the tokens that exist: the others neither decay the state nor enter it,
    and what they are answered is not read.  Sub-chunks of ``chunk`` tokens (one
    of ``s`` where ``chunk`` does not divide it).  Returns (o [b, s, heads, V]
    float32, the state after the tokens in its own dtype)."""
    f32 = jnp.float32
    b, s, h, dk = k.shape
    dv = v.shape[-1]
    c = chunk if s % chunk == 0 else s
    n = s // c
    channel = g.ndim == q.ndim
    cut = lambda t: t.astype(f32).reshape(b, n, c, h, -1).transpose(1, 0, 3, 2, 4)  # noqa: E731  [n, b, h, c, .]
    qf, kf, vf = cut(q), cut(k), cut(v)
    gate = cut(jnp.where(live[(...,) + (None,) * (g.ndim - 2)], g.astype(f32), 0.0))  # [n, b, h, c, K], or [n, b, h, c] a head
    gate = gate if channel else gate[..., 0]
    write = cut(jnp.where(live[..., None], beta.astype(f32), 0.0))  # [n, b, h, c, 1]: a token that is not writes nothing
    cum = jnp.cumsum(gate, axis=-2 if channel else -1)  # G: the decay's logarithm from the sub-chunk's start up to each token
    seen = jnp.tril(jnp.ones((c, c), bool))
    # ``lower()`` / ``inside()``: beta_i sum_c k_ic k_jc exp(G_i - G_j) and the same sum of the queries against the keys, each
    # made where the head's form made it (the program's text is a cache key); ``at(G)``: exp(G) against a row of K values
    if channel:
        kk, qk = _pairwise_channel(kf, qf, cum)
        lower, inside, at = (lambda: write * kk), (lambda: qk), jnp.exp
    else:
        since = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :], 0.0))  # exp(G_i - G_j), i >= j
        kk = jnp.einsum("nbhik,nbhjk->nbhij", kf, kf, precision=_HIGHEST)
        lower = lambda: write * kk * since  # noqa: E731
        inside = lambda: jnp.einsum("nbhik,nbhjk->nbhij", qf, kf, precision=_HIGHEST) * since  # noqa: E731
        at = lambda t: jnp.exp(t)[..., None]  # noqa: E731
    # the system a sub-chunk solves: it depends on no state, so every sub-chunk's is solved at once
    inverse = _unit_lower_inverse(jnp.where(jnp.tril(seen, -1), lower(), 0.0))
    rhs = jnp.concatenate([write * vf, write * at(cum) * kf], axis=-1)
    solved = jnp.einsum("nbhij,nbhjx->nbhix", inverse, rhs, precision=_HIGHEST)
    u, w = solved[..., :dv], solved[..., dv:]
    inside = jnp.where(seen, inside(), 0.0)
    q_in = qf * at(cum)  # a query against the carried state, decayed up to its own token
    total = cum[..., -1, :] if channel else cum[..., -1]
    k_out = kf * at((total[..., None, :] if channel else total[..., None]) - cum)  # a key decayed to the sub-chunk's end

    def body(s0, part):
        u, w, inside, q_in, k_out, total = part
        fresh = u - jnp.einsum("bhik,bhkv->bhiv", w, s0, precision=_HIGHEST)  # V': what each token really writes
        out = jnp.einsum("bhik,bhkv->bhiv", q_in, s0, precision=_HIGHEST) + jnp.einsum("bhij,bhjv->bhiv", inside, fresh, precision=_HIGHEST)
        s1 = _down_keys(jnp.exp(total), channel) * s0 + jnp.einsum("bhik,bhiv->bhkv", k_out, fresh, precision=_HIGHEST)
        return s1, out

    parts = (u, w, inside, q_in, k_out, total)
    if n == 1:
        s1, out = body(state.astype(f32), tuple(t[0] for t in parts))
        out = out[None]
    else:
        s1, out = jax.lax.scan(body, state.astype(f32), parts)
    return out.transpose(1, 0, 3, 2, 4).reshape(b, s, h, dv), s1.astype(state.dtype)


#: rows of a block of :func:`_pairwise_channel`, and the least decay's logarithm a token it takes: round a block's
#: middle row a factor's exponent reaches 8 rows x 5.5 = 44 either way, and the product of two ABOVE the diagonal (a
#: pair the caller masks) 15 rows x 5.5 = 82.5 < ln(float32 max) = 88.7: no masked pair is inf, so none is NaN in a gradient
_DECAY_BLOCK = 16
DECAY_FLOOR = -5.5


def _pairwise_channel(kf: jax.Array, qf: jax.Array, cum: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``sum_c x_ic k_jc exp(G_ic - G_jc)`` for ``x`` the keys and the queries
    [..., c, K] under a running decay ``cum`` (``G``) a channel, [..., c, c] each;
    what lies above the diagonal is the caller's to mask (it is finite).  The
    exponential does not leave the dot product as a scalar, so it is split round
    a reference row ``m``: ``(x_i exp(G_i - G_m)) . (k_j exp(G_m - G_j))``, the
    middle row of ``i``'s block of ``_DECAY_BLOCK`` rows.  A key of an EARLIER
    block is scaled by no more than one (``G`` only falls), one of the block
    itself and a row of it by ``exp(8 rows' decay)`` at most either way; the keys
    of later blocks are masked, so their exponent is set to 0.  A row before the
    middle one is scaled round a LATER row: what it is answered moves by a
    rounding (1e-7) with the tokens after it in its block, and by nothing more."""
    c, dk = kf.shape[-2:]
    m = _DECAY_BLOCK
    size = -(-c // m) * m  # whole blocks: the rows past ``c`` are zeros under the last row's decay
    pad = lambda t, mode: jnp.pad(t, [(0, 0)] * (t.ndim - 2) + [(0, size - c), (0, 0)], mode=mode)  # noqa: E731
    kf, qf, cum = pad(kf, "constant"), pad(qf, "constant"), pad(cum, "edge")
    blocks = lambda t: t.reshape(t.shape[:-2] + (size // m, m, dk))  # noqa: E731
    middle = blocks(cum)[..., m // 2, :]  # [..., blocks, K]
    left = jnp.exp(blocks(cum) - middle[..., None, :])  # a block's rows round its own middle row
    behind = (jnp.arange(size) // m)[None, :] <= jnp.arange(size // m)[:, None]  # [blocks, size]: key j not after block I
    right = kf[..., None, :, :] * jnp.exp(jnp.where(behind[..., None], middle[..., None, :] - cum[..., None, :, :], 0.0))
    pair = lambda x: jnp.einsum("...Iik,...Ijk->...Iij", blocks(x) * left, right, precision=_HIGHEST).reshape(x.shape[:-2] + (size, size))[..., :c, :c]  # noqa: E731
    return pair(kf), pair(qf)


#: rows of a diagonal block :func:`_unit_lower_inverse` inverts by substitution (the published kernels' 16)
_SOLVE_BLOCK = 16


def _diagonal_blocks(a: jax.Array, m: int) -> jax.Array:
    """The ``m x m`` blocks on the diagonal of ``a`` [..., n, n]: [..., n / m, m, m]."""
    n = a.shape[-1]
    blocks = a.reshape(a.shape[:-2] + (n // m, m, n // m, m))
    return jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1, -3)


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for a strictly lower-triangular ``a`` [..., c, c], float32: a
    blocked forward substitution.  Diagonal blocks of ``_SOLVE_BLOCK`` rows are
    inverted row by row (row ``i`` of the inverse is ``e_i - sum_{j<i} a_ij
    row_j``; unrolled, every block of every system at once), then neighbouring
    blocks are merged, twice as wide a pass: ``[[T11, 0], [-T22 A21 T11, T22]]``."""
    c = a.shape[-1]
    size = 1 << (c - 1).bit_length()  # whole pairs of blocks: the rows past ``c`` are the identity's
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 2) + [(0, size - c)] * 2)
    m = min(size, _SOLVE_BLOCK)
    block, eye, rows = _diagonal_blocks(a, m), jnp.eye(m, dtype=a.dtype), []
    for i in range(m):
        row = jnp.broadcast_to(eye[i], block.shape[:-2] + (m,))
        if i:
            row = row - jnp.einsum("...j,...jk->...k", block[..., i, :i], jnp.stack(rows, axis=-2), precision=_HIGHEST)
        rows.append(row)
    inverse = jnp.stack(rows, axis=-2)  # [..., size / m, m, m]
    while m < size:
        below = _diagonal_blocks(a, 2 * m)[..., m:, :m]  # A21 of each pair
        first, second = inverse[..., 0::2, :, :], inverse[..., 1::2, :, :]
        cross = -jnp.matmul(jnp.matmul(second, below, precision=_HIGHEST), first, precision=_HIGHEST)
        top = jnp.concatenate([first, jnp.zeros_like(first)], axis=-1)
        inverse = jnp.concatenate([top, jnp.concatenate([cross, second], axis=-1)], axis=-2)
        m *= 2
    return inverse[..., 0, :c, :c]


def gdn_recurrence(q, k, v, g, beta, state, live) -> Tuple[jax.Array, jax.Array]:
    """:func:`gdn_chunk`'s arguments (a decay a head or a channel) through the
    rule as it is stated, one token at a time under a ``scan``: what the chunked
    form and the decode step are held to in tests."""
    f32 = jnp.float32

    def token(s0, at):
        q_t, k_t, v_t, g_t, b_t, live_t = at  # [b, h, .]; [b, h]; [b]
        dec = _down_keys(jnp.exp(g_t), g_t.ndim == k_t.ndim) * s0
        r = jnp.sum(dec * k_t[..., :, None], axis=-2)
        s1 = dec + k_t[..., :, None] * (b_t[..., None] * (v_t - r))[..., None, :]
        s1 = jnp.where(live_t[:, None, None, None], s1, s0)
        return s1, jnp.sum(s1 * q_t[..., :, None], axis=-2)

    seq = lambda t: jnp.moveaxis(t.astype(f32), 1, 0)  # noqa: E731
    s1, out = jax.lax.scan(token, state.astype(f32), (seq(q), seq(k), seq(v), seq(g), seq(beta), jnp.moveaxis(live, 1, 0)))
    return jnp.moveaxis(out, 0, 1), s1.astype(state.dtype)


def _down_keys(kept: jax.Array, channel: bool) -> jax.Array:
    """A decay ``kept`` a head ``[..., heads]``, or under ``channel`` ``[..., heads, K]``, against a state ``[..., heads, K, V]``."""
    return kept[..., None] if channel else kept[..., None, None]


# ---------------------------------------------------------------------------
# one token a lane
# ---------------------------------------------------------------------------


def gdn_decode(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, state: jax.Array, layer, live: jax.Array,
    *, impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One decode step of one layer over the state pool, in place.

    ``q`` / ``k`` [lanes, heads, K] (a value head's own, normalised and scaled),
    ``v`` [lanes, heads, V], ``g`` [lanes, heads] (or [lanes, heads, K]: a decay a
    channel) and ``beta`` [lanes, heads] float32, ``state``
    the whole pool (:func:`state_shape`), ``layer`` the layer to update, ``live``
    [lanes] bool: an idle lane's slot is left as it is (neither read nor
    written) and its output is zeros.  Returns (o [lanes, heads, V] float32,
    state).

    ``impl``: ``"kernel"``, ``"kernel_interpret"`` (tests), ``"jnp"`` or None:
    the kernel on a TPU when :func:`kernel_takes` the shapes.
    """
    heads, (dk, dv) = q.shape[1], state.shape[-2:]
    impl = kernel_form.resolve_impl(
        impl, kernel_takes(heads, dk, dv, state.dtype),
        f"the delta-rule kernel needs keys of whole 16-wide and values of whole 128-wide tiles and heads in eights "
        f"(got {heads} heads, keys of {dk}, values of {dv}, {state.dtype})",
    )
    return _gdn_decode(q, k, v, g, beta, state, jnp.asarray(layer, jnp.int32), live, impl=impl)


# one jitted function, the layer an argument (``ops/kernel_form.py`` says why)
@functools.partial(jax.jit, static_argnames=("impl",))
def _gdn_decode(q, k, v, g, beta, state, layer, live, *, impl):
    f32 = jnp.float32
    q, k, v, kept, beta = q.astype(f32), k.astype(f32), v.astype(f32), jnp.exp(g.astype(f32)), beta.astype(f32)
    if impl == "jnp":
        out, state = _gdn_decode_jnp(q, k, v, kept, beta, state, layer, live)
    else:
        out, state = _gdn_decode_pallas(q, k, v, kept, beta, state, layer, live, interpret=impl == "kernel_interpret")
    return jnp.where(live[:, None, None], out, 0.0), state


def _gdn_decode_jnp(q, k, v, kept, beta, state, layer, live):
    lanes = q.shape[0]
    s0 = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)[:lanes]
    dec = _down_keys(kept, kept.ndim == k.ndim) * s0.astype(jnp.float32)
    r = jnp.sum(dec * k[..., :, None], axis=-2)
    s1 = dec + k[..., :, None] * (beta[..., None] * (v - r))[..., None, :]
    out = jnp.sum(s1 * q[..., :, None], axis=-2)
    s1 = jnp.where(live[:, None, None, None], s1.astype(state.dtype), s0)
    return out, jax.lax.dynamic_update_slice(state, s1[None], (layer, 0, 0, 0, 0))


def _gdn_kernel(layer_ref, live_ref, q_ref, k_ref, kept_ref, v_ref, beta_ref, s_ref, y_ref, s_out, *, heads):
    """One (block of ``heads`` heads, lane): ``q_ref`` / ``k_ref`` / ``kept_ref``
    [K, heads] hold a head's query, key and decay down a column, ``v_ref`` /
    ``beta_ref`` [heads, V] its value and write strength along a row, ``s_ref``
    [heads, K, V] the heads' states: the lane's own, or the scratch slot's where
    it is idle."""
    f32 = jnp.float32

    @pl.when(live_ref[pl.program_id(1)] > 0)
    def _update():
        for h in range(heads):
            key = k_ref[:, h:h + 1]  # [K, 1]
            dec = kept_ref[:, h:h + 1] * s_ref[h].astype(f32)  # [K, V]
            held = jnp.sum(dec * key, axis=0, keepdims=True)  # r = S^T k, [1, V]
            new = dec + key * (beta_ref[h:h + 1, :] * (v_ref[h:h + 1, :] - held))
            s_out[h] = new.astype(s_out.dtype)
            y_ref[h:h + 1, :] = jnp.sum(new * q_ref[:, h:h + 1], axis=0, keepdims=True)

    @pl.when(live_ref[pl.program_id(1)] <= 0)
    def _idle():  # ``s_out`` is left unwritten: whatever goes back lands in the scratch slot, which nobody reads
        y_ref[...] = jnp.zeros(y_ref.shape, f32)


def _gdn_decode_pallas(q, k, v, kept, beta, state, layer, live, *, interpret: bool):
    lanes, h, dk = q.shape
    dv = v.shape[-1]
    r = heads_a_program(h, dk, dv, state.dtype)
    blocks = h // r
    scratch = state.shape[1] - 1
    at_heads = lambda gi, li, *_: (li, gi, 0)  # noqa: E731
    at_block = lambda gi, li, *_: (li, gi, 0, 0)  # noqa: E731
    # an idle lane reads and writes the scratch slot: its own is left where it lies.  The lanes are the grid's
    # fast axis, so that idle lanes in a row ask for the same block and it is moved once for all of them
    at_slot = lambda gi, li, lay, alive: (lay[0], jnp.where(alive[li] > 0, li, scratch), gi, 0, 0)  # noqa: E731
    columns = lambda t: t.reshape(lanes, blocks, r, dk).transpose(0, 1, 3, 2)  # noqa: E731  (a head's K values down a column)
    rows = lambda t: t.reshape(lanes, blocks, r, dv)  # noqa: E731
    y, state = pl.pallas_call(
        functools.partial(_gdn_kernel, heads=r),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(blocks, lanes),
            in_specs=[
                pl.BlockSpec((None, None, dk, r), at_block),
                pl.BlockSpec((None, None, dk, r), at_block),
                pl.BlockSpec((None, None, dk, r), at_block),
                pl.BlockSpec((None, None, r, dv), at_block),
                pl.BlockSpec((None, None, r, dv), at_block),
                pl.BlockSpec((None, None, r, dk, dv), at_slot),
            ],
            out_specs=[
                pl.BlockSpec((None, r, dv), at_heads),
                pl.BlockSpec((None, None, r, dk, dv), at_slot),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((lanes, h, dv), jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the pool is updated where it lies (inputs count the two scalar-prefetch arguments)
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=kernel_form.interpret_params(interpret),
        name="gdn_decode",
    )(
        layer.reshape(1), live.astype(jnp.int32),
        columns(q), columns(k), columns(kept if kept.ndim == q.ndim else jnp.broadcast_to(kept[..., None], q.shape)),
        rows(v), rows(jnp.broadcast_to(beta[..., None], v.shape)), state,
    )
    return y, state
