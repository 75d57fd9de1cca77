"""Mamba-2's selective state-space scan (Dao and Gu, arXiv:2405.21060): a
diagonal state a head that a token decays, adds to and reads.

For one head of width ``P`` whose group shares ``B_t`` and ``C_t`` of ``N``
values (``dt_t > 0`` after its softplus, ``A < 0`` a head)::

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T        (P x N, float32)
    y_t = H_t C_t + D x_t

**The state pool** (``models/cache_kinds.py``, the kind ``ssm_slot``):
``[layers, lanes + 1, heads, P, N]``: a head's state lies ``[P, N]``, ``N``
along the chip's lanes, which is how both forms below multiply it (the
contracted dimension minor: the chunked form's products read it as it lies,
and XLA lays no second copy of the pool out); decode lane ``l`` owns slot
``l``, and the slot past the last lane is scratch (what an idle lane's program
is sent to: see :func:`ssm_decode`).

* :func:`ssm_decode`: one token a lane, in place.  The Pallas kernel's grid is
  (groups of heads, lane): a program reads the ``heads / groups`` states that
  share a group's ``B`` and ``C`` once (of as many groups as make
  ``_PROGRAM_BYTES`` of state: one at Falcon-H1's heads of 128 x 256, four at
  Nemotron-H's of 64 x 128), decays each, adds the token (a column of ``dt
  x`` times the row ``B``), reads it out (``C`` times the new state's
  transpose, on the MXU) and writes the state back into the pool's own buffer
  (``input_output_aliases``).  An idle lane's program is pointed at the
  scratch slot by the index map and does nothing: its own slot is neither
  read nor written.
* :func:`ssm_chunk`: a chunk of tokens after a state (the prefill walk's chunk,
  and a whole sequence as one chunk): the chunked (SSD) form.  Inside the
  chunk ``y_i = sum_{j<=i} (C_i . B_j) exp(sum_{j<k<=i} dt_k A) dt_j x_j`` as
  matrix products, plus the decayed read of the state at the chunk's start; the
  state at its end is folded once.  ``jax.numpy`` on every backend: at the
  walk's 256 tokens a layer's products are 0.5 GFLOP beside 110 of its MLP.
* :func:`ssm_scan`: a sequence as a ``lax.scan`` of such chunks (the flax
  module's whole-sequence form).
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
#: VMEM the decode kernel may take: a group's 16 states of [128, 256] float32
#: are 2 MB, held twice coming in and twice going out, beside what the unrolled
#: heads spill
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
#: the state one program of the decode kernel aims to move each way: a grid step costs ~0.35 us whatever it does,
#: and 2 MB take 2.6 us at the chip's 819 GB/s
_PROGRAM_BYTES = 2 * 1024 * 1024


def state_shape(layers: int, lanes: int, heads: int, head_dim: int, d_state: int) -> Tuple[int, ...]:
    """The state pool: a slot a decode lane a layer, and one scratch slot after them."""
    return (layers, lanes + 1, heads, head_dim, d_state)


def kernel_takes(heads: int, groups: int, head_dim: int, d_state: int, state_dtype) -> bool:
    """Whether the decode kernel runs these shapes: a head's state is whole
    tiles (its width whole sublane tiles of either dtype, its state values whole
    lane tiles: a head of 64 is half a lane tile in ``y`` alone, which is a
    row's masked store), a group's heads whole sublane tiles."""
    return (
        head_dim % 64 == 0 and d_state % 128 == 0 and heads % groups == 0 and (heads // groups) % 8 == 0
        and jnp.dtype(state_dtype).itemsize in (2, 4)
    )


def groups_a_program(groups: int, heads_a_group: int, head_dim: int, d_state: int, state_dtype) -> int:
    """How many groups' heads one program of the decode kernel takes, from the
    shapes: the most that divide ``groups`` and whose states stay within
    ``_PROGRAM_BYTES`` (at least one)."""
    group_bytes = heads_a_group * head_dim * d_state * jnp.dtype(state_dtype).itemsize
    return max(n for n in range(1, groups + 1) if groups % n == 0 and (n == 1 or n * group_bytes <= _PROGRAM_BYTES))


# ---------------------------------------------------------------------------
# a chunk of tokens against a state, and into it
# ---------------------------------------------------------------------------


def ssm_chunk(
    x: jax.Array, B: jax.Array, C: jax.Array, dt: jax.Array, A: jax.Array, Dskip: jax.Array, state: jax.Array,
    live: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """``s`` tokens a row of the batch after the ones ``state`` [b, heads, P, N]
    already holds.  ``x`` [b, s, heads, P], ``B`` / ``C`` [b, s, groups, N], ``dt``
    [b, s, heads] float32 (after its softplus), ``A`` / ``Dskip`` [heads], ``live``
    [b, s] marks the tokens that exist: the others neither decay the state nor
    enter it, and what they are answered is not read.  Returns (y [b, s, heads,
    P] float32, the state after the chunk in its own dtype)."""
    f32 = jnp.float32
    b, s, h, p = x.shape
    g = B.shape[2]
    r = h // g
    xf, bf, cf = x.astype(f32), B.astype(f32), C.astype(f32)
    step = jnp.where(live[:, :, None], dt.astype(f32), 0.0).transpose(0, 2, 1)  # [b, h, s]
    cum = jnp.cumsum(step * A.astype(f32)[None, :, None], axis=-1)  # the decay's logarithm up to each token
    # inside the chunk: the masked quadratic form, a group's scores under each of its heads' decays
    scores = jnp.einsum("bign,bjgn->bgij", cf, bf, precision=_HIGHEST)
    seen = jnp.tril(jnp.ones((s, s), bool))
    decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :], 0.0))
    weights = jnp.where(seen, decay * jnp.repeat(scores, r, axis=1) * step[..., None, :], 0.0)  # [b, h, i, j]
    y = jnp.einsum("bhij,bjhp->bihp", weights, xf, precision=_HIGHEST)
    # what came before it, decayed up to each token; and the chunk into it, each token decayed to the chunk's
    # end.  A head is a batch dimension of both products, so that the state is multiplied as it lies, [P, N]
    s0 = state.astype(f32)
    b_head, c_head = jnp.repeat(bf, r, axis=2), jnp.repeat(cf, r, axis=2)  # [b, s, h, N]
    since = jnp.exp(cum).transpose(0, 2, 1)[..., None]  # [b, s, h, 1]
    y = y + since * jnp.einsum("bihn,bhpn->bihp", c_head, s0, precision=_HIGHEST)
    total = cum[..., -1]
    left = (jnp.exp(total[..., None] - cum) * step).transpose(0, 2, 1)[..., None]  # [b, s, h, 1]; 0 where no token is
    s1 = jnp.exp(total)[..., None, None] * s0 + jnp.einsum("bjhp,bjhn->bhpn", xf, b_head * left, precision=_HIGHEST)
    y = y + Dskip.astype(f32)[None, None, :, None] * xf
    return y, s1.astype(state.dtype)


def ssm_scan(x, B, C, dt, A, Dskip, chunk: int) -> jax.Array:
    """A whole sequence from an empty state, ``chunk`` tokens at a time (one
    chunk where ``chunk`` does not divide it): y [b, s, heads, P] float32."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    empty = jnp.zeros((b, h, p, n), jnp.float32)
    if s <= chunk or s % chunk:
        return ssm_chunk(x, B, C, dt, A, Dskip, empty, jnp.ones((b, s), bool))[0]
    cut = lambda t: jnp.moveaxis(t.reshape(b, s // chunk, chunk, *t.shape[2:]), 1, 0)  # noqa: E731
    every = jnp.ones((b, chunk), bool)

    def body(state, part):
        y, state = ssm_chunk(*part, A, Dskip, state, every)
        return state, y

    _, y = jax.lax.scan(body, empty, (cut(x), cut(B), cut(C), cut(dt)))
    return jnp.moveaxis(y, 0, 1).reshape(b, s, h, p)


# ---------------------------------------------------------------------------
# one token a lane
# ---------------------------------------------------------------------------


def ssm_decode(
    x: jax.Array, B: jax.Array, C: jax.Array, dt: jax.Array, A: jax.Array, Dskip: jax.Array, state: jax.Array, layer,
    live: jax.Array, *, impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One decode step of one layer over the state pool, in place.

    ``x`` [lanes, heads, P], ``B`` / ``C`` [lanes, groups, N], ``dt`` [lanes,
    heads] float32 (after its softplus), ``A`` / ``Dskip`` [heads], ``state`` the
    whole pool (:func:`state_shape`), ``layer`` the layer to update, ``live``
    [lanes] bool: an idle lane's slot is left as it is (neither read nor
    written) and its output is zeros.  Returns (y [lanes, heads, P] float32,
    state).

    ``impl``: ``"kernel"``, ``"kernel_interpret"`` (tests), ``"jnp"`` or None:
    the kernel on a TPU when :func:`kernel_takes` the shapes.
    """
    heads, groups, (p, n) = x.shape[1], B.shape[1], state.shape[-2:]
    impl = kernel_form.resolve_impl(
        impl, kernel_takes(heads, groups, p, n, state.dtype),
        f"the ssm kernel needs a head of whole 64-wide and a state of whole 128-wide tiles and 8 heads a group or a multiple "
        f"(got {heads} heads over {groups} groups, P {p}, N {n}, {state.dtype})",
    )
    return _ssm_decode(x, B, C, dt, A, Dskip, state, jnp.asarray(layer, jnp.int32), live, impl=impl)


# one jitted function, the layer an argument (``ops/kernel_form.py`` says why)
@functools.partial(jax.jit, static_argnames=("impl",))
def _ssm_decode(x, B, C, dt, A, Dskip, state, layer, live, *, impl):
    f32 = jnp.float32
    xf, dt = x.astype(f32), dt.astype(f32)
    kept = jnp.exp(dt * A.astype(f32)[None, :])  # [lanes, heads]: what of the state outlives the token
    enters = dt[..., None] * xf  # [lanes, heads, P]
    if impl == "jnp":
        y, state = _ssm_decode_jnp(enters, kept, B.astype(f32), C.astype(f32), state, layer, live)
    else:
        y, state = _ssm_decode_pallas(enters, kept, B.astype(f32), C.astype(f32), state, layer, live, interpret=impl == "kernel_interpret")
    return jnp.where(live[:, None, None], y + Dskip.astype(f32)[None, :, None] * xf, 0.0), state


def _ssm_decode_jnp(enters, kept, B, C, state, layer, live):
    lanes, h, _ = enters.shape
    r = h // B.shape[1]
    s0 = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)[:lanes]
    bh, ch = jnp.repeat(B, r, axis=1), jnp.repeat(C, r, axis=1)  # [lanes, heads, N]
    s1 = kept[..., None, None] * s0.astype(jnp.float32) + enters[..., :, None] * bh[..., None, :]
    y = jnp.sum(s1 * ch[..., None, :], axis=-1)
    s1 = jnp.where(live[:, None, None, None], s1.astype(state.dtype), s0)
    return y, jax.lax.dynamic_update_slice(state, s1[None], (layer, 0, 0, 0, 0))


def _ssm_kernel(layer_ref, live_ref, enters_ref, kept_ref, b_ref, c_ref, s_ref, y_ref, s_out, *, heads, groups):
    """One (``groups`` groups of ``heads`` heads each, lane): ``enters_ref`` [P,
    groups * heads] holds ``dt x`` of the heads as columns, ``kept_ref`` alike
    each head's decay down its column, ``b_ref`` / ``c_ref`` [groups, N] the
    groups' B and C, ``s_ref`` [groups * heads, P, N] the heads' states: the
    lane's own, or the scratch slot's where it is idle."""
    f32 = jnp.float32

    @pl.when(live_ref[pl.program_id(1)] > 0)
    def _update():
        for g in range(groups):
            b_row = b_ref[...] if groups == 1 else b_ref[g:g + 1, :]
            c_row = c_ref[...] if groups == 1 else c_ref[g:g + 1, :]
            c_rows = jnp.broadcast_to(c_row, (8, c_ref.shape[-1]))  # a whole sublane tile; row 0 is read
            for h in range(g * heads, (g + 1) * heads):
                new = kept_ref[:, h:h + 1] * s_ref[h].astype(f32) + enters_ref[:, h:h + 1] * b_row  # [P, N]
                s_out[h] = new.astype(s_out.dtype)
                read = jax.lax.dot_general(c_rows, new, (((1,), (1,)), ((), ())), precision=_HIGHEST, preferred_element_type=f32)
                y_ref[h:h + 1, :] = read[0:1, :]

    @pl.when(live_ref[pl.program_id(1)] <= 0)
    def _idle():  # ``s_out`` is left unwritten: whatever goes back lands in the scratch slot, which nobody reads
        y_ref[...] = jnp.zeros(y_ref.shape, f32)


def _ssm_decode_pallas(enters, kept, B, C, state, layer, live, *, interpret: bool):
    lanes, h, p = enters.shape
    n = B.shape[2]
    per = groups_a_program(B.shape[1], h // B.shape[1], p, n, state.dtype)
    g, r = B.shape[1] // per, h // B.shape[1] * per  # the grid's blocks of heads, and the heads of one
    scratch = state.shape[1] - 1
    at_heads = lambda gi, li, *_: (li, gi, 0)  # noqa: E731
    at_group = lambda gi, li, *_: (li, gi, 0, 0)  # noqa: E731
    # an idle lane reads and writes the scratch slot: its own is left where it lies.  The lanes are the grid's
    # fast axis, so that idle lanes in a row ask for the same block and it is moved once for all of them
    at_slot = lambda gi, li, lay, alive: (lay[0], jnp.where(alive[li] > 0, li, scratch), gi, 0, 0)  # noqa: E731
    columns = lambda t: t.reshape(lanes, g, r, p).transpose(0, 1, 3, 2)  # noqa: E731  (a head's P values down a column)
    rows = lambda t: t[:, :, None, :] if per == 1 else t.reshape(lanes, g, per, n)  # noqa: E731  (a program's groups' B or C)
    y, state = pl.pallas_call(
        functools.partial(_ssm_kernel, heads=r // per, groups=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(g, lanes),
            in_specs=[
                pl.BlockSpec((None, None, p, r), at_group),
                pl.BlockSpec((None, None, p, r), at_group),
                pl.BlockSpec((None, None, per, n), at_group),
                pl.BlockSpec((None, None, per, n), at_group),
                pl.BlockSpec((None, None, r, p, n), at_slot),
            ],
            out_specs=[
                pl.BlockSpec((None, r, p), at_heads),
                pl.BlockSpec((None, None, r, p, n), at_slot),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((lanes, h, p), jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the pool is updated where it lies (inputs count the two scalar-prefetch arguments)
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=kernel_form.interpret_params(interpret),
        name="ssm_decode",
    )(
        layer.reshape(1), live.astype(jnp.int32),
        columns(enters), columns(jnp.broadcast_to(kept[..., None], enters.shape)), rows(B), rows(C), state,
    )
    return y, state
