"""Fused blocked cross-entropy: lm_head matmul + softmax-CE without ever
materializing the full ``[tokens, vocab]`` logits in HBM.

Motivation (TPU): with V=32k vocab and f32 logits, the standard
``logits = x @ W; softmax_xent(logits)`` pattern writes B*S*V*4 bytes to HBM
and reads them back in the backward pass — ~1 GiB per step at d2048/s1024/b8
— which is pure bandwidth waste on a bandwidth-bound chip (BASELINE.md: the
bs16 step *regresses* because of it).  Here the token dimension is scanned
in chunks: each chunk computes its logits tile in bf16 on the MXU, reduces
to per-token loss in f32, and the tile dies with its chunk.

Nothing is recomputed for the backward pass and no tile is kept for it.  The
loss is a mean over valid tokens, so ``d loss / d logits = (softmax - onehot)
* valid / count`` needs nothing the backward pass brings but one scalar (the
loss's cotangent; ``count`` comes from ``targets`` before the scan).  So the
FORWARD scan, while a chunk's float32 tile is alive, forms ``dlogits`` and
the two transposed products: ``dx_c = dlogits @ W.T`` stacked as the scan's
output, ``dk += x_c.T @ dlogits`` in a float32 carry.  That is three
vocabulary-wide products a chunk, which is what the mathematics needs
(``jax.checkpoint`` on the chunk body would run four: the logits a second
time in the backward scan, 1.1 TFLOP a chunk of 4,096 at d4096/V32k).  What the scan
keeps for the backward pass is ``dx`` (``[tokens, d]``, x's dtype) and ``dk``
(``[d, vocab]``, the kernel's dtype), from the forward pass's last act to the
backward pass's first; the backward rule multiplies both by the cotangent.
Called without a gradient (validation) the scan is one product a chunk and
keeps nothing.

The reference has no analog (loss math lives in user pytorch code); this is
TPU-native design per SURVEY §7 hard-part (e).

Sharding: hidden is batch-sharded (dp/fsdp), the kernel may be
vocab-sharded (tp).  Everything here is plain jnp under jit, so XLA inserts
the psum for the vocab-sharded logsumexp per chunk.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _chunk_loss(
    x_chunk: jax.Array,       # [chunk, d]
    kernel: jax.Array,        # [d, vocab]
    tgt_chunk: jax.Array,     # [chunk] int; < 0 = ignore
    compute_dtype,
    return_internals: bool = False,
):
    """Sum of token losses + valid-token count for one chunk.

    ``return_internals`` additionally returns (logits, lse) — the
    bf16-residual custom VJP shares this exact forward math so the two
    paths cannot drift.
    """
    logits = jnp.dot(
        x_chunk.astype(compute_dtype),
        kernel.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )  # [chunk, vocab] f32 accumulate on the MXU, lives only inside the chunk
    valid = tgt_chunk >= 0
    safe_tgt = jnp.where(valid, tgt_chunk, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)                     # [chunk]
    tgt_logit = jnp.take_along_axis(
        logits, safe_tgt[:, None], axis=-1
    )[:, 0]                                                     # [chunk]
    token_loss = jnp.where(valid, lse - tgt_logit, 0.0)
    loss_sum = token_loss.sum()
    count = valid.sum().astype(jnp.float32)
    if return_internals:
        return loss_sum, count, logits, lse
    return loss_sum, count


# ---------------------------------------------------------------------------
# bf16-residual single tile: the backward pass reconstructs softmax probs
# from a BF16 copy of the logits instead of the f32 tile autodiff would
# keep.  Halves the residual's HBM traffic (write + 2 reads of ~1 GiB at
# the flagship shape, measured ~+0.01 MFU) at the cost of ~bf16-epsilon
# relative error on the lm_head gradient — opt-in for that reason.
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _tile_ce_bf16_residual(x, kernel, tgt):
    loss_sum, count = _chunk_loss(x, kernel, tgt, jnp.bfloat16)
    return loss_sum, count


def _tile_ce16_fwd(x, kernel, tgt):
    loss_sum, count, logits, lse = _chunk_loss(
        x, kernel, tgt, jnp.bfloat16, return_internals=True
    )
    # the ONLY tensor-sized residual is the bf16 logits copy
    return (loss_sum, count), (x, kernel, tgt, logits.astype(jnp.bfloat16), lse)


def _tile_ce16_bwd(res, g):
    x, kernel, tgt, logits16, lse = res
    g_loss, _ = g  # count is a constant wrt inputs
    valid = tgt >= 0
    safe_tgt = jnp.where(valid, tgt, 0)
    # all elementwise (iota-compare instead of a scatter) so XLA fuses the
    # whole dlogits computation into the two consumer matmuls — nothing
    # f32 tensor-sized materializes
    cols = jax.lax.broadcasted_iota(jnp.int32, logits16.shape, 1)
    p = jnp.exp(logits16.astype(jnp.float32) - lse[:, None])    # [n, vocab]
    dlogits = p - (cols == safe_tgt[:, None]).astype(jnp.float32)
    dlogits = jnp.where(valid[:, None], dlogits, 0.0) * g_loss
    d16 = dlogits.astype(jnp.bfloat16)
    dx = jnp.dot(
        d16, kernel.astype(jnp.bfloat16).T, preferred_element_type=jnp.float32
    ).astype(x.dtype)
    dk = jnp.dot(
        x.astype(jnp.bfloat16).T, d16, preferred_element_type=jnp.float32
    ).astype(kernel.dtype)
    return dx, dk, None


_tile_ce_bf16_residual.defvjp(_tile_ce16_fwd, _tile_ce16_bwd)


# ---------------------------------------------------------------------------
# chunked scan: a chunk's dlogits, dx and dk are made in the FORWARD scan,
# while its float32 logits tile is alive, and the backward rule only scales
# them by the loss's cotangent (the module docstring has the why).
# ---------------------------------------------------------------------------


def _valid_count(tgt: jax.Array) -> jax.Array:
    return jnp.maximum((tgt >= 0).sum().astype(jnp.float32), 1.0)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _scan_ce(x, kernel, tgt, compute_dtype):
    """Mean loss over ``x [chunks, chunk, d]``, ``tgt [chunks, chunk]``.  Not
    differentiated (validation), it is one product a chunk and keeps nothing."""

    def step(loss_sum, chunk):
        xs, ts = chunk
        return loss_sum + _chunk_loss(xs, kernel, ts, compute_dtype)[0], None

    loss_sum, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (x, tgt))
    return loss_sum / _valid_count(tgt)


def _scan_ce_fwd(x, kernel, tgt, compute_dtype):
    count = _valid_count(tgt)
    inv_count = 1.0 / count
    k_c = kernel.astype(compute_dtype)

    def step(carry, chunk):
        loss_sum, dk = carry
        xs, ts = chunk
        s, _, logits, lse = _chunk_loss(xs, kernel, ts, compute_dtype, return_internals=True)
        # elementwise from the float32 tile (an iota compare, no scatter), so
        # nothing tile-sized is written but dlogits in the compute dtype: the
        # rounding the transposed products give their operand in one pass
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        dlogits = jnp.exp(logits - lse[:, None]) - (cols == ts[:, None]).astype(jnp.float32)
        dlogits = jnp.where((ts >= 0)[:, None], dlogits * inv_count, 0.0).astype(compute_dtype)
        dx = jnp.dot(dlogits, k_c.T, preferred_element_type=jnp.float32).astype(xs.dtype)
        dk = dk + jnp.dot(xs.astype(compute_dtype).T, dlogits, preferred_element_type=jnp.float32)
        return (loss_sum + s, dk), dx

    init = (jnp.zeros((), jnp.float32), jnp.zeros(kernel.shape, jnp.float32))
    (loss_sum, dk), dx = jax.lax.scan(step, init, (x, tgt))
    return loss_sum / count, (dx, dk.astype(kernel.dtype))


def _scan_ce_bwd(compute_dtype, res, g):
    del compute_dtype
    dx, dk = res
    # a custom_vjp's backward rule is traced outside the caller's scope
    with jax.named_scope("loss.ce"):
        return (g * dx).astype(dx.dtype), (g * dk).astype(dk.dtype), None


_scan_ce.defvjp(_scan_ce_fwd, _scan_ce_bwd)


@jax.named_scope("loss.ce")
def fused_cross_entropy(
    hidden: jax.Array,            # [batch, seq, d] (or [tokens, d])
    kernel: jax.Array,            # [d, vocab]
    targets: jax.Array,           # [batch, seq] (or [tokens]) int; < 0 ignored
    *,
    chunk_size: Optional[int] = None,
    compute_dtype=jnp.bfloat16,
    batch_shards: int = 1,
    bf16_residual: bool = False,
) -> jax.Array:
    """Mean softmax cross-entropy over valid tokens.

    Equivalent to
    ``optax.softmax_cross_entropy_with_integer_labels(hidden @ kernel, targets)``
    masked-mean'd, to f32 accuracy of the bf16 matmul.  Two modes:

    - single tile (``chunk_size=0``): one bf16 matmul with f32 accumulation;
      autodiff keeps the f32 logits tile as a backward residual (no
      recompute) — fastest when that residual fits (measured +1.2 MFU pts
      at d2048/V32k/8k tokens on v5e);
    - chunked scan (``chunk_size=N``): NO logits tile outlives its chunk —
      the long-context / huge-batch mode (caps live memory at chunk x vocab).
      Under differentiation the forward scan makes a chunk's ``dlogits``,
      ``dx`` and ``dk`` while its tile is alive (three vocabulary-wide
      products a chunk, none in the backward pass, nothing recomputed) and
      keeps ``dx`` (x's shape and dtype) and ``dk`` (the kernel's shape
      and dtype) for the backward rule, which scales them by the loss's
      cotangent ``g``.  Same arithmetic as autodiff of the chunk body:
      bf16 products with f32 accumulation, ``softmax`` from the f32 tile,
      ``dlogits`` rounded to the compute dtype as the MXU rounds a float32
      operand in its one pass, chunks of ``dk`` summed in float32.  The one
      new rounding: ``g * dx`` (and ``g * dk`` under a kernel that is not
      float32) is rounded to its dtype a second time when ``g`` is not a
      power of two (``g`` is 1 where the loss is what is differentiated).
      A caller that freezes the head (no gradient asked for the kernel)
      pays two products: ``jit`` prunes the ``dk`` carry.

    ``chunk_size=None`` picks by the PER-SHARD f32 residual size
    (``batch_shards`` = product of batch-sharding mesh axes: under dp the
    tile is sharded, so the global token count overstates it).

    Everything here, forward and backward, runs under the scope ``loss.ce``.
    """
    d = hidden.shape[-1]
    x = hidden.reshape(-1, d)
    tgt = targets.reshape(-1)
    n = x.shape[0]

    # the bf16-residual path is a single-tile variant whose fwd matmul is
    # bf16 by construction; honoring it under f32 compute would degrade
    # the forward loss beyond the documented backward-only tradeoff
    bf16_residual = bf16_residual and compute_dtype == jnp.bfloat16

    if chunk_size is None:
        vocab = kernel.shape[-1]
        # backward residual per batch shard in single-tile mode: f32
        # logits by default, a bf16 copy under bf16_residual
        bytes_per = 2 if bf16_residual else 4
        tile_bytes = n * vocab * bytes_per // max(batch_shards, 1)
        # measured on v5e (d2048/L8/V32k): 1GB residual (8k tokens) is
        # fastest; 2GB (16k tokens) loses to the scan
        chunk_size = 0 if tile_bytes <= (3 << 29) else 4096

    if chunk_size <= 0:
        # single-tile is an explicit opt-in (or auto pick): the f32 logits
        # tile survives as a backward residual.  An explicit chunk_size >= n
        # still runs the scan with one chunk — callers who asked for
        # chunking asked for the memory guarantee.
        if bf16_residual:
            loss_sum, count = _tile_ce_bf16_residual(x, kernel, tgt)
        else:
            loss_sum, count = _chunk_loss(x, kernel, tgt, compute_dtype)
        return loss_sum / jnp.maximum(count, 1.0)
    chunk_size = min(chunk_size, n)

    pad = (-n) % chunk_size
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)], axis=0)
        tgt = jnp.concatenate([tgt, jnp.full((pad,), -1, tgt.dtype)], axis=0)
    num_chunks = x.shape[0] // chunk_size
    x = x.reshape(num_chunks, chunk_size, d)
    tgt = tgt.reshape(num_chunks, chunk_size)

    return _scan_ce(x, kernel, tgt, compute_dtype)


def naive_cross_entropy(
    hidden: jax.Array, kernel: jax.Array, targets: jax.Array
) -> jax.Array:
    """Reference implementation (materializes logits); used by tests."""
    logits = jnp.dot(hidden, kernel).astype(jnp.float32)
    valid = targets >= 0
    safe = jnp.where(valid, targets, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    per_tok = jnp.where(valid, lse - tgt, 0.0)
    return per_tok.sum() / jnp.maximum(valid.sum().astype(jnp.float32), 1.0)
