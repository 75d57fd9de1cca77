"""Fused blocked cross-entropy: lm_head matmul + softmax-CE without ever
materializing the full ``[tokens, vocab]`` logits in HBM.

Motivation (TPU): with V=32k vocab and f32 logits, the standard
``logits = x @ W; softmax_xent(logits)`` pattern writes B*S*V*4 bytes to HBM
and reads them back in the backward pass — ~1 GiB per step at d2048/s1024/b8
— which is pure bandwidth waste on a bandwidth-bound chip (BASELINE.md: the
bs16 step *regresses* because of it).  Here the token dimension is scanned
in chunks: each chunk computes its logits tile in bf16 on the MXU, reduces
to per-token loss in f32, and the tile dies in VMEM/registers.
``jax.checkpoint`` on the chunk body makes the backward pass recompute the
tile instead of storing it, so the only HBM traffic is x, W, and the scan
carry.  The extra recompute is one lm_head matmul (<5% of model FLOPs); the
saved traffic is the whole logits tensor, twice.

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
    - chunked scan (``chunk_size=N``): ``jax.checkpoint`` per chunk, so NO
      logits tensor survives to the backward pass — the long-context /
      huge-batch mode (caps live memory at chunk x vocab).

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
        # fastest; 2GB (16k tokens) loses to the scan's remat
        chunk_size = 0 if tile_bytes <= (3 << 29) else 4096

    if chunk_size <= 0:
        # single-tile is an explicit opt-in (or auto pick): no remat, the
        # f32 logits tile survives as a backward residual.  An explicit
        # chunk_size >= n still runs the remat'd scan with one chunk —
        # callers who asked for chunking asked for the memory guarantee.
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

    body = jax.checkpoint(
        partial(_chunk_loss, compute_dtype=compute_dtype), prevent_cse=False
    )

    def scan_step(carry, chunk):
        loss_sum, count = carry
        xs, ts = chunk
        s, c = body(xs, kernel, ts)
        return (loss_sum + s, count + c), None

    (loss_sum, count), _ = jax.lax.scan(
        scan_step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (x, tgt)
    )
    return loss_sum / jnp.maximum(count, 1.0)


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
