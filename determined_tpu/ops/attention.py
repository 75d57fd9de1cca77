"""Attention ops: reference implementation + dispatcher.

The reference platform has NO in-repo attention/kernels (SURVEY.md §2.10 —
all math lives in torch/DeepSpeed).  On TPU the attention kernel IS the
performance story, so this framework ships its own:

- ``reference_attention``: pure-jnp softmax attention (correctness anchor,
  small-seq fallback; XLA already fuses it well for short sequences).
- ``flash_attention``: Pallas blockwise kernel (ops/flash_attention.py),
  O(seq) memory, MXU-tiled.
- ``ring_attention``: sequence-parallel blockwise attention over the mesh
  "seq" axis (ops/ring_attention.py) for long-context.

All take [batch, heads, q_len, head_dim] q and [batch, kv_heads, kv_len,
head_dim] k/v (GQA when kv_heads < heads).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from determined_tpu.ops import kernel_form
from determined_tpu.parallel.mesh import MeshAxes

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """Expand kv heads for grouped-query attention."""
    if n_rep == 1:
        return k
    b, h, s, d = k.shape
    return jnp.broadcast_to(k[:, :, None], (b, h, n_rep, s, d)).reshape(b, h * n_rep, s, d)


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    window: Optional[int] = None,
) -> jax.Array:
    """Plain softmax attention; the semantics every other impl must match.

    ``q_offset``: global position of q[0] relative to k[0] (used by ring
    attention shards and KV-cache decoding).  ``window`` (causal only):
    query i sees keys ``i - window < j <= i``.
    """
    if window is not None and not causal:
        raise ValueError("a window needs causal attention")
    *_, q_len, head_dim = q.shape
    kv_len = k.shape[-2]
    n_rep = q.shape[-3] // k.shape[-3]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    scale = scale if scale is not None else head_dim ** -0.5
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q_len)[:, None]
        k_pos = jnp.arange(kv_len)[None, :]
        seen = q_pos >= k_pos
        if window is not None:
            seen &= q_pos - k_pos < window
        logits = jnp.where(seen, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    impl: str = "auto",
    scale: Optional[float] = None,
    mesh: Optional[Mesh] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Dispatcher: 'auto' picks flash on TPU for seqs worth tiling.
    ``window`` is honoured by every implementation it can pick.

    ``mesh``: the mesh the (global) operands are sharded over.  With more
    than one device the flash kernel runs per device inside ``shard_map``
    (:func:`sharded_flash_attention`); leave it None where the caller is
    already inside a manual region (pipeline stages).
    """
    if impl == "auto":
        impl = "flash" if kernel_form.on_tpu() and q.shape[-2] >= 256 else "reference"
    if impl == "reference":
        return reference_attention(q, k, v, causal=causal, scale=scale, window=window)
    if impl == "flash":
        from determined_tpu.ops.flash_attention import flash_attention

        if mesh is not None and mesh.size > 1:
            return sharded_flash_attention(
                q, k, v, mesh, causal=causal, scale=scale, window=window
            )
        return flash_attention(q, k, v, causal=causal, scale=scale, window=window)
    raise ValueError(f"unknown attention impl {impl!r}")


def sharded_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention over global arrays on a multi-device mesh.

    XLA cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so under GSPMD a bare ``pallas_call`` on
    sharded operands fails to compile on real chips — while the CPU
    interpreter lowers it to plain ops that do partition, which is how
    every virtual-mesh test passed.  Attention is independent per (batch,
    head), so each device runs the kernel on its own block: batch over the
    ``(dcn, data, fsdp)`` axes and heads over ``tensor``, the layout the
    sharding rules already give q/k/v.  A dim its axes do not divide stays
    whole on every device (still the kernel, never another
    implementation); kv heads ``tensor`` does not divide are expanded to
    full heads first, as ring attention does.
    """
    from determined_tpu.ops.flash_attention import flash_attention

    b, h = q.shape[0], q.shape[1]
    batch_axes = tuple(a for a in MeshAxes.BATCH_AXES if mesh.shape.get(a, 1) > 1)
    if b % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = ()
    tp = mesh.shape.get(MeshAxes.TENSOR, 1)
    head_axis = MeshAxes.TENSOR if tp > 1 and h % tp == 0 else None
    if head_axis is not None and k.shape[1] % tp:
        n_rep = h // k.shape[1]
        k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    spec = P(batch_axes or None, head_axis, None, None)
    return jax.shard_map(
        functools.partial(flash_attention, causal=causal, scale=scale, window=window),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
