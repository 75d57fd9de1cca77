"""Fused AdamW + global-norm clip: one read-modify-write sweep over HBM.

Motivation (BASELINE.md r3 roofline): the optax ``chain(clip_by_global_norm,
adamw)`` step is bandwidth-bound at ~9 HBM passes over param-sized arrays
(~26 ms of the 231 ms headline step).  The information-theoretic floor is
7 passes — read p, m, v, g; write p, m, v — plus one read of g for the
global norm.  This module hits that floor with a single Pallas kernel per
(large) leaf:

- clip scale, learning rate, and Adam bias corrections enter as SMEM
  scalars; b1/b2/eps/weight_decay are compile-time constants;
- ``input_output_aliases`` makes the p/m/v updates in-place (the Trainer
  donates the whole TrainState, so XLA reuses the buffers);
- optional bf16 first moment (``mu_dtype``) halves that leaf's traffic with
  the conversion fused into the same pass — the standalone-conversion cost
  that made optax's ``mu_dtype=bf16`` a loss (r3) does not exist here;
- small leaves (norm scales, biases) take the plain-jnp path: their traffic
  is negligible and padding them to kernel tiles would waste more than it
  saves.

The reference has no analog (optimizers live in torch userland); this is
the TPU-native answer to SURVEY §7's "optimizer at the bandwidth roofline"
hard part.  Semantics match ``optax.chain(clip_by_global_norm(c),
adamw(lr, b1, b2, eps, weight_decay=wd, mu_dtype=...))`` exactly
(verified by ``tests/test_ops.py::test_fused_adamw*``).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from determined_tpu.ops import kernel_form

# Per-ref block budget.  7 refs (p/m/v/g in, p/m/v out) x double-buffered
# must fit the 16 MiB scoped-VMEM budget; 1 MiB blocks measured 16.84M > 16M
# on v5e (OOM), 768 KiB measured fastest of {512K, 768K}.
_BLOCK_BYTES = 768 * 1024


def _min_pallas_size() -> int:
    """Leaves below this ride the jnp path (one big XLA fusion, near-zero
    launch overhead); leaves above it get their own Pallas sweep.

    The r4 xplane accounting measured ~120 us of fixed per-call overhead x
    34 sweeps ≈ 4 ms/step — most of the fused kernel's saved HBM pass.
    The in-kernel bandwidth edge of Pallas over a well-fused XLA update is
    small, so small/mid leaves are better off batched into XLA's fusion;
    only leaves whose sweep time dwarfs the launch overhead keep their own
    call.  Measured sweep on the v5e chip (BASELINE.md r5): 256K (34
    calls) 0.693 MFU, 4M 0.696, 8M 0.699-0.701, 16M 0.701, 32M (2 calls)
    0.698, pure-jnp 0.688 — 8M default = embed/lm_head (67M) + the 24
    16M swiglu leaves, 26 Pallas calls.  DTPU_FUSED_MIN_SIZE overrides.
    """
    import os

    return int(os.environ.get("DTPU_FUSED_MIN_SIZE", 8 * 1024 * 1024))


class FusedAdamWState(NamedTuple):
    count: jax.Array  # int32 step counter
    mu: Any           # first moment (param dtype or mu_dtype)
    nu: Any           # second moment (f32)


def _adamw_kernel(b1, b2, eps, wd, scal_ref, p_ref, m_ref, v_ref, g_ref,
                  po_ref, mo_ref, vo_ref):
    lr = scal_ref[0, 0]
    cs = scal_ref[0, 1]     # global-clip scale
    bc1 = scal_ref[0, 2]    # 1 - b1^t
    bc2 = scal_ref[0, 3]    # 1 - b2^t
    g = g_ref[...].astype(jnp.float32) * cs
    m = m_ref[...].astype(jnp.float32) * b1 + g * (1.0 - b1)
    v = v_ref[...] * b2 + g * g * (1.0 - b2)
    mhat = m / bc1
    vhat = v / bc2
    p = p_ref[...]
    update = mhat / (jnp.sqrt(vhat) + eps) + wd * p
    po_ref[...] = p - lr * update
    mo_ref[...] = m.astype(mo_ref.dtype)
    vo_ref[...] = v


def _plan_matrix_rows(shape, budget):
    """The plan of a stack of matrices whose last dim halves to no multiple
    of 128 (experts: [held, 2304, 896]): a matrix at a time, its rows split
    and its last dim whole.  None (the jnp sweep) where that does not fit."""
    if len(shape) != 3:
        return None
    d0, mid, dk = shape
    rows = max(
        (r for r in range(8, mid + 1, 8) if mid % r == 0 and r * dk <= budget),
        default=None,
    )
    return None if rows is None else ((d0, mid // rows), (1, rows, dk), 1)


def _plan_blocks(shape):
    """(grid, block, axis) tiling a leaf IN ITS NATIVE SHAPE, or None to fall
    back to jnp: grid index 0 walks dim 0 and grid index 1 walks ``axis``.  Native-shape blocks are the point: flatten/reshape
    changes the TPU tiled layout and XLA then physically copies every
    operand around the kernel — the flattened first cut of this kernel
    measured ~3x slower than optax purely from those copies.

    2D leaves tile both dims (wide lm_head/vocab arrays need a column
    split to keep >=8 rows per block); 3D+ leaves keep trailing dims whole
    and split the leading dim; a stack of matrices whose ONE matrix is past
    the budget (experts: [held, d_model, d_ff]) is swept a matrix at a time,
    its rows split and its last dim whole.
    """
    budget = _BLOCK_BYTES // 4  # f32 elements per ref
    d0, dk = shape[0], shape[-1]
    mid = math.prod(shape[1:-1]) if len(shape) > 2 else 1
    # block's last two dims must be (multiple of 8, multiple of 128) or the
    # full dims; middle dims stay whole, first + last split to fit budget
    br_min = 8 if len(shape) == 2 else 1
    if d0 % br_min:
        return None
    bc = dk
    while bc % 2 == 0 and bc > 128 and br_min * mid * bc > budget:
        bc //= 2
    if bc != dk and bc % 128:
        return _plan_matrix_rows(shape, budget)
    br = br_min
    while br * 2 * mid * bc <= budget and d0 % (br * 2) == 0:
        br *= 2
    if br * mid * bc > budget:
        # middle dims alone exceed the budget
        return _plan_matrix_rows(shape, budget)
    return (d0 // br, dk // bc), (br,) + tuple(shape[1:-1]) + (bc,), len(shape) - 1


def _leaf_pallas(p, m, v, g, scalars, *, b1, b2, eps, wd):
    """One fused sweep over a large leaf in its native shape."""
    from jax.experimental import pallas as pl

    grid, block, axis = _plan_blocks(p.shape)
    index_map = lambda i, j: tuple(  # noqa: E731
        i if d == 0 else j if d == axis else 0 for d in range(p.ndim)
    )
    scal_map = lambda i, j: (0, 0)  # noqa: E731
    bspec = lambda: pl.BlockSpec(block, index_map)  # noqa: E731
    po, mo, vo = pl.pallas_call(
        partial(_adamw_kernel, b1, b2, eps, wd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 4), scal_map),  # scalars ride along
            bspec(), bspec(), bspec(), bspec(),
        ],
        out_specs=[bspec(), bspec(), bspec()],
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        # in-place p/m/v (argument order: scalars, p, m, v, g)
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=kernel_form.interpreted_off_chip(),
    )(scalars, p, m, v, g)
    return po, mo, vo


def _leaf_pallas_sharded(p, m, v, g, scalars, sharding, **kw):
    """The sweep on a leaf sharded over a multi-device mesh: XLA cannot
    partition a Mosaic kernel, so each device sweeps its own shard inside
    ``shard_map`` over the leaf's ``PartitionSpec`` (the update is
    elementwise; the clip scale in ``scalars`` was reduced outside)."""
    spec = sharding.spec
    return jax.shard_map(
        partial(_leaf_pallas, **kw),
        mesh=sharding.mesh,
        in_specs=(spec, spec, spec, spec, P()),
        out_specs=(spec, spec, spec),
        check_vma=False,
    )(p, m, v, g, scalars)


def _leaf_jnp(p, m, v, g, scalars, *, b1, b2, eps, wd):
    lr, cs, bc1, bc2 = (scalars[0, i] for i in range(4))
    gf = g.astype(jnp.float32) * cs
    m_new = m.astype(jnp.float32) * b1 + gf * (1.0 - b1)
    v_new = v * b2 + gf * gf * (1.0 - b2)
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps) + wd * p
    return p - lr * update, m_new.astype(m.dtype), v_new


@dataclasses.dataclass(frozen=True)
class FusedAdamW:
    """Full-step fused optimizer.  ``apply_step`` consumes grads and returns
    (new_params, new_state) directly — no separate "updates" tree, which is
    the point: materializing updates costs two extra HBM passes."""

    learning_rate: Union[float, Callable[[jax.Array], jax.Array]]
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    clip_norm: Optional[float] = 1.0
    mu_dtype: Optional[Any] = None

    def init(self, params: Any) -> FusedAdamWState:
        mu = jax.tree.map(
            lambda p: jnp.zeros_like(p, dtype=self.mu_dtype or p.dtype), params
        )
        nu = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
        return FusedAdamWState(jnp.zeros((), jnp.int32), mu, nu)

    def _scalars(self, count: jax.Array, grads: Any) -> jax.Array:
        t = (count + 1).astype(jnp.float32)
        lr = self.learning_rate(count) if callable(self.learning_rate) else self.learning_rate
        if self.clip_norm is not None:
            with jax.named_scope("optim.clip"):
                gn = optax.global_norm(grads)
                cs = jnp.minimum(1.0, self.clip_norm / jnp.maximum(gn, 1e-16))
        else:
            cs = jnp.ones(())
        bc1 = 1.0 - self.b1 ** t
        bc2 = 1.0 - self.b2 ** t
        return jnp.stack([jnp.asarray(lr, jnp.float32), cs.astype(jnp.float32),
                          bc1, bc2]).reshape(1, 4)

    def apply_step(
        self,
        grads: Any,
        state: FusedAdamWState,
        params: Any,
        shardings: Optional[Any] = None,
    ):
        """``shardings``: the ``NamedSharding`` pytree (matching ``params``)
        the update runs in — the Trainer's param shardings, or the
        reduce-scattered grad layout under ``overlap_grad_sync``.  Required
        knowledge on a multi-device mesh: the kernel is planned on each
        leaf's LOCAL shard shape and run per device; None means
        single-device arrays."""
        scalars = self._scalars(state.count, grads)
        kw = dict(b1=self.b1, b2=self.b2, eps=self.eps, wd=self.weight_decay)

        min_size = _min_pallas_size()

        def leaf(p, m, v, g, sharding=None):
            multi = sharding is not None and sharding.mesh.size > 1
            # the launch-overhead threshold and the block plan are about
            # what ONE device sweeps
            shape = sharding.shard_shape(p.shape) if multi else p.shape
            if (
                math.prod(shape) >= min_size
                and p.dtype == jnp.float32
                and p.ndim >= 2
                and _plan_blocks(shape) is not None
            ):
                if multi:
                    return _leaf_pallas_sharded(p, m, v, g, scalars, sharding, **kw)
                return _leaf_pallas(p, m, v, g, scalars, **kw)
            return _leaf_jnp(p, m, v, g, scalars, **kw)

        trees = (params, state.mu, state.nu, grads)
        out = jax.tree.map(leaf, *trees, *(() if shardings is None else (shardings,)))
        # out leaves are (p, m, v) triples; re-split into three trees
        is_triple = lambda x: isinstance(x, tuple) and len(x) == 3  # noqa: E731
        new_p = jax.tree.map(lambda t: t[0], out, is_leaf=is_triple)
        new_m = jax.tree.map(lambda t: t[1], out, is_leaf=is_triple)
        new_v = jax.tree.map(lambda t: t[2], out, is_leaf=is_triple)
        return new_p, FusedAdamWState(state.count + 1, new_m, new_v)

    # optax-compatible shim (not used by the Trainer's fused path): returns
    # an updates tree; costs the extra passes the fused path avoids
    def update(self, grads: Any, state: FusedAdamWState, params: Any):
        new_p, new_state = self.apply_step(grads, state, params)
        updates = jax.tree.map(lambda a, b: a - b, new_p, params)
        return updates, new_state


def fused_adamw(
    learning_rate,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.01,
    clip_norm: Optional[float] = 1.0,
    mu_dtype: Optional[Any] = None,
) -> FusedAdamW:
    return FusedAdamW(
        learning_rate=learning_rate, b1=b1, b2=b2, eps=eps,
        weight_decay=weight_decay, clip_norm=clip_norm, mu_dtype=mu_dtype,
    )
