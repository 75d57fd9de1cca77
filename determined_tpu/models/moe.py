"""Mixture-of-Experts layer with expert parallelism, TPU-first.

The reference has NO MoE/expert-parallel code (SURVEY §2.10: absent —
DeepSpeed passthrough at most); this is a capability the TPU build adds.
Design follows the GShard/Switch pjit formulation rather than explicit
all-to-all plumbing: expert weights are stacked ``[experts, ...]`` tensors
whose leading dim carries the ``"expert"`` logical axis, and token routing
is expressed as dense dispatch/combine einsums — under ``pjit`` over a mesh
with an ``expert`` axis, XLA partitions the expert dim and inserts the
all-to-all collectives itself (the "let the compiler place collectives"
recipe).  Top-2 gating with capacity limiting and the standard
load-balancing auxiliary loss (Switch Transformer eq. 4).

Shapes (g = tokens per group, e = experts, c = capacity, d/f = model/ff):
  gates      [g, e]      softmax router probabilities
  dispatch   [g, e, c]   0/1 token->expert-slot assignment
  combine    [g, e, c]   dispatch * gate prob (weighted un-routing)
  x          [g, d]  ->  expert inputs  [e, c, d]   (einsum with dispatch)
  expert ffn [e, c, d] @ w1[e, d, f] -> silu -> @ w2[e, f, d]
  y          [g, d]      (einsum with combine)
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn


def _top2_dispatch(
    gates: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Build dispatch/combine tensors for top-2 routing with capacity.

    Tokens beyond an expert's capacity are dropped (standard GShard
    behavior); the combine weights renormalize over the surviving routes.
    Returns (dispatch [g,e,c], combine [g,e,c], aux_loss scalar).
    """
    g, e = gates.shape
    # top-1 and top-2 expert per token
    idx1 = jnp.argmax(gates, axis=-1)                          # [g]
    mask1 = jax.nn.one_hot(idx1, e, dtype=gates.dtype)         # [g, e]
    gates2 = gates * (1.0 - mask1)
    idx2 = jnp.argmax(gates2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, e, dtype=gates.dtype)

    # load-balancing aux loss (Switch eq. 4): e * sum_e(fraction_tokens_e
    # * mean_prob_e) — equals 1 at perfect balance regardless of e, so the
    # aux weight means the same thing at any expert count
    density = mask1.mean(axis=0)                               # [e]
    density_proxy = gates.mean(axis=0)                         # [e]
    aux = (density * density_proxy).sum() * e

    # position of each token in its expert's queue (top-1 first)
    pos1 = (jnp.cumsum(mask1, axis=0) - 1.0) * mask1           # [g, e]
    used1 = jnp.sum(mask1, axis=0, keepdims=True)              # [1, e]
    pos2 = ((jnp.cumsum(mask2, axis=0) - 1.0) + used1) * mask2
    keep1 = mask1 * (pos1 < capacity)
    keep2 = mask2 * (pos2 < capacity)

    p1 = (gates * keep1).sum(axis=-1)                          # [g]
    p2 = (gates * keep2).sum(axis=-1)
    denom = jnp.maximum(p1 + p2, 1e-9)
    w1 = p1 / denom
    w2 = p2 / denom

    def slots(keep, pos):
        slot = jax.nn.one_hot(
            (pos * keep).sum(axis=-1).astype(jnp.int32), capacity,
            dtype=gates.dtype,
        )                                                       # [g, c]
        return keep[:, :, None] * slot[:, None, :]              # [g, e, c]

    d1, d2 = slots(keep1, pos1), slots(keep2, pos2)
    dispatch = d1 + d2
    combine = d1 * w1[:, None, None] + d2 * w2[:, None, None]
    return dispatch, combine, aux


class MoE(nn.Module):
    """Top-2 expert-parallel SwiGLU FFN (drop-in for a dense MLP block).

    Tokens route within fixed-size GROUPS (GShard's formulation): dispatch
    and combine are ``[groups, group_size, e, c]`` with ``c ~
    2*group_size/e``, so their size is linear in the token count —
    grouping capacity over the whole flattened batch would make them
    quadratic and OOM real configs (64k tokens x 8 experts would need
    ~1e10-element dispatch tensors).
    """

    num_experts: int
    d_ff: int
    capacity_factor: float = 1.25
    group_size: int = 4096
    dtype: Any = jnp.bfloat16
    partition: bool = True  # False under manual-SPMD pipeline stages
    # Manual-SPMD expert parallelism (inside pipeline-stage shard_map):
    # expert weights arrive sharded over this axis (only e/n local experts
    # per device); routing/gating stays replicated, each device computes
    # the FFN for ITS experts against the full token set, and the combine
    # is a psum over the axis — the intra-stage expert "all-to-all".
    expert_axis_name: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """[batch, seq, d] -> ([batch, seq, d], aux_loss)."""
        from determined_tpu.models.transformer import _maybe_partition

        b, s, d = x.shape
        g = b * s
        e = self.num_experts
        # pad up to a group multiple rather than shrinking groups: a
        # divisor fallback can degenerate to tiny groups (prime token
        # counts), collapsing capacity and dropping every top-2 route.
        # Padded (zero) tokens route uniformly and consume at most the pad
        # fraction of capacity; their outputs are sliced away.
        grp = min(self.group_size, g)
        pad = (-g) % grp
        n_groups = (g + pad) // grp
        capacity = max(int(self.capacity_factor * grp * 2 / e), 1)

        xf = x.reshape(g, d)
        if pad:
            xf = jnp.concatenate([xf, jnp.zeros((pad, d), x.dtype)], axis=0)
        xg = xf.reshape(n_groups, grp, d)
        router = self.param(
            "router",
            _maybe_partition(
                self.partition, nn.initializers.lecun_normal(), ("embed", "expert")
            ),
            (d, e),
            jnp.float32,
        )
        # routing decisions in f32: bf16 softmax ties misroute tokens
        gates = jax.nn.softmax(
            jnp.einsum("ngd,de->nge", xg.astype(jnp.float32), router)
        )
        dispatch, combine, aux = jax.vmap(
            lambda gate: _top2_dispatch(gate, capacity)
        )(gates)
        aux = aux.mean()

        # under manual SPMD the params hold only this device's experts
        e_param = e
        my_expert0 = None
        if self.expert_axis_name is not None:
            n_exp = jax.lax.axis_size(self.expert_axis_name)
            if e % n_exp:
                raise ValueError(f"num_experts={e} not divisible by axis {n_exp}")
            e_param = e // n_exp
            my_expert0 = jax.lax.axis_index(self.expert_axis_name) * e_param

        def expert_param(name, shape, logical):
            return self.param(
                name,
                _maybe_partition(
                    self.partition, nn.initializers.lecun_normal(), logical
                ),
                shape,
                jnp.float32,
            )

        w_in = expert_param("w_in", (e_param, d, self.d_ff), ("expert", "embed", "mlp"))
        w_gate = expert_param("w_gate", (e_param, d, self.d_ff), ("expert", "embed", "mlp"))
        w_out = expert_param("w_out", (e_param, self.d_ff, d), ("expert", "mlp", "embed"))

        if my_expert0 is not None:
            # keep only the dispatch/combine slices for MY experts; the
            # cross-device combine is the psum below
            dispatch = jax.lax.dynamic_slice_in_dim(dispatch, my_expert0, e_param, axis=2)
            combine = jax.lax.dynamic_slice_in_dim(combine, my_expert0, e_param, axis=2)

        cd = self.dtype
        # dispatch: [n,g,e,c] x [n,g,d] -> [n,e,c,d]; under an
        # "expert"-sharded mesh axis XLA turns these einsums into the
        # all-to-alls
        expert_in = jnp.einsum(
            "ngec,ngd->necd", dispatch.astype(cd), xg.astype(cd)
        )
        h = jnp.einsum("necd,edf->necf", expert_in, w_in.astype(cd))
        gate = jnp.einsum("necd,edf->necf", expert_in, w_gate.astype(cd))
        h = nn.silu(gate) * h
        expert_out = jnp.einsum("necf,efd->necd", h, w_out.astype(cd))
        y = jnp.einsum("ngec,necd->ngd", combine.astype(cd), expert_out)
        if self.expert_axis_name is not None:
            y = jax.lax.psum(y, self.expert_axis_name)
        y = y.reshape(n_groups * grp, d)[:g]
        return y.reshape(b, s, d), aux.astype(jnp.float32)
