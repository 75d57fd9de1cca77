"""Plain reference: one AdamW step with global-norm clipping and the
warm-up + cosine learning rate, as published (Loshchilov & Hutter, decoupled
weight decay; Adam's bias correction), in ``jax.numpy`` float32, with no
import from the program and none from optax.

The training cell holds the program's optimizer to it: from the parameters
and moments before a step and the reference's own gradient it computes what
the parameters and moments must be after it.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


def warmup_cosine_lr(count: int, *, peak: float, warmup_steps: int, decay_steps: int) -> float:
    """Linear from 0 to ``peak`` over ``warmup_steps`` updates, then half a
    cosine down to 0 at update ``decay_steps``; ``count`` updates were made
    before this one."""
    if count < warmup_steps:
        return peak * count / warmup_steps
    span = decay_steps - warmup_steps
    return peak * 0.5 * (1.0 + math.cos(math.pi * min(count - warmup_steps, span) / span))


def clip_scale(global_norm: float, max_norm: float) -> float:
    """What every gradient is multiplied by so that their joint norm is at
    most ``max_norm``."""
    return 1.0 if global_norm <= max_norm else max_norm / global_norm


def adamw_step(
    p: jax.Array, m: jax.Array, v: jax.Array, g: jax.Array, *,
    count: int, lr: float, b1: float, b2: float, eps: float, weight_decay: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(parameter, first moment, second moment) after update ``count + 1``
    with the (already clipped) gradient ``g``."""
    p, m, v, g = (x.astype(jnp.float32) for x in (p, m, v, g))
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    t = count + 1
    m_hat, v_hat = m / (1.0 - b1**t), v / (1.0 - b2**t)
    p = p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * p)
    return p, m, v
