"""TrainState: the complete on-device training state pytree.

Design note (TPU-first): the reference fetches per-batch metrics to host
inside the hot loop (``_pytorch_trial.py:716`` ``metric.cpu()``) — that
pattern stalls the XLA pipeline.  Here metric accumulation lives INSIDE the
jitted step as part of the state (``metric_acc``/``metric_count``): running
sums ride along on device and are fetched only at report boundaries.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import struct


@struct.dataclass
class TrainState:
    """Everything the jitted train step reads and writes.

    step:          global optimizer step counter (device scalar).
    params:        model parameters (possibly sharded).
    opt_state:     optax optimizer state (sharded like params).
    rng:           base PRNG key; per-step keys are folded from it.
    metric_acc:    running per-metric sums since the last report boundary.
    metric_count:  number of accumulated steps.
    """

    step: jax.Array
    params: Any
    opt_state: Any
    rng: jax.Array
    metric_acc: Dict[str, jax.Array]
    metric_count: jax.Array

    @classmethod
    def create(
        cls,
        params: Any,
        opt_state: Any,
        rng: jax.Array,
        metric_keys: tuple,
    ) -> "TrainState":
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
            rng=rng,
            metric_acc={k: jnp.zeros((), jnp.float32) for k in metric_keys},
            metric_count=jnp.zeros((), jnp.float32),
        )

    def reset_metrics(self) -> "TrainState":
        # zeros LIKE the accumulators, i.e. placed where they are: a fresh
        # ``jnp.zeros(())`` is an uncommitted single-device array, and on
        # jax 0.9 the step then sees another argument type than the
        # mesh-replicated scalar it was traced with — it traces and
        # compiles a second time after the first report boundary
        return self.replace(
            metric_acc={k: jnp.zeros_like(v) for k, v in self.metric_acc.items()},
            metric_count=jnp.zeros_like(self.metric_count),
        )

    def fetch_metrics(self) -> Dict[str, float]:
        """One host sync: mean of each accumulated metric."""
        acc, count = jax.device_get((self.metric_acc, self.metric_count))
        if count == 0:
            return {}
        return {k: float(v) / float(count) for k, v in acc.items()}
