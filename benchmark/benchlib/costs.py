"""Operations and bytes an algorithm needs, from the configuration's shapes.

Named by the metric files (``"cost": "<name>"``): a function of ``COSTS``
here, or ``benchmark/costs/<name>.py`` that a later PR brings with its
kernel, whose ``cost(config, traffic, chips, counters, arch)`` returns
``{"flops": ..., "bytes": ...}`` the same way.  Kept with the benchmark: a
roofline share is (least time the chip could take) / (time taken), and a PR
that claims a gain may not touch either half.  Recomputed operations (remat)
are never counted.  The parameter counts and attention's shape come from the
configuration's adapter (``arch``: ``benchmark/archs/``); the per-token
arithmetic is that of ``LMTrial.flops_per_token`` (6 x matmul parameters +
attention), made exact here for grouped-query attention, a gated MLP and an
untied head.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict

from . import model

Cost = Callable[[Dict[str, Any], Dict[str, Any], int, Dict[str, float], Any], Dict[str, float]]

_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def train_flops_per_token(config: Dict[str, Any], seq_len: int, arch: Any) -> float:
    """Forward and backward: 6 x matmul parameters, plus causal attention's
    two matmuls (QK^T and PV), 2 x 2 x hd x heads x seq / 2 forward per
    token per layer, x 3 for forward + backward."""
    s = arch.attention_shape(config)
    attn_fwd = 2 * 2 * s["heads"] * s["head_dim"] * seq_len / 2
    return 6.0 * arch.matmul_params(config) + 3.0 * s["layers"] * attn_fwd


def flash_attention(config: Dict[str, Any], traffic: Dict[str, Any], chips: int, counters: Dict[str, float], arch: Any) -> Dict[str, float]:
    """One training step's attention kernels, forward and backward, causal:
    forward 2 matmuls and backward 5 (recomputed scores, dP, dV, dQ, dK) over
    half of the seq x seq square.  The backward's recomputation of the scores
    is part of the algorithm (flash attention stores none), so it counts.
    Bytes: q, k, v, o and their gradients once, in the compute dtype."""
    s = arch.attention_shape(config)
    seq, batch = int(traffic["seq_len"]), int(config["train_batch"]["global_batch_sequences"])
    per_matmul = 2.0 * batch * s["heads"] * seq * seq * s["head_dim"] / 2
    qo = batch * seq * s["heads"] * s["head_dim"] * 2
    kv = batch * seq * s["kv_heads"] * s["head_dim"] * 2
    return {
        "flops": s["layers"] * 7 * per_matmul / chips,
        "bytes": s["layers"] * 2.0 * (2 * qo + 2 * kv) / chips,
    }


def adamw_sweep(config: Dict[str, Any], traffic: Dict[str, Any], chips: int, counters: Dict[str, float], arch: Any) -> Dict[str, float]:
    """One AdamW update over every parameter: read parameter, gradient and
    both moments, write parameter and both moments, all float32 as the
    configuration states (7 x 4 bytes a parameter), each chip its share."""
    return {"flops": 0.0, "bytes": 28.0 * arch.total_params(config) / chips}


def decode_step(config: Dict[str, Any], traffic: Dict[str, Any], chips: int, counters: Dict[str, float], arch: Any) -> Dict[str, float]:
    """One decode step must read every weight once, at the dtype the
    configuration serves them in, and the live K and V of every lane (the
    window's mean of live tokens a step, from the run's counters)."""
    s = arch.attention_shape(config)
    live_kv_tokens = counters["serve.live_kv_tokens"] / counters["serve.decode_steps"]
    wbytes = _BYTES[config["dtypes"]["serve_params"]]
    cbytes = _BYTES[config["dtypes"]["kv_cache"]]
    # the embedding is a lookup of one row a lane: not a sweep
    weights = (arch.total_params(config) - arch.embedding_params(config)) * wbytes
    kv = 2.0 * s["layers"] * live_kv_tokens * s["kv_heads"] * s["head_dim"] * cbytes
    lanes = int(traffic["engine"]["max_batch"])
    return {"flops": 2.0 * arch.matmul_params(config) * lanes, "bytes": weights + kv}


COSTS: Dict[str, Cost] = {f.__name__: f for f in (flash_attention, adamw_sweep, decode_step)}


def find(name: str, data_dir: str) -> Cost:
    """The cost function a metric file names: one of ``COSTS``, or the
    ``cost`` of ``<data_dir>/costs/<name>.py``."""
    return model.named(COSTS, name, os.path.join(data_dir, "costs", name + ".py"), "cost", "cost")
