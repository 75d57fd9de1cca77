"""Operations and bytes an algorithm needs, from the configuration's shapes.

Named by the metric files (``"cost": "<function>"``).  Kept with the
benchmark: a roofline share is (least time the chip could take) / (time
taken), and a PR that claims a gain may not touch either half.  Recomputed
operations (remat) are never counted.  The per-token arithmetic follows
``bench.py`` of the repo (6 x matmul parameters + attention), made exact
for grouped-query attention, a gated MLP and an untied head.
"""

from __future__ import annotations

from typing import Any, Dict

_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def _shape(config: Dict[str, Any]) -> Dict[str, int]:
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {
        "d": d,
        "layers": int(config["num_hidden_layers"]),
        "heads": h,
        "kv": int(config["num_key_value_heads"]),
        "hd": int(config.get("head_dim", d // h)),
        "ff": int(config["intermediate_size"]),
        "vocab": int(config["vocab_size"]),
    }


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication for every token:
    q, k, v, o, the gated MLP's three, and the output head (the embedding is
    a lookup)."""
    s = _shape(config)
    attn = s["d"] * s["hd"] * (2 * s["heads"] + 2 * s["kv"])
    return s["layers"] * (attn + 3 * s["d"] * s["ff"]) + s["d"] * s["vocab"]


def total_params(config: Dict[str, Any]) -> int:
    s = _shape(config)
    norms = s["layers"] * 2 * s["d"] + s["d"]
    return matmul_params(config) + s["d"] * s["vocab"] + norms


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: 6 x matmul parameters, plus causal attention's
    two matmuls (QK^T and PV), 2 x 2 x hd x heads x seq / 2 forward per
    token per layer, x 3 for forward + backward."""
    s = _shape(config)
    attn_fwd = 2 * 2 * s["heads"] * s["hd"] * seq_len / 2
    return 6.0 * matmul_params(config) + 3.0 * s["layers"] * attn_fwd


def flash_attention(config: Dict[str, Any], traffic: Dict[str, Any], chips: int, counters: Dict[str, float]) -> Dict[str, float]:
    """One training step's attention kernels, forward and backward, causal:
    forward 2 matmuls and backward 5 (recomputed scores, dP, dV, dQ, dK) over
    half of the seq x seq square.  The backward's recomputation of the scores
    is part of the algorithm (flash attention stores none), so it counts.
    Bytes: q, k, v, o and their gradients once, in the compute dtype."""
    s = _shape(config)
    seq, batch = int(traffic["seq_len"]), int(config["train_batch"]["global_batch_sequences"])
    per_matmul = 2.0 * batch * s["heads"] * seq * seq * s["hd"] / 2
    qo = batch * seq * s["heads"] * s["hd"] * 2
    kv = batch * seq * s["kv"] * s["hd"] * 2
    return {
        "flops": s["layers"] * 7 * per_matmul / chips,
        "bytes": s["layers"] * 2.0 * (2 * qo + 2 * kv) / chips,
    }


def adamw_sweep(config: Dict[str, Any], traffic: Dict[str, Any], chips: int, counters: Dict[str, float]) -> Dict[str, float]:
    """One AdamW update over every parameter: read parameter, gradient and
    both moments, write parameter and both moments, all float32 as the
    configuration states (7 x 4 bytes a parameter)."""
    return {"flops": 0.0, "bytes": 28.0 * total_params(config)}


def decode_step(config: Dict[str, Any], traffic: Dict[str, Any], chips: int, counters: Dict[str, float]) -> Dict[str, float]:
    """One decode step must read every weight once, at the dtype the
    configuration serves them in, and the live K and V of every lane (the
    window's mean of live tokens a step, from the run's counters)."""
    s = _shape(config)
    live_kv_tokens = counters["serve.live_kv_tokens"] / counters["serve.decode_steps"]
    wbytes = _BYTES[config["dtypes"]["serve_params"]]
    cbytes = _BYTES[config["dtypes"]["kv_cache"]]
    # the embedding is a lookup of one row a lane: not a sweep
    weights = (total_params(config) - s["d"] * s["vocab"]) * wbytes
    kv = 2.0 * s["layers"] * live_kv_tokens * s["kv"] * s["hd"] * cbytes
    lanes = int(traffic["engine"]["max_batch"])
    return {"flops": 2.0 * matmul_params(config) * lanes, "bytes": weights + kv}
