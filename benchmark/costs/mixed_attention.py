"""One training step's attention kernels where layers mix a sliding window
with full causal attention: forward 2 matmuls and backward 5 (recomputed
scores, dP, dV, dQ, dK), over the (query, key) pairs a layer's mask lets
through: ``sum_i min(i + 1, window)`` of a sliding layer, ``S (S + 1) / 2``
of a full one.  Pairs, not blocks: what a kernel computes of a block and then
masks away is not work the algorithm needs.  Bytes: q, k, v, o and their
gradients once a layer, in the compute dtype.

The layers' windows come from the adapter (``layer_windows``); an adapter
without one has full causal attention in every layer.
"""


def visible_pairs(seq: int, window) -> float:
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def windows(config, arch):
    layers = arch.attention_shape(config)["layers"]
    found = getattr(arch, "layer_windows", None)
    return found(config) if found is not None else [None] * layers


def cost(config, traffic, chips, counters, arch):
    s = arch.attention_shape(config)
    seq, batch = int(traffic["seq_len"]), int(config["train_batch"]["global_batch_sequences"])
    pairs = sum(visible_pairs(seq, w) for w in windows(config, arch))
    qo = batch * seq * s["heads"] * s["head_dim"] * 2
    kv = batch * seq * s["kv_heads"] * s["head_dim"] * 2
    return {
        "flops": 7 * 2.0 * batch * s["heads"] * s["head_dim"] * pairs / chips,
        "bytes": s["layers"] * 2.0 * (2 * qo + 2 * kv) / chips,
    }
