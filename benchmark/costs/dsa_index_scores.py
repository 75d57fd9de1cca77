"""One decode step's index-score passes (``ops/paged_attention.py``
``paged_index_scores``), all layers that hold an indexer: every live token's
index key is read once a layer, ``index_head_dim`` values in the cache's dtype
(128 x 2 B), and every indexer head multiplies it once: ``2 x index_n_heads x
index_head_dim`` operations a scored token (the ReLU and the weighted sum over
the heads are not counted).  At 32 heads that is 32 operations a byte: the
read bounds it.

Scored tokens a step: the program's own counter on the traced steps' spans
(``traced.serve.dsa.index_tokens``: the lanes' live contexts times the layers
that hold an indexer).
"""

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    s = arch.index_shape(config)
    tokens = counters["traced.serve.dsa.index_tokens"]
    return {
        "flops": 2.0 * s["heads"] * s["dim"] * tokens,
        "bytes": float(s["dim"] * _BYTES[config["dtypes"]["kv_cache"]]) * tokens,
    }
