"""One decode step's sliding-window attention (``ops/paged_attention.py``
``paged_decode_attention`` under ``window``), all window layers: a lane's
query reads the ``min(context, window)`` newest tokens of the lane's ring and
nothing older, K and V rows of ``kv_heads x head_dim`` values each in the
cache's dtype (2 x 8 x 128 x 2 B = 4,096 B a token a layer), once; every query
head multiplies a token's key of its KV head for the score and its value for
the output: ``4 x heads x head_dim`` operations a token read (4 x 128 x 128).
At 16 query heads a KV head that is 16 operations a byte against the v5e's
ridge of 240: the read bounds it.

Tokens read a step: what the program counted in the TRACED steps
(``traced.serve.kv.window_tokens``: the sum over lanes of ``min(context,
window)``, times the window layers, from the ``serve.decode`` spans'
arguments).  A program that counts no such thing gives no cost (KeyError: the
reader leaves the metric out).
"""

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    s = arch.window_shape(config)
    tokens = counters["traced.serve.kv.window_tokens"]
    return {
        "flops": 4.0 * s["heads"] * s["head_dim"] * tokens,
        "bytes": 2.0 * s["kv_heads"] * s["head_dim"] * _BYTES[config["dtypes"]["kv_cache"]] * tokens,
    }
