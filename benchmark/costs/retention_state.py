"""One decode step's retention kernels (``ops/retention.py``
``retention_decode``), all layers: every live lane's state and normaliser are
READ ONCE a layer, float32; on each value of the state the kernel does one
multiplication to decay it, a multiplication and an addition to add the new
token's outer product, and a multiplication and an addition for each of the
query heads its KV head answers: ``3 + 2 x query_heads_per_kv_head`` operations
a value (13 at Brumby's 5), 3.25 a byte against the v5e's ridge of 240: the
read bounds it.  What the kernel WRITES BACK (as many bytes again) is not
counted: the least a form could move is one read, if it folded several tokens
into the state before writing it.

Bytes read a step: what the program counted in the TRACED steps
(``traced.serve.state.bytes``: live lanes x retention layers x the bytes of a
slot, from the ``serve.decode`` spans' arguments).  A program that counts no
such thing gives no cost (KeyError: the reader leaves the metric out).
"""


def cost(config, traffic, chips, counters, arch):
    held = counters["traced.serve.state.bytes"]
    per_value = 3 + 2 * arch.state_shape(config)["query_heads_per_kv_head"]
    return {"flops": per_value * held / 4.0, "bytes": float(held)}
