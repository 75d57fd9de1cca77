"""One decode step's delta-rule kernels (``ops/gated_delta.py`` ``gdn_decode``),
all linear layers: every live lane's state is READ ONCE AND WRITTEN ONCE a
layer, float32 (the write is counted, as ``ssm_state`` counts its own: the rule
has to write what the next token's step reads).  On each value of the state the
kernel does one multiplication to decay it, a multiplication and an addition
for ``r = S^T k``, a multiplication and an addition to add the corrected
rank-one term and a multiplication and an addition to read it out: 7 operations
a value, 0.875 a byte moved against the v5e's ridge of 240: the bytes bound it.

Bytes a step: twice what the program counted in the TRACED steps
(``traced.serve.gdn.bytes``: live lanes x linear layers x the bytes of a slot,
from the ``serve.decode`` spans' arguments).  A program that counts no such
thing gives no cost (KeyError: the reader leaves the metric out).
"""


def cost(config, traffic, chips, counters, arch):
    held = counters["traced.serve.gdn.bytes"]
    return {"flops": 7.0 * held / 4.0, "bytes": 2.0 * float(held)}
