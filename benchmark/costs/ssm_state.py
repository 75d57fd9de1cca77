"""One decode step's state kernels (``ops/ssm.py`` ``ssm_decode``), all layers:
every live lane's state is READ ONCE AND WRITTEN ONCE a layer, float32.  The
write is counted, as ``retention_state`` does not count its own: there a form
that folds several tokens into the state before writing could pass one read;
here the decode step is one token a lane, and the recurrence has to write what
the next token's step reads.  On each value of the state the kernel does one
multiplication to decay it, a multiplication and an addition to add the new
token's outer product, and a multiplication and an addition to read it out: 5
operations a value, 0.625 a byte moved against the v5e's ridge of 240: the
bytes bound it.

Bytes a step: twice what the program counted in the TRACED steps
(``traced.serve.ssm.bytes``: live lanes x Mamba-2 layers x the bytes of a slot,
from the ``serve.decode`` spans' arguments).  A program that counts no such
thing gives no cost (KeyError: the reader leaves the metric out).
"""


def cost(config, traffic, chips, counters, arch):
    held = counters["traced.serve.ssm.bytes"]
    return {"flops": 5.0 * held / 4.0, "bytes": 2.0 * float(held)}
