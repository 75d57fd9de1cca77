"""One decode step's delta-rule kernels under a decay a CHANNEL (Kimi Delta
Attention through ``ops/gated_delta.py`` ``gdn_decode``), all KDA layers: every
live lane's state is READ ONCE AND WRITTEN ONCE a layer, float32 (the write is
counted, as ``gdn_state`` counts its own: the rule has to write what the next
token's step reads), and beside it the kernel reads a head's query, key and
decay down a column (``[K, heads]`` each: the decay is a value a channel, so it
is as wide as the key and no broadcast of a scalar) and its value and write
strength along a row (``[heads, V]`` each, as the kernel lays them out), and
writes the read-out ``[heads, V]``: ``3 K + 3 V`` float32 values a head beside
``2 K V`` of state, 2.3 % more at 128 x 128.  On each value of the state the
kernel does one multiplication to decay it, a multiplication and an addition for
``r = S^T k``, a multiplication and an addition to add the corrected rank-one
term and a multiplication and an addition to read it out: 7 operations a value,
under one a byte against the v5e's ridge of 240: the bytes bound it.

Bytes a step: twice what the program counted in the TRACED steps
(``traced.serve.gdn.bytes``: live lanes x KDA layers x the bytes of a slot, from
the ``serve.decode`` spans' arguments; the kind ``delta_slot`` counts a decay of
either rank under these names) and the operands of ``traced.serve.gdn.live_lanes``
lanes.  A program that counts no such thing gives no cost (KeyError: the reader
leaves the metric out).
"""


def cost(config, traffic, chips, counters, arch):
    s = arch.kda_shape(config)
    held, lanes = counters["traced.serve.gdn.bytes"], counters["traced.serve.gdn.live_lanes"]
    operands = lanes * s["layers"] * s["heads"] * (3 * s["key_dim"] + 3 * s["value_dim"]) * 4.0
    return {"flops": 7.0 * held / 4.0, "bytes": 2.0 * float(held) + operands}
