"""One whole decode step of a dense decoder whose layers are power retention:
what ``benchlib/costs.py decode_step`` is to a dense GQA decoder, which does
not fit here (it reads K and V a live token; this model caches none).

Bytes: every parameter once in the dtype the configuration serves them in,
less the embedding table (a lookup of one row a lane), plus the live lanes'
state and normaliser once (``traced.serve.state.bytes`` of the traced steps:
see ``retention_state``; the write-back is not counted there either).
Operations: 2 x the matrices a lane multiplies with x the active lanes, and
the state's.
"""

from benchlib import model

state = model.beside(__file__, "costs", "retention_state")

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    held = state.cost(config, traffic, chips, counters, arch)
    swept = arch.total_params(config) - arch.embedding_params(config)
    lanes = counters.get("traced.active", float(traffic["engine"]["max_batch"]))
    return {
        "flops": 2.0 * arch.matmul_params(config) * lanes + held["flops"],
        "bytes": swept * _BYTES[config["dtypes"]["serve_params"]] + held["bytes"],
    }
