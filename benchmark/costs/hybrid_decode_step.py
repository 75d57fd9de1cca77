"""One whole decode step of a dense decoder whose layers run attention heads
and Mamba-2 heads side by side: what ``benchlib/costs.py decode_step`` is to a
dense GQA decoder, which does not fit here (it knows no state, and counts the
parameters at the dtype the source states, not the one held).

Bytes: every parameter once in the dtype the configuration serves them in,
less the embedding table (a lookup of one row a lane); the live lanes' state
read AND written (``ssm_state``: twice ``traced.serve.ssm.bytes`` of the traced
steps); the live K and V rows read once (``traced.live_kv_tokens`` x layers x 2
x kv_heads x head_dim values at the compute dtype).  Operations: 2 x the
matrices a lane multiplies with x the active lanes, the state's, and 4 x heads
x head_dim a live token for the scores and the values.
"""

from benchlib import model

state = model.beside(__file__, "costs", "ssm_state")

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    held = state.cost(config, traffic, chips, counters, arch)
    a = arch.attention_shape(config)
    swept = arch.total_params(config) - arch.embedding_params(config)
    lanes = counters.get("traced.active", float(traffic["engine"]["max_batch"]))
    tokens = counters["traced.live_kv_tokens"] * a["layers"]
    return {
        "flops": 2.0 * arch.matmul_params(config) * lanes + held["flops"] + 4.0 * a["heads"] * a["head_dim"] * tokens,
        "bytes": swept * _BYTES[config["dtypes"]["serve_params"]] + held["bytes"]
        + tokens * 2 * a["kv_heads"] * a["head_dim"] * _BYTES[config["dtypes"]["compute"]],
    }
