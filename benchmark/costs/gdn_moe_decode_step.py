"""One whole decode step of a model of Gated-DeltaNet layers, gated
full-attention layers and routed experts in every layer: what
``benchlib/costs.py decode_step`` is to a dense GQA decoder, which does not fit
here (it knows no state, counts every held expert whether a row reached it or
not, and K and V in every layer).

Bytes, what one step MUST move: every parameter once in the dtype the
configuration serves them in, less the embedding table (a lookup of one row a
lane, which is counted) and less the routed experts no row reached
(``moe_decode_experts``: the hit experts' three matrices and the held picks'
rows, by ``traced.serve.moe.experts_hit``); the live lanes' state read AND
written (``gdn_state``: twice ``traced.serve.gdn.bytes``) and their convolution
tails read and written; the live K and V rows of the full layers read once
(``traced.live_kv_tokens`` x those layers x 2 x kv_heads x head_dim values).
Operations: 2 x the matrices every lane multiplies with (the mixers'
projections, the router, the shared expert, the head) x the active lanes, the
held picks' expert products, the state's, and 4 x heads x head_dim a live token
for the scores and the values.
"""

from benchlib import model

state = model.beside(__file__, "costs", "gdn_state")
experts = model.beside(__file__, "costs", "moe_decode_experts")

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    e, a, s = arch.expert_shape(config), arch.attention_shape(config), arch.gdn_shape(config)
    held = state.cost(config, traffic, chips, counters, arch)
    exp = experts.cost(config, traffic, chips, counters, arch)
    one = e["matrices"] * e["d_model"] * e["d_ff"]
    not_routed = arch.total_params(config) - arch.embedding_params(config) - e["layers"] * e["held"] * one
    lanes = counters.get("traced.active", float(traffic["engine"]["max_batch"]))
    every_lane = arch.matmul_params(config) - e["layers"] * e["expected_held_picks"] * one
    compute = _BYTES[config["dtypes"]["compute"]]
    tails = 2.0 * counters["traced.serve.gdn.live_lanes"] * s["layers"] * (s["conv"] - 1) * s["channels"] * compute
    tokens = counters["traced.live_kv_tokens"] * a["layers"]
    looked_up = lanes * e["d_model"] * _BYTES[config["dtypes"]["serve_params"]]
    return {
        "flops": 2.0 * every_lane * lanes + exp["flops"] + held["flops"] + 4.0 * a["heads"] * a["head_dim"] * tokens,
        "bytes": not_routed * _BYTES[config["dtypes"]["serve_params"]] + looked_up + exp["bytes"] + held["bytes"] + tails
        + tokens * 2 * a["kv_heads"] * a["head_dim"] * compute,
    }
