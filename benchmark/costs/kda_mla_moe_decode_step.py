"""One whole decode step of a model of Kimi-Delta-Attention layers, gated
latent-attention layers, a leading dense layer and routed experts in the rest:
what ``benchlib/costs.py decode_step`` is to a dense GQA decoder, which does not
fit here (it knows no state, counts every held expert whether a row reached it
or not, and K and V in every layer where ONE layer keeps a latent row).

Bytes, what one step MUST move: every parameter once in the dtype the
configuration serves them in, less the embedding table (a lookup of one row a
lane, which is counted) and less the routed experts no row reached
(``moe_decode_experts``: the hit experts' three matrices and the held picks'
rows, by ``traced.serve.moe.experts_hit``); the live lanes' state read AND
written with the kernel's operands (``kda_state``) and their convolution tails
read and written; the live latent rows of the latent layers read once
(``mla_paged_attention``: 576 values a token a layer, the stored row's 64 zeros
not counted).  Operations: 2 x the matrices every lane multiplies with (the
mixers' projections, the dense SwiGLU, the router, the shared expert, the head)
x the active lanes, the held picks' expert products, the state's, and the
latent kernels' ``2 x heads x (576 + 512)`` a live token a layer.
"""

from benchlib import model

state = model.beside(__file__, "costs", "kda_state")
attention = model.beside(__file__, "costs", "mla_paged_attention")
experts = model.beside(__file__, "costs", "moe_decode_experts")

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    e, s = arch.expert_shape(config), arch.kda_shape(config)
    held = state.cost(config, traffic, chips, counters, arch)
    att = attention.cost(config, traffic, chips, counters, arch)
    exp = experts.cost(config, traffic, chips, counters, arch)
    one = e["matrices"] * e["d_model"] * e["d_ff"]
    not_routed = arch.total_params(config) - arch.embedding_params(config) - e["layers"] * e["held"] * one
    lanes = counters.get("traced.active", float(traffic["engine"]["max_batch"]))
    every_lane = arch.matmul_params(config) - e["layers"] * e["expected_held_picks"] * one
    tails = 2.0 * counters["traced.serve.gdn.live_lanes"] * s["layers"] * (s["conv"] - 1) * s["channels"] * _BYTES[config["dtypes"]["compute"]]
    looked_up = lanes * e["d_model"] * _BYTES[config["dtypes"]["serve_params"]]
    return {
        "flops": 2.0 * every_lane * lanes + exp["flops"] + held["flops"] + att["flops"],
        "bytes": not_routed * _BYTES[config["dtypes"]["serve_params"]] + looked_up + exp["bytes"] + held["bytes"] + tails + att["bytes"],
    }
