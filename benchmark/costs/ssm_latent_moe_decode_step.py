"""One whole decode step of a model whose layers are ONE mixer each, a
Mamba-2 mixer, attention or latent two-matrix experts: what
``benchlib/costs.py decode_step`` is to a dense GQA decoder, which does not fit
here (it knows no state, counts every held expert whether a row reached it or
not, and K and V in every layer).

Bytes, what one step MUST move: every parameter once in the dtype the
configuration serves them in, less the embedding table (a lookup of one row a
lane, which is counted) and less the routed experts no row reached
(``latent_moe_decode_experts``: the hit experts' two matrices and the held
picks' rows); the live lanes' state read AND written (``ssm_state``: twice
``traced.serve.ssm.bytes``) and their convolution tails read and written; the
live K and V rows of the attention layers read once
(``traced.live_kv_tokens`` x those layers x 2 x kv_heads x head_dim values).
Operations: 2 x the matrices every lane multiplies with (both mixers'
projections, the router, the two latent projections, the shared expert, the
head) x the active lanes, the held picks' expert products, the state's, and 4 x
heads x head_dim a live token for the scores and the values.
"""

from benchlib import model

state = model.beside(__file__, "costs", "ssm_state")
experts = model.beside(__file__, "costs", "latent_moe_decode_experts")

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    e, a, s = arch.expert_shape(config), arch.attention_shape(config), arch.ssm_shape(config)
    held = state.cost(config, traffic, chips, counters, arch)
    exp = experts.cost(config, traffic, chips, counters, arch)
    one = e["matrices"] * e["d_model"] * e["d_ff"]
    not_routed = arch.total_params(config) - arch.embedding_params(config) - e["layers"] * e["held"] * one
    lanes = counters.get("traced.active", float(traffic["engine"]["max_batch"]))
    every_lane = arch.matmul_params(config) - e["layers"] * e["expected_held_picks"] * one
    compute = _BYTES[config["dtypes"]["compute"]]
    tails = 2.0 * counters["traced.serve.ssm.live_lanes"] * s["layers"] * (s["conv"] - 1) * s["channels"] * compute
    tokens = counters["traced.live_kv_tokens"] * a["layers"]
    looked_up = lanes * e["model_width"] * _BYTES[config["dtypes"]["serve_params"]]
    return {
        "flops": 2.0 * every_lane * lanes + exp["flops"] + held["flops"] + 4.0 * a["heads"] * a["head_dim"] * tokens,
        "bytes": not_routed * _BYTES[config["dtypes"]["serve_params"]] + looked_up + exp["bytes"] + held["bytes"] + tails
        + tokens * 2 * a["kv_heads"] * a["head_dim"] * compute,
    }
