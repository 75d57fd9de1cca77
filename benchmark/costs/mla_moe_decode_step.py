"""One whole decode step of a latent-attention, routed-expert model: what
``benchlib/costs.py decode_step`` is to a dense GQA decoder, which does not
fit here (it counts ``2 x kv_heads x head_dim`` values a cached token, 57
times a latent row, and every held expert whether a row reached it or not).

Bytes: every parameter once in the dtype the configuration serves them in,
less the embedding (a lookup of one row a lane) and less the routed experts
no row reached (``traced.serve.moe.experts_hit`` of the traced steps: see
``moe_decode_experts``), plus the live latent rows (``mla_paged_attention``).
Operations: 2 x the matrices a lane multiplies with (attention's
projections, the dense MLP or router and shared expert, the head) x the
active lanes, the held picks' expert products, and the attention kernels'.
"""

from benchlib import model

attention = model.beside(__file__, "costs", "mla_paged_attention")
experts = model.beside(__file__, "costs", "moe_decode_experts")

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    e = arch.expert_shape(config)
    att = attention.cost(config, traffic, chips, counters, arch)
    exp = experts.cost(config, traffic, chips, counters, arch)
    one = 3 * e["d_model"] * e["d_ff"]
    not_routed = arch.total_params(config) - arch.embedding_params(config) - e["layers"] * e["held"] * one
    lanes = counters.get("traced.active", float(traffic["engine"]["max_batch"]))
    every_lane = arch.matmul_params(config) - e["layers"] * e["expected_held_picks"] * one
    return {
        "flops": 2.0 * every_lane * lanes + exp["flops"] + att["flops"],
        "bytes": not_routed * _BYTES[config["dtypes"]["serve_params"]] + exp["bytes"] + att["bytes"],
    }
