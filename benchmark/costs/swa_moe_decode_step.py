"""One whole decode step of a model with window and full GQA layers, routed
experts and a tied head: what ``benchlib/costs.py decode_step`` is to a dense
GQA decoder, which does not fit here on three counts (it reads every layer at
the full context, every held expert whether a row reached it or not, and an
embedding apart from the head).

Bytes: every parameter once in the dtype the configuration serves them in (the
tied table counts once: it is the head, swept; the lanes' embedding rows are a
lookup), less the routed experts no row reached
(``traced.serve.moe.experts_hit`` of the traced steps: see
``moe_decode_experts``), plus the K and V rows the attention reads: the
contexts in the full layers (``traced.serve.kv.full_tokens``) and ``min(context,
window)`` in the window layers (``traced.serve.kv.window_tokens``), 2 x kv_heads
x head_dim values a token.  Operations: 2 x the matrices a lane multiplies with
(attention's projections, the router, the shared experts, the head) x the
active lanes, the held picks' expert products, and 4 x heads x head_dim a token
the attention reads.
"""

from benchlib import model

window = model.beside(__file__, "costs", "window_paged_attention")
experts = model.beside(__file__, "costs", "moe_decode_experts")

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    e = arch.expert_shape(config)
    exp = experts.cost(config, traffic, chips, counters, arch)
    win = window.cost(config, traffic, chips, counters, arch)
    # a full layer's token costs what a window layer's does
    full = window.cost(config, traffic, chips, {"traced.serve.kv.window_tokens": counters["traced.serve.kv.full_tokens"]}, arch)
    one = 3 * e["d_model"] * e["d_ff"]
    not_routed = arch.total_params(config) - e["layers"] * e["held"] * one
    lanes = counters.get("traced.active", float(traffic["engine"]["max_batch"]))
    every_lane = arch.matmul_params(config) - e["layers"] * e["expected_held_picks"] * one
    return {
        "flops": 2.0 * every_lane * lanes + exp["flops"] + win["flops"] + full["flops"],
        "bytes": not_routed * _BYTES[config["dtypes"]["serve_params"]] + exp["bytes"] + win["bytes"] + full["bytes"],
    }
