"""One whole decode step of a latent-attention, routed-expert model whose
attention reads the rows an indexer picks: ``mla_moe_decode_step``'s parameters
and experts (every parameter once in the dtype the configuration serves them
in, the indexers' leaves among them, less the embedding and less the routed
experts no row reached), with the indexers' score passes
(``dsa_index_scores``) and the attention over the picked rows
(``dsa_sparse_attention``) in the place of ``mla_paged_attention``'s every
live row.  The exact top-k moves no memory that must move and is not counted:
its time shows as lost share.
"""

from benchlib import model

index = model.beside(__file__, "costs", "dsa_index_scores")
attention = model.beside(__file__, "costs", "dsa_sparse_attention")
experts = model.beside(__file__, "costs", "moe_decode_experts")

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    e = arch.expert_shape(config)
    parts = [f.cost(config, traffic, chips, counters, arch) for f in (index, attention, experts)]
    one = 3 * e["d_model"] * e["d_ff"]
    not_routed = arch.total_params(config) - arch.embedding_params(config) - e["layers"] * e["held"] * one
    lanes = counters.get("traced.active", float(traffic["engine"]["max_batch"]))
    every_lane = arch.matmul_params(config) - e["layers"] * e["expected_held_picks"] * one
    return {
        "flops": 2.0 * every_lane * lanes + sum(p["flops"] for p in parts),
        "bytes": not_routed * _BYTES[config["dtypes"]["serve_params"]] + sum(p["bytes"] for p in parts),
    }
