"""One decode step's latent attention over the rows the indexers picked, all
layers: what it MUST move whatever implements it (rows gathered and then
attended, or every live row walked under a mask).  A picked token's cached row
is read once a layer, ``kv_lora_rank + qk_rope_head_dim`` values in the
cache's dtype (576 x 2 B; the 64 zeros that pad a stored row are not counted),
and every query head multiplies it twice, for its score over the whole row and
for its value over the latent part: ``2 x heads x (576 + 512)`` operations a
selected token a layer, as ``mla_paged_attention`` counts a live one.  A row
nobody picked need not be read: a program that reads it shows it as lost share.

Selected tokens a step: the program's own counter on the traced steps' spans
(``traced.serve.dsa.selected_tokens``: ``min(context, index_topk)`` a lane,
times the latent layers).
"""

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    s = arch.latent_shape(config)
    row = s["kv_lora_rank"] + s["qk_rope_head_dim"]
    tokens = counters["traced.serve.dsa.selected_tokens"]
    return {
        "flops": 2.0 * s["heads"] * (row + s["kv_lora_rank"]) * tokens,
        "bytes": float(row * _BYTES[config["dtypes"]["kv_cache"]]) * tokens,
    }
