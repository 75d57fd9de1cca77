"""One decode step's latent attention kernels (``ops/paged_attention.py``
``paged_latent_attention``), all layers: every live token's cached row is
read once a layer, ``kv_lora_rank + qk_rope_head_dim`` values in the cache's
dtype (576 x 2 B; the 64 zeros that pad a stored row to 640 are NOT counted:
they show as lost roofline), and every query head multiplies it twice, for
its score over the whole row and for its value over the latent part: ``2 x
heads x (576 + 512)`` operations a live token a layer.  At 128 heads that is
242 operations a byte, the v5e's ridge (197e12 / 819e9 = 240): the kernel is
bound by neither alone.

Live tokens a step: the traced steps' mean where the reader hands it over
(``traced.live_kv_tokens``, from the program's ``serve.decode`` spans), else
the window's mean from the runner's counters.
"""

_BYTES = {"float32": 4, "bfloat16": 2}


def live_tokens_per_step(counters):
    traced = counters.get("traced.live_kv_tokens")
    if traced is not None:
        return float(traced)
    return counters["serve.live_kv_tokens"] / counters["serve.decode_steps"]


def cost(config, traffic, chips, counters, arch):
    s = arch.latent_shape(config)
    row = s["kv_lora_rank"] + s["qk_rope_head_dim"]
    tokens = s["layers"] * live_tokens_per_step(counters)
    return {
        "flops": 2.0 * s["heads"] * (row + s["kv_lora_rank"]) * tokens,
        "bytes": float(row * _BYTES[config["dtypes"]["kv_cache"]]) * tokens,
    }
