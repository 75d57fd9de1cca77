"""Operations the forward and backward passes need for ONE token (no
recomputation) where experts are routed and layers mix windows: what
``train_mfu`` needs in such a cell.  ``costs.train_flops_per_token`` assumes
full causal attention in every layer and would overstate it.

6 x the parameters a token multiplies with (the adapter's ``matmul_params``:
projections, router, head and the EXPECTED held experts), corrected to the
held picks the program counted where the counter is there, plus attention's
two matmuls over the pairs each layer's mask lets through (``mixed_attention``),
x 3 for forward + backward.  Returned as ``flops`` of one token; no bytes.
An adapter without windows or experts gets the dense arithmetic.
"""

from benchlib import model

mixed = model.beside(__file__, "costs", "mixed_attention")


def cost(config, traffic, chips, counters, arch):
    s = arch.attention_shape(config)
    seq = int(traffic["seq_len"])
    flops = 6.0 * arch.matmul_params(config)
    counted, shape = counters.get("moe.held_picks"), getattr(arch, "expert_shape", None)
    if counted and shape is not None:
        e = shape(config)
        tokens = seq * int(config["train_batch"]["global_batch_sequences"])
        picks = counted / tokens - e["layers"] * e["expected_held_picks"]  # beyond the expected, a token
        flops += 6.0 * 3 * e["d_model"] * e["d_ff"] * picks
    pairs = sum(mixed.visible_pairs(seq, w) for w in mixed.windows(config, arch))
    flops += 3.0 * 2 * 2 * s["heads"] * s["head_dim"] * pairs / seq
    return {"flops": flops, "bytes": 0.0}
