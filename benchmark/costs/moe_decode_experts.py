"""One decode step's routed expert products, all expert layers
(``models/moe.py serve_routed_experts``: rows sorted by expert, gate, up and
down as grouped products): a held expert that got at least one row has its
three matrices read once, in the dtype the configuration serves them in; one
that got none is not read.  How many were hit is what the program counted in
the TRACED steps (``traced.serve.moe.experts_hit``, the expert layers' sum a
step, from the ``serve.decode`` spans' arguments), not the window's mean: the
count follows the lanes' tokens.  Beside the matrices, a held pick's row in
and out in the compute dtype, and ``3 x 2 x d_model x d_ff`` operations a
held pick.  At 2 rows an expert the matrices' bytes are all that matters.
"""

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    e = arch.expert_shape(config)
    hit, picks = counters["traced.serve.moe.experts_hit"], counters["traced.serve.moe.held_picks"]
    matrix = e["d_model"] * e["d_ff"]
    rows = picks * (2 * e["d_model"] + 3 * e["d_ff"]) * _BYTES[config["dtypes"]["compute"]]
    return {
        "flops": 3 * 2.0 * matrix * picks,
        "bytes": 3.0 * matrix * hit * _BYTES[config["dtypes"]["serve_params"]] + rows,
    }
