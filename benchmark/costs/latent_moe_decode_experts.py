"""One decode step's routed expert products where the experts are TWO
matrices each and work in a latent (``models/moe.py serve_routed_experts``
under ``moe_expert_act`` "relu2" and ``moe_latent_size``: rows sorted by
expert, up and down as grouped products), all expert layers: a held expert
that got at least one row has its two matrices of ``latent x width`` read once,
in the dtype the configuration serves them in; one that got none is not read.
How many were hit is what the program counted in the TRACED steps
(``traced.serve.moe.experts_hit``, the expert layers' sum a step, from the
``serve.decode`` spans' arguments), not the window's mean.  Beside the
matrices, a held pick's row in and out at the LATENT width in the compute
dtype (what the hidden values cost is the kernel's own business: a cost
counts what MUST move), and ``2 x 2 x latent x width`` operations a held pick.
At 11 rows an expert the matrices' bytes are all that matters.  The two
projections round the experts are not this kernel's: ``ssm_latent_moe_decode_step``
counts them with every other matrix a lane multiplies with.
"""

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(config, traffic, chips, counters, arch):
    e = arch.expert_shape(config)
    hit, picks = counters["traced.serve.moe.experts_hit"], counters["traced.serve.moe.held_picks"]
    matrix = e["d_model"] * e["d_ff"]  # ``d_model`` is the width the experts work in: the latent
    rows = picks * 2 * e["d_model"] * _BYTES[config["dtypes"]["compute"]]
    return {
        "flops": e["matrices"] * 2.0 * matrix * picks,
        "bytes": float(e["matrices"]) * matrix * hit * _BYTES[config["dtypes"]["serve_params"]] + rows,
    }
