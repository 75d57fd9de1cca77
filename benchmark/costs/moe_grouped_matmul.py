"""One training step's grouped expert products: gate, up and down of every
pick that landed on a held expert, forward and the two backward products
(to the rows and to the weights): 3 x 3 x 2 x d_model x d_ff operations a
held pick.  The held picks of a step are what the program counted
(``moe.held_picks``: the layers' sum, a step's mean over the window, handed
over by the reader); without the counter, what an even router would give.
Bytes: every held expert's three matrices once a product in the compute
dtype, and a pick's rows in and out.
"""


def held_picks_per_step(config, traffic, counters, arch):
    counted = counters.get("moe.held_picks")
    if counted:
        return float(counted)
    e = arch.expert_shape(config)
    tokens = int(traffic["seq_len"]) * int(config["train_batch"]["global_batch_sequences"])
    return e["layers"] * tokens * e["expected_held_picks"]


def cost(config, traffic, chips, counters, arch):
    e = arch.expert_shape(config)
    picks = held_picks_per_step(config, traffic, counters, arch)
    matrix = e["d_model"] * e["d_ff"]
    return {
        "flops": 9 * 2.0 * matrix * picks / chips,
        "bytes": (9 * 2.0 * e["layers"] * e["held"] * matrix + 9 * 2.0 * picks * (e["d_model"] + e["d_ff"])) / chips,
    }
