"""A test's cost function, brought as a file the way a PR brings one with
its kernel: one pass over every parameter in float32, and as many
operations as the counter the test sets."""


def cost(config, traffic, chips, counters, arch):
    return {"flops": counters["tiny.flops"], "bytes": 4.0 * arch.total_params(config) / chips}
