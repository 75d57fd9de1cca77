"""Model FLOP/s utilisation with the operations of a token taken from a
cost function (``cost``: its ``flops`` are one token's, forward and backward,
no recomputation) instead of ``costs.train_flops_per_token``: x tokens a
second (``rate``, a counter) over chips x peak.  ``events``: the program's
counter events the cost function is handed as means a step; one that the
program does not push is left out and the cost function falls back on what
the configuration lets it expect.
"""

from benchlib import costs, model

counter_events = model.beside(__file__, "readers", "counter_events")


def read(obs, args, peak):
    rate = obs.counters.get(args["rate"])
    if not rate:
        return None
    found = {name: counter_events.per_step(obs, name) for name in args.get("events", ())}
    counters = {**obs.counters, **{k: v for k, v in found.items() if v is not None}}
    need = costs.find(args["cost"], obs.data_dir)(obs.config, obs.traffic, obs.chips, counters, obs.arch)
    return 100.0 * need["flops"] * rate / (obs.chips * peak["bf16_flops_per_s"])
