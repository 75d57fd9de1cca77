"""``op_roofline`` (benchlib/readers.py) for a kernel whose work a step is
known only to the program: the least time the chip could take for what the
cost function counts, over the trace time of the operations whose name
matches ``pattern``, per ``per`` (a counter: steps traced).  ``events`` names
the program's counter events whose mean a step over the window
(``counter_events.per_step``) the cost function is handed, under their own
names, beside the run's counters.  A trace without such operations, or a
program without the counters, gives nothing.
"""

import dataclasses

from benchlib import model, readers

counter_events = model.beside(__file__, "readers", "counter_events")


def read(obs, args, peak):
    found = {name: counter_events.per_step(obs, name) for name in args.get("events", ())}
    if any(v is None for v in found.values()):
        return None
    return readers.op_roofline(dataclasses.replace(obs, counters={**obs.counters, **found}), args, peak)
