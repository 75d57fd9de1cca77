"""The throw-away root of these tests copies no ``costs/`` directory, and
``bench_testlib`` appends every ``train_*`` metric of ``BENCHMARK.json`` to
its tiny training cells: ``train_mfu_routed`` among them, whose metric file
names the cost ``train_flops_routed``.  So the root is handed the
benchmark's own file (an adapter without windows or experts gets the dense
arithmetic from it)."""

import os

from benchlib import model, spec

cost = model.load_file(
    os.path.join(spec.CHECKOUT, "benchmark", "costs", "train_flops_routed.py"),
    "the tests' root needs the benchmark's train_flops_routed",
).cost
