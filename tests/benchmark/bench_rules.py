"""Which scopes an architecture's programs have, for the metrics whose file
says ``"cells": {"of": ..., "scope": ...}`` (``benchlib/spec.py``: ``CELLS``,
``Spec.belongs``, ``Spec.list_faults``): the program decides, by a CPU
lowering of the architecture's tiny form, not a cell's or a model's name."""

import functools
import tempfile
import types
from typing import Any

import bench_testlib as B
from benchlib import model


@functools.lru_cache(maxsize=None)
def scopes_of(arch_name: str, serving: bool) -> frozenset:
    """The scopes of the architecture's decode program (serving) or step
    program (training): its tiny form (``tiny/<arch>.json``) lowered on the
    CPU, read off the ``jit.scopes`` instant the program itself emits."""
    from determined_tpu.observability import get_tracer

    form = B.tiny_form(arch_name)
    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)
    seen = len(tracer.chrome_events())
    try:
        if serving:
            from benchlib import serve_run

            cell = types.SimpleNamespace(
                config=form["config"], traffic={"engine": form["serve_engine"]}, data_dir=B.BENCH,
                config_file=f"tiny/{arch_name}.json",
            )
            serve_run.build_engine(cell, model.adapter(cell), 1)  # DecodeKernels makes the programs' first calls
            program = "jit.compile.serve.decode"
        else:
            from benchlib import train_run

            cell = types.SimpleNamespace(
                config=form["config"], traffic=B.TINY_TRAFFIC["tiny-train"], data_dir=B.BENCH,
                config_file=f"tiny/{arch_name}.json", chips=1, name=f"tiny-{arch_name}",
            )
            trainer = train_run.build_trainer(cell, model.adapter(cell), 1, None, tempfile.mkdtemp())
            trainer.fit({"batches": 1}, report_period={"batches": 1}, checkpoint_policy="none")
            program = "jit.compile.train"
        found = [e for e in tracer.chrome_events()[seen:]
                 if e.get("name") == "jit.scopes" and e["args"]["program"] == program]
    finally:
        tracer.configure(enabled=was)
    assert found, f"the {program} program of tiny/{arch_name}.json left no jit.scopes instant"
    return frozenset(found[0]["args"]["scopes"])


def has_scope(cell: Any, scope: str) -> bool:
    """What ``Spec.belongs`` asks: has this cell's program the scope?"""
    arch = str(cell.config.get("arch", model.DEFAULT_ARCH))
    return scope in scopes_of(arch, cell.traffic["kind"].startswith("serve-"))
