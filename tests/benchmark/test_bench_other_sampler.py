"""Nothing in the serving runner knows how the engine samples (PR 43).

Until PR 43 a traced run laid its own function over the module attribute
the engine module's host sampler and two metrics read the spans it wrote there, so an
engine that took its tokens any other way lost two listed metrics and was
refused.  Here the tiny serving cell is given an engine whose step samples
with a function of its own, and none of the engine module's; the harness
runs the cell traced, reads every serving metric of the contract from the
program's own spans, and finds it correct; every function of the engine's
module is, while the steps run, the one it was before the runner came.

The check itself still takes its logits from the timed ``kernels.decode``
(the one the runner wraps and the window drives): a program whose ``decode``
returns token ids needs a check of that call's ids, which comes with the
PR that brings such a program."""

import types

import numpy as np

import bench_testlib as B
from benchlib import harness, serve_run
from determined_tpu.serve import engine as engine_mod

#: what ``test_bench_contract.py`` asks of ``tiny.closed`` traced
EXPECT = {"serve_step_ms", "serve_step_sample_ms", "serve_lane_occupancy", "tiny_decode_calls_ms", "tiny_decode_steps"}


def test_an_engine_with_a_sampler_of_its_own_runs_traced_and_correct(tmp_path, monkeypatch, capsys):
    root = B.throwaway_root(str(tmp_path / "root"))
    build = serve_run.build_engine
    seen = {"own_sampler": 0, "faults": 0}
    functions = {name: f for name, f in vars(engine_mod).items() if isinstance(f, types.FunctionType)}

    def build_other(cell, arch, seed):
        engine, params, model_cfg = build(cell, arch, seed)

        def advance(self, seq, logits_row):
            seen["own_sampler"] += 1
            # nothing laid over a function of the engine's module (its host sampler among them).  Noted, and
            # asserted once the run is over: this is the engine's thread
            seen["faults"] += int(any(vars(engine_mod)[name] is not f for name, f in functions.items()))
            tok = int(np.argmax(logits_row))  # the other sampler: greedy, and not the module's
            seq.request.output.append(tok)
            seq.request.token_at.append(engine_mod.mono())
            seq.pos += 1
            seq.next_token = tok
            with self._stats_lock:
                self._tokens_generated += 1
            return self._sequence_finished(seq, tok)

        engine._advance_lane = types.MethodType(advance, engine)
        return engine, params, model_cfg

    monkeypatch.setattr(serve_run, "build_engine", build_other)
    line = harness.run_cell("tiny.closed", seed=2**31 + 43, seconds=1.5, traced=True, root=root, require_tpu=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) >= EXPECT and all(m["value"] > 0 for m in line["metrics"].values())
    assert {"rel_rms", "max_abs"} <= set(line["compared"])
    # every decode step took its tokens from the other sampler (a prompt's first token is admission's, not a step's)
    assert seen["own_sampler"] > line["attempted"] and seen["faults"] == 0
    assert len(functions) > 3 and all(vars(engine_mod)[name] is f for name, f in functions.items())


def test_an_answer_altered_where_it_is_produced_is_not_correct(tmp_path, monkeypatch, capsys):
    """The rest of a run with the timed path broken underneath: the decode
    call hands every lane its neighbour's vocabulary entry."""
    root = B.throwaway_root(str(tmp_path / "root"))
    build = serve_run.build_engine

    def build_broken(cell, arch, seed):
        engine, params, model_cfg = build(cell, arch, seed)
        decode = engine.kernels.decode
        engine.kernels.decode = lambda *args: np.roll(decode(*args), 1, axis=-1)
        return engine, params, model_cfg

    monkeypatch.setattr(serve_run, "build_engine", build_broken)
    line = harness.run_cell("tiny.closed", seed=2**31 + 45, seconds=1.0, traced=False, root=root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["rel_rms"][0] > line["compared"]["rel_rms"][1]
