"""The step check's statistic (PR 43): three consecutive states, a leaf's
error the MEDIAN of its three, a quantity's number the WORST leaf's, and no
single state over ``STATE_CEILING`` times a limit.  On the tiny training cell
through the harness, with an error planted where one state's comparison is
made, and on ``verdict`` alone."""

import json
import math

import pytest

import bench_testlib as B
from benchlib import harness, train_run

LEAF = "last.wo"  # the dense decoder's 8-row slice: the leaf whose one-state excursion refused sound runs
QUANTITIES = ("loss_rel", "logits_rel_rms", "grad_rel", "moment2_rel", "update_rel")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return B.throwaway_root(str(tmp_path_factory.mktemp("step_check_root")))


def run_with(monkeypatch, capsys, root, plant):
    """``tiny.train`` with ``plant(state number, that state's errors)`` applied
    where each state has been compared; the last line and the check's event."""
    real, calls = train_run._state_errors, []

    def planted(*args):
        errs = real(*args)
        plant(len(calls), errs)
        calls.append(1)
        return errs

    monkeypatch.setattr(train_run, "_state_errors", planted)
    line = harness.run_cell("tiny.train", seed=2**31 + 43, seconds=1.0, traced=False, root=root, require_tpu=False)
    assert len(calls) == train_run.STATES == 3
    event = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"train.check"' in x)
    return line, event


def test_three_consecutive_states_are_read_and_printed(monkeypatch, capsys, tiny_root):
    line, event = run_with(monkeypatch, capsys, tiny_root, lambda k, errs: None)
    assert line["correct"] is True
    assert set(QUANTITIES) | {q + ".state_max" for q in QUANTITIES} <= set(line["compared"])
    assert all(v <= limit for v, limit in line["compared"].values())
    # consecutive updates, each on its own sequence; all three states' values a quantity, the worst leaf named
    counts = [s["updates_before"] for s in event["states"]]
    assert counts == [counts[0], counts[0] + 1, counts[0] + 2]
    assert len({s["reference_loss"] for s in event["states"]}) == 3
    assert all(len(event["by_state"][q]) == 3 for q in QUANTITIES)
    assert set(event["worst_leaf"]) == {"grad_rel", "moment2_rel", "update_rel"}
    assert all(len(v) == 3 for leaves in event["leaves"].values() for v in leaves.values())
    assert LEAF in event["leaves"]["grad_rel"]


def limit_of(root, quantity="grad_rel"):
    from benchlib import spec

    return float(spec.Spec(root).cell("tiny.train").config["tolerance"]["train_step"][quantity])


def test_an_excursion_of_one_state_on_one_small_leaf_passes(monkeypatch, capsys, tiny_root):
    """What the chip shows of sound runs: one state reads up to 2.6 times a
    limit on one leaf, and a third of that or less an update later."""
    excursion = 3.0 * limit_of(tiny_root)

    def plant(k, errs):
        if k == 1:
            errs["grad_rel"][LEAF] = excursion

    line, event = run_with(monkeypatch, capsys, tiny_root, plant)
    assert line["correct"] is True and event["grad_rel"] < event["tolerance"]["grad_rel"] < excursion
    # it is printed all the same: that state's own worst leaf reads it, and it is compared with the ceiling
    assert event["by_state"]["grad_rel"][1] == excursion and event["leaves"]["grad_rel"][LEAF][1] == excursion
    assert line["compared"]["grad_rel.state_max"] == [excursion, train_run.STATE_CEILING * event["tolerance"]["grad_rel"]]


@pytest.mark.parametrize("state", [0, 1, 2])
def test_a_fault_in_one_state_of_three_is_not_an_excursion(monkeypatch, capsys, tiny_root, state):
    """An update skipped every third step reads 1 in that state: the
    median forgives it, the ceiling on every single state does not."""
    def plant(k, errs):
        if k == state:
            for q in train_run.BY_LEAF:
                errs[q] = {leaf: 1.0 for leaf in errs[q]}

    line, event = run_with(monkeypatch, capsys, tiny_root, plant)
    assert line["correct"] is False and line["failed"] == 0
    for q in train_run.BY_LEAF:
        assert event[q] < event["tolerance"][q]  # the median of the three does not see it
        assert line["compared"][q + ".state_max"] == [1.0, train_run.STATE_CEILING * event["tolerance"][q]]
        assert event["by_state"][q][state] == 1.0


def test_the_same_error_at_every_state_fails_and_the_event_names_the_leaf(monkeypatch, capsys, tiny_root):
    def plant(k, errs):
        errs["grad_rel"][LEAF] = 0.5

    line, event = run_with(monkeypatch, capsys, tiny_root, plant)
    assert line["correct"] is False and line["failed"] == 0
    assert event["grad_rel"] == 0.5 > event["tolerance"]["grad_rel"] and event["worst_leaf"]["grad_rel"] == LEAF
    assert line["compared"]["grad_rel"] == [0.5, event["tolerance"]["grad_rel"]]
    assert event["tolerance"]["grad_rel.state_max"] == train_run.STATE_CEILING * event["tolerance"]["grad_rel"]
    # the other quantities stay sound: the control fails one number, not each
    assert event["update_rel"] < event["tolerance"]["update_rel"]


@pytest.mark.parametrize("state", [0, 1, 2])
def test_a_value_that_is_not_finite_in_any_state_fails(monkeypatch, capsys, tiny_root, state):
    def plant(k, errs):
        if k == state:
            errs["moment2_rel"][LEAF] = float("nan")

    line, event = run_with(monkeypatch, capsys, tiny_root, plant)
    assert line["correct"] is False
    assert event["worst_leaf"]["moment2_rel"] == LEAF and not math.isfinite(event["moment2_rel"])
    assert line["compared"]["moment2_rel"][0] == "nan" and json.loads(json.dumps(line, allow_nan=False))  # the line stays JSON


def test_the_verdict_takes_the_median_a_leaf_and_then_the_worst_leaf():
    tol = {q: 0.1 for q in QUANTITIES}

    def state(small, big, loss=0.01):
        return {"loss_rel": loss, "logits_rel_rms": 0.02, **{q: {"small": small, "big": big} for q in train_run.BY_LEAF}}

    ok, detail = train_run.verdict([state(0.03, 0.02), state(0.12, 0.02), state(0.04, 0.02)], tol)
    assert ok and detail["grad_rel"] == 0.04 and detail["worst_leaf"]["grad_rel"] == "small"
    assert detail["by_state"]["grad_rel"] == [0.03, 0.12, 0.04]  # what a check of one state would have read
    # two states of three over the limit: the median is over it
    ok, detail = train_run.verdict([state(0.03, 0.02), state(0.12, 0.02), state(0.11, 0.02)], tol)
    assert not ok and detail["grad_rel"] == 0.11
    # excursions on DIFFERENT leaves in different states do not add up to a failure
    ok, detail = train_run.verdict([state(0.3, 0.02), state(0.03, 0.3), state(0.03, 0.02)], tol)
    assert ok and detail["by_state"]["grad_rel"] == [0.3, 0.3, 0.03] and detail["grad_rel.state_max"] == 0.3
    # but no single state may read over STATE_CEILING times the limit, on any leaf
    assert train_run.STATE_CEILING == 4.0 and detail["tolerance"]["grad_rel.state_max"] == pytest.approx(0.4)
    ok, detail = train_run.verdict([state(0.03, 0.02), state(0.03, 0.41), state(0.03, 0.02)], tol)
    assert not ok and detail["grad_rel"] == 0.03 and detail["grad_rel.state_max"] == 0.41
    # the loss and the logits: the median of the three states', each state under the ceiling
    ok, detail = train_run.verdict([state(0.03, 0.02, loss=0.3), state(0.03, 0.02), state(0.03, 0.02)], tol)
    assert ok and detail["loss_rel"] == 0.01 and detail["loss_rel.state_max"] == 0.3
    ok, detail = train_run.verdict([state(0.03, 0.02, loss=0.5), state(0.03, 0.02), state(0.03, 0.02)], tol)
    assert not ok and detail["loss_rel"] == 0.01
    ok, _ = train_run.verdict([state(0.03, 0.02, loss=float("inf")), state(0.03, 0.02), state(0.03, 0.02)], tol)
    assert not ok


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch, capsys, tiny_root):
    """The rest of a run with the timed path broken underneath: the check
    drives the window's own compiled step, here one that hands its state back."""
    real = train_run._check

    def broken(trainer, *args):
        trainer._train_step = lambda state, batch: state
        return real(trainer, *args)

    monkeypatch.setattr(train_run, "_check", broken)
    line = harness.run_cell("tiny.train", seed=2**31 + 44, seconds=1.0, traced=False, root=tiny_root, require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0
    # nothing moved where the reference moves every leaf: the parameters' change reads 1, and the moments,
    # which stand still, are about as far from the reference's as the reference's increments are long
    assert line["compared"]["update_rel"][0] == pytest.approx(1.0, abs=1e-9)
    assert all(line["compared"][q][0] > 0.7 for q in ("grad_rel", "moment2_rel"))
    assert line["compared"]["loss_rel"][0] < line["compared"]["loss_rel"][1]  # the forward pass is sound
