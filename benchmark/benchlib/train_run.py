"""A training cell: the program's ``Trainer.fit`` on packed sequences.

The system under test is ``train.Trainer(trial).fit(...)`` as a user runs
it: its own loop dispatches the steps, its own prefetching input pipeline
feeds them.  The trial is the program's ``LMTrial`` with three things a user
would also write in a subclass: what the configuration's adapter overrides
on the model config (the rotary base), a dataset made from ``--seed``, and a
callback.  The mesh and the experiment's ``optimizations`` block come with
the configuration's ``train_batch`` (absent: one chip, none).  The callback is the
benchmark's only hold on the loop: the Trainer calls it at every report
boundary, right after it has fetched the loss (a value fetch: every step
dispatched so far has run), and the window opens and closes at such calls.
"""

from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import model
from .observe import Observations, Profiler, tracer_epoch
from .spec import SpecError

mono = time.monotonic


class WindowClosed(Exception):
    """Raised from the callback to end ``fit`` when the window has closed."""


class Window:
    """Report boundaries, as the callback sees them.

    Boundary 1 ends the steps that compiled, boundary ``warm_boundaries``
    opens the window, and the first boundary at or after ``seconds`` closes
    it.  A traced run profiles exactly one report segment in the middle.
    """

    def __init__(self, seconds: float, warm_boundaries: int, profiler: Optional[Profiler], seg_steps: int) -> None:
        self.seconds = seconds
        self.warm = warm_boundaries
        self.profiler = profiler
        self.seg_steps = seg_steps
        self.marks: List[Tuple[float, int, float]] = []  # (time, steps, loss)
        self.traced_steps = 0
        self._tracing = False

    def boundary(self, steps: int, metrics: Dict[str, float]) -> None:
        now = mono()
        self.marks.append((now, steps, float(metrics.get("loss", float("nan")))))
        n = len(self.marks)
        if n < self.warm:
            return
        t_open = self.marks[self.warm - 1][0]
        if self.profiler is not None:
            if self._tracing:
                self.profiler.stop()
                self._tracing = False
                self.traced_steps = self.seg_steps
            elif self.profiler.started_at is None and now - t_open >= 0.4 * self.seconds:
                self.profiler.start()
                self._tracing = True
        if now - t_open >= self.seconds and not self._tracing:
            raise WindowClosed()

    @property
    def open_close(self) -> Tuple[Tuple[float, int, float], Tuple[float, int, float]]:
        return self.marks[self.warm - 1], self.marks[-1]


def _hparams(config: Dict[str, Any], traffic: Dict[str, Any], arch: Any) -> Dict[str, Any]:
    batch = int(config["train_batch"]["global_batch_sequences"])
    return {
        "lr": float(traffic["lr"]),
        "warmup_steps": int(traffic["warmup_steps"]),
        "decay_steps": int(traffic["decay_steps"]),
        "weight_decay": float(traffic["weight_decay"]),
        "grad_clip": float(traffic["grad_clip"]),
        "global_batch_size": batch,
        "seq_len": int(traffic["seq_len"]),
        "dataset_size": batch * int(traffic["dataset_batches"]),
        **arch.trial_hparams(config),
        "bf16": config["dtypes"]["compute"] == "bfloat16",
        "attention": traffic["attention"],
        "fused_ce": bool(traffic["fused_ce"]),
        "fused_adamw": bool(traffic["fused_adamw"]),
        "adam_mu_bf16": False,
        "remat": False,
    }


def _mesh(cell: Any) -> Dict[str, int]:
    """``train_batch.mesh`` of the configuration: axis name -> size, the
    names of the program's ``MeshConfig``.  Absent, one chip."""
    mesh = {str(k): int(v) for k, v in cell.config["train_batch"].get("mesh", {"data": 1}).items()}
    if math.prod(mesh.values()) != cell.chips:
        raise SpecError(
            f"{cell.config_file}: train_batch.mesh {mesh} spans {math.prod(mesh.values())} "
            f"chips and cell {cell.name} has {cell.chips}"
        )
    return mesh


def check(cell: Any) -> None:
    """What can be refused before a device is touched."""
    _mesh(cell)


def build_trainer(cell: Any, arch: Any, seed: int, window: Optional[Window], ckpt_dir: str) -> Any:
    import jax

    from determined_tpu import core, train
    from determined_tpu.config.experiment import ExperimentConfig, InvalidExperimentConfig
    from determined_tpu.data import SyntheticDataset
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig
    from determined_tpu.train._trial import Callback

    config, traffic = cell.config, cell.traffic
    arch.check_as_run(config)
    overrides = arch.trial_overrides(config)
    try:
        mesh_config = MeshConfig(**_mesh(cell))
        # an experiment's `optimizations:` block, as `dtpu experiment run` would hand it over
        optimizations = config["train_batch"].get("optimizations")
        exp_config = ExperimentConfig.parse({"optimizations": optimizations}) if optimizations else None
    except (TypeError, InvalidExperimentConfig) as e:
        raise SpecError(f"{cell.config_file}: train_batch: {e}") from None

    class Boundary(Callback):
        def on_training_workload_end(self, steps_completed: int, metrics: Dict[str, float]) -> None:
            if window is not None:
                window.boundary(steps_completed, metrics)

    class BenchTrial(LMTrial):
        def _cfg(self) -> Any:
            return dataclasses.replace(super()._cfg(), **overrides)

        def _dataset(self, split: int) -> Any:
            g = self.context.get_hparam
            # packed: every sequence is full, seq_len + 1 tokens, no padding
            return SyntheticDataset(
                {"tokens": ((int(g("seq_len")) + 1,), np.int32, int(g("vocab_size")))},
                size=int(g("dataset_size")),
                seed=np.random.SeedSequence([int(seed), split]).generate_state(1)[0],
            )

        def build_callbacks(self) -> Dict[str, Any]:
            return {"bench": Boundary()}

    ctx = train.init(
        hparams=_hparams(config, traffic, arch),
        mesh_config=mesh_config,
        exp_config=exp_config,
        core_context=core._dummy_init(checkpoint_dir=ckpt_dir),
        seed=model.seed32(seed),
        devices=jax.devices()[: cell.chips],
    )
    return train.Trainer(BenchTrial(ctx))


def run(
    cell: Any, arch: Any, seed: int, seconds: float, traced: bool,
    t_start: float, say: Callable[..., None], trace_dir: str,
) -> Dict[str, Any]:
    import jax

    from determined_tpu.observability import get_tracer

    config, traffic = cell.config, cell.traffic
    seg = int(traffic["report_every_steps"])
    profiler = Profiler(trace_dir) if traced else None
    window = Window(seconds, int(traffic["warm_boundaries"]), profiler, seg)
    tracer = get_tracer()
    tracer.configure(enabled=traced)
    epoch = tracer_epoch(tracer) if traced else 0.0
    ckpt_dir = os.path.join(os.path.dirname(trace_dir), "ckpt")
    trainer = build_trainer(cell, arch, seed, window, ckpt_dir)
    say("setup", stage="trainer_built", seconds_since_start=mono() - t_start)
    try:
        trainer.fit(
            {"batches": 10**9},
            report_period={"batches": seg},
            checkpoint_policy="none",
        )
    except WindowClosed:
        pass
    finally:
        if profiler is not None:
            profiler.close()
    (t_open, s_open, _), (t_close, s_close, _) = window.open_close
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices()[: cell.chips])
    tokens_per_step = int(config["train_batch"]["global_batch_sequences"]) * int(traffic["seq_len"])
    steps = s_close - s_open
    rate = steps * tokens_per_step / (t_close - t_open)
    losses = [loss for _, _, loss in window.marks]
    seg_rates = [
        (b[1] - a[1]) * tokens_per_step / (b[0] - a[0])
        for a, b in zip(window.marks[window.warm - 1:], window.marks[window.warm:])
    ]
    say(
        "train.window", window_s=t_close - t_open, steps=steps,
        tokens_per_step=tokens_per_step, segment_tokens_per_s=seg_rates,
        boundary_losses=losses, first_boundary_s=window.marks[0][0] - t_start,
    )
    obs = Observations(
        window=(t_open, t_close), spans=[],
        counters={
            "train.steps": float(steps),
            "train.steps_traced": float(window.traced_steps),
            "train.tokens_per_s": rate,
        },
        program_events=tracer.chrome_events() if traced else [],
        profiler=profiler, config=config, traffic=traffic, chips=cell.chips,
        program_epoch=epoch, arch=arch, data_dir=cell.data_dir,
    )
    ok, detail = _check(trainer, cell, arch, seed)
    say("train.check", **detail)
    finite = all(math.isfinite(x) for x in losses)
    return {
        "values": {"train_tokens_per_s": rate, "setup_s": t_open - t_start},
        "attempted": steps,
        "failed": 0 if finite else steps,
        "correct": bool(ok and finite and steps > 0),
        "check": detail,
        "memory_peak_bytes": peak,
        "observations": obs,
    }


def _adam_state(opt_state: Any) -> Any:
    """The part of the program's optimizer state that has ``count``, ``mu``
    and ``nu``, whichever optimizer the trial built."""
    import jax

    is_adam = lambda x: hasattr(x, "mu") and hasattr(x, "nu")  # noqa: E731
    return next(x for x in jax.tree_util.tree_leaves(opt_state, is_leaf=is_adam) if is_adam(x))


def _rel(got: Any, want: Any) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2)) / max(np.sqrt(np.sum(want**2)), 1e-30))


#: consecutive states a run's check reads, each on a seeded sequence of its own
STATES = 3
#: no single state's number may read over this many times its limit: the median
#: forgives one state's excursion (a flipped top-k pick: PERF.md section 5), not a
#: step that did not happen, which reads 1 and more
STATE_CEILING = 4.0
#: compared a state as one number, and a state and a probe leaf
SCALARS = ("loss_rel", "logits_rel_rms")
BY_LEAF = ("grad_rel", "moment2_rel", "update_rel")


def _state_errors(
    before: Dict[str, Any], after: Dict[str, Any], grads: Dict[str, Any], step: Dict[str, Any]
) -> Dict[str, Dict[str, float]]:
    """One state's three errors a probe leaf: quantity -> leaf -> error.
    ``before`` / ``after`` hold the program's parameters and moments round
    its one step, ``grads`` the reference's gradient, ``step`` what the plain
    AdamW needs (``count``, ``lr``, ``scale`` of the clip, the optimizer's
    numbers)."""
    from reference import adamw

    b1, b2 = float(step["opt"]["b1"]), float(step["opt"]["b2"])
    errs: Dict[str, Dict[str, float]] = {q: {} for q in BY_LEAF}
    for name, g in grads.items():
        g = step["scale"] * g
        p0, m0, v0 = (before[key][name] for key in "pmv")
        p1, m1, v1 = (after[key][name] for key in "pmv")
        want_p, _, want_v = adamw.adamw_step(p0, m0, v0, g, count=step["count"], lr=step["lr"], **step["opt"])
        # the gradient the program used, read back from its first moment
        errs["grad_rel"][name] = _rel((m1 - b1 * m0) / (1.0 - b1), g)
        errs["moment2_rel"][name] = _rel(v1 - b2 * v0, want_v - b2 * v0)
        errs["update_rel"][name] = _rel(p1 - p0, want_p - p0)
    return errs


def _median(values: List[float]) -> float:
    """The middle one; anything that is not a number makes it not a number."""
    return float(np.median(values)) if all(math.isfinite(v) for v in values) else float("nan")


def verdict(states: List[Dict[str, Any]], tol: Dict[str, Any]) -> Tuple[bool, Dict[str, Any]]:
    """What the states' errors come to, against the limits.

    A leaf's error in a quantity is the MEDIAN over the states, and the
    quantity's number the WORST leaf's: one state's excursion on one small
    leaf does not decide, an error that is there at every step does, on the
    smallest leaf.  The loss and the logits are the median of the states'.
    Beside each, ``<quantity>.state_max``: the worst leaf of the worst single
    state, held to ``STATE_CEILING`` times the limit, so that a fault in one
    state of three does not pass for an excursion.  A value that is not
    finite, in any state, fails its quantity."""
    found: Dict[str, float] = {q: _median([s[q] for s in states]) for q in SCALARS}
    by_state: Dict[str, List[float]] = {q: [s[q] for s in states] for q in SCALARS}
    worst_leaf: Dict[str, str] = {}
    leaves: Dict[str, Dict[str, List[float]]] = {}
    not_finite_first = lambda v: v if math.isfinite(v) else float("inf")  # noqa: E731
    for q in BY_LEAF:
        leaves[q] = {leaf: [s[q][leaf] for s in states] for leaf in states[0][q]}
        medians = {leaf: _median(v) for leaf, v in leaves[q].items()}
        # not-a-number sorts first in no order of its own: ask for it
        worst_leaf[q] = next((leaf for leaf, m in medians.items() if not math.isfinite(m)), None) or max(medians, key=medians.get)
        found[q] = medians[worst_leaf[q]]
        # a state's own worst leaf: what the check of one state read (before PR 43)
        by_state[q] = [max(s[q].values(), key=not_finite_first) for s in states]
    limits = {q: float(tol[q]) for q in found}
    for q in SCALARS + BY_LEAF:
        found[q + ".state_max"] = max(by_state[q], key=not_finite_first)
        limits[q + ".state_max"] = STATE_CEILING * limits[q]
    ok = bool(all(math.isfinite(v) and v <= limits[q] for q, v in found.items()))
    return ok, {
        **found, "tolerance": limits, "worst_leaf": worst_leaf,
        "by_state": by_state, "leaves": leaves, "ok": ok,
    }


def _check(trainer: Any, cell: Any, arch: Any, seed: int) -> Tuple[bool, Dict[str, Any]]:
    """The program against the reference on ``STATES`` consecutive steps from
    the parameters and moments as the window left them, each step on a
    seeded sequence of its own (``[seed, 0xC0FFEE, k]``).

    Forward: the program's logits at every position (its model in the
    configuration's compute dtype, flash kernel) and its loss (fused
    cross-entropy) against the reference's.  One whole step of the program
    (``Trainer``'s own jitted step: backward pass, fused cross-entropy's
    gradient, clipping, fused AdamW) on that sequence against the reference's
    ``jax.grad`` and its plain AdamW, on the leaves the adapter's ``probe``
    names: the clipped gradient the program used (read back from its first
    moment), the second moment it wrote, and the change of the parameters.
    One state does not decide (``verdict``): bfloat16 compute flips a few
    top-k picks of a starved expert, and rounds one layer's gradient several
    times worse on some sequences than on the next (PERF.md section 5).
    Over a mesh whose batch axes span several chips the program is given
    that many copies of the sequence: their mean loss and gradient are the
    one sequence's.
    """
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from reference import adamw

    config, traffic = cell.config, cell.traffic
    tol = config["tolerance"]["train_step"]
    n = int(tol["sequence_tokens"])
    trial = trainer.trial
    vocab = int(trial.context.get_hparam("vocab_size"))
    opt = {**traffic["adam"], "weight_decay": float(traffic["weight_decay"])}
    copies = int(trial.context.batch_axis_size)

    def named(tree: Any) -> Dict[str, Any]:
        return arch.reference_weights(meta.unbox(tree)["params"], config)

    probe = jax.jit(lambda tree, rows: arch.probe(named(tree), rows))

    def reference(weights: Dict[str, Any], tokens: jax.Array, rows: jax.Array):
        (loss, logits), grads = jax.value_and_grad(
            lambda w, t: arch.reference_loss_and_logits(w, t, config), has_aux=True
        )(weights, tokens)
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
        return loss, logits, norm, arch.probe(grads, rows)

    def program(params: Any, tokens: jax.Array):
        loss, _ = trial.loss(trainer.model, params, {"tokens": tokens}, jax.random.key(0))
        return loss, trainer.model.apply(params, tokens[:, :-1])[0]

    reference, program = jax.jit(reference), jax.jit(program)
    logits_rel_of = jax.jit(lambda got, want: jnp.sqrt(jnp.sum((got - want) ** 2) / jnp.sum(want * want)))
    took = {"reference_s": 0.0, "program_forward_s": 0.0, "program_step_s": 0.0, "compare_s": 0.0}

    def lap(what: str, t0: float) -> float:
        took[what] = round(took[what] + mono() - t0, 2)
        return mono()

    def f64(tree: Any) -> Any:
        return jax.tree.map(lambda x: np.asarray(x, np.float64), tree)

    states: List[Dict[str, Any]] = []
    steps: List[Dict[str, Any]] = []
    for k in range(STATES):
        rng = np.random.default_rng([int(seed), 0xC0FFEE, k])
        seq = rng.integers(1, vocab, size=n + 1, dtype=np.int64).astype(np.int32)
        present = np.unique(seq[:-1])
        absent = np.setdiff1d(np.arange(vocab), present)
        rows = np.concatenate([present[:192], absent[:64]])
        if copies == 1:
            batch = {"tokens": jnp.asarray(seq[None, :])}
        else:
            from determined_tpu.data._loader import to_global

            batch = to_global({"tokens": np.tile(seq[None, :], (copies, 1))}, trainer.mesh)
        t = mono()
        with trainer.mesh:
            state = trainer.state
            adam = _adam_state(state.opt_state)
            count = int(adam.count)
            before = {"p": probe(state.params, rows), "m": probe(adam.mu, rows), "v": probe(adam.nu, rows)}
            want_loss, want_logits, norm, grads = reference(named(state.params), jnp.asarray(seq), rows)
            norm = float(norm)
            t = lap("reference_s", t)
            got_loss, got_logits = program(state.params, batch["tokens"])
            logits_rel = float(logits_rel_of(got_logits, want_logits))
            del got_logits, want_logits
            t = lap("program_forward_s", t)
            # one step of the program, as Trainer.fit dispatches it (the state is donated)
            trainer.state = state = trainer._train_step(state, batch)
            adam = _adam_state(state.opt_state)
            after = {"p": probe(state.params, rows), "m": probe(adam.mu, rows), "v": probe(adam.nu, rows)}
            jax.block_until_ready(after)
            t = lap("program_step_s", t)
        got_loss, want_loss = float(got_loss), float(want_loss)
        step = {
            "count": count, "opt": opt, "scale": adamw.clip_scale(norm, float(traffic["grad_clip"])),
            "lr": adamw.warmup_cosine_lr(
                count, peak=float(traffic["lr"]), warmup_steps=int(traffic["warmup_steps"]),
                decay_steps=int(traffic["decay_steps"]),
            ),
        }
        states.append({
            "loss_rel": abs(got_loss - want_loss) / abs(want_loss), "logits_rel_rms": logits_rel,
            **_state_errors(f64(before), f64(after), f64(grads), step),
        })
        steps.append({
            "updates_before": count, "lr": step["lr"], "clip_scale": step["scale"], "reference_grad_norm": norm,
            "program_loss": got_loss, "reference_loss": want_loss,
        })
        lap("compare_s", t)
    ok, detail = verdict(states, tol)
    return ok, {**detail, "states": steps, "seconds": took}
