"""Pipeline parallelism: schedule-driven microbatch pipelining over the
``pipe`` mesh axis.

The reference's deepest pipeline support is a DeepSpeed passthrough
(``deepspeed/_mpu.py`` — topology bookkeeping, engine owned by DeepSpeed);
this is the TPU-native schedule itself.  Design (the SPMD pipelining
pattern from the scaling playbook): stage parameters are STACKED on a
leading ``[P, ...]`` dim sharded over ``pipe``; the whole schedule is one
``lax.scan`` inside ``shard_map``, where every tick each device applies
ITS stage to its current activation and hands the result to the next stage
with a single ``ppermute`` rotation.

Three schedules (``optimizations.pipeline_schedule``), all a single jitted
SPMD program with static trip counts — one trace, RetraceSentinel-clean:

- ``gpipe``: M microbatches drain in M + P - 1 ticks; reverse-mode AD
  differentiates straight through the scan + ppermute (its transpose is
  the reverse rotation).  Every tick's stage residuals are saved for
  backward, so live activations grow with M.
- ``1f1b``: same forward numerics and tick count, but the backward is a
  hand-written ``custom_vjp`` running ONE combined scan of
  2M + 2(P - 1) unit ticks that interleaves recomputed forward units with
  backward units (warmup of P - p forwards on stage p, then strict
  1F1B alternation).  Only a ring buffer of **P** stage-input
  activations is live at any tick — the Megatron-LM 1F1B memory cap,
  which is what buys larger M (hence a smaller bubble) at fixed HBM.
  Grad accumulation per stage runs in increasing-microbatch order (the
  scan-transpose of gpipe accumulates decreasing), so params agree with
  gpipe up to float reassociation; the loss itself is bit-exact.
- ``interleaved``: circular-interleaved virtual stages (GSPMD-style
  circular pipelining; Megatron's interleaved schedule).  Each pipe rank
  holds V NON-adjacent layer chunks — rank p owns chunks {v*P + p} on a
  ``[P, V, ...]`` param layout — and the existing ``(i+1) % P`` rotation
  IS the circular wrap: chunk c ends on rank P-1 and chunk c+1 starts on
  rank 0 one tick later.  Microbatches feed in groups of P, so the drain
  takes V*M + P - 1 ticks and the bubble fraction falls from
  (P-1)/(M+P-1) toward (P-1)/(V*M + P-1).

``PipelineSchedule`` is the analytic tick model behind all three (total /
busy / bubble ticks); ``BubbleModel`` folds it into the goodput ledger's
``step.bubble`` rows the way ``train/_overlap.py``'s CommModel feeds
``step.comm``.

Composition — the pipe axis composes with every other mesh axis (the
"one mesh subsumes the zoo" design claim, SURVEY §7):
- **data/fsdp**: microbatch rows stay sharded over the batch axes inside
  the schedule (specs below partition both pipe and batch);
- **seq**: the sequence dim of activations stays sharded over the seq
  axis; ring attention runs INSIDE each stage's blocks (the ring is over
  seq shards, orthogonal to the stage rotation over pipe) — see
  ``models/transformer.py`` ``seq_axis_name``;
- **expert**: MoE expert weights are sharded over the expert axis WITHIN
  each stage (``expert_leaf_paths``), and the expert combine is a psum
  over the expert axis inside the stage — the all-to-all never crosses a
  stage boundary.  The reference's DeepSpeed grid composes PP only with
  DP/TP (``deepspeed/_mpu.py:9-50``); seq and expert composition is net-new.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from determined_tpu.config.experiment import (
    PIPELINE_SCHEDULES as SCHEDULES,
    InvalidExperimentConfig,
)
from determined_tpu.parallel.mesh import MeshAxes

# MoE expert-weight param names: leading dim (after the stage stack) is the
# expert dim, shardable over the expert mesh axis.
# MoE's (w_in, w_gate, w_out) and RoutedExperts' (w_gate, w_up, w_down)
_EXPERT_PARAM_NAMES = frozenset({"w_in", "w_gate", "w_out", "w_up", "w_down"})


def _path_has_expert_leaf(path) -> bool:
    keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
    return any(k == "moe" for k in keys) and keys[-1] in _EXPERT_PARAM_NAMES


# ---------------------------------------------------------------------------
# Analytic tick model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PipelineSchedule:
    """Static description of one pipeline schedule: the analytic tick
    model behind both the runtime dispatch and the goodput ledger's
    bubble accounting.  Validation raises ``InvalidExperimentConfig`` so
    a bad knob fails at config/setup time, not at first step."""

    name: str = "gpipe"
    n_stages: int = 1
    num_microbatches: int = 1
    virtual_stages: int = 1

    def __post_init__(self) -> None:
        if self.name not in SCHEDULES:
            raise InvalidExperimentConfig(
                f"pipeline_schedule {self.name!r} not in {SCHEDULES}"
            )
        if self.n_stages < 1 or self.num_microbatches < 1:
            raise InvalidExperimentConfig(
                f"pipeline schedule needs n_stages >= 1 and microbatches >= 1 "
                f"(got P={self.n_stages}, M={self.num_microbatches})"
            )
        if self.virtual_stages < 1:
            raise InvalidExperimentConfig(
                f"virtual_stages must be >= 1 (got {self.virtual_stages})"
            )
        if self.name == "interleaved" and self.virtual_stages < 2:
            raise InvalidExperimentConfig(
                "pipeline_schedule: interleaved needs virtual_stages >= 2 "
                f"(got {self.virtual_stages}); with one virtual stage it IS "
                "gpipe — set pipeline_schedule: gpipe instead"
            )
        if self.name != "interleaved" and self.virtual_stages != 1:
            raise InvalidExperimentConfig(
                f"virtual_stages={self.virtual_stages} only applies to "
                f"pipeline_schedule: interleaved (got {self.name!r})"
            )

    @property
    def total_ticks(self) -> int:
        """Schedule makespan in unit ticks (one stage/chunk application —
        for 1f1b, one forward OR backward unit)."""
        p, m, v = self.n_stages, self.num_microbatches, self.virtual_stages
        if p <= 1:
            return m * v
        if self.name == "interleaved":
            # microbatch m-1 = group q, offset r; its last chunk (V*P-1)
            # runs on rank P-1 at tick q*V*P + (V-1)*P + r + (P-1)
            q, r = divmod(m - 1, p)
            return q * v * p + (v - 1) * p + r + p
        if self.name == "1f1b":
            return 2 * (m + p - 1)
        return m + p - 1  # gpipe forward drain

    @property
    def work_ticks(self) -> int:
        """Busy ticks per device (each device does every microbatch)."""
        p, m, v = self.n_stages, self.num_microbatches, self.virtual_stages
        if p <= 1:
            return self.total_ticks
        if self.name == "interleaved":
            return v * m
        if self.name == "1f1b":
            return 2 * m  # one F and one B unit per microbatch
        return m

    @property
    def bubble_ticks(self) -> int:
        return self.total_ticks - self.work_ticks

    @property
    def bubble_fraction(self) -> float:
        """Idle fraction of the schedule: (P-1)/(M+P-1) for gpipe AND
        1f1b (1f1b trades memory, not bubble), (P-1)/(V*M+P-1) for
        interleaved when P | M."""
        return self.bubble_ticks / max(self.total_ticks, 1)

    @property
    def live_activation_microbatches(self) -> int:
        """How many microbatches of stage-input activations the schedule
        keeps live for backward: the 1f1b stash is a ring of P; the AD
        schedules save one residual set per scan tick."""
        if self.n_stages <= 1:
            return 1
        if self.name == "1f1b":
            return min(self.n_stages, self.num_microbatches)
        return self.total_ticks

    def fingerprint(self) -> str:
        """jit-reuse cache key material: every field shapes the traced
        program (trip counts, param layout, custom backward)."""
        return (
            f"pipe:{self.name}:p={self.n_stages}"
            f":m={self.num_microbatches}:v={self.virtual_stages}"
        )


@dataclasses.dataclass(frozen=True)
class BubbleModel:
    """Analytic exposed-bubble model for the ``step.bubble`` ledger rows —
    the pipeline analog of ``train/_overlap.py``'s CommModel.  The split
    applies the schedule's idle fraction to the measured step time; it is
    a *model* (labeled ``pipeline-tick-v1`` in the ledger) that treats the
    whole step as pipeline ticks — embed/head/optimizer time outside the
    schedule makes it an upper bound.  The xplane op table stays the
    ground truth on real chips."""

    schedule: PipelineSchedule

    MODEL = "pipeline-tick-v1"

    @property
    def fraction(self) -> float:
        return self.schedule.bubble_fraction

    def split(self, avg_step_s: float) -> Tuple[float, float]:
        """(bubble_s, busy_s) per step under the tick model."""
        step = max(avg_step_s, 0.0)
        bubble = step * self.fraction
        return bubble, step - bubble


# ---------------------------------------------------------------------------
# Per-device schedule loops (inside shard_map)
# ---------------------------------------------------------------------------


def _gpipe_ticks(fn, my, xm_local, n: int):
    """The GPipe forward drain: M + P - 1 ticks, one rotation per tick.
    Differentiable by construction (gpipe AD path) and reused as the
    primal/fwd of the 1f1b custom_vjp — both schedules share these exact
    forward numerics."""
    p = jax.lax.axis_index(MeshAxes.PIPELINE)
    m = xm_local.shape[0]
    ticks = m + n - 1

    zero = jnp.zeros_like(xm_local[0])
    outputs = jnp.zeros_like(xm_local)
    aux0 = jnp.zeros((), jnp.float32)

    def tick(carry, t):
        state_in, outs, aux_sum = carry
        # stage 0 ingests microbatch t while it exists; later stages
        # consume the rotated activation from the previous tick
        fresh = jax.lax.dynamic_index_in_dim(
            xm_local, jnp.clip(t, 0, m - 1), 0, keepdims=False
        )
        use_fresh = jnp.logical_and(p == 0, t < m)
        x_in = jnp.where(use_fresh, fresh, state_in)
        y, aux = fn(my, x_in)
        # stage p processes microbatch t - p at tick t; outside [0, m)
        # the input is warm-up/drain garbage — gate its aux out
        mb_idx = t - p
        work_valid = jnp.logical_and(mb_idx >= 0, mb_idx < m)
        aux_sum = aux_sum + jnp.where(work_valid, aux, 0.0)
        # last stage emits microbatch t - (n - 1)
        out_idx = t - (n - 1)
        prev = jax.lax.dynamic_index_in_dim(
            outs, jnp.clip(out_idx, 0, m - 1), 0, keepdims=False
        )
        valid = jnp.logical_and(
            p == n - 1, jnp.logical_and(out_idx >= 0, out_idx < m)
        )
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(valid, y, prev), jnp.clip(out_idx, 0, m - 1), 0
        )
        # rotate activations one stage forward
        state_out = jax.lax.ppermute(
            y, MeshAxes.PIPELINE, [(i, (i + 1) % n) for i in range(n)]
        )
        return (state_out, outs, aux_sum), None

    (_, outputs, aux_sum), _ = jax.lax.scan(
        tick, (zero, outputs, aux0), jnp.arange(ticks)
    )
    return outputs, aux_sum


def _make_1f1b(fn, n: int):
    """1F1B as a ``custom_vjp`` over (stage params, microbatched input).

    Forward: the gpipe drain verbatim (bit-exact loss), saving ONLY
    (params, input) — no per-tick residuals.  Backward: one scan of
    2M + 2(P-1) unit ticks; each tick a device is (at most) one of

    - an **F unit** — recompute forward of microbatch f, stash its stage
      input in a ring buffer of P slots (slot f mod P), rotate the output
      one stage forward;
    - a **B unit** — vjp through this stage for microbatch b, consuming
      the stashed input and the cotangent rotated back from stage p+1
      (the last stage reads the output cotangent directly), rotate the
      input cotangent one stage back.

    The tick grid (stage p, microbatch k): F units at p + k during warmup
    (k < P - p) then p + 2k; B units at 2P - 1 - p + 2k.  F parity is
    p + k mod 2 in warmup / p mod 2 in steady state, B parity is p + 1 —
    never both in one tick, so one fn evaluation per tick serves both
    roles (the vjp's primal IS the forward recompute).  The stash slot
    for f + P is rewritten strictly after the B unit of f reads it
    (t_B(p, f) = 2P-1-p+2f < p + 2(f+P) = t_F(p, f+P)), so P slots
    suffice — the live-activation cap the schedule exists for.

    Activation arrival: the rotating register is a ONE-tick buffer, and
    on this grid every F unit consumes the value rotated in that same
    tick — with exactly one exception per stage.  Microbatch f* = P - p
    is stage p-1's last warmup forward (tick P-1, so it arrives at tick
    P) but stage p's FIRST steady forward (tick 2P - p): the single
    microbatch that crosses the warmup/steady boundary.  A one-register
    ``held`` parks that arrival until its F unit runs; everything else
    is same-tick (warmup: both stages on the p + k diagonal; steady
    f > f*: both stages on p + 2k).  This is the SPMD analog of the recv
    queue a message-passing 1F1B keeps per stage — depth 1 here because
    only one microbatch per stage transitions between regimes.
    """

    def primal(my, xm_local):
        return _gpipe_ticks(fn, my, xm_local, n)

    run = jax.custom_vjp(primal)

    def fwd(my, xm_local):
        return primal(my, xm_local), (my, xm_local)

    def bwd(res, cts):
        my, xm_local = res
        d_out, d_aux = cts
        p = jax.lax.axis_index(MeshAxes.PIPELINE)
        m = xm_local.shape[0]
        ticks = 2 * m + 2 * (n - 1)

        act0 = jnp.zeros_like(xm_local[0])
        stash0 = jnp.zeros((n,) + xm_local.shape[1:], xm_local.dtype)
        dmy0 = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), my)
        dxm0 = jnp.zeros_like(xm_local)

        def tick(carry, t):
            fwd_in, bwd_in, held, stash, dmy, dxm = carry
            u = t - p
            # F unit: warmup t in [p, P-1] (f = u), steady t = p + 2f
            warm = jnp.logical_and(u >= 0, t <= n - 1)
            steady = jnp.logical_and(u >= 2 * (n - p), u % 2 == 0)
            f = jnp.where(warm, u, u // 2)
            f_active = jnp.logical_and(jnp.logical_or(warm, steady), f < m)
            f_idx = jnp.clip(f, 0, m - 1)
            # B unit: t = 2P - 1 - p + 2b
            w = t - (2 * n - 1 - p)
            b = w // 2
            b_active = jnp.logical_and(
                jnp.logical_and(w >= 0, w % 2 == 0), b < m
            )
            b_idx = jnp.clip(b, 0, m - 1)

            # the one cross-regime microbatch f* = P - p arrives at tick
            # P (stage p-1's warmup tail) but runs at tick 2P - p: park
            # it in `held` on arrival, consume it at its F unit
            hold_f = n - p
            park = jnp.logical_and(p > 0, t == n)
            held = jnp.where(park, fwd_in, held)
            use_held = jnp.logical_and(steady, f == hold_f)

            fresh = jax.lax.dynamic_index_in_dim(
                xm_local, f_idx, 0, keepdims=False
            )
            x_f = jnp.where(
                p == 0, fresh, jnp.where(use_held, held, fwd_in)
            )
            x_b = jax.lax.dynamic_index_in_dim(
                stash, b_idx % n, 0, keepdims=False
            )
            # F and B are never co-active (parity), so one vjp serves
            # both: its primal output is the F result, its pullback the
            # B result — zero cotangents make the unused pullback inert
            x_sel = jnp.where(b_active, x_b, x_f)
            (y, aux), pull = jax.vjp(fn, my, x_sel)
            ct_from_next = jnp.where(
                p == n - 1,
                jax.lax.dynamic_index_in_dim(d_out, b_idx, 0, keepdims=False),
                bwd_in,
            )
            ct_y = jnp.where(b_active, ct_from_next, jnp.zeros_like(y))
            ct_aux = jnp.where(b_active, d_aux, jnp.zeros_like(aux))
            dmy_t, dx_t = pull((ct_y, ct_aux))
            dmy = jax.tree.map(
                lambda acc, g: acc + jnp.where(b_active, g, jnp.zeros_like(g)),
                dmy,
                dmy_t,
            )
            # stage 0's input cotangent IS the xm cotangent (other stages
            # rotate theirs back; their dxm rows stay zero and the
            # shard_map transpose sums them away, as in the gpipe path)
            cur = jax.lax.dynamic_index_in_dim(dxm, b_idx, 0, keepdims=False)
            write0 = jnp.logical_and(b_active, p == 0)
            dxm = jax.lax.dynamic_update_index_in_dim(
                dxm, jnp.where(write0, dx_t, cur), b_idx, 0
            )
            # stash write AFTER the B read: slot f mod P
            scur = jax.lax.dynamic_index_in_dim(
                stash, f_idx % n, 0, keepdims=False
            )
            stash = jax.lax.dynamic_update_index_in_dim(
                stash, jnp.where(f_active, x_f, scur), f_idx % n, 0
            )
            # both streams rotate every tick; garbage self-gates at the
            # consumer (F consumption implies the producer was F-active
            # one tick earlier — see the tick-grid proof above)
            fwd_out = jax.lax.ppermute(
                y, MeshAxes.PIPELINE, [(i, (i + 1) % n) for i in range(n)]
            )
            bwd_out = jax.lax.ppermute(
                dx_t, MeshAxes.PIPELINE, [(i, (i - 1) % n) for i in range(n)]
            )
            return (fwd_out, bwd_out, held, stash, dmy, dxm), None

        (_, _, _, _, dmy, dxm), _ = jax.lax.scan(
            tick, (act0, act0, act0, stash0, dmy0, dxm0), jnp.arange(ticks)
        )
        return dmy, dxm

    run.defvjp(fwd, bwd)
    return run


def _interleaved_ticks(fn, my, xm_local, n: int, v_stages: int):
    """Circular-interleaved drain: each device holds V chunks (leading
    ``[V, ...]`` dim after the stage slice) and applies chunk v of
    microbatch m = q*P + r at tick p + q*V*P + v*P + r.  The single
    ``(i+1) % P`` rotation carries both intra-chunk handoffs and the
    circular wrap (chunk c ends on rank P-1, chunk c+1 starts on rank 0
    one tick later).  Differentiated by AD like gpipe — interleaving
    buys bubble, not memory."""
    p = jax.lax.axis_index(MeshAxes.PIPELINE)
    m = xm_local.shape[0]
    sched = PipelineSchedule(
        name="interleaved",
        n_stages=n,
        num_microbatches=m,
        virtual_stages=v_stages,
    )
    ticks = sched.total_ticks
    vp = v_stages * n

    zero = jnp.zeros_like(xm_local[0])
    outputs = jnp.zeros_like(xm_local)
    aux0 = jnp.zeros((), jnp.float32)

    def tick(carry, t):
        state_in, outs, aux_sum = carry
        u = t - p
        # u = q*V*P + v*P + r  (floor/mod keep remainders in range for
        # u < 0; activity gates on u >= 0 and the microbatch bound)
        q = u // vp
        rem = u % vp
        v = rem // n
        r = rem % n
        mb = q * n + r
        active = jnp.logical_and(u >= 0, jnp.logical_and(mb >= 0, mb < m))
        mb_idx = jnp.clip(mb, 0, m - 1)
        my_v = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, jnp.clip(v, 0, v_stages - 1), 0, keepdims=False
            ),
            my,
        )
        fresh = jax.lax.dynamic_index_in_dim(
            xm_local, mb_idx, 0, keepdims=False
        )
        # chunk 0 (rank 0, virtual stage 0) ingests a fresh microbatch;
        # everything else continues the rotated activation
        use_fresh = jnp.logical_and(p == 0, jnp.logical_and(v == 0, active))
        x_in = jnp.where(use_fresh, fresh, state_in)
        y, aux = fn(my_v, x_in)
        aux_sum = aux_sum + jnp.where(active, aux, 0.0)
        # the LAST chunk (rank P-1, virtual stage V-1) emits the output
        emit = jnp.logical_and(
            p == n - 1, jnp.logical_and(v == v_stages - 1, active)
        )
        prev = jax.lax.dynamic_index_in_dim(outs, mb_idx, 0, keepdims=False)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(emit, y, prev), mb_idx, 0
        )
        state_out = jax.lax.ppermute(
            y, MeshAxes.PIPELINE, [(i, (i + 1) % n) for i in range(n)]
        )
        return (state_out, outs, aux_sum), None

    (_, outputs, aux_sum), _ = jax.lax.scan(
        tick, (zero, outputs, aux0), jnp.arange(ticks)
    )
    return outputs, aux_sum


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], Any],
    stacked_params: Any,
    x: jax.Array,
    mesh,
    num_microbatches: int,
    with_aux: bool = False,
    schedule: str = "gpipe",
    virtual_stages: int = 1,
) -> Any:
    """Run ``stage_fn`` across the mesh's ``pipe`` stages.

    - ``stacked_params``: pytree whose leaves have leading dim P (one slice
      per stage), placed with the leading dim sharded over ``pipe``; for
      ``schedule="interleaved"`` the leaves lead with ``[P, V, ...]``
      (stage-major, virtual-stage minor — ``[p, v]`` is chunk ``v*P + p``);
      MoE expert-weight leaves (``.../moe/w_*``) are additionally sharded
      over the expert axis on their first post-stack dim;
    - ``x``: ``[batch, ...]`` global input; batch must divide into
      ``num_microbatches``; when the mesh has a seq axis, dim 1 of ``x``
      is the (sharded) sequence dim;
    - ``with_aux``: ``stage_fn`` returns ``(y, aux_scalar)``; the schedule
      accumulates aux over VALID ticks only (warm-up/drain garbage is
      gated out) and returns ``(out, aux)`` with aux averaged over
      microbatches and summed over stages — matching the unpipelined
      per-layer aux sum;
    - ``schedule``/``virtual_stages``: one of ``SCHEDULES`` (validated via
      ``PipelineSchedule``);
    - returns ``[batch, ...]`` outputs (plus aux), as if the stages were
      applied sequentially to each microbatch.
    """
    n_stages = mesh.shape.get(MeshAxes.PIPELINE, 1)
    if n_stages == 1:
        if schedule == "interleaved":
            raise InvalidExperimentConfig(
                "pipeline_schedule: interleaved needs a pipe mesh axis > 1 "
                f"(mesh has {dict(mesh.shape)})"
            )
        params0 = jax.tree.map(lambda a: a[0], stacked_params)
        return stage_fn(params0, x)

    batch = x.shape[0]
    # validates schedule/virtual_stages/microbatches with clear errors
    sched = PipelineSchedule(
        name=schedule,
        n_stages=n_stages,
        num_microbatches=num_microbatches,
        virtual_stages=virtual_stages,
    )
    if batch % num_microbatches:
        raise InvalidExperimentConfig(
            f"global batch {batch} not divisible by pipe_microbatches "
            f"{num_microbatches} (pipeline_schedule {schedule!r}, "
            f"P={n_stages}): pick a microbatch count dividing the batch"
        )
    mb = batch // num_microbatches
    xm = x.reshape(num_microbatches, mb, *x.shape[1:])
    bshards = 1
    for a in MeshAxes.BATCH_AXES:
        bshards *= mesh.shape.get(a, 1)

    expert_ax = (
        MeshAxes.EXPERT if mesh.shape.get(MeshAxes.EXPERT, 1) > 1 else None
    )
    interleaved = schedule == "interleaved"

    def leaf_spec(path, leaf):
        if expert_ax is not None and _path_has_expert_leaf(path):
            # expert dim sits after the stage (and virtual-stage) dims
            if interleaved:
                return P(MeshAxes.PIPELINE, None, expert_ax)
            return P(MeshAxes.PIPELINE, expert_ax)
        return P(MeshAxes.PIPELINE)

    pspec = jax.tree_util.tree_map_with_path(leaf_spec, stacked_params)
    # microbatch rows shard over the batch axes present in the mesh, so
    # data/fsdp parallelism composes through the pipeline instead of being
    # silently all-gathered away by a replicated in_spec; microbatches too
    # small to split fall back to replication (still correct, no speedup)
    batch_axes = tuple(
        a for a in MeshAxes.BATCH_AXES if mesh.shape.get(a, 1) > 1
    )
    if mb % bshards:
        batch_axes = ()
    # seq axis: dim 1 of the original x (dim 2 of xm) stays sharded — ring
    # attention inside the stage works on the local shard
    seq_ax = (
        MeshAxes.SEQUENCE
        if (x.ndim >= 2 and mesh.shape.get(MeshAxes.SEQUENCE, 1) > 1)
        else None
    )
    xspec = P(None, batch_axes or None, seq_ax, *([None] * (x.ndim - 2)))

    fn = stage_fn if with_aux else (lambda p, h: (stage_fn(p, h), jnp.zeros((), jnp.float32)))

    def per_device(params, xm_local):
        # params leaves: [1, ...] (my stage); xm_local: [M, mb, ...]
        my = jax.tree.map(lambda a: a[0], params)
        m = xm_local.shape[0]
        if schedule == "interleaved":
            outputs, aux_sum = _interleaved_ticks(
                fn, my, xm_local, n_stages, virtual_stages
            )
        elif schedule == "1f1b":
            outputs, aux_sum = _make_1f1b(fn, n_stages)(my, xm_local)
        else:
            outputs, aux_sum = _gpipe_ticks(fn, my, xm_local, n_stages)
        # outputs accumulated on the last stage only (zeros elsewhere):
        # psum replicates the final result across the pipe axis
        out = jax.lax.psum(outputs, MeshAxes.PIPELINE)
        # aux: sum over stages (≡ the unpipelined per-layer sum), averaged
        # over microbatches and over the batch/seq shards each aux saw
        aux = jax.lax.psum(aux_sum, MeshAxes.PIPELINE) / m
        norm_axes = tuple(a for a in (*batch_axes, seq_ax) if a)
        if norm_axes:
            aux = jax.lax.pmean(aux, norm_axes)
        return out, aux

    out, aux = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(pspec, xspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(stacked_params, xm)
    out = out.reshape(batch, *x.shape[1:])
    return (out, aux) if with_aux else out


def stack_stage_params(param_list) -> Any:
    """Stack per-stage parameter pytrees into the leading-``P`` layout
    ``pipeline_apply`` consumes."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *param_list)


def stack_chunk_params(param_list, n_stages: int) -> Any:
    """Stack V*P per-chunk parameter pytrees (chunk order: the order the
    microbatch traverses them) into the ``[P, V, ...]`` interleaved
    layout: ``out[p, v]`` is chunk ``v*P + p`` — rank p's v-th virtual
    stage."""
    total = len(param_list)
    if n_stages < 1 or total % n_stages:
        raise InvalidExperimentConfig(
            f"{total} pipeline chunks do not divide over {n_stages} stages"
        )
    v_stages = total // n_stages
    return jax.tree.map(
        lambda *leaves: jnp.stack(
            [
                jnp.stack([leaves[v * n_stages + p] for v in range(v_stages)])
                for p in range(n_stages)
            ]
        ),
        *param_list,
    )
