"""Virtual-mesh weak-scaling curve: n=1..32 devices on CPU.

What this measures (and what it does not): each point jits the FULL sharded
training step (grad + optimizer + metrics) of the flagship transformer over
an n-device mesh with a fixed per-device batch, and times steady-state
steps.  On a CPU host the "devices" are virtual
(``--xla_force_host_platform_device_count``), so the numbers capture
*sharding correctness and XLA collective/partitioning overhead trends* —
the part of scaling the framework controls — not ICI bandwidth, which
needs a real pod (BASELINE.json north star: >=90% efficiency 8->256 chips).

Each point runs in a subprocess because the device count is fixed at JAX
init.  Output: one JSON line per n + a markdown table for BASELINE.md.

Usage: python scripts/weak_scaling.py [--ns 1,2,4,8,16,32] [--steps 8]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fixed per-device batch for the weak-scaling points; the comm-free
# control must use the SAME global batch (PER_DEVICE_BATCH * n on one
# device) or the overhead ratio compares different computations
PER_DEVICE_BATCH = 2


def run_point(
    n: int, steps: int, profile: bool = False, gbs: int = 0, devices: int = 0
) -> dict:
    env = dict(os.environ)
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={devices or n}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    env["_DTPU_SCALING_N"] = str(n)
    env["_DTPU_SCALING_STEPS"] = str(steps)
    env["_DTPU_SCALING_PROFILE"] = "1" if profile else "0"
    if gbs:
        env["_DTPU_SCALING_GBS"] = str(gbs)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=1800,
    )
    if out.returncode != 0:
        raise RuntimeError(f"n={n} failed:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def child() -> None:
    import time

    import jax

    n = int(os.environ["_DTPU_SCALING_N"])
    steps = int(os.environ["_DTPU_SCALING_STEPS"])

    from determined_tpu import core, train
    from determined_tpu.data import to_global
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig

    per_device_batch = PER_DEVICE_BATCH
    gbs_override = os.environ.get("_DTPU_SCALING_GBS")
    hp = {
        "lr": 1e-3,
        "global_batch_size": int(gbs_override) if gbs_override else per_device_batch * n,
        "seq_len": 128,
        "vocab_size": 1024,
        "d_model": 128,
        "n_layers": 2,
        "n_heads": 4,
        "dataset_size": 4 * (int(gbs_override) if gbs_override else per_device_batch * n),
        "bf16": False,
        "attention": "reference",
        "warmup_steps": 1,
    }
    # dp soaks most devices; fsdp=2 keeps a param-sharding collective in
    # the measured path once n allows it
    mesh = MeshConfig(data=n // 2, fsdp=2) if n >= 2 else MeshConfig(data=1)
    ctx = train.init(
        hparams=hp, mesh_config=mesh, core_context=core._dummy_init(), seed=0
    )
    trainer = train.Trainer(LMTrial(ctx))
    trainer._setup()
    it = iter(trainer.train_loader)

    def step_once():
        trainer.state = trainer._train_step(
            trainer.state, to_global(next(it), trainer.mesh)
        )

    for _ in range(3):
        step_once()
    jax.device_get(trainer.state.metric_count)
    t0 = time.perf_counter()
    for _ in range(steps):
        step_once()
    jax.device_get(trainer.state.metric_count)
    dt = time.perf_counter() - t0
    tokens = steps * hp["global_batch_size"] * hp["seq_len"]
    row = {
        "n": n,
        "tokens_per_sec": round(tokens / dt, 1),
        "step_ms": round(dt / steps * 1000, 2),
        "mesh": f"data={mesh.data},fsdp={mesh.fsdp}",
    }
    if os.environ.get("_DTPU_SCALING_PROFILE") == "1" and n > 1:
        # Attribute the emulated-collective term by MEASURING the step's
        # collectives in isolation at their real shapes (CPU xplanes carry
        # no per-HLO device events, so a trace can't do this):
        #  - all-reduce of the full gradient tree over the batch axes (the
        #    collective the dp axis inserts every step)
        #  - all-gather of the fsdp-sharded params (what ZeRO-style
        #    sharding inserts around each matmul)
        from jax.sharding import NamedSharding, PartitionSpec as P

        params = trainer.state.params
        jmesh = trainer.mesh
        rep = jax.tree.map(lambda _: P(), params)
        psum_fn = jax.jit(
            jax.shard_map(
                lambda t: jax.tree.map(
                    lambda a: jax.lax.psum(a, ("data", "fsdp")), t
                ),
                mesh=jmesh,
                in_specs=(rep,),
                out_specs=rep,
                check_vma=False,
            )
        )
        rep_params = jax.device_put(
            params, jax.tree.map(lambda _: NamedSharding(jmesh, P()), params)
        )

        def timed(fn, arg):
            out = fn(arg)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(steps):
                out = fn(arg)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / steps * 1000

        row["comm_allreduce_ms"] = round(timed(psum_fn, rep_params), 2)

        # fsdp all-gather at param shapes (sharded -> replicated)
        shardings = trainer._param_specs
        from determined_tpu.parallel.sharding import param_shardings

        sharded = jax.device_put(
            params, param_shardings(shardings, jmesh, trainer.context.rules)
        )
        gather_fn = jax.jit(
            lambda t: t,
            out_shardings=jax.tree.map(
                lambda _: NamedSharding(jmesh, P()), params
            ),
        )
        row["comm_allgather_ms"] = round(timed(gather_fn, sharded), 2)
    print(json.dumps(row))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ns", default="1,2,4,8,16,32")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--child", action="store_true")
    ap.add_argument(
        "--attribute",
        action="store_true",
        help="per-n xplane attribution (collective vs compute) + a "
        "communication-free control (same global batch, ONE device) so the "
        "emulation term is measured, not asserted",
    )
    args = ap.parse_args()
    if args.child:
        child()
        return
    ns = [int(x) for x in args.ns.split(",")]
    rows = []
    for n in ns:
        r = run_point(n, args.steps, profile=args.attribute)
        if args.attribute:
            # control: identical global computation, 1 device, 0 collectives
            ctrl = run_point(1, args.steps, gbs=PER_DEVICE_BATCH * n, devices=1)
            r["control_step_ms"] = ctrl["step_ms"]
            r["overhead_vs_control"] = round(r["step_ms"] / ctrl["step_ms"], 2)
        rows.append(r)
        print(json.dumps(r), flush=True)
    base = rows[0]["tokens_per_sec"] / rows[0]["n"]
    if args.attribute:
        print(
            "\n| devices | step ms | comm-free control ms | overhead | "
            "grad all-reduce ms | fsdp all-gather ms |"
        )
        print("|---|---|---|---|---|---|")
        for r in rows:
            print(
                f"| {r['n']} | {r['step_ms']} | {r['control_step_ms']} "
                f"| {r['overhead_vs_control']}x "
                f"| {r.get('comm_allreduce_ms', '-')} "
                f"| {r.get('comm_allgather_ms', '-')} |"
            )
        return
    print("\n| devices | tokens/s | step ms | per-device tokens/s | weak-scaling eff |")
    print("|---|---|---|---|---|")
    for r in rows:
        per_dev = r["tokens_per_sec"] / r["n"]
        print(
            f"| {r['n']} | {r['tokens_per_sec']} | {r['step_ms']} "
            f"| {per_dev:.1f} | {per_dev / base:.2f} |"
        )


if __name__ == "__main__":
    main()
