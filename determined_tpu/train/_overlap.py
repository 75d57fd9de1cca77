"""Overlapped gradient synchronization: bucketed reduce-scatter in backward.

Motivation (BASELINE.md r3 roofline, docs/performance.md): with matmul
fusions at 85-88% of peak and the optimizer at its bandwidth roofline, the
remaining step-time lever on the gradient path is *structural* — the
single end-of-backward gradient reduction over the data/fsdp axes sits on
the critical path with nothing left to hide behind.  The Megatron-LM /
ZeRO recipe restructures it: issue the gradient collectives per-bucket as
backward products become available, keep the optimizer consuming SHARDED
gradients and state (reduce-scatter -> sharded update -> all-gather
params), and let the scheduler interleave the collectives with the
remaining backward compute.

The XLA/jax-native expression of that recipe (this module):

- ``build_plan`` partitions the (abstract) grad pytree into size-bounded
  **buckets** in reverse-forward order — the order backward produces them;
- each bucket gets a ``custom_vjp`` identity **marker** applied to the
  params inside the loss: its backward rule pins that bucket's cotangents
  to a sharded layout over the sync axes
  (``parallel/sharding.py:grad_sync_spec``), which XLA lowers to a
  reduce-scatter at the grad's production point.  Each bucket's collective
  is an independent dataflow node (no false dependency on the other
  buckets), which is exactly what XLA's latency-hiding scheduler needs to
  interleave them with backward compute on TPU;
- the optimizer state mirrors the grad shardings (``opt_shardings`` — the
  ZeRO-1/2 memory win: mu/nu live at 1/n per device), and the updated
  params are constrained back to their own shardings, which lowers to the
  closing all-gather.  Total bytes moved equal the baseline all-reduce
  (ring RS + ring AG == ring AR); only the exposure changes;
- deliberately NOT done: concatenating a bucket's leaves into one flat
  payload (the DDP trick).  Under GSPMD the flatten/unflatten of a
  sharded payload inserts extra resharding collectives that cost more
  than the per-leaf launch overhead they save; the bucket here is the
  unit of marker arity and comm accounting, while fusion of adjacent
  small collectives is left to XLA.

Numerics: reduce-scatter + all-gather sums the same shard partials as the
all-reduce, so the step is equivalent up to float reassociation —
``tests/test_step_optimizations.py`` pins params/opt_state allclose after
N steps on the 8-device virtual mesh, and the compiled HLO contains the
expected reduce-scatter structure.

Comm accounting (``CommModel``): the goodput ledger's ``step.comm``
category is fed from an explicit bucket-schedule model — measured payload
bytes over a per-chip interconnect bandwidth, with bucket k's collective
hideable behind the backward compute of buckets k+1..B (baseline: one
bucket, nothing hides).  It is a *model* (labeled as such in the ledger);
the xplane op table stays the ground truth on real chips.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from determined_tpu.parallel.mesh import MeshAxes
from determined_tpu.parallel.sharding import grad_sync_spec

#: axes a gradient reduction runs over: every batch-carrying axis
SYNC_AXES = MeshAxes.BATCH_AXES

#: batch axes reachable over ICI (within one slice) — the hierarchical
#: sync reduce-scatters over these and crosses ``dcn`` with the fragment
ICI_SYNC_AXES = MeshAxes.ICI_BATCH_AXES

#: leaves below this ride the final all-reduce: a reduce-scatter of a few
#: KiB is pure launch overhead (norm scales, biases)
_MIN_SYNC_BYTES = 64 * 1024

# Per-chip interconnect bandwidth (bytes/s, one direction) for the comm
# model — public ICI spec-sheet numbers, longest-prefix matched like the
# peak-FLOPs table in observability/_goodput.py.  DTPU_COMM_BW_GBPS
# overrides (and is the only honest choice on CPU test meshes).
ICI_BW_BY_KIND = {
    "TPU v4": 3 * 2 * 50e9,
    "TPU v5 lite": 1 * 2 * 50e9,   # v5e: 1 ICI link pair per chip side
    "TPU v5p": 3 * 2 * 100e9,
    "TPU v5": 3 * 2 * 100e9,
    "TPU v6 lite": 2 * 2 * 90e9,
    "TPU v6e": 2 * 2 * 90e9,
}
# A CPU virtual mesh has no interconnect at all: the model's arithmetic is
# exercised there against a nominal figure.  A TPU kind missing from the
# table is an error (link_bandwidths), never this number.
_EMULATED_BW = 10e9

# Per-chip cross-slice (DCN) bandwidth: host NIC share per chip.  Order of
# magnitude below ICI — which is the whole point of the hierarchical sync.
DCN_BW_BY_KIND = {
    "TPU v4": 6.25e9,       # ~200 Gb/s host NIC / 4 chips
    "TPU v5 lite": 6.25e9,
    "TPU v5p": 12.5e9,      # ~400 Gb/s host NIC / 4 chips
    "TPU v5": 12.5e9,
    "TPU v6 lite": 12.5e9,
    "TPU v6e": 12.5e9,
}
_EMULATED_DCN_BW = 1e9  # CPU virtual mesh only, as _EMULATED_BW


def _parse_bw_env(raw: str) -> Dict[str, float]:
    """Parse ``DTPU_COMM_BW_GBPS``: either a single number (every link,
    back-compat) or the per-link form ``ici:90,dcn:12``.  Values are GB/s;
    garbage raises at parse time instead of silently mis-modeling comm."""
    parts = [p.strip() for p in raw.split(",") if p.strip()]
    if not parts:
        raise ValueError("DTPU_COMM_BW_GBPS is set but empty")
    out: Dict[str, float] = {}
    if len(parts) == 1 and ":" not in parts[0]:
        try:
            v = float(parts[0])
        except ValueError:
            raise ValueError(
                f"DTPU_COMM_BW_GBPS={raw!r}: expected a number (GB/s) or "
                "per-link 'ici:90,dcn:12'"
            ) from None
        if v <= 0:
            raise ValueError(f"DTPU_COMM_BW_GBPS={raw!r}: bandwidth must be > 0")
        return {"ici": v * 1e9, "dcn": v * 1e9}
    for part in parts:
        link, sep, val = part.partition(":")
        link = link.strip().lower()
        if not sep or link not in ("ici", "dcn"):
            raise ValueError(
                f"DTPU_COMM_BW_GBPS={raw!r}: bad entry {part!r} "
                "(expected 'ici:<GB/s>' or 'dcn:<GB/s>')"
            )
        if link in out:
            raise ValueError(f"DTPU_COMM_BW_GBPS={raw!r}: duplicate link {link!r}")
        try:
            v = float(val)
        except ValueError:
            raise ValueError(
                f"DTPU_COMM_BW_GBPS={raw!r}: {val!r} is not a number (GB/s)"
            ) from None
        if v <= 0:
            raise ValueError(f"DTPU_COMM_BW_GBPS={raw!r}: bandwidth must be > 0")
        out[link] = v * 1e9
    return out


def _table_bw(device_kind: str, table: Dict[str, float], emulated: float) -> float:
    for prefix in sorted(table, key=len, reverse=True):
        if device_kind.startswith(prefix):
            return table[prefix]
    if device_kind.startswith("TPU"):
        raise ValueError(
            f"no interconnect bandwidth known for device kind {device_kind!r}: "
            "add it to the tables in train/_overlap.py or set DTPU_COMM_BW_GBPS"
        )
    return emulated


def link_bandwidths(device_kind: str) -> Tuple[float, float]:
    """(ici_bw, dcn_bw) in bytes/s for the comm model, env-overridable."""
    env = os.environ.get("DTPU_COMM_BW_GBPS")
    override = _parse_bw_env(env) if env else {}
    ici = override.get("ici") or _table_bw(device_kind, ICI_BW_BY_KIND, _EMULATED_BW)
    dcn = override.get("dcn") or _table_bw(device_kind, DCN_BW_BY_KIND, _EMULATED_DCN_BW)
    return ici, dcn


@dataclasses.dataclass(frozen=True)
class CommModel:
    """Bucket-schedule exposure model for the ``step.comm`` ledger rows.

    Link-aware since the multi-slice PR: the intra-slice (ICI) and the
    cross-slice (DCN) hop carry different payloads over bandwidths an
    order of magnitude apart, so the ledger models them separately.  A
    single-slice mesh has ``dcn_bytes == 0`` and collapses to the old
    one-hop model.
    """

    bytes_per_step: int      # ICI RS+AG (or AR) payload bytes, ring-counted
    n_buckets: int           # 1 = baseline end-of-backward reduction
    bandwidth: float         # ICI bytes/s
    bwd_frac: float = 0.6    # share of a step that is backward compute
    dcn_bytes_per_step: int = 0   # cross-slice hop payload bytes
    dcn_bandwidth: float = _EMULATED_DCN_BW

    def split_hops(self, avg_step_s: float) -> Dict[str, Tuple[float, float]]:
        """Per-hop ``{hop: (exposed_s, hidden_s)}`` under the bucket
        schedule.

        Baseline (one bucket): everything is exposed — backward is already
        finished when the reduction runs.  Overlapped (B buckets): bucket
        k's collective can hide behind buckets k+1..B's backward compute,
        so up to (B-1)/B of each hop hides, bounded by the backward time
        actually available.  The DCN hop is issued earliest in backward
        (it is the slowest link with the longest tail to hide behind), so
        it gets first claim on the hiding budget.
        """
        ici_s = self.bytes_per_step / max(self.bandwidth, 1.0)
        dcn_s = self.dcn_bytes_per_step / max(self.dcn_bandwidth, 1.0)
        if self.n_buckets <= 1:
            return {"ici": (ici_s, 0.0), "dcn": (dcn_s, 0.0)}
        frac = (self.n_buckets - 1) / self.n_buckets
        budget = max(avg_step_s, 0.0) * self.bwd_frac
        out: Dict[str, Tuple[float, float]] = {}
        for hop, comm_s in (("dcn", dcn_s), ("ici", ici_s)):
            hidden = min(comm_s * frac, budget)
            budget -= hidden
            out[hop] = (comm_s - hidden, hidden)
        return out

    def split(self, avg_step_s: float) -> Tuple[float, float]:
        """(exposed_s, hidden_s) per step, summed over both hops."""
        hops = self.split_hops(avg_step_s)
        return (
            sum(e for e, _ in hops.values()),
            sum(h for _, h in hops.values()),
        )

    @property
    def total_bytes_per_step(self) -> int:
        return self.bytes_per_step + self.dcn_bytes_per_step


def _make_bucket_marker(shardings: Tuple[Optional[NamedSharding], ...]):
    """custom_vjp identity over one bucket's leaves whose backward pins
    each cotangent to its sync sharding (the reduce-scatter issue point).
    Forward is the identity, so the marker never perturbs the loss."""

    @jax.custom_vjp
    def mark(*leaves):
        return leaves

    def fwd(*leaves):
        return leaves, None

    def bwd(_, cts):
        return tuple(
            ct if s is None else jax.lax.with_sharding_constraint(ct, s)
            for ct, s in zip(cts, shardings)
        )

    mark.defvjp(fwd, bwd)
    return mark


@dataclasses.dataclass
class GradSyncPlan:
    """Everything the train step needs to overlap gradient sync.

    Built once per Trainer setup from the abstract param tree; all methods
    are trace-safe (called inside the jitted step).
    """

    mesh: Mesh
    enabled: bool
    treedef: Any
    param_shardings: List[NamedSharding]          # flat, param order
    sync_shardings: List[Optional[NamedSharding]]  # flat; None = unsynced
    buckets: List[Tuple[int, ...]]                 # leaf indices per bucket
    comm: CommModel
    synced_leaves: int
    # hierarchical two-level sync: grads reduce-scatter over ICI axes only
    # and cross `dcn` as the 1/N_ici fragment (0 = flat treatment)
    hierarchical_dcn: int = 0
    _markers: List[Any] = dataclasses.field(default_factory=list)
    _shape_map: Dict[Tuple[int, ...], NamedSharding] = dataclasses.field(
        default_factory=dict
    )

    _leaf_shapes: List[Tuple[int, ...]] = dataclasses.field(default_factory=list)

    def __post_init__(self) -> None:
        self._markers = [
            _make_bucket_marker(tuple(self.sync_shardings[i] for i in b))
            for b in self.buckets
        ]
        # shape -> sync sharding, for optimizer-state mirror leaves.  Well
        # defined: the sync spec is a function of (shape, param spec), and
        # same-shape params get the same spec by construction.
        for i, s in enumerate(self.sync_shardings):
            if s is not None:
                self._shape_map.setdefault(self._leaf_shapes[i], s)

    def mark(self, params: Any) -> Any:
        """Apply the bucket markers to the param pytree inside the loss."""
        leaves = jax.tree.leaves(params)
        out = list(leaves)
        for marker, idxs in zip(self._markers, self.buckets):
            marked = marker(*(leaves[i] for i in idxs))
            for j, i in enumerate(idxs):
                out[i] = marked[j]
        return jax.tree.unflatten(self.treedef, out)

    def apply_grad_sync(self, grads: Any) -> Any:
        """Pin an already-accumulated grad tree to the sync shardings —
        the gradient-accumulation path, where the sync must happen ONCE
        per optimizer step on the summed grads, not per microbatch."""
        leaves = list(jax.tree.leaves(grads))
        for i, s in enumerate(self.sync_shardings):
            if s is not None:
                leaves[i] = jax.lax.with_sharding_constraint(leaves[i], s)
        return jax.tree.unflatten(self.treedef, leaves)

    def restore_params(self, new_params: Any) -> Any:
        """Constrain updated params back to their own shardings — the
        closing all-gather of the reduce-scatter/all-gather pair."""
        leaves = list(jax.tree.leaves(new_params))
        for i, s in enumerate(self.param_shardings):
            if self.sync_shardings[i] is not None:
                leaves[i] = jax.lax.with_sharding_constraint(leaves[i], s)
        return jax.tree.unflatten(self.treedef, leaves)

    def update_shardings(self) -> Any:
        """Per-leaf layout the optimizer update runs in: the sync
        (reduce-scattered) sharding where a leaf has one, else the param's
        own — what a per-device optimizer kernel (``ops/fused_adamw.py``)
        maps over."""
        return jax.tree.unflatten(
            self.treedef,
            [s or p for s, p in zip(self.sync_shardings, self.param_shardings)],
        )

    def _sharding_for_shape(self, shape: Tuple[int, ...]) -> Optional[NamedSharding]:
        return self._shape_map.get(tuple(shape))

    def opt_shardings(self, abstract_opt: Any) -> Any:
        """Sharding tree for the optimizer state: param-shaped mirror
        leaves (adam mu/nu) follow the GRAD shardings — each device owns
        1/n of the moments (the ZeRO memory win); everything else
        (counts, schedule scalars) replicates."""
        repl = NamedSharding(self.mesh, PartitionSpec())
        return jax.tree.map(
            lambda l: self._sharding_for_shape(getattr(l, "shape", ())) or repl,
            abstract_opt,
        )

    def pin_opt_state(self, opt_state: Any) -> Any:
        """Constrain a NEW optimizer state to the same shardings its input
        had, so the donated buffers round-trip stably step over step."""
        return jax.tree.map(
            lambda l: (
                jax.lax.with_sharding_constraint(
                    l, self._sharding_for_shape(l.shape)
                )
                if getattr(l, "ndim", 0) and self._sharding_for_shape(l.shape)
                else l
            ),
            opt_state,
        )

    def fingerprint(self) -> str:
        """Key material for the jit-reuse cache: anything that changes the
        traced collective structure."""
        if not self.enabled:
            return "overlap:off"
        hier = (
            f":hier=dcn{self.hierarchical_dcn}" if self.hierarchical_dcn > 1 else ":flat"
        )
        return (
            f"overlap:on:buckets={len(self.buckets)}:synced={self.synced_leaves}{hier}"
        )


def sync_axis_size(mesh: Mesh) -> int:
    n = 1
    for a in SYNC_AXES:
        n *= mesh.shape.get(a, 1)
    return n


def build_plan(
    abstract_params: Any,
    param_shardings: Any,
    mesh: Mesh,
    *,
    enabled: bool,
    bucket_bytes: int = 4 * 1024 * 1024,
    min_sync_bytes: int = _MIN_SYNC_BYTES,
    hierarchical: bool = False,
) -> Optional[GradSyncPlan]:
    """Plan the overlapped sync for one param tree; None when the mesh has
    no gradient-reduction axes (nothing to sync — single device or pure
    model parallelism).

    ``hierarchical`` (``optimizations.hierarchical_collectives``) switches
    a multi-slice mesh to the two-level scheme: per-bucket reduce-scatter
    over the intra-slice ICI axes only, leaving ``dcn`` replicated — XLA
    then closes the reduction with a cross-slice all-reduce carrying only
    the 1/N_ici sharded fragment, and the param restore all-gathers within
    the slice.  Flat treatment instead shards over every batch axis, which
    rings full-gradient-scale payload across the slow DCN links.
    """
    n_sync = sync_axis_size(mesh)
    if n_sync <= 1:
        return None

    n_dcn = mesh.shape.get(MeshAxes.DCN, 1)
    n_ici = max(1, n_sync // max(1, n_dcn))
    hier = bool(hierarchical) and n_dcn > 1 and n_ici > 1
    sync_axes = ICI_SYNC_AXES if hier else SYNC_AXES

    leaves, treedef = jax.tree.flatten(abstract_params)
    shard_leaves = jax.tree.leaves(param_shardings)
    if len(shard_leaves) != len(leaves):
        raise ValueError(
            "param_shardings tree does not match the param tree "
            f"({len(shard_leaves)} vs {len(leaves)} leaves)"
        )

    import math

    sync_shardings: List[Optional[NamedSharding]] = []
    ici_bytes = 0
    dcn_bytes = 0
    grad_itemsize = 4  # grads reduce in f32
    for aval, psh in zip(leaves, shard_leaves):
        shape = tuple(getattr(aval, "shape", ()))
        nbytes = math.prod(shape) * grad_itemsize
        # per-hop ring accounting: RS+AG within the slice moves
        # 2*(n_ici-1)/n_ici of the payload over ICI; the cross-slice hop
        # rings 2*(n_dcn-1)/n_dcn of the payload over DCN — the FULL
        # payload under flat treatment, only the 1/n_ici fragment under
        # the hierarchical scheme.
        ici_bytes += int(2 * (n_ici - 1) / n_ici * nbytes)
        if n_dcn > 1:
            dcn_payload = nbytes // n_ici if hier else nbytes
            dcn_bytes += int(2 * (n_dcn - 1) / n_dcn * dcn_payload)
        spec = None
        if enabled and nbytes >= min_sync_bytes:
            spec = grad_sync_spec(
                shape, getattr(psh, "spec", PartitionSpec()), mesh, sync_axes
            )
        sync_shardings.append(
            NamedSharding(mesh, spec) if spec is not None else None
        )

    # buckets in REVERSE flatten order: backward produces the last-used
    # params' grads first, so reverse order approximates production order
    buckets: List[Tuple[int, ...]] = []
    cur: List[int] = []
    cur_bytes = 0
    for i in reversed(range(len(leaves))):
        if sync_shardings[i] is None:
            continue
        shape = tuple(leaves[i].shape)
        nbytes = 1
        for d in shape:
            nbytes *= d
        nbytes *= grad_itemsize
        if cur and cur_bytes + nbytes > bucket_bytes:
            buckets.append(tuple(cur))
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(tuple(cur))

    dev = mesh.devices.flat[0]
    ici_bw, dcn_bw = link_bandwidths(getattr(dev, "device_kind", ""))
    comm = CommModel(
        bytes_per_step=ici_bytes,
        n_buckets=len(buckets) if enabled else 1,
        bandwidth=ici_bw,
        dcn_bytes_per_step=dcn_bytes,
        dcn_bandwidth=dcn_bw,
    )
    plan = GradSyncPlan(
        mesh=mesh,
        enabled=enabled,
        treedef=treedef,
        param_shardings=list(shard_leaves),
        sync_shardings=sync_shardings,
        buckets=buckets,
        comm=comm,
        synced_leaves=sum(1 for s in sync_shardings if s is not None),
        hierarchical_dcn=n_dcn if hier else 0,
        _leaf_shapes=[tuple(getattr(l, "shape", ())) for l in leaves],
    )
    return plan
