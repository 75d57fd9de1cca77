"""Logical-axis sharding rules: how arrays map onto the mesh.

Model code annotates parameters and activations with *logical* axis names
("embed", "mlp", "heads", "batch", "length", ...).  A ``LogicalAxisRules``
table maps logical names to mesh axes.  Swapping the table reconfigures a
model between DP / FSDP / TP / SP without touching model code — the TPU
answer to the reference's per-launcher wrapping (``wrap_model`` DDP at
``_pytorch_context.py:36-...``, DeepSpeed engine wrap, Horovod broadcast).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from determined_tpu.parallel.mesh import MeshAxes

# A logical spec is a tuple of logical axis names (or None), one per dim.
LogicalSpec = Tuple[Optional[str], ...]

# Rules: logical axis name -> mesh axis (str), tuple of mesh axes, or None.
LogicalAxisRules = Dict[str, Union[str, Tuple[str, ...], None]]

# Default rules: batch over (data, fsdp); params sharded over fsdp on their
# largest dim; tensor-parallel on heads/mlp; sequence activations over seq.
DEFAULT_RULES: LogicalAxisRules = {
    "batch": (MeshAxes.DCN, MeshAxes.DATA, MeshAxes.FSDP),
    "length": MeshAxes.SEQUENCE,
    "embed": None,
    "mlp": MeshAxes.TENSOR,
    "heads": MeshAxes.TENSOR,
    "kv": None,
    "head_dim": None,
    "vocab": MeshAxes.TENSOR,
    "expert": MeshAxes.EXPERT,
    "stage": MeshAxes.PIPELINE,
    # FSDP: weight dims tagged "fsdp_shard" get scattered over the fsdp axis.
    "fsdp_shard": MeshAxes.FSDP,
}


def logical_to_mesh_spec(
    logical: Optional[LogicalSpec],
    rules: LogicalAxisRules,
    mesh: Optional[Mesh] = None,
) -> P:
    """Translate a logical spec into a ``PartitionSpec``.

    Mesh axes that do not exist in ``mesh`` (size-1 or absent) are dropped,
    so the same model + rules run unchanged on any topology.
    """
    if logical is None:
        return P()
    # mesh.shape works for both concrete Mesh and AbstractMesh
    axis_sizes = dict(mesh.shape) if mesh is not None else None

    def resolve(name: Optional[str]):
        if name is None:
            return None
        target = rules.get(name, None)
        if target is None:
            return None
        targets = target if isinstance(target, tuple) else (target,)
        if axis_sizes is not None:
            targets = tuple(t for t in targets if axis_sizes.get(t, 1) > 1)
        if not targets:
            return None
        return targets if len(targets) > 1 else targets[0]

    resolved = [resolve(n) for n in logical]
    # PartitionSpec forbids repeating a mesh axis; keep first occurrence.
    seen = set()
    out = []
    for r in resolved:
        flat = r if isinstance(r, tuple) else (r,) if r else ()
        if any(f in seen for f in flat):
            out.append(None)
            continue
        seen.update(flat)
        out.append(r)
    return P(*out)


def named_sharding(
    mesh: Mesh, logical: Optional[LogicalSpec], rules: Optional[LogicalAxisRules] = None
) -> NamedSharding:
    rules = rules if rules is not None else DEFAULT_RULES
    return NamedSharding(mesh, logical_to_mesh_spec(logical, rules, mesh))


# In spec pytrees the LEAVES are logical specs: tuples of names (or None, or
# a PartitionSpec).  Without this is_leaf, tree.map would descend into the
# tuples and iterate the axis-name strings character by character.
def _is_spec_leaf(x: Any) -> bool:
    return (
        x is None
        or isinstance(x, P)
        or (isinstance(x, tuple) and all(n is None or isinstance(n, (str, tuple)) for n in x))
    )


def shard_params(params: Any, specs: Any, mesh: Mesh, rules: Optional[LogicalAxisRules] = None) -> Any:
    """Device-put a param pytree according to its logical-spec pytree."""
    rules = rules if rules is not None else DEFAULT_RULES
    return jax.tree.map(
        lambda p, s: jax.device_put(p, named_sharding(mesh, s, rules)),
        params,
        specs,
        is_leaf=_is_spec_leaf,
    )


def param_shardings(specs: Any, mesh: Mesh, rules: Optional[LogicalAxisRules] = None) -> Any:
    """NamedSharding pytree matching a logical-spec pytree (for jit in/out)."""
    rules = rules if rules is not None else DEFAULT_RULES
    return jax.tree.map(
        lambda s: named_sharding(mesh, s, rules), specs, is_leaf=_is_spec_leaf
    )


def with_sharding_constraint(
    x: Any, logical: LogicalSpec, mesh: Optional[Mesh] = None, rules: Optional[LogicalAxisRules] = None
) -> Any:
    """Annotate an activation with a logical sharding inside jit."""
    rules = rules if rules is not None else DEFAULT_RULES
    try:
        if mesh is None:
            mesh = _current_mesh()
        if mesh is None:
            return x
        spec = logical_to_mesh_spec(logical, rules, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except (ValueError, RuntimeError):
        return x


def _current_mesh() -> Optional[Mesh]:
    """The mesh of an enclosing ``with mesh:`` block, else the abstract
    mesh (``jax.set_mesh``, or the manual mesh inside ``shard_map``)."""
    # the legacy ``with mesh:`` context has no public reader in jax 0.9
    from jax._src.mesh import thread_resources

    env_mesh = thread_resources.env.physical_mesh
    if not env_mesh.empty:
        return env_mesh
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def batch_sharding(mesh: Mesh, rules: Optional[LogicalAxisRules] = None, extra_dims: int = 1) -> NamedSharding:
    """Sharding for an input batch: ('batch', None, ...)."""
    logical: LogicalSpec = ("batch",) + (None,) * extra_dims
    return named_sharding(mesh, logical, rules)


def grad_sync_spec(
    shape: Sequence[int], param_spec: P, mesh: Mesh, sync_axes: Sequence[str]
) -> Optional[P]:
    """PartitionSpec for a gradient leaf synced by reduce-scatter.

    The overlapped gradient sync (``train/_overlap.py``) wants each grad
    leaf SHARDED over the gradient-reduction axes (data x fsdp) instead of
    replicated-after-all-reduce: XLA then lowers the reduction to a
    reduce-scatter at the grad's production point, the optimizer update
    runs on 1/n of the elements per device (ZeRO-style), and the updated
    params all-gather back to ``param_spec``.

    Dim choice: prefer a dim already carrying one of ``sync_axes`` in the
    param's own spec (the fsdp-sharded dim — extending it avoids a
    resharding hop), else the largest dim with no existing assignment.
    The chosen dim's total shard count must divide its size; a leaf with
    no such dim returns None (it rides the default all-reduce).
    """
    entries = list(param_spec) if param_spec is not None else []
    entries += [None] * (len(shape) - len(entries))
    norm = [
        tuple(e) if isinstance(e, (tuple, list)) else ((e,) if e else ())
        for e in entries
    ]
    used = {a for e in norm for a in e}
    missing = [a for a in sync_axes if a not in used]
    if not missing:
        return None  # already fully sharded over the sync axes
    missing_n = 1
    for a in missing:
        missing_n *= mesh.shape.get(a, 1)
    if missing_n <= 1:
        return None

    def dim_ok(d: int, extra: int) -> bool:
        have = 1
        for a in norm[d]:
            have *= mesh.shape.get(a, 1)
        return shape[d] >= have * extra and shape[d] % (have * extra) == 0

    # a dim already sharded over one of the sync axes, then largest free dim
    carrier = None
    for d in range(len(shape)):
        if any(a in sync_axes for a in norm[d]) and dim_ok(d, missing_n):
            carrier = d
            break
    if carrier is None:
        free = [d for d in range(len(shape)) if not norm[d] and dim_ok(d, missing_n)]
        if not free:
            return None
        carrier = max(free, key=lambda d: shape[d])
    out = list(norm)
    out[carrier] = out[carrier] + tuple(missing)
    return P(*[e if len(e) > 1 else (e[0] if e else None) for e in out])
