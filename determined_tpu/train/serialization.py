"""Sharded-jax-array checkpoint serialization (orbax-backed).

The reference's sharded checkpoint (``core/_checkpoint.py _upload_sharded``)
has every rank write its own files and merges the file lists.  The TPU
analog: every *process* writes only its addressable shards of each global
``jax.Array``; orbax (ocdbt/zarr) is the battle-tested writer for that, so
the array plane rides orbax while loop/loader state rides a plain JSON —
both into the SAME checkpoint directory managed by CheckpointContext.

Layout inside one checkpoint dir:
    state/         orbax pytree (params, opt_state, rng, step)
    trainer_state.json   loop counters, loader state, callbacks state

The backend is imported by the first call that needs it, never by
``import determined_tpu.*``: ``orbax.checkpoint`` pulls in tensorstore and
a cloud logging client (``google.cloud.logging`` -> ``google.api_core``'s
dependency-version checks), seconds to tens of seconds that a process
which never checkpoints must not pay (``tests/test_import_graph.py``).  A
run that knows it will checkpoint calls ``prefetch_backend()`` so the
import overlaps its set-up instead of standing in front of the first save.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from determined_tpu.observability import get_tracer

logger = logging.getLogger("determined_tpu.train")

ARRAY_SUBDIR = "state"
TRAINER_STATE_FILE = "trainer_state.json"

# guards the once-a-process import below and what it records
_backend_lock = threading.Lock()
# one save at a time enters the backend's ``save``: orbax numbers a save with a
# process-wide counter (``OperationIdGenerator``) and every future that call
# creates reads the CURRENT number, so two threads (concurrent trials of a
# packed local experiment) that start a save together wait on each other's
# signals until the 300 s time-out.  The writes themselves still overlap.
_save_lock = threading.Lock()
# (orbax.checkpoint, seconds its import took, whether the prefetch thread ran it)
_loaded: Optional[Tuple[Any, float, bool]] = None
_prefetch_started = False
_import_reported = False


def _backend(*, prefetch: bool = False) -> Any:
    """``orbax.checkpoint``, imported on first call, once a process.

    A save or restore that arrives while the prefetch thread is still
    importing waits on the lock: same module, never two imports.  The
    process's first save or restore leaves one ``ckpt.backend_import``
    span (``cat="setup"``) over the time it stood here, with ``seconds``
    (the import itself, wherever it ran), ``prefetched`` (the background
    thread ran it) and ``waited_s`` (what this caller paid for it: 0 when
    the prefetch had finished, ``seconds`` when it had to import itself).
    """
    global _loaded, _import_reported
    t0 = time.monotonic()
    with _backend_lock:
        if _loaded is None:
            t_import = time.monotonic()
            import orbax.checkpoint as ocp

            _loaded = (ocp, time.monotonic() - t_import, prefetch)
        report = not prefetch and not _import_reported
        if report:
            _import_reported = True
    ocp, seconds, prefetched = _loaded
    if report:
        t1 = time.monotonic()
        get_tracer().record_span(
            "ckpt.backend_import", "setup", t0, t1,
            {"seconds": seconds, "prefetched": prefetched, "waited_s": t1 - t0},
        )
    return ocp


def prefetch_backend() -> None:
    """Start the backend's import on a daemon thread; called where a run
    first learns it will read or write a checkpoint.  Idempotent: a second
    call, or one after the backend is loaded, does nothing."""
    global _prefetch_started
    with _backend_lock:
        if _loaded is not None or _prefetch_started:
            return
        _prefetch_started = True

    def work() -> None:
        try:
            _backend(prefetch=True)
        except Exception:
            # the first save or restore imports again and raises where the
            # run can handle it
            logger.warning("checkpoint backend prefetch failed", exc_info=True)

    threading.Thread(target=work, name="dtpu-ckpt-import", daemon=True).start()


def _is_key_dtype(dtype: Any) -> bool:
    import jax.numpy as jnp

    return jnp.issubdtype(dtype, jax.dtypes.prng_key)


def _unkey(tree: Any) -> Any:
    """Replace PRNG-key leaves with their uint32 key data.  Orbax's array
    serializer cannot np.array() extended-dtype key arrays, so keys ride
    as raw counter words and are re-wrapped on restore."""

    def one(x):
        if isinstance(x, jax.Array) and _is_key_dtype(x.dtype):
            return jax.random.key_data(x)
        return x

    return jax.tree.map(one, tree)


def _unkey_abstract(abstract_tree: Any) -> Any:
    """The data-plane aval tree matching ``_unkey``'s output: key leaves
    become their key-data ShapeDtypeStructs (same sharding; trailing
    counter dims are unconstrained by a PartitionSpec prefix)."""

    def one(a):
        if _is_key_dtype(getattr(a, "dtype", None)):
            data = jax.eval_shape(jax.random.key_data, jax.ShapeDtypeStruct(a.shape, a.dtype))
            return jax.ShapeDtypeStruct(
                data.shape, data.dtype, sharding=getattr(a, "sharding", None)
            )
        return a

    return jax.tree.map(one, abstract_tree)


def _rekey(restored: Any, abstract_tree: Any) -> Any:
    """Re-wrap restored key-data leaves into key arrays of the impl the
    abstract tree's dtype carries."""

    def one(x, a):
        if _is_key_dtype(getattr(a, "dtype", None)):
            return jax.random.wrap_key_data(x, impl=a.dtype._impl)
        return x

    return jax.tree.map(one, restored, abstract_tree)


def save_arrays(ckpt_dir: str, tree: Any) -> None:
    """Write a pytree of (possibly sharded) jax arrays; collective across
    processes — every process must call with the same tree structure."""
    path = os.path.join(os.path.abspath(ckpt_dir), ARRAY_SUBDIR)
    with _backend().StandardCheckpointer() as ckptr:
        with _save_lock:
            ckptr.save(path, _unkey(tree))
        ckptr.wait_until_finished()


def restore_arrays(ckpt_dir: str, abstract_tree: Any) -> Any:
    """Restore into the shardings carried by ``abstract_tree`` (a pytree of
    jax.ShapeDtypeStruct with .sharding set, e.g. from eval_shape +
    shardings)."""
    path = os.path.join(os.path.abspath(ckpt_dir), ARRAY_SUBDIR)
    with _backend().StandardCheckpointer() as ckptr:
        restored = _rekey(ckptr.restore(path, _unkey_abstract(abstract_tree)), abstract_tree)
    # Belt-and-braces: guarantee placement matches the requested shardings
    # (a replicated scalar must span the mesh, not sit on one device, or the
    # next jitted step sees incompatible device sets).  No-op when already
    # placed correctly.
    return jax.tree.map(
        lambda x, a: jax.device_put(x, a.sharding) if getattr(a, "sharding", None) else x,
        restored,
        abstract_tree,
    )


def abstract_like(tree: Any, shardings: Optional[Any] = None) -> Any:
    """ShapeDtypeStruct pytree of ``tree``; shardings taken from the arrays
    themselves unless an explicit sharding pytree is given."""

    def one(x, s=None):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s or x.sharding)
        arr = np.asarray(x)
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype, sharding=s)

    if shardings is None:
        return jax.tree.map(one, tree)
    return jax.tree.map(one, tree, shardings)


def save_trainer_state(ckpt_dir: str, state: Dict[str, Any]) -> None:
    with open(os.path.join(ckpt_dir, TRAINER_STATE_FILE), "w") as f:
        json.dump(state, f, indent=2, sort_keys=True)


def load_trainer_state(ckpt_dir: str) -> Dict[str, Any]:
    with open(os.path.join(ckpt_dir, TRAINER_STATE_FILE)) as f:
        return json.load(f)
