"""Which form of a kernel runs: asked here, and nowhere else in ``ops/`` or
``models/``.

A kernel of this package has up to three forms: the Pallas kernel compiled by
Mosaic, the same kernel in the Pallas interpreter, and (the serving kernels
alone) the same mathematics in ``jax.numpy``.  Which one a call takes depends
on where the process runs (:func:`on_tpu`) and on which side the kernel
serves; the two sides fall back differently off the chip, each for a reason:

* **A serving kernel runs its ``jax.numpy`` form** (:func:`resolve_impl`).  The
  serving forward is run eagerly by tests and by small deployments on a CPU,
  kernel beside kernel, and an interpreted kernel in an eagerly run model
  deadlocked the test process two runs in three (PR 34).  The ``jax.numpy``
  form is also what serves the shapes a kernel does not take, on any backend,
  and is each kernel's parity reference.
* **A training kernel runs in the Pallas interpreter**
  (:func:`interpreted_off_chip`).  There is no second form of flash
  attention's backward, of the grouped product or of the fused AdamW sweep to
  fall back to: the kernel's body is the only statement of them.

Tests steer every kernel of a process through the one switch
(``monkeypatch.setattr(kernel_form, "on_tpu", ...)``), which is why callers
reach it through the module and never import the function by name.

The unit a kernel is written in is **a Mosaic call inside a jitted function of
its own, the layer it works on an argument** (``_paged_attention``,
``_paged_latent``, ``_ssm_decode``, ``_retention_decode``,
``_chunk_state_pallas``, ``ops/expert_rows.py``'s three, the flash programs),
never a bare ``pallas_call`` in its caller's trace, because

1. its ``op_name`` survives: inside a jitted function of its own the call
   keeps its ``name=`` and the scopes round it in the optimized program, which
   is how a trace's reader finds it (PR 33, 36; the flash programs are
   ``inline=True`` for the same end: their readers look for
   ``%tpu_custom_call.N`` under the caller's scopes);
2. it is lowered once for all call sites: with the layer an argument a
   model's layers share one trace and one lowering to Mosaic, ~0.2 s a call
   site at every start, cached program or not (PR 25: 3.4 s of ``setup_s``
   over 24 layers; PR 33: 8 s);
3. its cache key does not carry its caller's line: a kernel's payload holds the
   file and line that called it, so an edit above a bare call re-keys every
   program that holds it (PR 36, 38, 42, 55).
"""

from __future__ import annotations

from typing import Any, Optional

import jax

#: what a serving kernel's ``impl=`` may say (None: chosen here)
IMPLS = ("kernel", "kernel_interpret", "jnp")


def on_tpu() -> bool:
    """The one switch: whether Mosaic can compile for the device in use."""
    return jax.default_backend() == "tpu"


def interpreted_off_chip() -> bool:
    """A training kernel's ``interpret=``: Mosaic on the chip, the Pallas
    interpreter everywhere else (see the module's text)."""
    return not on_tpu()


def resolve_impl(impl: Optional[str], takes: bool, needs: str) -> str:
    """A serving kernel's form.  ``impl`` None: the kernel on a TPU where it
    ``takes`` the shapes (the caller's ``*_kernel_takes``), else ``"jnp"``.
    A kernel asked for by name on shapes it does not take is refused with
    ``needs``, the caller's own sentence on what it runs."""
    if impl is None:
        return "kernel" if on_tpu() and takes else "jnp"
    if impl not in IMPLS:
        raise ValueError(f"impl is one of {IMPLS} or None, not {impl!r}")
    if impl != "jnp" and not takes:
        raise ValueError(needs)
    return impl


def interpret_params(interpret: bool) -> Any:
    """``pallas_call``'s ``interpret=`` for a serving kernel: the TPU
    interpreter (it runs the kernels' copies and semaphores as the chip
    orders them) for ``"kernel_interpret"``, Mosaic otherwise."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.InterpretParams() if interpret else False
