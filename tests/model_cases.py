"""What more than one test file of the models and their kernels takes: inputs,
plain forms to compare with and jitted steps, beside ``tests/faults.py`` and
``tests/parallel_utils.py``.  A file cut along a seam (``--dist loadfile``
balances by the file: tests/conftest.py) keeps its helpers here, once."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.models.moe import RoutedExperts
from determined_tpu.ops import retention



def reference_module(name: str):
    """A plain reference the benchmark keeps (``benchmark/reference/<name>.py``: float32, no cache, no import from the program)."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def causal_forward(model, width: int):
    """``(variables, seq) -> logits [len(seq), vocab]`` of the full forward, as
    ONE program on a padded width: the forward is causal, so what follows a
    position cannot move it; called bare it is compiled an operation at a
    time, anew at every length it is asked for."""
    forward = jax.jit(model.apply)

    def logits(variables, seq):
        padded = np.zeros((1, width), np.int32)
        padded[0, : len(seq)] = seq
        return forward(variables, jnp.asarray(padded))[0, : len(seq)]

    return logits


# -- the dropless expert layer (tests/test_routed_experts.py, tests/test_expert_rows.py) --

def dense_experts(x, p, k, first, count):
    """Every held expert on every token, then the picks: plain."""
    probs = jax.nn.softmax(x @ p["router"], -1)
    top, idx = jax.lax.top_k(probs, k)
    w = top / top.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(first, first + count):
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        hidden = jax.nn.silu(x @ p["w_gate"][e - first]) * (x @ p["w_up"][e - first])
        y = y + mine[:, None] * (hidden @ p["w_down"][e - first])
    experts = probs.shape[-1]
    share = jnp.mean(jax.nn.one_hot(idx, experts).sum(1), 0) / k
    return y, experts * jnp.sum(share * probs.mean(0))


def routed_layer(held=None, experts=8, k=3, **kw):
    return RoutedExperts(num_experts=experts, top_k=k, d_ff=12, held=held, dtype=jnp.float32, partition=False, **kw)


# -- power retention (tests/test_retention_serving.py, tests/test_retention_chunk_kernel.py) --


def retention_heads(seed, b=2, h=4, g=2, s=24, d=16, bias=2.0):
    """q [b, h, s, d], k and v [b, g, s, d], log_g [b, g, s] of a gate that remembers."""
    ks = jax.random.split(jax.random.key(seed), 4)
    q, k, v = (jax.random.normal(ks[i], (b, n, s, d), jnp.float32) for i, n in enumerate((h, g, g)))
    return q, k, v, jax.nn.log_sigmoid(bias + jax.random.normal(ks[3], (b, g, s)))


@functools.lru_cache(maxsize=None)
def retention_chunk_step(impl):
    """``retention_chunk`` as ONE program a shape (called bare, its ``jax.numpy`` half is compiled an operation at a time)."""
    return jax.jit(functools.partial(retention.retention_chunk, impl=impl))


# -- compiling for a described chip (tests/test_tpu_compile.py, tests/test_tpu_compile_cells.py) --

flash_mod = importlib.import_module("determined_tpu.ops.flash_attention")
adamw_mod = importlib.import_module("determined_tpu.ops.fused_adamw")
paged_mod = importlib.import_module("determined_tpu.ops.paged_attention")
grouped_mod = importlib.import_module("determined_tpu.ops.grouped_matmul")
rows_mod = importlib.import_module("determined_tpu.ops.expert_rows")

TOPOLOGY = "v5e:2x2"


@pytest.fixture(scope="module")
def tpu_devices():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)
    except Exception as e:  # noqa: BLE001 - no libtpu, or it cannot describe the chip
        pytest.skip(f"cannot describe a {TOPOLOGY} topology here: {e}")
    return list(topo.devices)


@pytest.fixture(autouse=True)
def real_kernels_no_cache(monkeypatch):
    """Mosaic, not the interpreter; and no persistent cache around the
    compiles (an entry compiled for a described chip cannot be read back
    without one, and the next run would warn)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(flash_mod, "_interpret", lambda: False)
    monkeypatch.setattr(adamw_mod, "_interpret", lambda: False)
    monkeypatch.setattr(grouped_mod, "_interpret", lambda: False)
    monkeypatch.setattr(paged_mod, "_on_tpu", lambda: True)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # as a fresh process has it: ``setup_compilation_cache`` turns it off for
    # the process (any Trainer or DecodeKernels an earlier test file built),
    # and a compile made HERE for a described chip then names a Mosaic call
    # ``custom-call.N`` whatever its ``name=``
    tracebacks = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    yield
    jax.config.update("jax_include_full_tracebacks_in_locations", tracebacks)
    jax.config.update("jax_enable_compilation_cache", prev)


def compile_text(fn, *avals) -> str:
    """The optimized program's text; raises what the chip's compiler would."""
    return jax.jit(fn).lower(*avals).compile().as_text()


def mosaic_calls(text: str) -> int:
    return text.count('custom_call_target="tpu_custom_call"')
