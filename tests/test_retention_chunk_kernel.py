"""The chunk kernel of the power-retention layers (ops/retention.py
``retention_chunk``) in interpret mode against its ``jax.numpy`` form, and
against the quadratic form across chunks.  Cut from
tests/test_retention_serving.py, which keeps the forms, the decode kernel,
the model and the engine: the kernel's 65 feature rows are unrolled, a query
head's rows at a time under each, so every program here costs seconds to
trace and compile whatever its shapes, and ``--dist loadfile`` balances by
the file."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.ops import retention
from tests.model_cases import retention_chunk_step as _chunk_step, retention_heads as _heads


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n_rep", [1, 5])
def test_the_chunk_kernel_in_interpret_mode_is_its_jnp_form(n_rep, state_dtype):
    """Two rows of 16 tokens (the second holds 11) against a state that holds
    something: what the chunk is answered, and the state and the normaliser after it."""
    b, g, s, d = 2, 2, 16, 128
    ks = jax.random.split(jax.random.key(10 + n_rep), 6)
    unit = lambda x: (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True))).astype(jnp.bfloat16)  # noqa: E731  (as after the norm a head)
    q, k = unit(jax.random.normal(ks[0], (b, g * n_rep, s, d))), unit(jax.random.normal(ks[1], (b, g, s, d)))
    v = jax.random.normal(ks[2], (b, g, s, d), jnp.bfloat16)
    log_g = jax.nn.log_sigmoid(4.0 + jax.random.normal(ks[3], (b, g, s)))
    shapes = retention.state_shapes(1, b, g, d)
    state = jax.random.normal(ks[4], shapes[0][1:]).astype(state_dtype)
    norm = (1.0 + jnp.abs(jax.random.normal(ks[5], shapes[1][1:]))).astype(state_dtype)
    valid = jnp.arange(s)[None, :] < jnp.asarray([s, 11])[:, None]
    want = _chunk_step("jnp")(q, k, v, log_g, state, norm, valid)
    got = _chunk_step("kernel_interpret")(q, k, v, log_g, state, norm, valid)
    tol = 2e-3 if state_dtype == jnp.float32 else 0.15
    for a, c in zip(got, want):
        assert a.dtype == c.dtype and a.shape == c.shape
    keep = np.asarray(valid)[:, None, :, None]                                         # what a token that does not exist is answered is not read
    np.testing.assert_allclose(np.where(keep, np.asarray(got[0]), 0.0), np.where(keep, np.asarray(want[0]), 0.0), atol=tol, rtol=1e-4)
    for a, c in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(c, np.float32), atol=tol, rtol=1e-2 if state_dtype != jnp.float32 else 1e-5)
    with pytest.raises(ValueError, match="chunk kernel does not take"):
        retention.retention_chunk(q[:, :, :12], k[:, :, :12], v[:, :, :12], log_g[:, :, :12], state, norm, valid[:, :12], impl="kernel")


@pytest.mark.parametrize("chunk", [8, 24])
def test_chunks_that_carry_a_state_through_the_kernel_give_the_quadratic_form(chunk):
    q, k, v, log_g = _heads(6, b=1, h=2, g=1, s=24, d=128)
    q, k = (t / jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True)) for t in (q, k))
    want = retention.retention_quadratic(q, k, v, log_g)
    shapes = retention.state_shapes(1, 1, 1, 128)
    state, norm, outs = jnp.zeros(shapes[0][1:]), jnp.zeros(shapes[1][1:]), []
    for lo in range(0, 24, chunk):
        part = lambda t: t[:, :, lo:lo + chunk]  # noqa: E731
        out, state, norm = _chunk_step("kernel_interpret")(part(q), part(k), part(v), part(log_g), state, norm, jnp.ones((1, chunk), bool))
        outs.append(out)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=2)), np.asarray(want), atol=2e-4, rtol=2e-3)
