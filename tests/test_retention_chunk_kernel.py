"""The chunk kernel of the power-retention layers (ops/retention.py
``retention_chunk``) in interpret mode against its ``jax.numpy`` form, and
against the quadratic form across chunks.  Cut from
tests/test_retention_serving.py, which keeps the forms, the decode kernel,
the model and the engine.  Since PR 65 the kernel's 65 feature rows are a
loop whose body is stated once, five rows a trip (it was 65 copies of the
body: seconds to trace and compile whatever the shapes), so the cell's own
shape is a case here too."""

import functools

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


def test_the_cells_ratio_at_a_chunk_of_256_tokens_with_a_lane_part_empty():
    """Five query heads a KV head and the walk's 256 tokens, 1,280 query rows under each feature row: a lane whose
    chunk is whole beside one whose last chunk holds 77 tokens (the others neither decay the state nor enter it)."""
    b, g, n_rep, s, d = 2, 1, 5, 256, 128
    assert retention.chunk_kernel_takes(n_rep * s, s, d, jnp.float32)
    q, k, v, log_g = _heads(65, b=b, h=g * n_rep, g=g, s=s, d=d, bias=6.0)
    q, k = ((t / jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True))).astype(jnp.bfloat16) for t in (q, k))
    ks = jax.random.split(jax.random.key(65), 2)
    shapes = retention.state_shapes(1, b, g, d)
    state = 30.0 * jax.random.normal(ks[0], shapes[0][1:])
    norm = 50.0 + 10.0 * jnp.abs(jax.random.normal(ks[1], shapes[1][1:]))
    valid = jnp.arange(s)[None, :] < jnp.asarray([s, 77])[:, None]
    want = _chunk_step("jnp")(q, k, v.astype(jnp.bfloat16), log_g, state, norm, valid)
    got = _chunk_step("kernel_interpret")(q, k, v.astype(jnp.bfloat16), log_g, state, norm, valid)
    keep = np.asarray(valid)[:, None, :, None]
    np.testing.assert_allclose(np.where(keep, np.asarray(got[0]), 0.0), np.where(keep, np.asarray(want[0]), 0.0), atol=2e-3, rtol=1e-4)
    for a, c in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=2e-3, rtol=1e-5)
    # the empty part of the second lane left its state where the 77 tokens put it: the same chunk cut to 80 tokens
    cut = lambda t: t[1:, :, :80]  # noqa: E731
    short = _chunk_step("kernel_interpret")(cut(q), cut(k), cut(v.astype(jnp.bfloat16)), cut(log_g), state[1:], norm[1:], valid[1:, :80])
    for a, c in zip(short[1:], got[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c[1:]), atol=2e-3, rtol=1e-5)


@pytest.mark.parametrize("n_rep", [1, 5])
def test_the_kernels_feature_rows_are_a_loop_whose_body_is_stated_once(n_rep):
    """What the traced kernel holds: ONE loop over the feature rows, ``_CHUNK_ROWS_A_TRIP`` of them a trip, and for each
    row of a trip the queries' three bfloat16 products against the state's row, a query head's rows at a time, and the
    keys' one float32 product at ``HIGHEST``; nothing of the state is multiplied outside the loop.  (Written out, the
    65 rows were 65 x (3 x n_rep + 1) products in the body: what made the program too large for two loops of the walk.)"""
    b, g, s, d = 1, 2, 16, 128
    f32 = jnp.float32
    rows, a_trip = retention.phi_rows(d), retention._CHUNK_ROWS_A_TRIP
    aval = lambda *shape: jax.ShapeDtypeStruct(shape, f32)  # noqa: E731
    jaxpr = jax.make_jaxpr(functools.partial(retention._chunk_state_pallas, interpret=True))(
        aval(b, g, n_rep, s, d), aval(b, g, s, d), aval(b, g, s, d), aval(b, g, s), aval(b, g), aval(b, g, rows * d, d), aval(b, g, rows, d))

    def eqns(jaxpr, depth=0):
        for eqn in jaxpr.eqns:
            yield eqn, depth
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(inner, depth + (eqn.primitive.name in ("scan", "while")))

    (call,) = [e for e, _ in eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    body = list(eqns(call.params["jaxpr"]))
    (loop,) = [e for e, depth in body if e.primitive.name in ("scan", "while") and depth == 0]
    assert loop.params["length"] * a_trip == rows == 65
    products = [(e, depth) for e, depth in body if e.primitive.name == "dot_general"]
    assert all(depth == 1 for _, depth in products)
    left = sorted((e.invars[0].aval.shape, str(e.invars[0].aval.dtype), e.params["precision"]) for e, _ in products)
    a_row = [((s, d), "bfloat16", None)] * (3 * n_rep) + [((d, s), "float32", (jax.lax.Precision.HIGHEST,) * 2)]
    assert left == sorted(a_row * a_trip)
