"""The retention decode kernel (ops/retention.py ``retention_decode``) in
interpret mode against its ``jax.numpy`` form: lanes due, not due and idle in
one call, and the blocks the pipeline is told it holds whichever lanes are live
and due.  Cut from tests/test_retention_serving.py along the kernel's seam (a
FILE is the unit of the suite's balance: PERF.md section 8); what Mosaic makes
of the products on the chip interpret mode does not show (PERF.md section 5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.ops import retention

EVERY = retention.FOLD_EVERY  # tokens a lane's recent rows hold before a decode step folds them into its slot


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n_rep", [1, 5])
def test_the_kernel_in_interpret_mode_is_its_jnp_form(n_rep, state_dtype):
    """Five lanes in ONE call: due (its rows whole with this token), not due, idle
    with whole rows, due again, and not due with no row yet."""
    lanes, g, d = 5, 2, 128
    ks = jax.random.split(jax.random.key(n_rep), 9)
    q = jax.random.normal(ks[0], (lanes, g * n_rep, d), jnp.bfloat16)
    k, v = (jax.random.normal(ks[i], (lanes, g, d), jnp.bfloat16) for i in (1, 2))
    log_g = jax.nn.log_sigmoid(4.0 + jax.random.normal(ks[3], (lanes, g)))
    shapes, rows = retention.state_shapes(2, lanes, g, d), retention.recent_shapes(2, lanes, g, d)
    state = jax.random.normal(ks[4], shapes[0]).astype(state_dtype)
    norm = (1.0 + jnp.abs(jax.random.normal(ks[5], shapes[1]))).astype(state_dtype)
    recent = (
        jax.random.normal(ks[6], rows[0], jnp.bfloat16), jax.random.normal(ks[7], rows[1], jnp.bfloat16),
        jnp.cumsum(jax.nn.log_sigmoid(4.0 + jax.random.normal(ks[8], rows[2])), axis=-1),
        jnp.asarray([[0] * lanes, [EVERY - 1, 3, EVERY - 1, EVERY - 1, 0]], jnp.int32),
    )
    live = jnp.asarray([True, True, False, True, True])
    want = retention.retention_decode(q, k, v, log_g, state, norm, recent, 1, live, impl="jnp")
    got = retention.retention_decode(q, k, v, log_g, state, norm, recent, 1, live, impl="kernel_interpret")
    tol = 2e-3 if state_dtype == jnp.float32 else 0.15
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=tol, rtol=1e-2 if state_dtype != jnp.float32 else 1e-5)
    assert np.array_equal(np.asarray(got[3][3]), [[0] * lanes, [0, 4, EVERY - 1, 0, 1]])
    # the slots of the lanes not due, of the idle lane and of the other layer: bit for bit what they were; the due lanes' are not
    for pool, was in ((got[1], state), (got[2], norm)):
        assert np.array_equal(np.asarray(pool[1, [1, 2, 4]], np.float32), np.asarray(was[1, [1, 2, 4]], np.float32))
        assert np.array_equal(np.asarray(pool[0], np.float32), np.asarray(was[0], np.float32))
        assert not any(np.array_equal(np.asarray(pool[1, lane], np.float32), np.asarray(was[1, lane], np.float32)) for lane in (0, 3))
    assert not np.asarray(got[0][2]).any()
    for leaf, was in zip(got[3][:3], recent[:3]):                                      # the idle lane's rows too
        assert np.array_equal(np.asarray(leaf[1, 2], np.float32), np.asarray(was[1, 2], np.float32))
    with pytest.raises(ValueError, match="head_dim 128"):
        retention.retention_decode(q[..., :64], k[..., :64], v[..., :64], log_g, state, norm, recent, 0, live, impl="kernel")


@pytest.mark.parametrize("due, idle", [
    ((), ()), ((0,), ()), ((3,), ()), ((1, 2), ()), ((0, 1, 2, 3), ()),
    ((), (0,)), ((), (0, 1, 2, 3)), ((2,), (0, 3)), ((1, 3), (2,)), ((3,), (0, 1, 2)), ((0,), (1, 2, 3)),
], ids=lambda lanes: "-".join(map(str, lanes)) or "none")
def test_the_kernels_blocks_follow_the_lanes_that_are_live_and_due(due, idle):
    """Whichever lanes are live and due (the pipeline is told which block it
    holds at each program, and copies only where that moves), every slot and
    every answer is the ``jax.numpy`` form's: a slot that is not due bit for bit
    as it was, an idle lane's rows too."""
    lanes, g, d, n_rep = 4, 2, 128, 2
    ks = jax.random.split(jax.random.key(11), 9)
    q = jax.random.normal(ks[0], (lanes, g * n_rep, d), jnp.float32)
    k, v = (jax.random.normal(ks[i], (lanes, g, d), jnp.float32) for i in (1, 2))
    log_g = jax.nn.log_sigmoid(4.0 + jax.random.normal(ks[3], (lanes, g)))
    shapes, rows = retention.state_shapes(1, lanes, g, d), retention.recent_shapes(1, lanes, g, d)
    state, norm = jax.random.normal(ks[4], shapes[0]), 1.0 + jnp.abs(jax.random.normal(ks[5], shapes[1]))
    pending = jnp.asarray([[EVERY - 1 if lane in due or lane in idle else lane for lane in range(lanes)]], jnp.int32)
    recent = (jax.random.normal(ks[6], rows[0]), jax.random.normal(ks[7], rows[1]), jnp.cumsum(-jnp.abs(jax.random.normal(ks[8], rows[2])) * 0.1, axis=-1), pending)
    live = jnp.asarray([lane not in idle for lane in range(lanes)])
    want = retention.retention_decode(q, k, v, log_g, state, norm, recent, 0, live, impl="jnp")
    got = retention.retention_decode(q, k, v, log_g, state, norm, recent, 0, live, impl="kernel_interpret")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3, rtol=1e-5)
    for lane in range(lanes):
        assert np.array_equal(np.asarray(got[1][0, lane]), np.asarray(state[0, lane])) == (lane not in due)
        assert np.array_equal(np.asarray(got[2][0, lane]), np.asarray(norm[0, lane])) == (lane not in due)
        assert bool(np.asarray(got[0][lane]).any()) == (lane not in idle)
        assert int(got[3][3][0, lane]) == (int(pending[0, lane]) if lane in idle else 0 if lane in due else lane + 1)
