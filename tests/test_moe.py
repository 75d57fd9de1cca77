"""MoE layer + expert parallelism (no reference counterpart — SURVEY §2.10
lists EP/MoE as absent upstream; TPU-first capability)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.models.moe import MoE, _top2_dispatch
from determined_tpu.parallel.mesh import MeshConfig, make_mesh


def test_top2_dispatch_routes_and_renormalizes():
    g, e, c = 8, 4, 8  # ample capacity: nothing dropped
    rng = np.random.default_rng(0)
    gates = jax.nn.softmax(jnp.asarray(rng.standard_normal((g, e)), jnp.float32))
    dispatch, combine, aux = _top2_dispatch(gates, c)
    assert dispatch.shape == (g, e, c)
    # every token lands on exactly two expert slots
    np.testing.assert_allclose(np.asarray(dispatch.sum(axis=(1, 2))), 2.0)
    # combine weights renormalize the two surviving gate probs to 1
    np.testing.assert_allclose(np.asarray(combine.sum(axis=(1, 2))), 1.0, rtol=1e-5)
    assert float(aux) > 0


def test_top2_dispatch_respects_capacity():
    # all tokens prefer expert 0 -> only `capacity` of them survive there
    g, e, c = 16, 4, 2
    gates = jnp.tile(jnp.asarray([[0.7, 0.3, 0.0, 0.0]], jnp.float32), (g, 1))
    dispatch, combine, aux = _top2_dispatch(gates, c)
    per_expert = np.asarray(dispatch.sum(axis=(0, 2)))
    assert per_expert[0] == c  # expert 0 full
    assert per_expert[1] == c  # expert 1 (everyone's second choice) full
    # unbalanced routing => large aux loss (signal to the optimizer)
    assert float(aux) > 1.0


def test_moe_layer_trains_and_is_finite():
    b, s, d = 2, 16, 32
    layer = MoE(num_experts=4, d_ff=64, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
    params = layer.init(jax.random.key(0), x)

    def loss_fn(p, x):
        y, aux = layer.apply(p, x)
        return (y**2).mean() + 0.01 * aux

    val, grads = jax.value_and_grad(loss_fn)(params, x)
    assert np.isfinite(float(val))
    for leaf in jax.tree.leaves(grads):
        assert np.all(np.isfinite(np.asarray(leaf)))
    # router must receive gradient (it is on the aux + routing path)
    from flax.core import meta

    router_grad = meta.unbox(grads)["params"]["router"]
    assert float(jnp.abs(router_grad).sum()) > 0


def test_moe_expert_sharding_matches_unsharded(devices8):
    """The same MoE computation over an expert=4 mesh equals the
    single-device result — XLA's inserted collectives preserve numerics."""
    b, s, d = 2, 16, 32
    layer = MoE(num_experts=4, d_ff=64, dtype=jnp.float32)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
    params = layer.init(jax.random.key(0), x)

    from flax.core import meta
    from jax.sharding import NamedSharding, PartitionSpec as P

    raw = meta.unbox(params)
    ref_y, ref_aux = layer.apply(raw, x)

    mesh = make_mesh(MeshConfig(data=2, expert=4), devices8)
    # expert-stacked weights REALLY sharded over the expert axis (the
    # router [d, e] shards its expert output dim)
    def shard_leaf(path, leaf):
        name = path[-1].key
        if name == "router":
            spec = P(None, "expert")
        else:  # w_in/w_gate/w_out: leading expert dim
            spec = P("expert")
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    import jax.tree_util as jtu

    sharded_params = jtu.tree_map_with_path(shard_leaf, raw)
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    with mesh:
        sharded = jax.jit(lambda p, x: layer.apply(p, x))(sharded_params, xs)
    np.testing.assert_allclose(
        np.asarray(sharded[0]), np.asarray(ref_y), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(float(sharded[1]), float(ref_aux), rtol=1e-5)


def test_lm_with_moe_trains(tmp_path):
    """TransformerLM with MoE blocks trains end-to-end on an
    expert-parallel mesh; aux loss is reported and finite."""
    from determined_tpu import core, train
    from determined_tpu.config import Length
    from determined_tpu.models.transformer import LMTrial

    ctx = train.init(
        hparams={
            "lr": 1e-3,
            "global_batch_size": 16,
            "seq_len": 32,
            "vocab_size": 128,
            "d_model": 64,
            "n_layers": 2,
            "n_heads": 4,
            "dataset_size": 64,
            "bf16": False,
            "attention": "reference",
            "warmup_steps": 1,
            "moe_experts": 4,
            "moe_every": 2,
        },
        mesh_config=MeshConfig(data=2, expert=4),
        core_context=core._dummy_init(checkpoint_dir=str(tmp_path / "ck")),
        seed=0,
    )
    trainer = train.Trainer(LMTrial(ctx))
    reported = []
    orig = ctx.core.train.report_training_metrics
    ctx.core.train.report_training_metrics = lambda s, m: (
        reported.append((s, m)),
        orig(s, m),
    )
    result = trainer.fit(Length.batches(8), report_period=Length.batches(4))
    assert result["steps_completed"] == 8
    assert any("moe_aux_loss" in m for _, m in reported)
    last = reported[-1][1]
    assert np.isfinite(last["loss"]) and np.isfinite(last["moe_aux_loss"])
    assert last["loss"] < reported[0][1]["loss"]


# ---------------------------------------------------------------------------
# the grouped sigmoid router selects without a sort, and as ``top_k`` would
# ---------------------------------------------------------------------------


def _route_sigmoid_grouped_by_top_k(logits, bias, *, top_k, n_group, topk_group, scaling):
    """The oracle: the router as three ``top_k`` and a gather state it (a
    group's score the sum of its two largest, the groups kept, the picks and
    their scores), whose rule on a tie (the lower index first) the function
    under test has to keep."""
    tokens, e = logits.shape
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    select = scores + bias.astype(jnp.float32)[None, :]
    group_score = jnp.sum(jax.lax.top_k(select.reshape(tokens, n_group, e // n_group), 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, topk_group)
    group_kept = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
    inside = jnp.where(jnp.repeat(group_kept, e // n_group, axis=1), select, -jnp.inf)
    _, picks = jax.lax.top_k(inside, top_k)
    top = jnp.take_along_axis(scores, picks, axis=1)
    return top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * scaling, picks


def _router_inputs(ties: str, tokens: int, e: int, n_group: int):
    """(logits [T, E], bias [E]) that tie where ``ties`` says: equal logits
    under equal biases are equal selection values to the bit."""
    rng = np.random.default_rng([tokens, e, n_group, len(ties)])
    size = e // n_group
    logits = rng.normal(size=(tokens, n_group, size)).astype(np.float32)
    bias = (rng.normal(size=e) * 0.1).astype(np.float32)
    if ties == "none":
        pass
    elif ties == "two_largest_of_a_group":  # every group's largest value twice, at two random places
        bias[:] = 0.0
        for t in range(tokens):
            for g in range(n_group):
                a, b = rng.choice(size, 2, replace=False)
                logits[t, g, a] = logits[t, g, b] = logits[t, g].max() + 0.5
    elif ties == "groups_at_the_cut":  # groups are copies of three rows: one high, most equal, the last low
        bias = np.tile(bias[:size], n_group)
        high, mid, low = rng.normal(size=(3, tokens, size)).astype(np.float32) + np.array([2.0, 0.0, -2.0], np.float32)[:, None, None]
        for t in range(tokens):
            order = rng.permutation(n_group)
            logits[t] = mid[t]
            logits[t, order[0]], logits[t, order[-1]] = high[t], low[t]
    elif ties == "large_negative_bias":  # values a float32 apart collapse under the bias; some experts are out at -inf
        bias = np.where(rng.random(e) < 0.7, -1e4, bias).astype(np.float32)
        bias[rng.random(e) < 0.1] = -np.inf
        if n_group > 1:
            bias[:size] = -np.inf  # a whole group out
    elif ties == "all_equal":
        logits[:], bias[:] = 0.25, 0.0
    else:
        raise ValueError(ties)
    return jnp.asarray(logits.reshape(tokens, e)), jnp.asarray(bias)


@pytest.mark.parametrize("ties", ["none", "two_largest_of_a_group", "groups_at_the_cut", "large_negative_bias", "all_equal"])
@pytest.mark.parametrize(
    "e,n_group,topk_group,top_k,tokens",
    [(512, 8, 4, 8, 128), (256, 8, 4, 8, 64), (512, 8, 4, 8, 1024), (512, 1, 1, 22, 64), (64, 4, 4, 6, 32), (96, 4, 1, 5, 7)],
    ids=["ling", "dsv3", "ling_walk", "one_group", "every_group_kept", "one_group_kept"],
)
def test_the_grouped_router_selects_bit_for_bit_as_three_top_k_would(e, n_group, topk_group, top_k, tokens, ties):
    """``route_sigmoid_grouped`` makes a group's score and the kept groups
    without a sort and reads the picked scores without a gather; its weights
    and picks are those of the ``top_k`` form to the bit, ties included (the
    lower index first)."""
    from determined_tpu.models.moe import route_sigmoid_grouped

    logits, bias = _router_inputs(ties, tokens, e, n_group)
    kw = dict(top_k=top_k, n_group=n_group, topk_group=topk_group, scaling=2.5)
    want_w, want_p = jax.jit(functools.partial(_route_sigmoid_grouped_by_top_k, **kw))(logits, bias)
    got_w, got_p = jax.jit(functools.partial(route_sigmoid_grouped, **kw))(logits, bias)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    assert got_p.dtype == want_p.dtype and got_w.dtype == want_w.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got_w).view(np.uint32), np.asarray(want_w).view(np.uint32))
    if ties != "none":  # the input does tie somewhere a selection is made
        select = np.sort(np.asarray(jax.nn.sigmoid(logits)) + np.asarray(bias)[None], axis=1)
        assert (select[:, 1:] == select[:, :-1]).any()
