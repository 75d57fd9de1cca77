"""A block of compressed convolutional attention, the MLP router that carries
a state from layer to layer, and learned residual scaling (ZAYA1's block:
``models/transformer.py`` ``CompressedAttention``, ``ResidualScale``, ``Block``'s
second value; ``models/moe.py`` ``route_mlp`` and the "mlp" router of
``RoutedExperts``), against plain statements of what each does.  The whole
model against ``benchmark/reference/zaya_cca_moe.py`` is
tests/benchmark/test_bench_zaya.py.  CPU, small sizes, seeded weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.models import moe
from determined_tpu.models import transformer as T
from determined_tpu.models.transformer import CCA, FULL, TransformerConfig, TransformerLM
from tests.model_cases import routed_layer


def _tiny(**kw):
    base = dict(
        vocab_size=96, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=16, max_seq_len=24,
        dtype=jnp.float32, attention_impl="reference", layer_types=(CCA,) * 3, partial_rotary_factor=0.5, rope_theta=5e6,
        moe_experts=8, moe_every=1, moe_top_k=1, moe_intermediate_size=24, moe_experts_held=(2, 4), moe_router="mlp",
        router_hidden_size=12, residual_scaling=True, tie_embeddings=True, norm_eps=1e-5, partition_params=False,
    )
    return TransformerConfig(**{**base, **kw})


TOKENS = jax.random.randint(jax.random.key(1), (2, 24), 0, 96)


def _stirred(params, seed=9):
    """The leaves that start at zero or one (scales, biases, the temperature,
    the state's mix) moved off their start, so that each of them matters."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(treedef, [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def model():
    lm = TransformerLM(_tiny(n_layers=2, layer_types=(CCA,) * 2))
    return lm, _stirred(jax.jit(lm.init)(jax.random.key(0), TOKENS))


# ---------------------------------------------------------------------------
# compressed attention
# ---------------------------------------------------------------------------


def test_compressed_attention_is_causal_and_a_token_reads_the_one_befores_value():
    cfg = _tiny()
    layer = T.CompressedAttention(cfg)
    u = jax.random.normal(jax.random.key(2), (1, 12, 32))
    params = _stirred(layer.init(jax.random.key(3), u))
    apply = jax.jit(layer.apply)
    out = apply(params, u)
    # causal: what comes after position 6 moves nothing up to it (two convolutions of two taps, the shift and the mask look back only)
    later = u.at[:, 7:].set(jax.random.normal(jax.random.key(4), (1, 5, 32)))
    np.testing.assert_allclose(apply(params, later)[:, :7], out[:, :7], atol=1e-6)
    assert float(jnp.abs(apply(params, later)[:, 7:] - out[:, 7:]).max()) > 1e-3
    # the shifted half of the values: with wv1 zeroed, position 0 has only a zero row to read (nothing lies before the first token)
    only_shifted = jax.tree.map(lambda x: x, params)
    only_shifted["params"]["wv1"]["kernel"] = jnp.zeros_like(params["params"]["wv1"]["kernel"])
    shifted = apply(only_shifted, u)
    heads_of_kv1 = np.asarray(shifted[0, 0])            # position 0: KV head 0 gives zeros, KV head 1 reads u[-1] = 0
    np.testing.assert_allclose(heads_of_kv1, 0.0, atol=1e-7)
    assert float(jnp.abs(shifted[0, 1]).max()) > 1e-4   # position 1 reads u[0] Wv2 through KV head 1


def test_the_mix_is_two_causal_convolutions_and_the_qk_mean_as_written():
    cfg = _tiny()
    p = {name: init(jax.random.key(i), shape, jnp.float32) for i, (name, (shape, _, init)) in enumerate(T._cca_param_shapes(cfg).items())}
    q = jax.random.normal(jax.random.key(20), (1, 9, 4, 16))
    k = jax.random.normal(jax.random.key(21), (1, 9, 2, 16))
    got_q, got_k = T._cca_mix(cfg, p, q, k)
    # by the equations, a position and a channel at a time
    z = np.concatenate([np.asarray(q[0]), np.asarray(k[0])], axis=1)          # [S, 6, 16]
    a, a0, b, b0 = (np.asarray(p[n]) for n in ("conv0_w", "conv0_b", "conv1_w", "conv1_b"))
    at = lambda x, t: x[t] if t >= 0 else np.zeros_like(x[0])  # noqa: E731
    z1 = np.stack([a[0] * at(z, t - 1) + a[1] * at(z, t) + a0 for t in range(9)])
    z2 = np.stack([
        np.einsum("hc,hcd->hd", at(z1, t - 1), b[0]) + np.einsum("hc,hcd->hd", at(z1, t), b[1]) + b0 for t in range(9)
    ])
    mq = (np.asarray(q[0]) + np.repeat(np.asarray(k[0]), 2, axis=1)) / 2
    mk = mq.reshape(9, 2, 2, 16).mean(axis=2)
    np.testing.assert_allclose(got_q[0], z2[:, :4] + mq, atol=1e-5)
    np.testing.assert_allclose(got_k[0], z2[:, 4:] + mk, atol=1e-5)


def test_keys_and_queries_enter_attention_at_a_learned_length_and_half_a_head_turns():
    x = jax.random.normal(jax.random.key(5), (2, 7, 4, 16)) * 3.0
    unit = T._l2_heads(x, 4.0)
    np.testing.assert_allclose(jnp.linalg.norm(unit, axis=-1), 4.0, rtol=1e-5)
    per_head = T._l2_heads(x, jnp.array([1.0, 2.0, 3.0, 4.0]))
    np.testing.assert_allclose(jnp.linalg.norm(per_head, axis=-1), jnp.broadcast_to(jnp.array([1.0, 2.0, 3.0, 4.0]), (2, 7, 4)), rtol=1e-5)
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        _tiny(partial_rotary_factor=0.3)                  # 4.8 values of 16: no even count
    with pytest.raises(ValueError, match="partial_rotary_factor < 1 runs in cca layers and in GQA's attention layers"):
        _tiny(layer_types=("power_retention",) * 3, moe_router="softmax", router_hidden_size=None, moe_top_k=2)


@pytest.mark.parametrize("kw,says", [
    (dict(n_kv_heads=1, n_heads=4), "even count of KV heads"),
    (dict(cca_time1=0), "cca_time0, cca_time1 >= 1"),
    (dict(parallel_block=True), "sequential block"),
    (dict(router_hidden_size=None), "router_hidden_size"),
    (dict(moe_router="softmax"), "router_hidden_size"),
    (dict(residual_scaling=True, expert_axis_name="expert", moe_router="softmax", router_hidden_size=None, moe_top_k=2, moe_experts_held=None), "residual_scaling"),
])
def test_the_config_refuses_what_a_cca_layer_or_its_router_cannot_run(kw, says):
    with pytest.raises(ValueError, match=says):
        _tiny(**kw)


# ---------------------------------------------------------------------------
# the router and its state
# ---------------------------------------------------------------------------


def _router_leaves(seed=0, d=16, hidden=6, experts=8):
    shapes = moe._mlp_router_shapes(d, hidden, experts)
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    p = {name: init(k, shape, jnp.float32) for k, (name, (shape, _, init)) in zip(keys, shapes.items())}
    return _stirred(p, seed + 1)


def test_the_mlp_router_is_the_written_formula_and_hands_on_its_state_after_the_mix():
    p = _router_leaves()
    x = jax.random.normal(jax.random.key(3), (10, 16))
    before = jax.random.normal(jax.random.key(4), (10, 6))
    probs, state = moe.route_mlp(p, x, before, 1e-5)
    r = np.asarray(x) @ np.asarray(p["router_down"]) + np.asarray(p["router_down_bias"]) + np.asarray(p["router_mix"]) * np.asarray(before)
    np.testing.assert_allclose(state, r, atol=1e-5)     # after ITS mix, before the norm
    h = r / np.sqrt((r * r).mean(-1, keepdims=True) + 1e-5) * np.asarray(p["router_norm"])
    for w, b in (("router_w1", "router_b1"), ("router_w2", "router_b2")):
        h = np.asarray(jax.nn.gelu(h @ np.asarray(p[w]) + np.asarray(p[b]), approximate=False))
    np.testing.assert_allclose(probs, jax.nn.softmax(h @ np.asarray(p["router_w3"]), axis=-1), atol=1e-5)
    # the first layer has no state to take: the mix's leaf is there and unused
    alone, _ = moe.route_mlp(p, x, None, 1e-5)
    zeros, _ = moe.route_mlp(p, x, jnp.zeros_like(before), 1e-5)
    np.testing.assert_allclose(alone, zeros, atol=1e-7)


def test_top_1_keeps_the_picks_probability_and_the_bias_picks_without_weighing():
    layer = routed_layer(held=(2, 4), k=1, router_kind="mlp", router_hidden=6)
    x = jax.random.normal(jax.random.key(0), (2, 24, 16))
    params = layer.init(jax.random.key(1), x)["params"]
    apply = jax.jit(lambda p: layer.apply({"params": p}, x, mutable=["intermediates"]))
    (y, aux, state), sown = apply(params)
    probs, want_state = moe.route_mlp(params, x.reshape(48, 16), None, layer.norm_eps)
    picks = sown["intermediates"]["picks"][0][:, 0]
    np.testing.assert_array_equal(picks, jnp.argmax(probs + params["router_bias"], axis=-1))
    np.testing.assert_allclose(state.reshape(48, 6), want_state, atol=1e-6)
    weight = jnp.take_along_axis(probs, picks[:, None], axis=1)[:, 0]
    assert float(weight.max()) < 0.9                      # the probability as it is, not renormalised to 1
    np.testing.assert_allclose(sown["intermediates"]["pick_weight"][0], weight.mean(), rtol=1e-6)
    # a large bias on one expert picks it everywhere and leaves every weight what the softmax says
    tilted = dict(params, router_bias=params["router_bias"].at[3].set(5.0))
    (y3, _, _), sown3 = apply(tilted)
    assert (sown3["intermediates"]["picks"][0] == 3).all()
    np.testing.assert_allclose(sown3["intermediates"]["pick_weight"][0], probs[:, 3].mean(), rtol=1e-6)
    # the experts' output reaches the router through that weight: a gradient, with no auxiliary term in the loss
    grads = jax.jit(jax.grad(lambda p: jnp.sum(layer.apply({"params": p}, x)[0] ** 2)))(params)
    assert float(jnp.abs(grads["router_w3"]).max()) > 1e-6 and float(jnp.abs(grads["router_down"]).max()) > 1e-6
    assert float(jnp.abs(grads["router_bias"]).max()) == 0.0


@pytest.mark.parametrize("kind", ["softmax", "sigmoid", "sigmoid_grouped"])
def test_a_router_that_renormalises_refuses_top_1_by_name(kind):
    """At ``top_k`` 1 the renormalised weight is a constant: the router would
    get no gradient from the experts, in silence."""
    layer = routed_layer(k=1, router_kind=kind, n_group=2, topk_group=1)
    with pytest.raises(ValueError, match=f"router_kind='{kind}' with top_k=1 renormalises"):
        layer.init(jax.random.key(0), jnp.zeros((1, 8, 16)))
    with pytest.raises(ValueError, match="router_kind='mlp' is top-1"):
        routed_layer(k=2, router_kind="mlp", router_hidden=6).init(jax.random.key(0), jnp.zeros((1, 8, 16)))


# ---------------------------------------------------------------------------
# the block and the model: a second value from layer to layer
# ---------------------------------------------------------------------------


def test_a_block_hands_the_next_its_routers_state_and_remat_carries_it(model):
    lm, params = model
    cfg = lm.cfg
    block = T.Block(cfg, use_moe=True, layer_type=CCA)
    x = jax.random.normal(jax.random.key(6), (2, 24, 32))
    p1 = {"params": params["params"]["block_1"]}
    apply = jax.jit(block.apply)
    out, aux, state = apply(p1, x, None)
    assert state.shape == (2, 24, 12) and state.dtype == jnp.float32
    handed = jax.random.normal(jax.random.key(7), (2, 24, 12))
    out2, _, state2 = apply(p1, x, handed)
    mix = params["params"]["block_1"]["moe"]["router_mix"]
    np.testing.assert_allclose(state2 - state, mix * handed, atol=1e-5)
    assert float(jnp.abs(out2 - out).max()) > 1e-5       # another state, other picks or weights
    # under another router a block hands on what it was handed: nothing
    plain = T.Block(_tiny(layer_types=(FULL,) * 3, partial_rotary_factor=1.0, moe_router="softmax", router_hidden_size=None, moe_top_k=2), use_moe=True)
    assert jax.jit(plain.apply)(plain.init(jax.random.key(0), x), x)[2] is None

    def loss(lm):
        def of(p):
            logits, aux = lm.apply(p, TOKENS, return_aux=True)
            return jnp.mean(logits ** 2) + aux
        return jax.jit(jax.value_and_grad(of))

    value, grads = loss(lm)(params)
    again, regrads = loss(TransformerLM(dataclasses.replace(cfg, remat=True)))(params)
    assert float(value) == pytest.approx(float(again), rel=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4), grads, regrads)
    # the state's mix learns in every layer but the first, which is handed none
    mixes = [float(jnp.abs(grads["params"][f"block_{i}"]["moe"]["router_mix"]).max()) for i in range(2)]
    assert mixes[0] == 0.0 and mixes[1] > 1e-8


def test_residual_scaling_starts_as_the_plain_merge_and_is_the_written_form():
    merge = T.ResidualScale(partition=False)
    x, f = (jax.random.normal(jax.random.key(i), (2, 5, 8)) for i in (0, 1))
    fresh = merge.init(jax.random.key(2), x, f)
    np.testing.assert_allclose(merge.apply(fresh, x, f), x + f, atol=1e-7)
    p = _stirred(fresh)["params"]
    want = (x + p["res_bias"]) * p["res_scale"] + (f + p["out_bias"]) * p["out_scale"]
    np.testing.assert_allclose(merge.apply({"params": p}, x, f), want, atol=1e-6)


def test_the_tree_holds_what_the_layer_counts_and_the_ledger_counts_its_products():
    cfg = _tiny()
    shapes = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.key(0), TOKENS))
    d, r, hd, heads, e, f = 32, 12, 16, 6, 8, 24
    projections = d * hd * (2 * 4 + 2 * 2)
    convolutions = heads * hd * (2 + 2 * hd)
    router = d * r + 2 * r * r + r * e
    vectors = 2 * heads * hd + 5 * r + e + 10 * d + 2
    layer = projections + convolutions + router + vectors + 4 * 3 * d * f
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 3 * layer + 96 * d + d

    from determined_tpu.models.transformer import LMTrial

    hparams = dict(
        vocab_size=96, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=16, seq_len=24, layer_types=[CCA] * 3,
        partial_rotary_factor=0.5, moe_experts=8, moe_every=1, moe_top_k=1, moe_intermediate_size=24,
        moe_experts_held=[2, 4], moe_router="mlp", router_hidden_size=12, residual_scaling=True, tie_embeddings=True,
    )

    class Context:
        mesh = exp_config = None

        def get_hparam(self, name, default=None):
            return hparams.get(name, default)

    trial = LMTrial.__new__(LMTrial)
    trial.context = Context()
    assert trial._cfg() == dataclasses.replace(cfg, dtype=jnp.bfloat16, moe_experts_held=(2, 4), partition_params=True, norm_eps=1e-6, rope_theta=10000.0, attention_impl="auto")
    active = 96 * d + 3 * (projections + convolutions + router + 0.5 * 3 * d * f)
    assert trial.flops_per_token == 6 * active + 12 * 3 * 24 * 4 * hd


# ---------------------------------------------------------------------------
# what does not run such a layer says so by name
# ---------------------------------------------------------------------------


def test_pipeline_stages_and_serving_refuse_a_cca_layer_by_name(tmp_path):
    from determined_tpu import core, train
    from determined_tpu.models.serving import _check_decodable
    from determined_tpu.models.transformer import LMTrial
    from determined_tpu.parallel.mesh import MeshConfig

    hparams = dict(
        lr=1e-3, global_batch_size=8, seq_len=24, vocab_size=96, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16,
        dataset_size=32, bf16=False, attention="reference", fused_ce=False, fused_adamw=False,
    )
    ours = dict(layer_types=[CCA] * 4, partial_rotary_factor=0.5, residual_scaling=True, moe_experts=4, moe_every=1, moe_top_k=1,
                moe_intermediate_size=16, moe_router="mlp", router_hidden_size=12)
    for i, (part, names) in enumerate([
        (ours, "a cca layer, moe_router mlp, residual_scaling"),
        (dict(residual_scaling=True), "residual_scaling"),
    ]):
        ctx = train.init(
            hparams={**hparams, **part}, mesh_config=MeshConfig(pipe=2, data=4),
            core_context=core._dummy_init(checkpoint_dir=str(tmp_path / f"ckpt{i}")), seed=7,
        )
        with pytest.raises(ValueError, match=f"pipe=2: {names} not run inside pipeline stages"):
            LMTrial(ctx)._cfg()
    with pytest.raises(ValueError, match="KV-cache serving does not run a cca layer .* a kind models/cache_kinds.py does not have.*moe_router mlp.*residual_scaling"):
        _check_decodable(_tiny())
    with pytest.raises(ValueError, match="KV-cache serving does not run residual_scaling"):
        _check_decodable(TransformerConfig(vocab_size=96, d_model=32, n_layers=2, n_heads=4, residual_scaling=True))
    # the engine asks the same function before it builds anything (serve/engine.py DecodeKernels)
    from determined_tpu.models.serving import transformer_decode

    with pytest.raises(ValueError, match="such a model is trained, not served yet"):
        transformer_decode(_tiny(), {}, jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32), jnp.zeros((1, 4), jnp.int32), {})
