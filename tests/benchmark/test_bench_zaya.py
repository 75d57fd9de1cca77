"""The ZAYA1-8B configuration, its adapter, reference and metrics: the
arithmetic the cell's numbers rest on, the program against
``reference/zaya_cca_moe.py`` at a tiny size (forward, loss, every leaf's
gradient, the picks and their weights), the controls that the comparison must
fail, the two shares of a layer's experts against the uncut layer, and the
cell run end to end in a throw-away root on the CPU (what the step check's
three-state verdict does with a reference that is not the program's:
test_bench_mellum.py, test_bench_step_check.py)."""

import dataclasses
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib as B
from benchlib import costs, harness, model, readers, spec as S
from benchlib.observe import Observations

CELL = "train-zaya1-8b-l5-ep2-seq8k"
CONFIG = "zaya1-8b-l5-ep2"
LAYER, EXPERT, TABLE = 6_256_914, 12_582_912, 67_141_632
PARAMS, ACTIVE = 601_744_730, 129_761_792
NEW = {"train_cca_mix_device_share", "train_rescale_device_share", "moe_top1_held_share", "moe_pick_weight"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

TINY = B.tiny_form("zaya_cca_moe")["config"]
TOKENS = jax.random.randint(jax.random.key(5), (41,), 1, 96)


@pytest.fixture(scope="module")
def cell():
    return S.Spec().cell(CELL)


def _tiny_cell(config=TINY):
    import types

    return types.SimpleNamespace(config=config, data_dir=B.BENCH, config_file="tiny/zaya_cca_moe.json")


# ---------------------------------------------------------------------------
# the configuration as published, and the arithmetic of its cut
# ---------------------------------------------------------------------------


def the_configuration_keeps_every_published_width_and_states_its_cut(spec):
    cell = spec.cell(CELL)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "ZAYA1-8B")
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == published["source_url"] == cell.config["source"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"] == list(cell.config["reduced"])
    for key, value in published["config"].items():
        if key in entry["reduced"]:
            continue
        assert cell.config[key] == (value[:5] if key == "layer_types" else value), key  # cut with num_hidden_layers
    assert (cell.config["num_hidden_layers"], cell.config["num_experts"], cell.config["vocab_size"]) == (5, 8, 32784)
    assert (cell.config["num_experts_published"], cell.config["first_expert_held"]) == (16, 0)
    assert 8 * cell.config["vocab_size"] >= published["vocab_size"] and cell.config["vocab_size"] % 128  # an eighth; no multiple of 128
    assert {"deployment", "assumed", "deviations", "dtypes", "tolerance", "train_batch"} <= set(cell.config)
    assert "skip_expert" in cell.config["assumed"] and "serve_params" not in cell.config["dtypes"]
    assert cell.chips == 1 and cell.traffic_name == "train-seq8k" and cell.traffic["seq_len"] == 8192
    mine = {m["name"] for m in cell.per_layer}
    assert NEW | {"train_mfu_routed", "flash_attn_roofline", "moe_grouped_matmul_roofline", "adamw_hbm_roofline"} <= mine
    assert not mine & {"train_mfu", "mixed_attn_roofline", "moe_held_picks_per_token", "train_mlp_device_share"}


#: what this file asserts of the DOCUMENT: each takes a ``Spec`` (test_bench_rules.py holds a document with one more cell to them)
DOCUMENT_CHECKS = [the_configuration_keeps_every_published_width_and_states_its_cut]


def test_the_configuration_keeps_every_published_width_and_states_its_cut():
    the_configuration_keeps_every_published_width_and_states_its_cut(S.Spec())


def test_parameter_counts_match_the_issues_arithmetic(cell):
    arch, config = model.adapter(cell), cell.config
    projections, convolutions, router, vectors = 5_242_880, 3_840 + 328_960, 660_752, 20_482
    assert projections + convolutions + router + vectors == LAYER and 3 * 2048 * 2048 == EXPERT
    assert 40 * (LAYER + 16 * EXPERT) == pytest.approx(8.30e9, rel=2e-3)      # the family's "8.3B", without the table
    assert 40 * (LAYER + EXPERT) == pytest.approx(0.754e9, rel=2e-3)          # its "A0.76B"
    assert arch.total_params(config) == 5 * (LAYER + 8 * EXPERT) + TABLE + 2048 == PARAMS
    assert PARAMS * 16 == pytest.approx(9.63e9, rel=1e-3)                       # 16 bytes a parameter of training state
    # a token: CCA's projections and convolutions, the router's four matrices, half an expert, the tied head
    products = 5_242_880 + 1280 * (2 + 2 * 128) + 2048 * 256 + 2 * 256 * 256 + 256 * 16
    assert arch.matmul_params(config) == 5 * (products + EXPERT // 2) + TABLE == ACTIVE
    assert TABLE / (5 * (LAYER + EXPERT) + TABLE) == pytest.approx(0.42, abs=0.005)  # the head's share, as in the deployment (43 %)
    assert arch.embedding_params(config) == TABLE and arch.layer_windows(config) == [None] * 5
    assert arch.expert_shape(config) == {"d_model": 2048, "d_ff": 2048, "held": 8, "layers": 5, "expected_held_picks": 0.5}
    assert arch.attention_shape(config) == {"heads": 8, "kv_heads": 2, "head_dim": 128, "layers": 5}
    cfg = arch.model_config(config, 64)
    assert (cfg.layer_types, cfg.moe_router, cfg.moe_top_k, cfg.moe_experts, cfg.moe_experts_held) == (("cca",) * 5, "mlp", 1, 16, (0, 8))
    assert (cfg.partial_rotary_factor, cfg.rope_theta, cfg.norm_eps, cfg.tie_embeddings, cfg.residual_scaling) == (0.5, 5e6, 1e-5, True, True)
    # the program's own tree holds as many (shapes only: nothing this size is built on a CPU)
    from determined_tpu.models.transformer import LMTrial, TransformerLM

    shapes = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == PARAMS
    layer = shapes["params"]["block_3"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(layer)) == LAYER + 8 * EXPERT and "lm_head" not in shapes["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(layer["moe"])) - 8 * EXPERT == router

    class Ctx:
        mesh = exp_config = None

        def get_hparam(self, name, default=None):
            return {**arch.trial_hparams(config), "seq_len": 8192}.get(name, default)

    trial = LMTrial.__new__(LMTrial)
    trial.context = Ctx()
    assert trial.flops_per_token == 6 * ACTIVE + 12 * 5 * 8192 * 8 * 128     # the ledger counts what the benchmark counts


def test_cost_functions_count_this_cells_work(cell):
    arch, config, traffic = model.adapter(cell), cell.config, cell.traffic
    batch = config["train_batch"]["global_batch_sequences"]
    pairs = 8192 * 8193 / 2
    routed = costs.find("train_flops_routed", cell.data_dir)
    attention = 3 * 2 * 2 * 8 * 128 * 5 * pairs / 8192
    assert routed(config, traffic, 1, {}, arch)["flops"] == pytest.approx(6 * ACTIVE + attention)
    assert routed(config, traffic, 1, {}, arch)["flops"] == pytest.approx(1.03e9, rel=0.005)       # ISSUE 51's ~1.03 GFLOP a token
    assert 2 * 2 * 8 * 128 * pairs / 8192 == pytest.approx(16.8e6, rel=2e-3)                    # attention's forward operations a token a layer
    # every token's pick on a held expert: half an expert a layer more
    tokens = batch * 8192
    more = routed(config, traffic, 1, {"moe.held_picks": 5.0 * tokens}, arch)["flops"]
    assert more - routed(config, traffic, 1, {}, arch)["flops"] == pytest.approx(6 * 5 * 0.5 * EXPERT)
    grouped = costs.find("moe_grouped_matmul", cell.data_dir)
    assert grouped(config, traffic, 1, {}, arch)["flops"] == pytest.approx(9 * 2 * 2048 * 2048 * 5 * tokens * 0.5)
    flash = costs.find("flash_attention", cell.data_dir)
    assert flash(config, traffic, 1, {}, arch)["flops"] == pytest.approx(5 * 7 * 2 * batch * 8 * 128 * 8192 * 8192 / 2)
    assert costs.find("adamw_sweep", cell.data_dir)(config, traffic, 1, {}, arch)["bytes"] == 28.0 * PARAMS


def _counter(name, at, value):
    return {"ph": "C", "name": name, "ts": at * 1e6, "args": {"value": value}}


def test_the_new_counter_metrics_read_the_trainers_events(cell):
    events = [
        _counter("train.steps", 9.99, 8.0), _counter("moe.held_picks", 9.99, 8 * 9e4), _counter("moe.picks", 9.99, 8 * 122880.0),
        _counter("moe.pick_weight", 9.99, 8 * 0.9),
        _counter("train.steps", 15.0, 8.0), _counter("moe.held_picks", 15.0, 8 * 61440.0), _counter("moe.picks", 15.0, 8 * 122880.0),
        _counter("moe.pick_weight", 15.0, 8 * 0.10),
        _counter("train.steps", 19.9, 4.0), _counter("moe.held_picks", 19.9, 4 * 67584.0), _counter("moe.picks", 19.9, 4 * 122880.0),
        _counter("moe.pick_weight", 19.9, 4 * 0.16),
    ]
    obs = Observations(
        window=(10.0, 20.0), spans=[], counters={"train.tokens_per_s": 70000.0}, program_events=events, profiler=None,
        config=cell.config, traffic=cell.traffic, chips=1, program_epoch=0.0, arch=model.adapter(cell), data_dir=cell.data_dir,
    )
    metric = lambda name: next(m for m in cell.per_layer if m["name"] == name)  # noqa: E731
    # the boundary that opens the window reports steps that ran before it: not counted
    assert readers.read(metric("moe_top1_held_share"), obs, PEAK) == pytest.approx((8 * 0.5 + 4 * 0.55) / 12)
    assert readers.read(metric("moe_pick_weight"), obs, PEAK) == pytest.approx((8 * 0.10 + 4 * 0.16) / 12)
    per_token = costs.find("train_flops_routed", cell.data_dir)(
        cell.config, cell.traffic, 1, {"moe.held_picks": (8 * 61440.0 + 4 * 67584.0) / 12}, obs.arch)["flops"]
    assert readers.read(metric("train_mfu_routed"), obs, PEAK) == pytest.approx(100 * per_token * 70000.0 / 197e12)
    # a program without the counters (the parent commit): nothing, and nothing raised; no trace: no device share
    bare = dataclasses.replace(obs, program_events=[])
    for name in NEW:
        assert readers.read(metric(name), bare, PEAK) is None
    for name in ("train_cca_mix_device_share", "train_rescale_device_share", "flash_attn_roofline"):
        assert readers.read(metric(name), obs, PEAK) is None


def test_the_adapter_refuses_what_the_program_does_not_run(cell):
    arch = model.adapter(cell)
    arch.check_as_run(cell.config)
    for change, says in [
        ({"zaya_use_mod": True}, "no skip expert"),
        ({"sliding_window": 4096}, "sliding_window"),
        ({"layer_types": ["hybrid"] * 4 + ["hybrid_sliding"]}, "every layer is 'hybrid'"),
        ({"num_experts_per_tok": 2}, "num_experts_per_tok"),
        ({"tie_word_embeddings": False}, "tie_word_embeddings"),
        ({"dtypes": dict(cell.config["dtypes"], serve_params="bfloat16")}, "not served"),
        ({"dtypes": dict(cell.config["dtypes"], params="bfloat16")}, "float32 parameters"),
        ({"first_expert_held": 12}, "held experts lie inside"),
    ]:
        with pytest.raises(ValueError, match=says):
            arch.check_as_run({**cell.config, **change})


# ---------------------------------------------------------------------------
# the program against the reference, the controls, and the shares
# ---------------------------------------------------------------------------


@jax.jit
def _stirred(params):
    """Leaves that start at zero or one (scales, biases, the temperature, the
    state's mix, the norms) moved off their start, so that each matters; the
    selection bias at its own scale."""
    def move(path, x, key):
        if x.ndim != 1:
            return x
        return x + (0.01 if "router_bias" in jax.tree_util.keystr(path) else 0.1) * jax.random.normal(key, x.shape)

    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.key(9), len(leaves))
    return jax.tree.unflatten(treedef, [move(path, x, k) for (path, x), k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def params():
    arch = model.adapter(_tiny_cell())
    return _stirred(arch.init_params(arch.model_config(TINY, 40), seed=3))


def _compare(arch, config, params, attention="reference", gradients=True):
    """The program and the reference on one seeded sequence: (loss, logits,
    what the layers sow, gradients) of the one, (loss, logits, gradients) of
    the other; without ``gradients`` the forward passes alone (None for both)."""
    from determined_tpu.models.transformer import TransformerLM

    lm = TransformerLM(dataclasses.replace(arch.model_config(config, 40), attention_impl=attention))
    aux_weight = float(config["assumed"]["moe_aux_weight"]["value"])

    def program(p):
        (logits, aux), state = lm.apply({"params": p}, TOKENS[None, :-1], return_aux=True, mutable=["intermediates"])
        logp = jax.nn.log_softmax(logits[0], axis=-1)
        loss = -jnp.mean(jnp.take_along_axis(logp, TOKENS[1:, None], axis=-1)) + aux_weight * aux
        return loss, (logits[0], state["intermediates"])

    def reference(p):
        return arch.reference_loss_and_logits(arch.reference_weights(p, config), TOKENS, config)

    if not gradients:
        (loss, (logits, sown)), (want_loss, want_logits) = jax.jit(program)(params), jax.jit(reference)(params)
        return (loss, logits, sown, None), (want_loss, want_logits, None)
    (loss, (logits, sown)), grads = jax.jit(jax.value_and_grad(program, has_aux=True))(params)
    (want_loss, want_logits), want_grads = jax.jit(jax.value_and_grad(reference, has_aux=True))(params)
    return (loss, logits, sown, grads), (want_loss, want_logits, want_grads)


def _off(got, want):
    """How far the program is from the reference, as the step check reads it:
    loss, logits' rel. rms and, where both sides took them, the worst leaf's gradient."""
    rel = lambda a, b: float(jnp.sqrt(jnp.sum((a - b) ** 2)) / jnp.maximum(jnp.sqrt(jnp.sum(b * b)), 1e-30))  # noqa: E731
    off = {"loss_rel": abs(float(got[0]) - float(want[0])) / abs(float(want[0])), "logits_rel_rms": rel(got[1], want[1])}
    if want[2] is not None:
        off["grad_rel"] = max(jax.tree.leaves(jax.tree.map(rel, got[3], want[2])))
    return off


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_the_program_matches_the_reference_forward_loss_gradients_picks_and_weights(params, attention):
    arch = model.adapter(_tiny_cell())
    got, want = _compare(arch, TINY, params, attention)
    loss, logits, sown, grads = got
    assert float(loss) == pytest.approx(float(want[0]), rel=1e-5)
    np.testing.assert_allclose(logits, want[1], atol=2e-4)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-3), grads, want[2])
    limits = TINY["tolerance"]["train_step"]
    assert all(v < limits[q] for q, v in _off(got, want).items())
    # no gradient reaches the selection bias, nor the first layer's mix (it is handed no state); every other leaf learns
    flat = {jax.tree_util.keystr(k): float(jnp.abs(v).max()) for k, v in jax.tree_util.tree_leaves_with_path(grads)}
    still = {k for k, v in flat.items() if v == 0.0}
    assert still == {f"['block_{i}']['moe']['router_bias']" for i in range(2)} | {"['block_0']['moe']['router_mix']"}
    # the picks the layers sow and the weight they give them are the reference's, token for token, layer for layer
    _, _, want_picks, want_weights = jax.jit(
        lambda p: arch.reference.forward(arch.reference_weights(p, TINY), TOKENS[:-1], **arch.numerics(TINY)))(params)
    got_picks = jnp.stack([sown[f"block_{i}"]["moe"]["picks"][0][:, 0] for i in range(2)])
    np.testing.assert_array_equal(got_picks, want_picks)
    assert 0.2 < float(jnp.mean((want_picks >= 2) & (want_picks < 6))) < 0.8   # held and absent experts both picked
    got_weight = jnp.stack([sown[f"block_{i}"]["moe"]["pick_weight"][0] for i in range(2)])
    np.testing.assert_allclose(got_weight, want_weights.mean(axis=1), rtol=1e-5)
    assert float(want_weights.max()) < 0.9                                      # a probability, not renormalised to 1


def _program(name, patch):
    """A control that breaks the PROGRAM: ``patch(monkeypatch)``."""
    return pytest.param("program", patch, id=name)


def _told(name, told):
    """A control whose REFERENCE is told something else: ``told(arch)``."""
    return pytest.param("reference", told, id=name)


def _no_qk_mean(monkeypatch):
    from determined_tpu.models import transformer as T

    mix = T._cca_mix

    def without(cfg, p, q, k):
        mean_q = (q + jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)) * 0.5
        mean_k = jnp.mean(mean_q.reshape(*k.shape[:3], -1, k.shape[-1]), axis=3)
        mixed_q, mixed_k = mix(cfg, p, q, k)
        return mixed_q - mean_q, mixed_k - mean_k

    monkeypatch.setattr(T, "_cca_mix", without)


def _taps(values=None, latent=None):
    """``_causal_taps`` with the normed input's taps (3-D: the value shift) or
    the latent's (4-D: the convolutions) changed by the function given."""
    def patch(monkeypatch):
        from determined_tpu.models import transformer as T

        taps = T._causal_taps

        def changed(x, n):
            change = values if x.ndim == 3 else latent
            return change(taps(x, n)) if change else taps(x, n)

        monkeypatch.setattr(T, "_causal_taps", changed)

    return patch


def _mix_dropped(monkeypatch):
    from determined_tpu.models import moe

    route = moe.route_mlp
    monkeypatch.setattr(moe, "route_mlp", lambda p, xf, before, eps: route(p, xf, None, eps))


def _reference_weights(change):
    """The reference handed other weights than the program's: ``change(layer)`` a layer."""
    def told(arch):
        weights = arch.reference_weights
        arch.reference_weights = lambda params, config: (lambda w: {**w, "layers": [change(dict(layer)) for layer in w["layers"]]})(weights(params, config))

    return told


def _renormalised(arch):
    route = arch.reference.route
    arch.reference.route = lambda *a: (lambda probs, pick, weight, r: (probs, pick, jnp.ones_like(weight), r))(*route(*a))


def _no_scaling(layer):
    plain = {"res_bias": 0.0, "res_scale": 1.0, "out_bias": 0.0, "out_scale": 1.0}
    return {**layer, **{k: {n: jnp.full_like(v, plain[n]) for n, v in layer[k].items()} for k in ("attn_scaling", "mlp_scaling")}}


CONTROLS = [
    _program("no-qk-mean", _no_qk_mean),
    _program("no-value-shift", _taps(values=lambda taps: (taps[1], taps[1]))),          # token t reads its own normed input
    _program("taps-reversed", _taps(latent=lambda taps: taps[::-1])),
    _program("state-mix-dropped", _mix_dropped),
    _told("beta-ignored", _reference_weights(lambda layer: {**layer, "router": {**layer["router"], "beta": jnp.zeros_like(layer["router"]["beta"])}})),
    _told("top-1-renormalised", _renormalised),
    _told("scales-left-out", _reference_weights(_no_scaling)),
]


@pytest.mark.parametrize("side,control", CONTROLS)
def test_every_control_fails_the_comparison(monkeypatch, params, side, control):
    """Each leaves ONE thing out of the program, or tells the reference one
    thing else than the configuration states: the forward comparison of the
    test above, under the step check's own limits for float32 on both sides,
    has to say so, by three times a limit or more."""
    arch = model.adapter(_tiny_cell())
    control(monkeypatch if side == "program" else arch)
    off = _off(*_compare(arch, TINY, params, gradients=False))
    limits = TINY["tolerance"]["train_step"]
    assert any(off[q] > 3 * limits[q] for q in off), off


def test_two_shares_of_a_layers_experts_add_up_to_the_uncut_layer():
    """Experts 0-3 on one chip and 4-7 on the other, as the deployment shares
    a layer: each routes over all eight and adds what ITS experts give; what
    both compute alike (the probabilities, the picks, the state handed on, the
    auxiliary term) is counted once; the sum is the uncut reference layer."""
    from determined_tpu.models.moe import RoutedExperts

    arch = model.adapter(_tiny_cell())
    whole = RoutedExperts(num_experts=8, top_k=1, d_ff=24, held=None, dtype=jnp.float32, partition=False, router_kind="mlp", router_hidden=12, norm_eps=1e-5)
    x = jax.random.normal(jax.random.key(0), (1, 40, 32))
    before = jax.random.normal(jax.random.key(1), (1, 40, 12))
    params = _stirred(whole.init(jax.random.key(2), x, before)["params"])
    names = {theirs: ours for ours, theirs in arch._ROUTER.items()}
    as_reference = {"router": {names[k]: v for k, v in params.items() if k in names}, **{k: params[k] for k in ("w_gate", "w_up", "w_down")}}
    with jax.default_matmul_precision("highest"):
        want_y, want_aux, want_picks, want_weight, want_state = jax.jit(
            lambda w: arch.reference._experts(x[0], w, before[0], 0, 1e-5))(as_reference)
    assert len(np.unique(want_picks)) >= 4
    total = jnp.zeros_like(x)
    for first in (0, 4):
        share = dataclasses.replace(whole, held=(first, 4))
        mine = {**params, **{k: params[k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}}
        (y, aux, state), sown = jax.jit(lambda p: share.apply({"params": p}, x, before, mutable=["intermediates"]))(mine)
        total = total + y
        np.testing.assert_array_equal(sown["intermediates"]["picks"][0][:, 0], want_picks)
        np.testing.assert_allclose(aux, want_aux, rtol=1e-5)
        np.testing.assert_allclose(state[0], want_state, atol=1e-5)
        held = (want_picks >= first) & (want_picks < first + 4)
        assert int(jnp.sum(sown["intermediates"]["load"][0])) == int(held.sum()) and 0 < int(held.sum()) < 40
        np.testing.assert_allclose(y[0][~held], 0.0, atol=1e-7)                 # a pick held elsewhere gives nothing here
    np.testing.assert_allclose(total[0], want_y, atol=2e-5)
    assert float(jnp.abs(want_y).max()) > 1e-3


# ---------------------------------------------------------------------------
# the cell end to end, by files alone
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tests' throw-away root plus this cell at a tiny size."""
    tmp = B.throwaway_root(str(tmp_path_factory.mktemp("zaya_root")))
    shutil.copytree(os.path.join(B.BENCH, "costs"), os.path.join(tmp, "benchmark", "costs"), dirs_exist_ok=True)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        doc = json.load(f)
    configs = {"tiny-zaya": TINY}
    for name, config in configs.items():
        with open(os.path.join(tmp, "benchmark", "configs", f"{name}.json"), "w") as f:
            json.dump(config, f)
        doc["configs"].append({"name": name, "source": "none", "file": f"benchmark/configs/{name}.json", "reduced": [], "why": "test"})
    with open(os.path.join(tmp, "benchmark", "traffic", "tiny-train-flash.json"), "w") as f:
        json.dump(dict(B.TINY_TRAFFIC["tiny-train"], attention="flash", fused_ce=True, fused_adamw=True), f)
    cells = {"tiny-zaya.flash": ("tiny-zaya", "tiny-train-flash")}
    for name, (config, traffic) in cells.items():
        doc["workloads"].append({"name": name, "config": config, "traffic": traffic, "chips": 1, "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] += list(cells)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return tmp


def test_the_cell_runs_end_to_end_by_files_alone_and_reports_the_programs_counters(root, capsys):
    """Through ``LMTrial`` and ``Trainer.fit`` with the flash kernel, fused CE
    and fused AdamW (interpret mode), traced: ``correct`` by the three-state
    step check, and the counters the new metrics read."""
    line = harness.run_cell("tiny-zaya.flash", seed=2**31 + 11, seconds=1.0, traced=True, root=root, require_tpu=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    check = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if '"train.check"' in x)
    assert check["update_rel"] < 1e-3 and check["logits_rel_rms"] < 1e-4 and set(check["leaves"]["grad_rel"]) >= {
        "second.conv1", "second.tau", "second.wv2", "second.router.mix", "second.router.w3", "last.experts.w_down", "embed"}
    # 4 of 8 experts held: about half of the picks land here; the pick's weight is a probability over 8
    assert 0.2 < line["metrics"]["moe_top1_held_share"]["value"] < 0.8
    assert 1 / 8 < line["metrics"]["moe_pick_weight"]["value"] < 0.9
    assert 1.0 <= line["metrics"]["moe_expert_load_imbalance"]["value"] < 4.0 and line["metrics"]["train_mfu_routed"]["value"] > 0
    # device metrics have nothing to read on a CPU and are left out
    assert not any("roofline" in k or "device" in k for k in line["metrics"])
