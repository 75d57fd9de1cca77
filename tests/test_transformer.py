"""Flagship transformer tests: sharded training across mesh topologies."""

import jax
import numpy as np
import pytest

from determined_tpu import core, train
from determined_tpu.config import Length
from determined_tpu.models.transformer import LMTrial, TransformerConfig, TransformerLM
from determined_tpu.parallel.mesh import MeshConfig, make_mesh


HPARAMS = {
    "lr": 1e-3,
    "global_batch_size": 8,
    "seq_len": 64,
    "vocab_size": 256,
    "d_model": 64,
    "n_layers": 2,
    "n_heads": 4,
    "dataset_size": 64,
    "bf16": False,
    "warmup_steps": 2,
    "attention": "reference",
}


def make_trainer(tmp_path, mesh_config, **hp_over):
    hp = {**HPARAMS, **hp_over}
    ctx = train.init(
        hparams=hp,
        mesh_config=mesh_config,
        core_context=core._dummy_init(checkpoint_dir=str(tmp_path / "ckpts")),
        seed=11,
    )
    return train.Trainer(LMTrial(ctx))


def test_forward_shapes():
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, max_seq_len=32,
        dtype=jax.numpy.float32, attention_impl="reference",
    )
    model = TransformerLM(cfg)
    tokens = jax.numpy.zeros((2, 32), jax.numpy.int32)
    params = model.init(jax.random.key(0), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 32, 128)
    assert logits.dtype == jax.numpy.float32


@pytest.mark.parametrize(
    "mesh_config",
    [
        MeshConfig(data=8),
        # the sharded-axis compiles cost ~20-25s each on the 2-core verify
        # box: dp8 stays as the tier-1 smoke, the rest run full-suite
        pytest.param(MeshConfig(fsdp=2, tensor=4), marks=pytest.mark.slow),
        pytest.param(
            MeshConfig(data=2, tensor=2, seq=2), marks=pytest.mark.slow
        ),
    ],
    ids=["dp8", "fsdp2-tp4", "dp2-tp2-sp2"],
)
def test_lm_trains_under_parallelism(tmp_path, mesh_config):
    attention = "auto" if mesh_config.seq > 1 else "reference"
    trainer = make_trainer(tmp_path, mesh_config, attention=attention)
    reported = []
    result = None
    try:
        ctx = trainer.context
        orig = ctx.core.train.report_training_metrics
        ctx.core.train.report_training_metrics = lambda s, m: (
            reported.append((s, dict(m))),
            orig(s, m),
        )
        result = trainer.fit(Length.batches(20), report_period=Length.batches(5))
    finally:
        ctx.core.train.report_training_metrics = orig
    assert result["steps_completed"] == 20
    first, last = reported[0][1]["loss"], reported[-1][1]["loss"]
    assert last < first, (first, last)


def test_tp_weights_actually_sharded(tmp_path):
    trainer = make_trainer(tmp_path, MeshConfig(fsdp=2, tensor=4))
    trainer._setup()
    flat = jax.tree_util.tree_flatten_with_path(trainer.state.params)[0]
    mlp_kernels = [
        (str(path), leaf) for path, leaf in flat if "w_gate" in str(path)
    ]
    assert mlp_kernels
    for path, leaf in mlp_kernels:
        spec = leaf.sharding.spec
        assert "tensor" in str(spec), f"{path} not tensor-sharded: {spec}"


def test_gqa_and_remat_variants(tmp_path):
    trainer = make_trainer(
        tmp_path, MeshConfig(data=2), n_kv_heads=2, remat=True
    )
    result = trainer.fit(Length.batches(4), report_period=Length.batches(4))
    assert result["steps_completed"] == 4


@pytest.mark.slow  # ~28s BERT compile; gpt2 keeps HF coverage in tier-1
def test_hf_bert_trial_learns(tmp_path):
    """HF Flax BERT drops into the JaxTrial contract (hf_trainer_api
    analog): trains under dp and learns the marker-token task."""
    pytest.importorskip("transformers")
    from determined_tpu import core, train
    from determined_tpu.config import Length
    from determined_tpu.models.hf_bert import BertClassifyTrial
    from determined_tpu.parallel.mesh import MeshConfig

    ctx = train.init(
        hparams={
            "lr": 1e-3,
            "global_batch_size": 32,
            "seq_len": 32,
            "vocab_size": 256,
            "hidden_size": 64,
            "num_layers": 1,
            "num_heads": 2,
            "num_labels": 4,
            "dataset_size": 256,
            "warmup_steps": 2,
        },
        mesh_config=MeshConfig(data=4),
        core_context=core._dummy_init(checkpoint_dir=str(tmp_path / "ck")),
        seed=0,
    )
    trainer = train.Trainer(BertClassifyTrial(ctx))
    result = trainer.fit(Length.batches(30), validation_period=Length.batches(30))
    vm = result["validation_metrics"]
    assert vm["validation_accuracy"] > 0.6, vm  # 4 classes -> random 0.25
    assert result["latest_checkpoint"]


def test_hf_gpt2_trial_learns(tmp_path):
    """HF Flax GPT-2 causal-LM fine-tune through the same contract
    (BASELINE.json hf_trainer GPT-2 analog): loss falls well below the
    uniform-vocabulary entropy on the Markov-chain task."""
    pytest.importorskip("transformers")
    import math

    from determined_tpu import core, train
    from determined_tpu.config import Length
    from determined_tpu.models.hf_gpt2 import GPT2FinetuneTrial
    from determined_tpu.parallel.mesh import MeshConfig

    vocab = 128
    ctx = train.init(
        hparams={
            "lr": 2e-3,
            "global_batch_size": 32,
            "seq_len": 32,
            "vocab_size": vocab,
            "hidden_size": 64,
            "num_layers": 1,
            "num_heads": 2,
            "dataset_size": 256,
            "warmup_steps": 2,
        },
        mesh_config=MeshConfig(data=4),
        core_context=core._dummy_init(checkpoint_dir=str(tmp_path / "ck")),
        seed=0,
    )
    trainer = train.Trainer(GPT2FinetuneTrial(ctx))
    result = trainer.fit(Length.batches(40), validation_period=Length.batches(40))
    vm = result["validation_metrics"]
    # 85% of tokens follow a deterministic successor: learnable far below
    # the ln(128)=4.85 uniform baseline
    assert vm["validation_loss"] < 0.8 * math.log(vocab), vm
    assert result["latest_checkpoint"]


# ---------------------------------------------------------------------------
# KV-cache decode path: step-for-step parity with the full-sequence forward
# (pins the paged cache layout before anything serves from it)
# ---------------------------------------------------------------------------

import functools  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from determined_tpu.models.serving import init_kv_cache, transformer_decode, transformer_prefill
from determined_tpu.serve.engine import sample_token  # noqa: E402
from tests.model_cases import causal_forward  # noqa: E402

# bf16 keeps ~8 mantissa bits; logits here are O(1), so 1/32 absolute slack
# covers the re-associated attention reductions without masking layout bugs
_DECODE_TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-4),
               jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


def _tiny_lm(dtype, n_kv_heads=None, seed=0):
    cfg = TransformerConfig(
        vocab_size=101, d_model=32, n_layers=2, n_heads=4,
        n_kv_heads=n_kv_heads, max_seq_len=64, dtype=dtype,
        attention_impl="reference",
    )
    model = TransformerLM(cfg)
    from flax.core import meta as flax_meta

    variables = flax_meta.unbox(
        jax.jit(model.init)(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32))
    )
    return cfg, model, variables


def _programs(cfg, model):
    """The wide prefill, the decode step and the full forward (on one padded
    width) as ONE program a shape each: called bare they are compiled an
    operation at a time."""
    return (
        jax.jit(functools.partial(transformer_prefill, cfg)),
        jax.jit(functools.partial(transformer_decode, cfg), static_argnames=("chunk_blocks",)),
        causal_forward(model, 32),
    )


# f32 decode parity costs ~16-24s per case on the 2-core verify box; the
# bf16 cases keep step-for-step coverage in tier-1, f32 runs full-suite
@pytest.mark.parametrize(
    "dtype",
    [pytest.param(jnp.float32, marks=pytest.mark.slow), jnp.bfloat16],
    ids=["f32", "bf16"],
)
@pytest.mark.parametrize("n_kv_heads", [None, 2], ids=["mha", "gqa"])
def test_decode_matches_full_forward_logits(dtype, n_kv_heads):
    """Prefill + per-token decode logits == full-sequence forward logits,
    at each generation step, for MHA and GQA (n_kv_heads < n_heads)."""
    cfg, model, variables = _tiny_lm(dtype, n_kv_heads)
    params = variables["params"]
    block_size = 4
    cache = init_kv_cache(cfg, num_blocks=16, block_size=block_size)
    prompt = list(np.random.default_rng(1).integers(0, cfg.vocab_size, size=9))
    prompt = [int(t) for t in prompt]
    max_prompt = 16
    table = np.arange(1, 1 + (32 // block_size), dtype=np.int32)[None, :]
    padded = np.zeros((1, max_prompt), np.int32)
    padded[0, : len(prompt)] = prompt
    prefill, decode, full_forward = _programs(cfg, model)
    logits_pf, cache = prefill(params, padded, jnp.asarray([len(prompt)]), table, cache)
    tol = _DECODE_TOL[dtype]

    # every prompt position's logits match the full forward (causality:
    # the padding after them cannot contribute)
    full = full_forward(variables, prompt)
    np.testing.assert_allclose(
        np.asarray(logits_pf[0, : len(prompt)]), np.asarray(full), **tol
    )

    seq = list(prompt)
    tok = int(np.argmax(np.asarray(logits_pf[0, len(prompt) - 1])))
    for _ in range(6):
        seq.append(tok)
        pos = len(seq) - 1
        logits_dec, cache = decode(
            params, jnp.asarray([tok], jnp.int32), jnp.asarray([pos], jnp.int32), table, cache,
        )
        full = full_forward(variables, seq)
        np.testing.assert_allclose(
            np.asarray(logits_dec[0]), np.asarray(full[-1]), **tol
        )
        tok = int(np.argmax(np.asarray(logits_dec[0])))


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "temp0.8"])
def test_decode_sampling_matches_full_forward(temperature):
    """Seeded sampling over decode logits reproduces sampling over the
    full-forward logits token for token (GQA config, f32)."""
    cfg, model, variables = _tiny_lm(jnp.float32, n_kv_heads=2, seed=3)
    params = variables["params"]
    block_size = 4
    cache = init_kv_cache(cfg, num_blocks=16, block_size=block_size)
    prompt = [5, 17, 3, 99, 42]
    table = np.arange(1, 9, dtype=np.int32)[None, :]
    padded = np.zeros((1, 8), np.int32)
    padded[0, : len(prompt)] = prompt
    prefill, decode, full_forward = _programs(cfg, model)
    logits_pf, cache = prefill(params, padded, jnp.asarray([len(prompt)]), table, cache)

    rng_dec = np.random.default_rng(7)
    rng_full = np.random.default_rng(7)
    dec_tokens = []
    tok = sample_token(
        np.asarray(logits_pf[0, len(prompt) - 1]), temperature, rng_dec
    )
    dec_tokens.append(tok)
    seq = list(prompt)
    for _ in range(5):
        seq.append(tok)
        logits_dec, cache = decode(
            params, jnp.asarray([tok], jnp.int32), jnp.asarray([len(seq) - 1], jnp.int32), table, cache,
        )
        tok = sample_token(np.asarray(logits_dec[0]), temperature, rng_dec)
        dec_tokens.append(tok)

    # oracle: same sampler over full-forward logits
    full_tokens = []
    seq = list(prompt)
    for _ in range(6):
        logits = full_forward(variables, seq)
        tok = sample_token(np.asarray(logits[-1]), temperature, rng_full)
        full_tokens.append(tok)
        seq.append(tok)
    assert dec_tokens == full_tokens


def test_decode_inactive_lanes_do_not_disturb_active(devices8):
    """A batch mixing active and empty (-1) lanes produces the same logits
    for the active lane as a batch of one — the scratch-block writes of
    idle lanes must never leak into real sequences."""
    cfg, model, variables = _tiny_lm(jnp.float32, n_kv_heads=2, seed=5)
    params = variables["params"]
    prefill, decode, _ = _programs(cfg, model)
    block_size = 4
    prompt = [9, 8, 7, 6, 5, 4]

    def run(batch_lanes):
        cache = init_kv_cache(cfg, num_blocks=32, block_size=block_size)
        tables = np.zeros((batch_lanes, 8), np.int32)
        tables[0] = np.arange(1, 9)
        padded = np.zeros((1, 8), np.int32)
        padded[0, : len(prompt)] = prompt
        logits_pf, cache = prefill(params, padded, jnp.asarray([len(prompt)]), tables[:1], cache)
        tok = int(np.argmax(np.asarray(logits_pf[0, len(prompt) - 1])))
        toks = np.zeros(batch_lanes, np.int32)
        poss = np.full(batch_lanes, -1, np.int32)
        toks[0] = tok
        poss[0] = len(prompt)
        logits_dec, cache = decode(params, jnp.asarray(toks), jnp.asarray(poss), jnp.asarray(tables), cache)
        return np.asarray(logits_dec[0])

    solo = run(1)
    mixed = run(4)
    # f32 rounding, not leakage: XLA:CPU tiles the [1, d] and [4, d] matmuls
    # differently (jax 0.9.0 measured 1.2e-6 on logits of magnitude ~2)
    np.testing.assert_allclose(mixed, solo, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# serving fast path: lazy chunked decode + suffix prefill (ISSUE 17)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk_blocks", [1, 2, 4, 8], ids=lambda c: f"chunk{c}")
def test_chunked_decode_matches_full_gather(chunk_blocks):
    """The lazy decode (online-softmax over dynamic block-table slices)
    equals the full-table gather step for step at f32 tolerance; the
    scratch block is the only cache cell allowed to differ (inactive-lane
    padding writes land there by design)."""
    cfg, model, variables = _tiny_lm(jnp.float32, n_kv_heads=2, seed=9)
    params = variables["params"]
    prefill, decode, _ = _programs(cfg, model)
    block_size = 4
    prompt = [11, 4, 93, 7, 55, 21, 8]
    table = np.arange(1, 9, dtype=np.int32)[None, :]  # 8 blocks = 32 tokens

    def run(chunk):
        cache = init_kv_cache(cfg, num_blocks=16, block_size=block_size)
        padded = np.zeros((1, 8), np.int32)
        padded[0, : len(prompt)] = prompt
        logits_pf, cache = prefill(params, padded, jnp.asarray([len(prompt)]), table, cache)
        tok = int(np.argmax(np.asarray(logits_pf[0, len(prompt) - 1])))
        outs = []
        for step in range(6):
            pos = len(prompt) + step
            logits, cache = decode(
                params, jnp.asarray([tok], jnp.int32), jnp.asarray([pos], jnp.int32), table, cache,
                chunk_blocks=chunk,
            )
            outs.append(np.asarray(logits[0]))
            tok = int(np.argmax(outs[-1]))
        return outs, cache

    full_outs, full_cache = run(0)
    lazy_outs, lazy_cache = run(chunk_blocks)
    for full, lazy in zip(full_outs, lazy_outs):
        np.testing.assert_allclose(lazy, full, atol=2e-5, rtol=2e-4)
    for full, lazy in zip(
        jax.tree_util.tree_leaves(full_cache), jax.tree_util.tree_leaves(lazy_cache)
    ):
        np.testing.assert_allclose(
            np.asarray(lazy)[1:], np.asarray(full)[1:], atol=2e-5, rtol=2e-4
        )


def test_chunked_decode_rejects_nondivisor_chunk():
    cfg, _model, variables = _tiny_lm(jnp.float32, n_kv_heads=2, seed=9)
    cache = init_kv_cache(cfg, num_blocks=16, block_size=4)
    table = np.arange(1, 9, dtype=np.int32)[None, :]
    with pytest.raises(ValueError, match="chunk_blocks"):
        transformer_decode(
            cfg, variables["params"], jnp.asarray([1], jnp.int32),
            jnp.asarray([0], jnp.int32), table, cache, chunk_blocks=3,
        )


# -- the chunked prefill walk: lengths that straddle a chunk's edges -----------

WALK_BLOCK, WALK_PAD = 16, 768  # three chunks of 256
WALK_LENGTHS = [255, 256, 257, 2 * 256 + 17, WALK_PAD]


@pytest.fixture(scope="module")
def walk_setup():
    """A tiny GQA model and ONE jitted walk, under the retrace sentinel, for
    every length below; the full forward's logits of one 768-token row."""
    import dataclasses

    from determined_tpu.lint._runtime import get_retrace_sentinel
    from determined_tpu.models import serving as tx

    cfg, model, variables = _tiny_lm(jnp.float32, n_kv_heads=2, seed=5)
    cfg = dataclasses.replace(cfg, max_seq_len=WALK_PAD)
    params = variables["params"]
    assert tx.prefill_chunk_tokens(WALK_BLOCK, WALK_PAD) == tx.PREFILL_CHUNK_TOKENS == 256
    tokens = np.asarray(jax.random.randint(jax.random.key(2), (1, WALK_PAD), 1, cfg.vocab_size), np.int32)
    full = np.asarray(jax.jit(TransformerLM(cfg).apply)(variables, jnp.asarray(tokens)))[0]
    table = jnp.arange(1, 1 + WALK_PAD // WALK_BLOCK, dtype=jnp.int32)[None, :]
    sentinel = get_retrace_sentinel()
    walk = jax.jit(sentinel.wrap(
        "test.prefill_walk", lambda t, s, n, c: tx.transformer_prefill_chunked(cfg, params, t, s, n, table, c), allowed=1,
    ))

    def run(n, start=0, cache=None):
        padded = tokens.copy()
        padded[0, n:] = 0
        if cache is None:
            cache = init_kv_cache(cfg, num_blocks=1 + WALK_PAD // WALK_BLOCK, block_size=WALK_BLOCK)
        return walk(jnp.asarray(padded), jnp.asarray([start], jnp.int32), jnp.asarray([n], jnp.int32), cache)

    return run, full, sentinel


@pytest.mark.parametrize("n", WALK_LENGTHS)
def test_the_prefill_walk_matches_the_full_forward_across_chunk_edges(walk_setup, n):
    """C - 1, C, C + 1, 2C + 17 and the padded width: the walk's one logits
    row is the full forward's at ``n - 1``, and a warm start that is
    block-aligned but not chunk-aligned is bitwise the cold run."""
    run, full, sentinel = walk_setup
    cold, cache = run(n)
    assert cold.shape == (1, full.shape[1]) and cold.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(cold[0]), full[n - 1], atol=2e-5, rtol=2e-4)
    start = (n // 2) // WALK_BLOCK * WALK_BLOCK + WALK_BLOCK  # 128, 144, 144, 272, 400: inside a chunk
    assert start % WALK_BLOCK == 0 and start % 256
    warm, _ = run(n, start, cache)
    np.testing.assert_array_equal(np.asarray(warm), np.asarray(cold))
    # one trace, whatever the length and the start
    assert {r.label: r.traces for r in sentinel.records()}["test.prefill_walk"] == 1


def test_the_prefill_walk_refuses_a_width_that_is_no_whole_chunk():
    from determined_tpu.models.serving import prefill_chunk_tokens, transformer_prefill_chunked

    # whole blocks and whole 128-wide tiles; the longest prompt where that is shorter
    assert prefill_chunk_tokens(16, 4096) == 256 and prefill_chunk_tokens(16, 100) == 112
    assert prefill_chunk_tokens(4, 16) == 16 and prefill_chunk_tokens(48, 4096) == 384
    cfg, _model, variables = _tiny_lm(jnp.float32, n_kv_heads=2)
    cache = init_kv_cache(cfg, num_blocks=40, block_size=16)
    with pytest.raises(ValueError, match="whole chunks"):
        transformer_prefill_chunked(
            cfg, variables["params"], np.zeros((1, 272), np.int32), jnp.asarray([0]), jnp.asarray([5]),
            np.arange(1, 18, dtype=np.int32)[None, :], cache,
        )


def test_prefill_suffix_matches_wide_prefill():
    """The walk from start=0 reproduces the wide padded prefill (the oracle)
    at f32 tolerance, and a warm start over already-written prefix blocks is
    BITWISE equal to the cold run — both attend over the same stored cache
    bits, so prefix-cached admission cannot drift."""
    from determined_tpu.models.serving import transformer_prefill_chunked

    cfg, _model, variables = _tiny_lm(jnp.float32, n_kv_heads=2, seed=11)
    params = variables["params"]
    block_size = 4
    prompt = list(range(30, 41))  # 11 tokens: 2 full blocks + partial tail
    table = np.arange(1, 9, dtype=np.int32)[None, :]

    padded16 = np.zeros((1, 16), np.int32)
    padded16[0, : len(prompt)] = prompt
    cache = init_kv_cache(cfg, num_blocks=16, block_size=block_size)
    wide_logits, _wide_cache = transformer_prefill(
        cfg, params, padded16, jnp.asarray([len(prompt)]), table, cache
    )

    padded12 = np.zeros((1, 12), np.int32)  # whole blocks only
    padded12[0, : len(prompt)] = prompt
    cache = init_kv_cache(cfg, num_blocks=16, block_size=block_size)
    cold_logits, cold_cache = transformer_prefill_chunked(
        cfg, params, padded12, jnp.asarray([0]), jnp.asarray([len(prompt)]),
        table, cache,
    )
    np.testing.assert_allclose(
        np.asarray(cold_logits[0]), np.asarray(wide_logits[0, len(prompt) - 1]),
        atol=2e-5, rtol=2e-4,
    )

    # warm admission: the first 2 blocks already hold the prefix bits;
    # re-run only the suffix (start=8) against the cold run's cache
    warm_logits, _warm_cache = transformer_prefill_chunked(
        cfg, params, padded12, jnp.asarray([8]), jnp.asarray([len(prompt)]),
        table, cold_cache,
    )
    assert np.array_equal(np.asarray(warm_logits), np.asarray(cold_logits))
