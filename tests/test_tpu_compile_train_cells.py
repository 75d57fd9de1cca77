"""What the TPU's own compiler says of the training cells' whole steps — no
chip (the why and the how: tests/test_tpu_compile.py): Mellum2's, ZAYA1's and
Mistral's step at the configuration's widths and batch, state and scratch
inside the chip, the calls each reader of the benchmark takes.  Cut from
tests/test_tpu_compile_cells.py at PR 67, every test under its name:
``--dist loadfile`` balances by the file, and that one was the run's tail."""

import json
import os
import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from tests.model_cases import (  # noqa: F401  (fixture reuse)
    mosaic_calls as _kernels,
    real_kernels_no_cache,
    tpu_devices,
)


# -- a training cell's whole step: the loss, its gradient, the clip and fused AdamW ------


def _train_step_compiled(one, cell_name: str, batch=None):
    """A training cell's step as ``Trainer``'s ``train_step`` puts it together
    (``LMTrial.loss`` under the cell's hparams, its gradient, the optimizer's
    ``apply_step``, the state donated), compiled for one described chip at the
    configuration's widths and its ``train_batch`` (or ``batch`` sequences):
    shapes only, nothing is built."""
    from flax.core import meta as flax_meta

    from tests.benchmark import bench_testlib  # noqa: F401  (puts the harness on sys.path)
    from benchlib import model, spec, train_run
    from determined_tpu.models.transformer import LMTrial

    cell = spec.Spec().cell(cell_name)
    arch = model.adapter(cell)
    arch.check_as_run(cell.config)
    hparams = train_run._hparams(cell.config, cell.traffic, arch)

    class Context:
        mesh = exp_config = None
        batch_axis_size = 1

        def get_hparam(self, name, default=None):
            return hparams.get(name, default)

        def get_global_batch_size(self):
            return hparams["global_batch_size"]

    trial = LMTrial.__new__(LMTrial)
    trial.context = Context()
    lm, tx = trial.build_model(), trial.build_optimizer()
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = flax_meta.unbox(jax.eval_shape(lambda: lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))
    opt_state = jax.eval_shape(tx.init, params)

    def step(params, opt_state, tokens):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: trial.loss(lm, p, {"tokens": tokens}, jax.random.key(0)), has_aux=True
        )(params)
        with jax.named_scope("optim.update"):
            params, opt_state = tx.apply_step(grads, opt_state, params)
        return params, opt_state, loss, metrics

    tokens = jax.ShapeDtypeStruct((batch or hparams["global_batch_size"], hparams["seq_len"] + 1), jnp.int32, sharding=one)
    return jax.jit(step, donate_argnums=(0, 1)).lower(jax.tree.map(on_chip, params), jax.tree.map(on_chip, opt_state), tokens).compile()


def _loss_products(text: str) -> list:
    """The products (XLA's ``convolution``) a compiled step holds under the
    scope ``loss.ce``, by their results' shapes."""
    return re.findall(r"= (\w+\[[\d,]+\])\S* convolution\(.*op_name=\"[^\"]*loss\.ce[^\"]*\"", text)


def _calls_a_reader_takes(text: str) -> dict:
    """How many of a compiled step's Mosaic calls each of the benchmark's
    readers that tell calls apart by RESULT SHAPE would take for its own
    (the patterns are the metric files'; an instruction is named as a trace
    names it), and how many none of them takes."""
    results = re.findall(r"= (\(?\w+\[[\d,]*\]\{[^=]*) custom-call\(.*custom_call_target=\"tpu_custom_call\"", text)
    taken = {}
    for metric in ("moe_grouped_matmul_roofline", "adamw_hbm_roofline", "mixed_attn_roofline"):
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "metrics", metric + ".json")) as f:
            pattern = re.compile(json.load(f)["args"]["pattern"])
        taken[metric] = sum(bool(pattern.search(f"%tpu_custom_call.1 = {r}")) for r in results)
    return dict(taken, none=len(results) - sum(taken.values()))


def test_the_mellum_cells_step_compiles_and_each_reader_finds_the_calls_it_found(tpu_devices):
    """Mellum2's cell: four layers, 16 held experts of 2,304 x 896 under a
    worst-case buffer of 69,632 rows, one sequence of 8,192.  A layer's nine
    grouped products are nine calls with the results they had (2-D bf16, 3-D
    float32), the attention kernels' first result is 4-D bf16 and AdamW's a
    tuple led by float32: what PR 33 counted on the chip (36, 22 and 12
    instructions).  The other calls are a layer's five row movements and,
    since PR 63, hidden (forward and backward) and its derivative over live
    tiles: 3-D results in the compute dtype (a tuple led by one), which no
    reader takes."""
    compiled = _train_step_compiled(SingleDeviceSharding(tpu_devices[0]), "train-mellum2-l4-ep4-seq8k")
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes < 15.75 * 2**30
    taken = _calls_a_reader_takes(text)
    assert taken == {"moe_grouped_matmul_roofline": 4 * 9, "adamw_hbm_roofline": 22, "mixed_attn_roofline": 4 * 3, "none": 4 * (5 + 3)}, taken
    assert sum(taken.values()) == _kernels(text)
    for name in ("moe_hidden_rows", "moe_hidden_grads", "moe_rows_of_tokens", "moe_tokens_of_rows"):
        assert name in text, name
    # nothing elementwise sweeps the whole buffer under the experts' scope any more
    swept = re.findall(r"= \w+\[69632,(?:896|2304)\]\S* fusion\(.*op_name=\"[^\"]*moe\.experts", text)
    assert not swept, swept
    print("temp", mem.temp_size_in_bytes, "kernels", _kernels(text), taken)


def test_the_zaya_cells_step_compiles_at_the_published_widths_and_its_batch(tpu_devices):
    """ZAYA1-8B's cell: five CCA layers at 8 over 2 heads of 128 and 8,192
    keys, the MLP router, top-1 into 8 held experts of 2048 x 2048, a tied head
    of 32,784 rows (16 x 2,049: no multiple of 128) through fused CE, fused
    AdamW over 601,744,730 parameters, at the configuration's batch: the
    flash kernels forward and backward a layer, the grouped products, the
    sweeps; state and scratch inside the chip's 15.75 GiB.  Since PR 54 the
    fused CE's scan makes dx and dk beside a chunk's logits and keeps them
    (96 and 256 MiB) where a remat'd scan kept the hidden rows: 6.73 GiB of
    state + 8.57 of scratch = 15.29 GiB at three sequences (15.31 before),
    with three products under ``loss.ce`` where four ran."""
    compiled = _train_step_compiled(SingleDeviceSharding(tpu_devices[0]), "train-zaya1-8b-l5-ep2-seq8k")
    mem, text = compiled.memory_analysis(), compiled.as_text()
    state = mem.argument_size_in_bytes
    assert 12 * 601_744_730 <= state < 12 * 601_744_730 + (1 << 20)         # parameters and both moments (the gradient is scratch)
    assert state + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes < 15.75 * 2**30
    # a layer: flash forward + its two backward kernels, 3 + 6 grouped products and the rows' movements; the sweeps on top
    assert _kernels(text) >= 5 * (3 + 9)
    # by result shape, as the benchmark's readers tell them apart: the products, attention, the sweeps; and the row
    # movements with the passes over live tiles (PR 63), which none of them takes
    taken = _calls_a_reader_takes(text)
    assert (taken["moe_grouped_matmul_roofline"], taken["mixed_attn_roofline"], taken["none"]) == (5 * 9, 5 * 3, 5 * (5 + 3)), taken
    assert len(_loss_products(text)) == 3, _loss_products(text)             # logits, dk, dx (a remat'd scan: the logits twice)
    print("args", state, "out", mem.output_size_in_bytes, "alias", mem.alias_size_in_bytes, "temp", mem.temp_size_in_bytes, "kernels", _kernels(text))


def test_the_mistral_cells_step_compiles_at_the_published_widths_and_its_batch(tpu_devices):
    """Mistral-7B-v0.3's cell: two layers at 32 over 8 heads of 128, an
    untied head of 32,768 rows through the fused CE's scan (a tile of 4 x
    4,096 x 32,768 float32 is 2 GiB a step: over the 1.6 GB at which it takes
    the scan), fused AdamW, at the configuration's four sequences.  The scan
    makes dx and dk beside a chunk's logits and keeps them (128 and 512 MiB)
    from the forward pass's end to the backward pass's start: 7.88 GiB of
    state + 6.21 of scratch = 14.08 GiB of the chip's 15.75 (14.10 with the
    remat'd scan; PR 23's compile read 14.45), and the compiled text holds
    three products under ``loss.ce`` where four ran."""
    compiled = _train_step_compiled(SingleDeviceSharding(tpu_devices[0]), "train-mistral7b-l2-seq4k")
    mem, text = compiled.memory_analysis(), compiled.as_text()
    state = mem.argument_size_in_bytes
    total = state + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    print("args", state, "out", mem.output_size_in_bytes, "alias", mem.alias_size_in_bytes, "temp", mem.temp_size_in_bytes, "total GiB", total / 2**30, "kernels", _kernels(text))
    assert total < 15.75 * 2**30
    assert _kernels(text) >= 2 * 3                                          # a layer: flash forward + its two backward kernels
    assert len(_loss_products(text)) == 3, _loss_products(text)             # logits, dk, dx
