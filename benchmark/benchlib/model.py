"""From a configuration file to its architecture's adapter.

A configuration file names its architecture (``"arch"``; absent, the dense
decoder) and ``benchmark/archs/<arch>.py`` is everything the harness knows
about it: how the file's keys map onto the program's model and trial, where
its plain reference lives and under which names it takes the weights, which
leaves one training step is compared on, and the parameter counts the cost
functions start from.  The harness names no architecture's module, config
class or leaf; a PR that brings an architecture brings ``archs/``,
``reference/`` and ``configs/`` files and edits nothing here.
"""

from __future__ import annotations

import importlib.util
import os
from types import ModuleType
from typing import Any, Dict

from .spec import SpecError

DEFAULT_ARCH = "dense_decoder"

#: what ``archs/<arch>.py`` must define, with the arguments each is called with
INTERFACE = {
    "check_as_run": "(config): refuse a file that states what the program cannot run as stated",
    "model_config": "(config, max_seq_len): the program's model config object, as serving builds it",
    "trial_hparams": "(config): the model's part of the training trial's hparams",
    "trial_overrides": "(config): fields replaced on the trial's own model config",
    "init_params": "(model_cfg, seed): the program's initialiser, one jitted call on the device",
    "reference_weights": "(params, config): the program's parameter tree under the reference's names",
    "reference_forward": "(weights, tokens, config): the reference's logits [S, V] for one sequence",
    "reference_loss_and_logits": "(weights, tokens, config): the training loss, auxiliary terms included, and its logits",
    "probe": "(weights_or_grads, embed_rows): the leaves one training step is compared on",
    "matmul_params": "(config): parameters in a matrix multiplication for every token (active experts only)",
    "total_params": "(config): every parameter",
    "embedding_params": "(config): parameters looked up a row a token and never swept",
    "attention_shape": "(config): {'heads', 'kv_heads', 'head_dim', 'layers'}",
}


def seed32(seed: int) -> int:
    """``--seed`` may pass 2**31; jax's keys take 31 bits."""
    return int(seed) % (2**31 - 1)


def load_file(path: str, what: str) -> ModuleType:
    """The module a benchmark file holds, loaded by its path (so that a
    throw-away root's files and a later PR's are found the same way)."""
    if not os.path.isfile(path):
        raise SpecError(f"{what}: no file {path}")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(os.path.dirname(path)) + "_" + os.path.basename(path)[:-3], path
    )
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def named(table: Dict[str, Any], name: str, path: str, attr: str, what: str) -> Any:
    """What a data file names (a reader, a cost function): an entry of the
    harness's own ``table``, or ``attr`` of the file a PR brought at ``path``."""
    fn = table.get(name)
    if fn is None:
        fn = getattr(load_file(path, f"{what} {name!r} is none of {', '.join(sorted(table))}"), attr, None)
        if not callable(fn):
            raise SpecError(f"{path} defines no {attr}()")
    return fn


def beside(adapter_file: str, directory: str, name: str) -> ModuleType:
    """For an adapter: ``<directory>/<name>.py`` of the benchmark directory
    the adapter itself sits in (its reference, or an adapter it builds on)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(adapter_file)))
    return load_file(os.path.join(root, directory, name + ".py"), f"{os.path.basename(adapter_file)} needs {directory}/{name}")


def adapter(cell: Any) -> ModuleType:
    """The adapter of the cell's configuration.  An unknown ``arch`` or an
    adapter that lacks a function is an error, never the dense decoder."""
    arch = str(cell.config.get("arch", DEFAULT_ARCH))
    path = os.path.join(cell.data_dir, "archs", arch + ".py")
    module = load_file(path, f"{cell.config_file} names arch {arch!r}")
    missing = [name for name in INTERFACE if not callable(getattr(module, name, None))]
    if missing:
        raise SpecError(f"{path} lacks {', '.join(missing)}: an adapter defines {', '.join(INTERFACE)}")
    return module
