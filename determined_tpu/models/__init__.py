"""Model zoo: MNIST tutorials, flagship transformer LM, DDPM diffusion,
HF Flax fine-tune families (BERT, GPT-2 — imported lazily from their
modules to keep transformers optional)."""

import time as _time

_IMPORT_T0 = _time.monotonic()  # the span ``import.determined_tpu.models``: from here to this file's last line

from determined_tpu.models.diffusion import DiffusionTrial, UNet, ddpm_sample
from determined_tpu.models.mnist import MnistCNN, MnistMLP, MnistTrial
from determined_tpu.models.transformer import (
    LMTrial,
    TransformerConfig,
    TransformerLM,
)

__all__ = [
    "DiffusionTrial",
    "UNet",
    "ddpm_sample",
    "MnistCNN",
    "MnistMLP",
    "MnistTrial",
    "LMTrial",
    "TransformerConfig",
    "TransformerLM",
]

from determined_tpu.observability import get_tracer as _get_tracer  # noqa: E402

_get_tracer().record_span("import.determined_tpu.models", "setup", _IMPORT_T0, _time.monotonic())
