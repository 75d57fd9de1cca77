"""``ops/kernel_form.py``: the one place that asks where the process runs and
which form of a kernel a call takes."""

import pathlib
import re

import pytest

from determined_tpu.ops import kernel_form

NEEDS = "the kernel needs whole tiles (got 7)"


@pytest.mark.parametrize(
    "on_tpu,impl,takes,want",
    [
        (True, None, True, "kernel"),                # on the chip and the shapes tile
        (True, None, False, "jnp"),                  # on the chip, shapes the kernel does not take
        (False, None, True, "jnp"),                  # off the chip: a serving kernel's jax.numpy form
        (False, "kernel_interpret", True, "kernel_interpret"),  # the parity tests' pick passes through
        (False, "kernel", True, "kernel"),           # compiled for a described chip
        (True, "jnp", False, "jnp"),                 # the reference, whatever the shapes
        (True, "kernel", False, ValueError(NEEDS)),  # forced on shapes it does not take: the caller's sentence
        (False, "kernel_interpret", False, ValueError(NEEDS)),
        (True, "kernal", True, ValueError("impl is one of")),
    ],
)
def test_a_serving_kernels_form_is_resolved_in_one_place(on_tpu, impl, takes, want, monkeypatch):
    monkeypatch.setattr(kernel_form, "on_tpu", lambda: on_tpu)
    assert kernel_form.interpreted_off_chip() is (not on_tpu)  # a training kernel's rule reads the same switch
    if isinstance(want, Exception):
        with pytest.raises(type(want), match=re.escape(str(want))):
            kernel_form.resolve_impl(impl, takes, NEEDS)
    else:
        assert kernel_form.resolve_impl(impl, takes, NEEDS) == want


def test_no_other_module_of_ops_or_models_asks_the_backend():
    package = pathlib.Path(kernel_form.__file__).parents[1]
    asks, switches = [], []
    for path in sorted((package / "ops").glob("*.py")) + sorted((package / "models").glob("*.py")):
        text = path.read_text()
        asks += [path.name] * text.count("default_backend")
        switches += [f"{path.name}: {m}" for m in re.findall(r"def (_on_tpu|_interpret)\b", text)]
    assert asks == ["kernel_form.py"] and switches == []
