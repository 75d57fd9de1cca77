"""The device a measuring program ran on, and the refusal to measure a CPU.

What this platform's users pay for is time on a TPU.  A benchmark or
profile that finds none must fail rather than time XLA:CPU (or quietly
swap a kernel for its reference) and print the result under a device
metric's name; and every result names the device it came from.
"""

from __future__ import annotations

from typing import Any, Dict


def device_facts() -> Dict[str, Any]:
    """``{"platform", "kind", "count"}`` as jax reports them."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_tpu(what: str) -> Dict[str, Any]:
    """Device facts, or ``SystemExit`` where jax is not on a TPU."""
    facts = device_facts()
    if facts["platform"] != "tpu":
        raise SystemExit(
            f"{what} measures a TPU and jax reports platform "
            f"{facts['platform']!r} ({facts['kind']}): refusing to time it. "
            "Run it on the chip."
        )
    return facts
