"""The device a call runs on: the one notion of device identity.

A lane runs its work under ``jax.default_device(lane_device)``, so the
device a call runs on is the innermost ``jax.default_device`` (a
thread-local setting), else the first device of the default backend.
Everything that depends on the hardware — the Pallas interpret flag,
the autotuner's default and cache key, the cost model's hardware
profile, the calibration store's section — asks here, never
``jax.default_backend()``: on a TPU host the host lane's calls run on
the CPU while the process's default backend is the TPU.
"""
from __future__ import annotations

import jax


def current_device(device=None):
    """``device`` if given, else the device the next call runs on."""
    if device is not None:
        return device
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.devices()[0]
    if isinstance(dev, str):
        return jax.devices(dev)[0]
    return dev


def platform(device=None) -> str:
    """Platform (``"tpu"``, ``"cpu"``) of ``current_device(device)``."""
    return current_device(device).platform
