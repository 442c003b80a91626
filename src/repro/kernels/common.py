"""Shared kernel utilities."""
from __future__ import annotations

from typing import Optional

from repro.core.device import platform


def default_interpret() -> bool:
    """Pallas interpret mode: True unless the call runs on a TPU (the
    kernels target the TPU; elsewhere interpret mode validates their
    semantics).  Decided by the device of the calling lane, so a
    kernel on the host lane of a TPU host is interpreted, not lowered
    for the TPU."""
    return platform() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Kernel entry points take ``interpret=None`` and resolve here, so
    a *direct* call (not via ops.py) picks the device-correct mode
    instead of silently running interpret mode on TPU."""
    return default_interpret() if interpret is None else bool(interpret)
