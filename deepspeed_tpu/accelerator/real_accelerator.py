"""Accelerator selection (reference: accelerator/real_accelerator.py:51).

``get_accelerator()`` returns the process-wide accelerator singleton. The
backend is chosen from (in priority order):

1. ``set_accelerator()`` explicit injection (tests),
2. the ``DS_ACCELERATOR`` environment variable (``tpu`` | ``cpu``),
3. autodetection from the platform of ``jax.devices()[0]`` (a backend
   that fails to start raises; it is never read as "cpu").
"""

from __future__ import annotations

import os
from typing import Optional

from deepspeed_tpu.accelerator.abstract_accelerator import Accelerator
from deepspeed_tpu.accelerator.tpu_accelerator import CpuAccelerator, TpuAccelerator

_accelerator: Optional[Accelerator] = None


def _detect() -> Accelerator:
    env = os.environ.get("DS_ACCELERATOR", "").lower()
    if env == "tpu":
        return TpuAccelerator()
    if env == "cpu":
        return CpuAccelerator()
    from deepspeed_tpu.utils.platform import on_tpu

    return TpuAccelerator() if on_tpu() else CpuAccelerator()


def get_accelerator() -> Accelerator:
    global _accelerator
    if _accelerator is None:
        _accelerator = _detect()
    return _accelerator


def set_accelerator(accel: Accelerator) -> None:
    global _accelerator
    _accelerator = accel


def is_current_accelerator_supported() -> bool:
    return get_accelerator().is_available()
