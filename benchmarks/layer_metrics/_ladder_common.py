"""Shared by the ladder's device readers: device seconds of the widened-K
rung programs in the traced window."""
from __future__ import annotations

from typing import Optional

from _replay_common import kernel_device_s


def ladder_device_s(ctx: dict) -> Optional[float]:
    """`_replay_common.kernel_device_s` of the programs the cell names as
    `ladder_kernel_modules`; None where it names none (another driver, or
    a program without the ladder)."""
    keys = ctx.get("ladder_kernel_modules")
    return kernel_device_s(dict(ctx, kernel_modules=keys)) if keys else None
