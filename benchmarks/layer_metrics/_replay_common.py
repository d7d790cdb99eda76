"""Shared by the replay readers: sums over the window's calls and the traced
kernel's device seconds."""
from __future__ import annotations

from typing import Optional


def calls(ctx: dict) -> list:
    return ctx["calls"] if ctx.get("kind") == "replay" else []


def total(ctx: dict, key: str) -> float:
    return float(sum(c[key] for c in calls(ctx)))


def calls_wall_s(ctx: dict) -> float:
    """The window less what starting and stopping a trace took inside it."""
    return total(ctx, "wall_s")


def kernel_device_s(ctx: dict) -> Optional[float]:
    """Device seconds of the replay program's runs in the traced window,
    summed over the chips; None where the trace holds none."""
    trace = ctx.get("trace")
    if not trace or ctx.get("rehearse"):
        return None
    secs = sum(entry["seconds"] for name, entry in trace["modules"].items()
               if any(key in name for key in ctx["kernel_modules"]))
    return secs or None


def traced(ctx: dict, key: str) -> float:
    return float(sum(c[key] for c in calls(ctx) if c.get("traced")))
