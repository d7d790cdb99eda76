"""The least a kernel must do for a call, from the call's shapes.

`replay_wirec_least_bytes`: the compressed replay reads every wire byte of
its chunk once from HBM and writes one CRC32 and one error word for each
workflow; the mutable state of a workflow lives on the chip between its
events and is never written out. Integer compare-and-select work has no
published peak on a TPU, so memory is the only roofline this kernel can be
held against, and its share says how far the replay is from being bound by
the bytes it moves (it is bound by its sequential scan over events).
"""
from __future__ import annotations

import json
import os

#: bytes written back for each workflow: CRC32 (uint32) and error (int32)
REPLAY_OUT_BYTES_PER_WORKFLOW = 8


def replay_wirec_least_bytes(wire_bytes: int, workflows: int) -> int:
    return int(wire_bytes) + REPLAY_OUT_BYTES_PER_WORKFLOW * int(workflows)


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in benchmarks/peaks.json (has: {sorted(table)})")
    return table[device_kind]


def roofline_share_pct(least_bytes: float, device_seconds: float,
                       device_kind: str) -> float:
    """100 x (least time the chip's memory could take) / (time it took)."""
    least_s = least_bytes / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / device_seconds
