"""The least an escalation rung must move for a call, from its shapes.

`ladder_wirec_least_bytes`: a widened-K rung reads every wire byte of its
gathered sub-corpus once from HBM and writes, for each gathered row, one
CRC32, one error word and one flag that says whether the row's final state
still fits the base payload. Like the base replay (`counts.py`) it keeps a
workflow's state on the chip between events, and memory is the only
published peak it can be held against.
"""
from __future__ import annotations

#: bytes written back for each gathered row: CRC32 (uint32), error (int32),
#: narrow-overflow flag (bool)
LADDER_OUT_BYTES_PER_ROW = 9


def ladder_wirec_least_bytes(wire_bytes: int, rows: int) -> int:
    return int(wire_bytes) + LADDER_OUT_BYTES_PER_ROW * int(rows)
