"""The least a recovery's device work must move, from what the log holds.

`recover_least_bytes`: whatever format feeds the device, rebuilding a run
reads every serialized byte of its history once from HBM (the log's `h`
blobs before base64: what any recovery must read once) and, unlike the CRC
replay of `counts.py`, has to hand the state back: one canonical payload row
a run out. That is counted ONCE a run, though today's path replays every run
twice (rebuild, then verify) out of dense int64 lanes at 144 B an event:
the count is of the work, not of the implementation, so a change that feeds
the rebuilder a leaner format or drops the second replay reads a higher
share of the same yardstick. Memory is the only published peak an integer
scan can be held against (`counts.py`).
"""
from __future__ import annotations

#: the canonical payload row, the program's and `refimpl/replay.py`'s
#: alike: 89 little-endian int64
STATE_ROW_BYTES = 89 * 8


def recover_least_bytes(history_bytes: int, runs: int) -> int:
    return int(history_bytes) + STATE_ROW_BYTES * int(runs)
