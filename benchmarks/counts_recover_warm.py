"""The least a WARM recovery's device work must move, from what the log
holds.

A run whose `snap` record is hydrated reads its persisted state row once
into HBM (the W=1 `ReplayState` row: every leaf of the layout, 3,602 B at
the default layout; the caller measures it from the layout) and then only
the serialized bytes of the batches committed after the record; a run with
no record reads its whole serialized history, as in `counts_recover.py`.
Every run hands one canonical payload row back. Counted ONCE a run, though
today's path hydrates and replays every suffix twice (rebuild, then verify)
out of dense int64 lanes padded to a power of two, through one state row of
66 buffers a run: the count is of the work, not of the implementation, so a
change that hydrates in bulk, shares one pool between the passes or drops
the second pass reads a higher share of the same yardstick.
"""
from __future__ import annotations

from counts_recover import STATE_ROW_BYTES


def warm_least_bytes(state_row_bytes: int, hydrated_runs: int,
                     suffix_bytes: int, cold_history_bytes: int,
                     runs: int) -> int:
    """`hydrated_runs` state rows and the `suffix_bytes` their runs
    committed since in, `cold_history_bytes` of the runs with no record
    in, one payload row for each of `runs` out."""
    return (int(state_row_bytes) * int(hydrated_runs) + int(suffix_bytes)
            + int(cold_history_bytes) + STATE_ROW_BYTES * int(runs))
