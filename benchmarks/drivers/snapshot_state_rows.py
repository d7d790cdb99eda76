#!/usr/bin/env python3
"""The payload rows that snapshot records' packed states hydrate to, on the
CPU: a child of the served driver, whose launcher stays off JAX while the
service host holds the chip.

Reads a pickled list of (state_blob, layout signature) on stdin and prints
one JSON line: for each record, the canonical payload row, as a list of
ints, of the state a hydration would admit
(`payload_rows(unpack_state_row(blob))` at the program's default layout),
or null where the blob does not decode at that layout.

    python3 benchmarks/drivers/snapshot_state_rows.py < records.pickle
"""
from __future__ import annotations

import json
import os
import pickle
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    import functools

    import jax
    import numpy as np

    from cadence_tpu.core.checksum import DEFAULT_LAYOUT
    from cadence_tpu.engine.snapshot import (
        SnapshotFormatError,
        layout_signature,
        unpack_state_row,
    )
    from cadence_tpu.ops.payload import payload_rows

    project = jax.jit(functools.partial(payload_rows, layout=DEFAULT_LAYOUT))
    want = tuple(layout_signature(DEFAULT_LAYOUT))
    rows = []
    for blob, layout in pickle.load(sys.stdin.buffer):
        if tuple(layout) != want:
            rows.append(None)
            continue
        try:
            state = unpack_state_row(blob, DEFAULT_LAYOUT)
        except SnapshotFormatError:
            rows.append(None)
            continue
        rows.append([int(v) for v in np.asarray(project(state))[0]])
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
