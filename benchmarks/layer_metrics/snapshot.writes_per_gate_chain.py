"""Records the serving tier's snapshot policy wrote for each gate chain it
entered, over the whole window: `tpu.snapshot/writes` over
`tpu.snapshot/gate-chains`, the host's own counters after minus before,
read with no profiler. A workflow that is due but below the age floor (a
churn start, a pool workflow seeded young) is a chain that writes
nothing."""


def read(ctx):
    window = ctx.get("snapshot_window")
    if not window or window.get("writes") is None \
            or not window.get("gate-chains"):
        return None
    return window["writes"] / window["gate-chains"]
