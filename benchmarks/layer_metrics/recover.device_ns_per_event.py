"""Device nanoseconds of a recovery for each event of the log: the chip's
busy time in the traced pass (the union of its `XLA Ops` intervals: the
rebuild's and the verify's replay programs, the payload and compare
programs, the resident pool's row slices) over the log's real events, each
counted once though the path replays it twice."""
from _recover_common import device_busy_s, traced_events


def read(ctx):
    secs, events = device_busy_s(ctx), traced_events(ctx)
    if not secs or not events:
        return None
    return secs * 1e9 / events
