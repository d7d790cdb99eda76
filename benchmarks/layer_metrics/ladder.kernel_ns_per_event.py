"""Device nanoseconds of the widened-K rung programs for each event they
re-replayed, in the traced window: their runs on the trace's `XLA Modules`
line over the traced calls' `ladder_events` (every rung counted)."""
from _ladder_common import ladder_device_s
from _replay_common import traced


def read(ctx):
    secs = ladder_device_s(ctx)
    if not secs or not traced(ctx, "ladder_events"):
        return None
    return secs * 1e9 / traced(ctx, "ladder_events")
