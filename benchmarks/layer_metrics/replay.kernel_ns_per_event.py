"""Device nanoseconds of the replay program for each event it replayed, in
the traced window: the program's runs on the trace's `XLA Modules` line,
summed over the chips, over the traced calls' real events."""
from _replay_common import kernel_device_s, traced


def read(ctx):
    secs, events = kernel_device_s(ctx), traced(ctx, "events")
    if not secs or not events:
        return None
    return secs * 1e9 / events
