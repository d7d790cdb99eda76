"""The replay kernel's share of its memory roofline in the traced window:
the least bytes it must move (counts.replay_wirec_least_bytes) over the
chip's published HBM rate, over its device seconds. Chip-seconds on both
sides, so four chips read like one."""
import counts
from _replay_common import kernel_device_s, traced


def read(ctx):
    secs = kernel_device_s(ctx)
    if not secs or not traced(ctx, "events"):
        return None
    least = counts.replay_wirec_least_bytes(traced(ctx, "wire_bytes"),
                                            traced(ctx, "workflows"))
    return counts.roofline_share_pct(least, secs, ctx["device"]["kind"])
