"""A recovery's device work against its memory roofline in the traced pass:
the least bytes the WORK must move (counts_recover.recover_least_bytes: the
serialized bytes of the histories replayed in, one canonical state row a run
out, counted once though today's path replays twice) over the chip's
published HBM rate, over the chip's busy time."""
import counts
import counts_recover
from _recover_common import device_busy_s, passes


def read(ctx):
    secs = device_busy_s(ctx)
    traced = sum(1 for p in passes(ctx) if p.get("traced"))
    if not secs or not traced:
        return None
    least = traced * counts_recover.recover_least_bytes(
        ctx["history_bytes"], ctx["runs"])
    return counts.roofline_share_pct(least, secs, ctx["device"]["kind"])
