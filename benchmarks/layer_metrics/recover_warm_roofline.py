"""A warm recovery's device work against its memory roofline in the traced
pass: the least bytes the WORK must move (counts_recover_warm: a state row
in for each hydrated run, the serialized bytes committed since its record,
the whole history of a run with none, one canonical payload row a run out,
counted once though today's path does it twice) over the chip's published
HBM rate, over the chip's busy time."""
import counts
import counts_recover_warm
from _recover_common import device_busy_s, passes


def read(ctx):
    warm, secs = ctx.get("warm"), device_busy_s(ctx)
    traced = sum(1 for p in passes(ctx) if p.get("traced"))
    if not warm or not secs or not traced:
        return None
    least = traced * counts_recover_warm.warm_least_bytes(
        warm["state_row_bytes"], warm["eligible_runs"],
        warm["suffix_bytes"], warm["cold_history_bytes"], ctx["runs"])
    return counts.roofline_share_pct(least, secs, ctx["device"]["kind"])
