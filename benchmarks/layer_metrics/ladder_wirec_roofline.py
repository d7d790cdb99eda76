"""The rung programs' share of their memory roofline in the traced window:
the least bytes they must move (counts_ladder.ladder_wirec_least_bytes: the
gathered sub-corpora's wire bytes in, 9 B a gathered row out) over the
chip's published HBM rate, over their device seconds."""
import counts
import counts_ladder
from _ladder_common import ladder_device_s
from _replay_common import traced


def read(ctx):
    secs = ladder_device_s(ctx)
    if not secs or not traced(ctx, "ladder_rows"):
        return None
    least = counts_ladder.ladder_wirec_least_bytes(
        traced(ctx, "ladder_wire_bytes"), traced(ctx, "ladder_rows"))
    return counts.roofline_share_pct(least, secs, ctx["device"]["kind"])
