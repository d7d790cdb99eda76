"""Summed duration of the op's `store.*` spans, each one round trip to the
store server as the calling side sees it (retries inside). Median over the
traced window's measured ops."""
import _spans


def read(ctx):
    return _spans.p50_ms(_spans.part(ctx, "store"))
