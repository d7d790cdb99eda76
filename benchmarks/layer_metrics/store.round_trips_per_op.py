"""Store round trips for each measured op of the traced window: count of
`store.*` spans over count of ops."""
import _spans


def read(ctx):
    return _spans.mean(_spans.part(ctx, "store_trips"))
