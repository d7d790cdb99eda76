"""Summed self time of the op's `history.*` spans other than
`history.lock-wait`: the history engine's own Python (state builder,
serializer, commit bookkeeping, the hand-off to the serving tier) less its
store calls. Median over the traced window's measured ops."""
import _spans


def read(ctx):
    return _spans.p50_ms(_spans.part(ctx, "history"))
