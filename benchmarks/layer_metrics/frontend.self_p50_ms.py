"""Self time of the op's `frontend.*` span: the frontend handler less the
history engine and store calls under it. Median over the traced window's
measured ops."""
import _spans


def read(ctx):
    return _spans.p50_ms(_spans.part(ctx, "frontend"))
