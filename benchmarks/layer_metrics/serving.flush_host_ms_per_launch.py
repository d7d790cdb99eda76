"""Host time of one flush of the serving tier: `serving.flush` less the
blocking waits and readbacks inside it (`serving.device-wait` on the cold
path, `resident.device-wait` where the resident cache appends), mean over
the traced window's flushes."""
import _spans


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    flushes = _spans.spans_named(ctx, "serving.flush")
    if not flushes:
        return None
    host_s = sum(f.seconds - _spans.total_of(f, "serving.device-wait")
                 - _spans.total_of(f, "resident.device-wait")
                 for f in flushes)
    return host_s / len(flushes) * 1e3
