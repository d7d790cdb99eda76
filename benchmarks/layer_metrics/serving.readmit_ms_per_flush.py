"""Milliseconds a flush of the serving tier spends re-pinning the rows its
suffix appends replayed: `resident.readmit` inside each `serving.flush`
(one a chunk of the resident cache's append), mean over the traced
window's flushes, the base of `serving.flush_host_ms_per_launch`."""
import _spans


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    flushes = _spans.spans_named(ctx, "serving.flush")
    readmit_s = [_spans.total_of(f, "resident.readmit") for f in flushes]
    if not any(readmit_s):
        return None
    return sum(readmit_s) / len(flushes) * 1e3
