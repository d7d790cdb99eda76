"""How late the generator sent: actual send minus intended send, 95th
percentile over every op of the window. A starved generator must not be
read as a fast server."""
from harness import percentile


def read(ctx):
    samples = ctx.get("samples")
    if not samples:
        return None
    return percentile([s.late_s for s in samples], 95) * 1e3
