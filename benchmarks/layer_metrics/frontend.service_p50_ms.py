"""Reply minus ACTUAL send, median over the measured ops that succeeded:
the service time of frontend + history engine + store round trips, without
the wait a late send adds."""
from harness import percentile


def read(ctx):
    ok = [s.service_s for s in ctx.get("measured") or ()
          if s.outcome == "ok"]
    if not ok:
        return None
    return percentile(ok, 50) * 1e3
