"""How far the window's passes spread: the distance between the first and
third quartile of the untraced calls' wall time over their median, in
percent (host clock). The kernel and the bytes of a pass are the same every
time, so this is the host's noise: the pack threads' stragglers, and what
the next `benchmark` PR reads before it tightens `replay_events_per_s`'
bound."""
import statistics

from _replay_common import calls


def read(ctx):
    walls = [c["wall_s"] for c in calls(ctx) if not c.get("traced")]
    if len(walls) < 2:
        return None
    q1, _q2, q3 = statistics.quantiles(walls, n=4)
    return 100.0 * (q3 - q1) / statistics.median(walls)
