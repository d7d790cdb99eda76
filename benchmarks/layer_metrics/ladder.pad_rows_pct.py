"""Share of the rungs' lanes that are padding: the pow2 bucket of each
launch less its real rows, over the bucket, every rung of every call of the
window: an exact count."""
from _replay_common import calls


def read(ctx):
    if not calls(ctx) or "ladder_lanes" not in calls(ctx)[0]:
        return None
    lanes = sum(c["ladder_lanes"] for c in calls(ctx))
    if not lanes:
        return None
    return 100.0 * (lanes - sum(c["ladder_rows"] for c in calls(ctx))) / lanes
