"""Wire bytes staged to the device for each real event: an exact count."""
from _replay_common import calls, total


def read(ctx):
    if not calls(ctx) or not total(ctx, "events"):
        return None
    return total(ctx, "wire_bytes") / total(ctx, "events")
