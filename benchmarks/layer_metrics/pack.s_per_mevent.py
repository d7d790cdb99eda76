"""Host seconds inside the pack stage (summed over the pack threads) for
each million events packed: FeedReport.pack_s over FeedReport.events."""
from _replay_common import calls, total


def read(ctx):
    if not calls(ctx) or not total(ctx, "events"):
        return None
    return total(ctx, "pack_s") / (total(ctx, "events") / 1e6)
