"""Share of the window in which the device consumer stood stalled on the
pack pool (`pack-queue-wait`, FeedReport.pack_queue_wait_s): host clock."""
from _replay_common import calls, calls_wall_s, total


def read(ctx):
    if not calls(ctx):
        return None
    return 100.0 * total(ctx, "pack_queue_wait_s") / calls_wall_s(ctx)
