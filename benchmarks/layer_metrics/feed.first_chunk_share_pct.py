"""Share of a bulk call in which the device has nothing to do yet or any
more: `feed.setup` (executor, staging buffers), `feed.first-chunk-wait`
(chunk 0 packed alone) and `feed.gather` (after the last readback) over
`feed.call`, summed over the traced calls."""
import _spans

PARTS = ("feed.setup", "feed.first-chunk-wait", "feed.gather")


def read(ctx):
    if ctx.get("kind") != "replay":
        return None
    calls = _spans.spans_named(ctx, "feed.call")
    call_s = sum(c.seconds for c in calls)
    if not call_s:
        return None
    part_s = sum(n.seconds for c in calls for n in c.walk()
                 if n.name in PARTS)
    return 100.0 * part_s / call_s
