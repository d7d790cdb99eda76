"""Share of a bulk call that its consumer thread spends inside the
capacity-escalation ladder: the `feed.ladder.*` spans under `feed.call`
(gather of flagged rows, pad + H2D + launch of a rung, the wait for a
rung's results, the patch of the call's results) over `feed.call`, summed
over the traced calls. A gather that a pack thread made is not in it."""
import _spans


def read(ctx):
    if ctx.get("kind") != "replay" or "ladder_kernel_modules" not in ctx:
        return None
    calls = _spans.spans_named(ctx, "feed.call")
    call_s = sum(c.seconds for c in calls)
    if not call_s:
        return None
    return 100.0 * sum(_spans.total_of(c, "feed.ladder.")
                       for c in calls) / call_s
