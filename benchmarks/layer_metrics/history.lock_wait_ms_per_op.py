"""Seconds the op stood waiting for its shard's lock (`history.lock-wait`
ends when the lock is held), mean over the traced window's measured ops."""
import _spans


def read(ctx):
    wait_s = _spans.mean(_spans.part(ctx, "lock_wait"))
    return None if wait_s is None else wait_s * 1e3
