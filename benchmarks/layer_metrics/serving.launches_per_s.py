"""Batched device launches of the serving tier for each second of the
window: the host's own counter."""


def read(ctx):
    before, after = ctx.get("serving_before"), ctx.get("serving_after")
    if not before or not after or not ctx.get("window_s"):
        return None
    return (after["batched_launches"] - before["batched_launches"]) \
        / ctx["window_s"]
