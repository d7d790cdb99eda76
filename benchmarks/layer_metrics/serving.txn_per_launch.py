"""Transactions the serving tier folded into each batched device launch,
over the window: the host's own counters, after minus before."""


def read(ctx):
    before, after = ctx.get("serving_before"), ctx.get("serving_after")
    if not before or not after:
        return None
    launches = after["batched_launches"] - before["batched_launches"]
    if launches <= 0:
        return None
    return (after["transactions"] - before["transactions"]) / launches
