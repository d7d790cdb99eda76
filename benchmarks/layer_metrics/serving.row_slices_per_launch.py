"""W=1 `slice_row` launches of the resident pool for each batched device
launch of the serving tier, over the window: the host's own counters
(`row_slices`, `batched_launches` of `ServingScheduler.stats()`), after
minus before. Each re-pinned append row, cold admit and first read of a
view is one."""


def read(ctx):
    before, after = ctx.get("serving_before"), ctx.get("serving_after")
    if not before or not after or "row_slices" not in after \
            or "row_slices" not in before:
        return None
    launches = after["batched_launches"] - before["batched_launches"]
    if launches <= 0:
        return None
    return (after["row_slices"] - before["row_slices"]) / launches
