"""Seconds the serving tier spent inside flushes for each batched device
launch, over the whole window: the host's own totals, `flush_s_total` over
`batched_launches`, after minus before. The twin of
`serving.flush_host_ms_per_launch` that no profiler slows: that one sees
the traced seconds, this one every flush, device wait included."""


def read(ctx):
    before, after = ctx.get("serving_before"), ctx.get("serving_after")
    if not before or not after or "flush_s_total" not in after \
            or "flush_s_total" not in before:
        return None
    launches = after["batched_launches"] - before["batched_launches"]
    if launches <= 0:
        return None
    return (after["flush_s_total"] - before["flush_s_total"]) \
        / launches * 1e3
