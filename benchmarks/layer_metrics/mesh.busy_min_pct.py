"""The busy share of the least busy chip of the mesh over the traced
window: device trace."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or ctx.get("rehearse") or len(trace["devices"]) < 2 \
            or not ctx.get("traced_window_s"):
        return None
    return 100.0 * min(d["busy_s"] for d in trace["devices"]) \
        / ctx["traced_window_s"]
