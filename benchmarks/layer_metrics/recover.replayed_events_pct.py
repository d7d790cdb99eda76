"""Events the device replayed in a recovery, both passes, for each hundred
events the log holds: the suffixes after the hydrated records
(`tpu.recover/suffix-events`) plus the whole histories the full-replay path
took in the rebuild (`events-rebuilt`) and in the verify
(`events-verified`), over `history-events`. An exact count, taken over the
window's recoveries alone (set-up's bring-up of the cut log counts under
the same names): each is of the same log, so their quotient is one
recovery's. The cold path reads 200; a warm restart whose records all
hydrate reads twice its suffix share."""

NEEDS = ("suffix-events", "events-rebuilt", "events-verified",
         "history-events")


def read(ctx):
    counters = ctx.get("window_counters") or {}
    if ctx.get("kind") != "recover" \
            or any(name not in counters for name in NEEDS) \
            or not counters["history-events"]:
        return None
    replayed = sum(counters[name] for name in NEEDS[:3])
    return 100.0 * replayed / counters["history-events"]
