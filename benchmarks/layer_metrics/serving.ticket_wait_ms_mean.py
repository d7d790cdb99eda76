"""What a transaction's ticket waited in the serving tier's queue before
its flush began, mean over the window: the host's own totals,
`queue_wait_s_total` over `transactions`, after minus before. A ticket's
wait crosses threads, so it is a counter and not a span."""


def read(ctx):
    before, after = ctx.get("serving_before"), ctx.get("serving_after")
    if not before or not after or "queue_wait_s_total" not in after \
            or "queue_wait_s_total" not in before:
        return None
    txns = after["transactions"] - before["transactions"]
    if txns <= 0:
        return None
    return (after["queue_wait_s_total"] - before["queue_wait_s_total"]) \
        / txns * 1e3
