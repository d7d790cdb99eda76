"""Store round trips the serving tier's snapshot policy makes for each gate
chain it enters: the `store.*` spans nested in `serving.snapshot` inside
each traced `serving.flush` (the due probe, the gate chain's reads, the
record's put: each a call to the store server), over the
`serving.snapshot-gate-chain` spans inside those hooks; an exact count."""
import _spans


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    hooks = [n for f in _spans.spans_named(ctx, "serving.flush")
             for n in f.walk() if n.name == "serving.snapshot"]
    chains = sum(1 for hook in hooks for n in hook.walk()
                 if n.name == "serving.snapshot-gate-chain")
    if not chains:
        return None
    trips = sum(1 for hook in hooks for n in hook.walk()
                if n.name.startswith("store."))
    return trips / chains
