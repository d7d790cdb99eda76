"""Milliseconds the serving tier's snapshot policy spends for each gate
chain it enters: `serving.snapshot` (the hook inside a `serving.flush` that
notes each parity-clean key's appended events, then asks each key whether
it is due and runs a due key's gate chain and write) summed over the traced
window's flushes, over the `serving.snapshot-gate-chain` spans inside those
hooks. A gate chain, and not a flush, is the unit: a flush carries as many
cold admits, and so as many chains, as its drain coalesced."""
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
    return sum(hook.seconds for hook in hooks) / chains * 1e3
