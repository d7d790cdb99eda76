"""Share of a recovery spent in its second device pass (`recover.verify`:
`TPUReplayEngine.verify_all` over every rebuilt state, resident seeding and
the engine's teardown included) over `recover.call`, in the traced pass."""
from _recover_common import share_pct


def read(ctx):
    return share_pct(ctx, "recover.verify")
