"""Share of a recovery spent making a `MutableState` of each device row and
checking it against the device's payload row (`rebuild.hydrate`) over
`recover.call`, in the traced pass."""
from _recover_common import share_pct


def read(ctx):
    return share_pct(ctx, "rebuild.hydrate")
