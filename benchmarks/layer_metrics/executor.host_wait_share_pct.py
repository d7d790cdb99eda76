"""Share of the window the host spent waiting on the device: the `kernel`
leg (a host clock around block_until_ready, not device time) plus the
`readback` leg."""
from _replay_common import calls, calls_wall_s


def read(ctx):
    if not calls(ctx):
        return None
    waited = sum(c["legs"]["kernel"] + c["legs"]["readback"]
                 for c in calls(ctx))
    return 100.0 * waited / calls_wall_s(ctx)
