"""Share of a warm recovery spent replaying the batches committed since
each run's record: `rebuild.suffix-replay` plus `verify.suffix-replay` (the
two `ResidentStateCache.replay_append` calls: suffix encode, the W=1 rows
stacked, the from-state scan, a row slice and a re-admit a run) over
`recover.call`, in the traced pass."""
from _recover_warm_common import share_of_all_pct


def read(ctx):
    return share_of_all_pct(ctx, "rebuild.suffix-replay",
                            "verify.suffix-replay")
