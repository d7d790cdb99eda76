"""Share of a recovery spent pinning verified rows into the resident pool
(`verify.seed-resident`: a state-row slice and an admit a run) over
`recover.call`, in the traced pass. Nested: the span lies inside
`recover.verify`, so this share is part of `recover.verify_share_pct` and
the two do not add up."""
from _recover_common import share_pct


def read(ctx):
    return share_pct(ctx, "verify.seed-resident")
