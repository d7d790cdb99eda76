"""Share of a warm recovery spent bringing persisted `snap` records into a
resident pool: `rebuild.snapshot-consult` (the rebuilder's pool) plus
`verify.snapshot-consult` (the verify engine's own pool, the same records
again) over `recover.call`, in the traced pass: blob CRC, address check,
66 leaf views a record, one W=1 row admitted a run."""
from _recover_warm_common import share_of_all_pct


def read(ctx):
    return share_of_all_pct(ctx, "rebuild.snapshot-consult",
                            "verify.snapshot-consult")
