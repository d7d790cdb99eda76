"""Host encode of both device passes over a recovery: `rebuild.encode`
(`encode_corpus` into dense int64 lanes, a rebuild chunk) plus `verify.pack`
(the verify's encode of the same runs and their expected rows) over
`recover.call`, in the traced pass. The two run on the executor's pack
threads: thread time against the call's wall time, so overlapping packers
can read over 100."""
from _recover_common import share_pct


def read(ctx):
    return share_pct(ctx, "rebuild.encode", "verify.pack")
