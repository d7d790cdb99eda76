"""Share of a recovery spent getting the log back into the stores:
`recover.log-replay` (the file read and parsed, the records lifted to the
current schema, then the record loop: base64, `deserialize_history`, store
appends) over `recover.call`, in the traced pass."""
from _recover_common import share_pct


def read(ctx):
    return share_pct(ctx, "recover.log-replay")
