"""Share of a warm recovery spent re-pinning the rows its two suffix
replays appended: every `resident.readmit` span inside the traced
`recover.call` (one a chunk: each row's `slice_row` launch, the narrow
where due, `admit`), over `recover.call`."""
from _resident_common import call_and_inside_s


def read(ctx):
    got = call_and_inside_s(ctx, "resident.readmit")
    if got is None:
        return None
    call_s, readmit_s = got
    return 100.0 * readmit_s / call_s
