"""Milliseconds of `resident.readmit` for each row a warm recovery
appended: the spans' seconds inside the traced `recover.call` over the
traced pass's `RecoveryReport.suffix_rows`, rebuild and verify summed."""
from _resident_common import call_and_inside_s, traced_suffix_rows


def read(ctx):
    got = call_and_inside_s(ctx, "resident.readmit")
    rows = traced_suffix_rows(ctx)
    if got is None or not rows:
        return None
    return got[1] * 1e3 / rows
