"""Shared by the readers of the warm restart: a share that needs EVERY one
of its spans. A warm recovery consults and replays in both device passes; a
program from before the verify's own legs were spans has the rebuild's
alone, and half a share under the whole's name would be read as a gain."""
from __future__ import annotations

from typing import Optional

from _recover_common import CALL, seconds_of


def share_of_all_pct(ctx: dict, *names: str) -> Optional[float]:
    """100 x the spans of these names over `recover.call`; None unless the
    traced pass holds the call and a span of each name."""
    call_s = seconds_of(ctx, CALL)
    parts = [seconds_of(ctx, name) for name in names]
    if not call_s or not all(parts):
        return None
    return 100.0 * sum(parts) / call_s
