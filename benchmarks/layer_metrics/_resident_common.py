"""Shared by the readers of the resident pool's append inside a warm
recovery: the traced recovery's spans together with the pool's own.

`_recover_common.forest` matches the recovery's prefixes alone and
`_spans.py` does not know them, so neither tree holds a `resident.*` span
under `recover.call`; this forest holds both, containment on one thread's
line being the call tree as there. The append's legs are `resident.launch`,
`resident.device-wait` and `resident.readmit`, once a chunk, each directly
under the caller's `rebuild.suffix-replay` or `verify.suffix-replay`
(`cadence_tpu/engine/resident.py`). A program from before them has none,
and every reader gives nothing. A rehearsal's line leaves them out too: on
the CPU a row's slice is no device launch, and what it costs there says
nothing of the chip.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from _recover_common import CALL, PREFIXES, passes
from _spans import Node

RESIDENT = "resident."


def forest(ctx: dict) -> List[Node]:
    """The containment forest of every host thread's recovery and
    resident-pool spans."""
    trace = ctx.get("trace") if passes(ctx) else None
    roots: List[Node] = []
    for _line, events in (trace or {}).get("_host_lines") or ():
        stack: List[Node] = []
        spans = [e for e in events
                 if e[0].startswith(PREFIXES + (RESIDENT,))]
        for name, lo, hi in sorted(spans, key=lambda e: (e[1], -e[2])):
            while stack and stack[-1].hi <= lo:
                stack.pop()
            node = Node(name, lo, hi)
            (stack[-1].children if stack else roots).append(node)
            stack.append(node)
    return roots


def call_and_inside_s(ctx: dict, name: str) -> Optional[Tuple[float, float]]:
    """(seconds of the traced `recover.call` spans, seconds of the spans
    `name` inside them); None where the trace holds no call or no such
    span inside one."""
    if ctx.get("rehearse"):
        return None
    calls = [n for root in forest(ctx) for n in root.walk() if n.name == CALL]
    inside = sum(n.seconds for call in calls for n in call.walk()
                 if n.name == name)
    if not calls or not inside:
        return None
    return sum(c.seconds for c in calls), inside


def traced_suffix_rows(ctx: dict) -> int:
    """The suffix rows both device passes appended in the traced passes
    (`RecoveryReport.suffix_rows`, by pass); 0 where the report has none."""
    return sum(int(p.get("report", {}).get("suffix_rows", {}).get(leg, 0))
               for p in passes(ctx) if p.get("traced")
               for leg in ("rebuild", "verify"))
