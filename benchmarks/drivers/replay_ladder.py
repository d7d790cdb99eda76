"""Driver of a bulk-replay deployment whose histories do not all fit the
device's tables: the replay driver (`drivers/replay.py`) with what the
feeder's capacity-escalation ladder reports, and three more numbers that
decide `correct`.

The timed entry is the same `native/feeder.feed_serialized_wirec`: it
resolves capacity-flagged rows itself, inside the call. Each call's record
also carries the report's ladder fields; a program whose report lacks them
ends the run in set-up, on the warm-up pass.
"""
from __future__ import annotations

import importlib.util
import math
import os
import time
from typing import List

import corpus as corpus_mod
from harness import Compared, say

_spec = importlib.util.spec_from_file_location(
    "drivers._replay_under_ladder",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "replay.py"))
_replay = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_replay)

_program_feed = _replay.feed
LADDER_FIELDS = ("ladder_rows", "ladder_lanes", "ladder_events",
                 "ladder_wire_bytes", "ladder_resolved", "ladder_residual")
_reports: list = []


def feed(blobs, max_events, chunk_workflows, mesh):
    """The program's entry, alone in a function so that a test can break
    it underneath the harness."""
    return _program_feed(blobs, max_events, chunk_workflows, mesh)


def _feed_and_keep_report(*args):
    out = feed(*args)
    _reports.append(out[2])
    return out


# the replay driver's `_call` reaches the program through this module's
# `feed`, and leaves the report where the subclass finds it
_replay.feed = _feed_and_keep_report


class Driver(_replay.Driver):

    def _call(self) -> dict:
        call = super()._call()
        rep = _reports.pop()
        if not hasattr(rep, "ladder_indices"):
            raise SystemExit(
                "this program's FeedReport has no ladder fields: its "
                "feeder hands capacity-flagged rows back unresolved, and "
                f"the cell {self.cell['name']} cannot run on it")
        for field in LADDER_FIELDS:
            call[field] = int(getattr(rep, field))
        call["ladder_s"] = float(rep.ladder_s)
        call["escalated"] = [int(j) for j in rep.ladder_indices]
        return call

    def context(self, device, reduced_trace) -> dict:
        return dict(super().context(device, reduced_trace),
                    ladder_kernel_modules=list(
                        self.config["ladder_kernel_modules"]))

    def end_to_end(self):
        say(driver="replay_ladder",
            flagged=[len(c["escalated"]) for c in self.calls],
            ladder_s=[round(c["ladder_s"], 4) for c in self.calls],
            **{field: sum(c[field] for c in self.calls)
               for field in LADDER_FIELDS})
        return super().end_to_end()

    def check(self) -> List[Compared]:
        """The replay driver's six numbers, then: every row any call
        reports as escalated against the plain reference, in every call;
        rows no rung resolved; and calls that flagged too few rows to have
        measured the ladder."""
        import numpy as np

        compared = super().check()
        n = len(self.suites) * self.per_suite
        rows = sorted({j for c in self.calls for j in c["escalated"]})
        t0 = time.perf_counter()
        want = corpus_mod.reference_crcs(self.suites, rows, self.opts.seed,
                                         self.target_events)
        ref = np.asarray([want[j] for j in rows], dtype=np.uint32)
        idx = np.asarray(rows, dtype=np.int64)
        mismatched = np.zeros(len(rows), dtype=bool)
        if self.opts.control:
            ctl = corpus_mod.reference_crcs(
                self.suites, rows, self.opts.seed, self.target_events,
                control=self.opts.control)
            mismatched |= np.asarray([ctl[j] for j in rows],
                                     dtype=np.uint32) != ref
        else:
            for call in self.calls:
                if len(call["crc"]) == n:
                    mismatched |= np.asarray(call["crc"]).astype(
                        np.uint32)[idx] != ref
        say(driver="replay_ladder", reference_s=time.perf_counter() - t0,
            escalated_rows_compared=len(rows))
        floor = float(self.config["flagged_share_floor"])
        return compared + [
            Compared("escalated_crc_mismatch", int(mismatched.sum()), 0),
            Compared("ladder_residual_rows",
                     sum(c["ladder_residual"] for c in self.calls), 0),
            Compared("flagged_rows_short_of_floor", sum(
                max(0, math.ceil(floor * c["workflows"])
                    - len(c["escalated"])) for c in self.calls), 0),
        ]
