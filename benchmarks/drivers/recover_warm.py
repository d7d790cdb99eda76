"""Driver of a warm restart: a history host that was brought up, swept by
`admin snapshot --sweep`, served on and was then killed, started again over
a log that holds its `snap` records. The recovery driver
(`drivers/recover.py`) with the log's writing replaced and three more
numbers that decide `correct`.

The timed entry is the same `recover_stores(path)` with every argument at
its default and no `CADENCE_TPU_*` variable set; the passes, the look at
what each leaves and `replay_events_per_s` (every event of the log's
histories once a recovery, whether the path replayed it or hydrated past
it) are that driver's, so the two recovery cells read the same histories on
one yardstick.

The log (set-up; every record written by the program's own writers, the
benchmark serialises no snapshot): for run `j` draw `s_j` uniform in 0..31
from the seed (`DEFAULT_EVERY_EVENTS` - 1: a host killed at a random
moment finds its last record 0 to 31 events behind the tip); the run's cut
is the last batch boundary that leaves at least `s_j` events after it, one
batch at least. (a) domains, shards and every run's `h` + `cur` records up
to its cut, as `drivers/recover.py` writes them; (b) that log brought up in
this process (`recover_stores(path)`), a `TPUReplayEngine` on its stores,
`verify_all()`, `snapshot_sweep(force=True)`: what `admin snapshot --sweep`
runs (cli.py); the sweep's tip and checksum gates decide which runs get a
record (the "eligible" runs); (c) the remaining batches and their `cur`
records through the same stores.

A program whose resident pool stacks an append chunk's rows in ONE program
cannot run this cell on the chip (2,048 rows x 66 leaves as operands: hours
in the TPU's compiler, PERF.md PR 36); set-up ends the run on it at once.
"""
from __future__ import annotations

import gc
import importlib.util
import os
import random
import time
from typing import Dict, List, Optional

from harness import Compared, say

_spec = importlib.util.spec_from_file_location(
    "drivers._recover_under_warm",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "recover.py"))
_recover = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_recover)

_program_recover = _recover.recover
_write_plain_log = _recover.write_log
#: `RecoveryReport`'s fields of the warm path: counts, and counts a device
#: pass. A program from before them reports none: -1, never a guess
WARM_COUNTS = ("snapshot_records", "verify_hydrated")
WARM_BY_PASS = ("exact_rows", "suffix_rows", "suffix_events")
#: the scopes whose counters are taken over the WINDOW (set-up's bring-up
#: and sweep count under the same names), and the two the window may not
#: move
SNAPSHOT_SCOPE = "tpu.snapshot"
SCOPES = (_recover.RECOVER_SCOPE, SNAPSHOT_SCOPE, "tpu.resident")
IGNORED = ("ignored-stale", "ignored-torn")
#: the programs of the warm path, by a part of their module's name
WARM_MODULES = ("replay_from_state_to_payload", "slice_row", "stack")
SUFFIX_EVENTS_BELOW = 32  # engine/snapshot.DEFAULT_EVERY_EVENTS


def recover(path: str):
    """The program's entry, alone in a function so that a test can break
    it underneath the harness."""
    return _program_recover(path)


def _report_fields(report) -> dict:
    out = _base_report_fields(report)
    for name in WARM_COUNTS:
        out[name] = int(getattr(report, name, -1))
    for name in WARM_BY_PASS:
        out[name] = {k: int(v) for k, v in
                     (getattr(report, name, None) or {}).items()}
    return out


_base_report_fields = _recover.report_fields
_recover.report_fields = _report_fields
# the recovery driver's `_pass` reaches the program through this module's
# `recover`
_recover.recover = lambda path: recover(path)


def cut_of(batch_events: List[int], events_after: int) -> int:
    """Batches before the cut: the last boundary that leaves at least
    `events_after` events after it; where the history has fewer, the
    nearest to it, its first batch's end."""
    left, cut = 0, len(batch_events)
    while cut > 1 and left < events_after:
        cut -= 1
        left += batch_events[cut]
    return cut


class Driver(_recover.Driver):

    def setup(self) -> dict:
        from cadence_tpu.engine import resident

        if not hasattr(resident, "STACK_BLOCK"):
            raise SystemExit(
                "this program's resident pool stacks the rows of an append "
                "chunk in one program, 66 operands a row: at this cell's "
                "2,048-row chunks the TPU's compiler takes hours (28 s at "
                "64 rows, 90 s at 128, 308 s at 256: PERF.md, PR 36); the "
                f"cell {self.cell['name']} cannot run on it")
        _recover.write_log = self._write_warm_log
        return super().setup()

    # -- the log -----------------------------------------------------------

    def _append(self, stores, key, history: dict, lo: int, hi: int) -> None:
        from cadence_tpu.core.codec import deserialize_history
        from cadence_tpu.engine.durability import current_run_record
        from cadence_tpu.engine.persistence import CurrentExecution

        for blob, pointer in zip(history["blobs"][lo:hi],
                                 history["pointers"][lo:hi]):
            (batch,) = deserialize_history(blob, *key)
            stores.history.append_batch(*key, batch.events, blob=blob)
            stores.wal.append(current_run_record(
                key[0], key[1], CurrentExecution(key[2], *pointer)))

    def _write_warm_log(self, path: str, keys, histories: List[dict],
                        domains: int, shards: int) -> dict:
        """The log as the module's docstring states it; what it holds,
        counted. Stands in `drivers/recover.py`'s `write_log`."""
        from cadence_tpu.engine.tpu_engine import TPUReplayEngine
        from cadence_tpu.ops.state import init_state

        rng = random.Random(f"{self.opts.seed}:snapshot-lag")
        cuts = [cut_of([n for _first, n in h["handed"]],
                       rng.randrange(SUFFIX_EVENTS_BELOW))
                for h in histories]
        t0 = time.perf_counter()
        # (a) the recovery driver's own writer, handed each run up to its cut
        _write_plain_log(path, keys, [
            {name: h[name][:cut] for name in ("blobs", "pointers")}
            for h, cut in zip(histories, cuts)], domains, shards)
        t1 = time.perf_counter()
        # (b) what `admin snapshot --sweep` runs on the host brought up
        stores, report = recover(path)
        t2 = time.perf_counter()
        engine = TPUReplayEngine(stores)
        engine.verify_all()
        t3 = time.perf_counter()
        before_sweep = os.path.getsize(path)
        sweep = engine.snapshot_sweep(force=True)
        snap_bytes = os.path.getsize(path) - before_sweep
        t4 = time.perf_counter()
        eligible = set(sweep.keys_written)
        floor = float(self.traffic["eligible_share_at_least"])
        if len(eligible) < floor * len(keys):
            raise SystemExit(
                f"the sweep wrote {len(eligible)} records for {len(keys)} "
                f"runs, under the traffic's floor of {floor}: this log is "
                "not the one the configuration states, and a recovery of "
                "it would not be a warm restart")
        del engine, report
        # (c) the host serves on: the batches after each run's cut
        for key, history, cut in zip(keys, histories, cuts):
            self._append(stores, key, history, cut, len(history["blobs"]))
        stores.wal.close()
        del stores
        gc.collect()
        t5 = time.perf_counter()

        after = [sum(n for _first, n in h["handed"][cut:])
                 for h, cut in zip(histories, cuts)]
        batches = sum(len(h["blobs"]) for h in histories)
        #: what `counts_recover_warm.py` is handed; a record an eligible run
        self.warm = {
            "state_row_bytes": sum(
                int(leaf.nbytes) for leaf in
                self.jax.tree_util.tree_leaves(init_state(1))),
            "eligible_runs": len(eligible),
            "suffix_bytes": sum(
                len(b) for key, h, cut in zip(keys, histories, cuts)
                if key in eligible for b in h["blobs"][cut:]),
            "cold_history_bytes": sum(
                len(b) for key, h in zip(keys, histories)
                if key not in eligible for b in h["blobs"]),
        }
        say(driver="recover_warm", cut_log_write_s=t1 - t0,
            bring_up_s=t2 - t1, verify_s=t3 - t2, sweep_s=t4 - t3,
            rest_write_s=t5 - t4,
            sweep={name: int(getattr(sweep, name)) for name in (
                "considered", "written", "skipped_policy",
                "skipped_checksum", "skipped_not_at_tip")},
            suffix_events_mean=sum(after) / len(after),
            suffix_events_max=max(after), **self.warm)
        return {"log_bytes": os.path.getsize(path),
                "history_bytes": sum(len(b) for h in histories
                                     for b in h["blobs"]),
                "history_batches": batches,
                "records": domains + shards + 2 * batches + sweep.written,
                "snap_records": sweep.written,
                "eligible_runs": len(eligible),
                "snap_bytes": snap_bytes,
                "suffix_events": sum(after),
                "exact_runs": sum(1 for n in after if n == 0)}

    # -- the window --------------------------------------------------------

    def _series(self) -> Dict[str, Dict[str, int]]:
        counters, _gauges, _hists = self.m.DEFAULT_REGISTRY.raw_series()
        out: Dict[str, Dict[str, int]] = {scope: {} for scope in SCOPES}
        for (scope, name), value in counters.items():
            if scope in out:
                out[scope][name] = int(value)
        return out

    def run_window(self, seconds: float, trace_dir: Optional[str]) -> None:
        before = self._series()
        super().run_window(seconds, trace_dir)
        #: what the window's recoveries counted, a scope
        self.window_series = {
            scope: {name: value - before[scope].get(name, 0)
                    for name, value in series.items()}
            for scope, series in self._series().items()}

    def context(self, device: dict, reduced_trace: Optional[dict]) -> dict:
        ctx = dict(super().context(device, reduced_trace), warm=self.warm,
                   window_counters=self.window_series[_recover.RECOVER_SCOPE])
        modules = (reduced_trace or {}).get("modules") or {}
        say(driver="recover_warm", window_series=self.window_series,
            warm_modules={name[:60]: [entry["seconds"], entry["runs"]]
                          for name, entry in modules.items()
                          if any(part in name for part in WARM_MODULES)}
            if not self.opts.rehearse else None)
        return ctx

    # -- what decides `correct` --------------------------------------------

    def check(self) -> List[Compared]:
        """The recovery cell's nine numbers as they stand, and three of
        the mechanism: every eligible run hydrated in BOTH device passes of
        every pass, no record passed over as stale or torn, and every
        record the log was handed installed by the log replay."""
        reports = [p["report"] for p in self.passes]
        eligible = self.warm["eligible_runs"]
        say(driver="recover_warm", eligible_runs=eligible,
            hydrated=[(r["snapshot_hydrated"], r["verify_hydrated"])
                      for r in reports],
            by_pass=[{name: r[name] for name in WARM_BY_PASS}
                     for r in reports])
        return super().check() + [
            Compared("snapshots_not_hydrated", sum(
                abs(eligible - r["snapshot_hydrated"])
                + abs(eligible - r["verify_hydrated"])
                for r in reports), 0),
            Compared("snapshots_ignored", sum(
                self.window_series[SNAPSHOT_SCOPE].get(name, 0)
                for name in IGNORED), 0),
            Compared("snap_records_missing", sum(
                abs(eligible - r["snapshot_records"])
                for r in reports), 0),
        ]
