"""Driver of the served deployment a user gets from `rpc/cluster.launch()`
unchanged: the served driver (`drivers/serve.py`) with the host's snapshot
tier at its defaults (no `CADENCE_TPU_SNAPSHOT*` variable set, so
`engine/snapshot.enabled()` reads on), and three more numbers that decide
`correct`.

The cluster, the load, the window, `op_p50_ms` and every number
`serve.Driver.check()` compares are that driver's, so this cell and
`serve.standard` read the same traffic on one yardstick and their quotient
is what the snapshot tier costs the served path. Added:

- `snapshot_crc_mismatch` (limit 0): EVERY record the store server's
  snapshot store holds once the window has closed, read back through
  `rpc/client.RemoteStores` (`snapshot.items()`), not a sample: the CRC32
  of the record's payload row against the plain reference's replay of the
  run's persisted history through the record's `batch_count`. The
  program's payload and the reference's both leave the sticky slot 0, as
  the twin check compares them;
- `snapshot_state_crc_mismatch` (limit 0): the same records, by the state
  a hydration would admit: the record's `state_blob` checked against its
  `blob_crc`, unpacked at the program's layout and projected to its
  payload row (`snapshot_state_rows.py`, a child on the CPU), whose CRC32
  must equal the same reference's. The payload row is what the writer's
  own checksum gate matched before its `put`; the state is what a cold
  admit or a restart goes on from;
- `snapshot_write_errors` (limit 0): `tpu.snapshot/write-errors` over the
  window, read through the host's `admin_metrics`;
- `snapshot_gate_chains_short_of_floor` (limit 0): how far
  `tpu.snapshot/gate-chains` over the window, the policy's gate chains,
  written or not, falls short of the configuration's
  `snapshot_gate_chains_in_window_at_least`. A host whose tier is off runs
  none: that is another deployment.

`tpu.snapshot/writes` over the window is printed in the run's record and
not held to a floor: at the program's defaults this traffic's window
writes none (PERF.md, section 6, `serve.standard-snap`), so on the chip
the two record checks compare the few records there are, often none.

A host that lacks one of the tier's counters (a program from before
`write-errors` and `gate-chains`) cannot state what this configuration
guarantees: the run says which, and ends before set-up goes on, with an
exit code other than 0. Such a program's snapshot writer failed serving
flushes on a service host (PERF.md, section 7).
"""
from __future__ import annotations

import importlib.util
import json
import os
import pickle
import subprocess
import sys
import time
import zlib
from typing import Dict, List, Optional

from harness import Compared, say

_spec = importlib.util.spec_from_file_location(
    "drivers._serve_under_snap",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve.py"))
_serve = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_serve)

SNAPSHOT_SCOPE = "tpu.snapshot"
#: the tier's counters taken over the window, after minus before
WINDOW_COUNTERS = ("writes", "write-errors", "gate-chains", "hydrates",
                   "checksum-skips", "ignored-stale", "ignored-torn")


def payload_crc(payload) -> int:
    """The CRC32 of a payload row as the plain reference computes its own
    (little-endian int64, `refimpl/replay.crc32`)."""
    from refimpl import replay as reference

    return reference.crc32(int(v) for v in payload)


def hydrated_rows(records) -> List[Optional[List[int]]]:
    """The payload row of the state each record would hydrate to, None
    where its blob does not decode, from a child on the CPU."""
    if not records:
        return []
    blobs = [(rec.state_blob, tuple(rec.layout)) for _key, rec in records]
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "snapshot_state_rows.py")],
        input=pickle.dumps(blobs), capture_output=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError("snapshot_state_rows.py failed: "
                           + proc.stderr.decode()[-2000:])
    return json.loads(proc.stdout.decode().strip().splitlines()[-1])


class Driver(_serve.Driver):

    def _snapshot_counters(self) -> Dict[str, Optional[float]]:
        """The host's `tpu.snapshot/*` counters; None for one it lacks."""
        doc = self.cluster.admin(self.host, "admin_metrics", timeout=60)
        scope = doc["snapshot"].get(SNAPSHOT_SCOPE, {})
        return {name: scope.get(name) for name in WINDOW_COUNTERS}

    def _launch(self):
        super()._launch()
        absent = [name for name, value in self._snapshot_counters().items()
                  if value is None]
        say(driver="serve_snap", counters_absent=absent)
        if absent:
            raise SystemExit(
                "this program's service host has no "
                f"{', '.join(SNAPSHOT_SCOPE + '/' + n for n in absent)}: it "
                "cannot state that a snapshot write fails no ticket, nor "
                "that the policy ran (its writer failed serving flushes on "
                "a service host: PERF.md, section 7); the cell "
                f"{self.cell['name']} cannot run on it")

    def run_window(self, seconds: float, trace_dir: Optional[str]) -> None:
        before = self._snapshot_counters()
        super().run_window(seconds, trace_dir)
        after = self._snapshot_counters()
        self.snapshot_window = {name: after[name] - before[name]
                                for name in WINDOW_COUNTERS}
        say(driver="serve_snap", snapshot_window=self.snapshot_window)

    def context(self, device: dict, reduced_trace: Optional[dict]) -> dict:
        ctx = super().context(device, reduced_trace)
        ctx["snapshot_window"] = self.snapshot_window
        return ctx

    def check(self) -> List[Compared]:
        from cadence_tpu.rpc.client import RemoteStores

        compared = super().check()
        t0 = time.perf_counter()
        stores = RemoteStores(("127.0.0.1", self.cluster.store_port))
        records = stores.snapshot.items()
        wants = [_serve.reference_crc(
            stores.history.as_history_batches(*key)[:rec.batch_count],
            self.opts.control) for key, rec in records]
        rows = hydrated_rows(records)
        mismatched = sum(payload_crc(rec.payload) != want
                         for (_key, rec), want in zip(records, wants))
        state_mismatched = sum(
            row is None or zlib.crc32(rec.state_blob) != rec.blob_crc
            or payload_crc(row) != want
            for (_key, rec), want, row in zip(records, wants, rows))
        window = self.snapshot_window
        errors, chains = window["write-errors"], window["gate-chains"]
        floor = int(self.config["snapshot_gate_chains_in_window_at_least"])
        say(driver="serve_snap", snapshot_check_s=time.perf_counter() - t0,
            snapshot_records=len(records),
            snapshot_records_mismatched=mismatched,
            snapshot_states_mismatched=state_mismatched,
            snapshots_written_in_window=window["writes"],
            write_errors=errors, gate_chains=chains,
            gate_chains_floor=floor, hydrates=window["hydrates"],
            checksum_skips=window["checksum-skips"])
        return compared + [
            Compared("snapshot_crc_mismatch", mismatched, 0),
            Compared("snapshot_state_crc_mismatch", state_mismatched, 0),
            Compared("snapshot_write_errors", errors, 0),
            Compared("snapshot_gate_chains_short_of_floor",
                     max(0, floor - chains), 0),
        ]
