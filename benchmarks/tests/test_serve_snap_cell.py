"""Tests of the cell `serve.standard-snap`: a rehearsed run's last line,
the faults that must each make it not correct (a record's payload row or
its packed state altered in the store server, a host with the snapshot
tier off underneath, the control), a host without the tier's counters,
which cannot run the cell, and the three readers of the snapshot policy
on hand-made span forests and counter pairs.

    python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import json

import pytest
from test_recover_cell import _reader

CELL = "serve.standard-snap"
SNAP_COMPARED = ("snapshot_crc_mismatch", "snapshot_state_crc_mismatch",
                 "snapshot_write_errors",
                 "snapshot_gate_chains_short_of_floor")


def _rehearse(monkeypatch, capsys, control=""):
    """A tiny wire cluster on the CPU backend (8 pool workflows a domain,
    40 ops/s for 3 s), in this process; both snapshot policy knobs are 1
    in a rehearsal, so that it writes records."""
    import run

    argv = ["--workload", CELL, "--seed", str(2**31 + 40), "--seconds",
            "3", "--trace", "0", "--rehearse"]
    if control:
        argv += ["--control", control]
    assert run.main(argv) == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


def _json_lines(stderr: str, needle: str) -> list:
    return [json.loads(line) for line in stderr.splitlines()
            if line.startswith("{") and needle in line]


def test_rehearsed_cell_prints_a_well_formed_last_line(monkeypatch, capsys):
    last, err = _rehearse(monkeypatch, capsys)
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    assert last["attempted"] == 120
    compared = last["compared"]
    assert tuple(compared)[-4:] == SNAP_COMPARED
    assert all(compared[name]["value"] == 0 for name in SNAP_COMPARED)
    assert "rehearsal.op_p50_ms" in last["metrics"]
    (record,) = _json_lines(err, '"snapshot_records"')
    assert record["snapshots_written_in_window"] > 0
    assert record["snapshot_records"] > 0
    assert record["gate_chains"] >= record["snapshots_written_in_window"]


def _alter_records_in_the_store(monkeypatch, alter):
    """Once the window has closed, the store server's records handed to
    `alter` and the one it returns put back: the store keeps a record that
    no replay of the history gives."""
    from cadence_tpu.rpc.client import RemoteStores
    from cadence_tpu.rpc.cluster import Cluster

    real = Cluster.admin
    done = []

    def admin(self, name, op, *args, **kw):
        if op == "admin_cluster" and args and args[0] and not done:
            stores = RemoteStores(("127.0.0.1", self.store_port))
            stores.snapshot.put(alter([rec for _key, rec
                                       in stores.snapshot.items()]))
            done.append(1)
        return real(self, name, op, *args, **kw)

    monkeypatch.setattr(Cluster, "admin", admin)


def _alter_a_payload_row(monkeypatch):
    """One record's payload row with one value off by one."""
    def alter(records):
        rec = records[0]
        rec.payload = rec.payload.copy()
        rec.payload[5] += 1  # the signal count
        return rec

    _alter_records_in_the_store(monkeypatch, alter)
    return "snapshot_crc_mismatch"


def _alter_a_packed_state(monkeypatch):
    """One record's packed state replaced by another record's, whose
    payload differs, with its blob CRC made to match: a record whose
    payload row is right and whose state a hydration would admit is not."""
    import zlib

    def alter(records):
        rec = records[0]
        other = next(r for r in records[1:]
                     if (r.payload != rec.payload).any())
        rec.state_blob = other.state_blob
        rec.blob_crc = zlib.crc32(rec.state_blob)
        return rec

    _alter_records_in_the_store(monkeypatch, alter)
    return "snapshot_state_crc_mismatch"


def _turn_the_tier_off_underneath(monkeypatch):
    """The host started with CADENCE_TPU_SNAPSHOT=0, as the parity-audit
    configuration does: a served path that runs no snapshot policy."""
    monkeypatch.setenv("CADENCE_TPU_SNAPSHOT", "0")
    return "snapshot_gate_chains_short_of_floor"


@pytest.mark.parametrize("fault", [_alter_a_payload_row,
                                   _alter_a_packed_state,
                                   _turn_the_tier_off_underneath])
def test_cell_is_not_correct_with_a_fault_planted(monkeypatch, capsys,
                                                  fault):
    number = fault(monkeypatch)
    last, _err = _rehearse(monkeypatch, capsys)
    assert last["correct"] is False
    bad = [name for name, c in last["compared"].items()
           if c["value"] > c["limit"]]
    assert bad == [number], last["compared"]


def test_a_host_without_the_counters_cannot_run_the_cell(monkeypatch,
                                                          capsys):
    """A host whose `tpu.snapshot/*` lacks `write-errors` and `gate-chains`
    (the program from before them) ends the run at launch, before any
    pool is seeded, with an exit code other than 0, naming what it lacks."""
    import run
    from cadence_tpu.rpc.cluster import Cluster

    real = Cluster.admin

    def admin(self, name, op, *args, **kw):
        doc = real(self, name, op, *args, **kw)
        if op == "admin_metrics":
            for counter in ("write-errors", "gate-chains"):
                doc["snapshot"]["tpu.snapshot"].pop(counter, None)
        return doc

    monkeypatch.setattr(Cluster, "admin", admin)
    with pytest.raises(SystemExit) as ended:
        run.main(["--workload", CELL, "--seed", str(2**31 + 41),
                  "--seconds", "3", "--trace", "0", "--rehearse"])
    assert "tpu.snapshot/write-errors, tpu.snapshot/gate-chains" \
        in str(ended.value.code)
    assert CELL in str(ended.value.code)
    _out, err = capsys.readouterr()
    assert '"seeded"' not in err


def test_the_control_comes_out_not_correct(monkeypatch, capsys):
    last, _err = _rehearse(monkeypatch, capsys, control="drop-last-batch")
    assert last["correct"] is False
    compared = last["compared"]
    assert compared["twin_crc_mismatch"]["value"] > 0
    assert compared["snapshot_crc_mismatch"]["value"] > 0
    assert compared["snapshot_state_crc_mismatch"]["value"] > 0


# -- the readers ---------------------------------------------------------------


def _ms(lo: float, hi: float):
    return lo * 1e6, hi * 1e6


#: the drain thread's store round trips of three snapshot hooks, by
#: flush: two gate chains in the first (a due probe before each), one in
#: the second, none in the third (its one key is not due)
_HOOK_TRIPS = [
    ("store.snapshot.get", *_ms(42, 42.5)),
    ("store.snapshot.get", *_ms(42.6, 43)),
    ("store.history.batch_count", *_ms(43, 44)),
    ("store.snapshot.put", *_ms(45, 46)),
    ("store.snapshot.get", *_ms(46, 46.5)),
    ("store.history.branch_count", *_ms(47, 48)),
    ("store.snapshot.get", *_ms(68, 68.5)),
    ("store.history.batch_count", *_ms(68.6, 69.4)),
    ("store.snapshot.get", *_ms(84, 84.5)),
]
_HOOKS = [
    ("serving.snapshot", *_ms(42, 50)),
    ("serving.snapshot", *_ms(68, 70)),
    ("serving.snapshot", *_ms(84, 85)),
]
_CHAINS = [
    ("serving.snapshot-gate-chain", *_ms(42.5, 46)),
    ("serving.snapshot-gate-chain", *_ms(46.5, 49)),
    ("serving.snapshot-gate-chain", *_ms(68.5, 69.5)),
]


def _serve_ctx(program: str = "change", window=None) -> dict:
    """Three flushes on the drain thread, of 20, 10 and 5 ms, whose
    snapshot hooks take 8, 2 and 1 ms and hold 2, 1 and 0 gate chains
    (`program` "change"); the same round trips with hooks and no chain
    ("no-chain"), or in no hook at all, as the parent's program lays
    them ("parent"). A frontend op on another thread makes store round
    trips that no snapshot reader may count."""
    drain = [
        ("serving.flush", *_ms(30, 50)),
        ("serving.route", *_ms(30, 32)),
        ("resident.launch", *_ms(32, 34)),
        ("serving.parity", *_ms(34, 42)),
        ("serving.flush", *_ms(60, 70)),
        ("serving.launch", *_ms(61, 63)),
        ("serving.parity", *_ms(63, 68)),
        ("serving.flush", *_ms(80, 85)),
    ] + _HOOK_TRIPS
    if program != "parent":
        drain += _HOOKS
    if program == "change":
        drain += _CHAINS
    frontend = [
        ("rpc.frontend", *_ms(0, 20)),
        ("frontend.start-workflow-execution", *_ms(1, 19)),
        ("store.execution.get_workflow", *_ms(2, 4)),
    ]
    return {"kind": "serve",
            "trace": {"_host_lines": [("drain", drain),
                                      ("dispatch", frontend)]},
            "snapshot_window": window}


@pytest.mark.parametrize("metric,want", [
    ("serving.snapshot_ms_per_gate_chain", (8 + 2 + 1) / 3),
    ("serving.snapshot_round_trips_per_gate_chain", 9 / 3),
])
def test_snapshot_per_gate_chain_on_a_planted_forest(metric, want):
    read = _reader(metric).read
    assert read(_serve_ctx()) == pytest.approx(want)
    assert read(_serve_ctx("no-chain")) is None
    assert read(_serve_ctx("parent")) is None
    assert read({"kind": "serve", "trace": None}) is None
    assert read({"kind": "recover", "trace": None}) is None


@pytest.mark.parametrize("window,want", [
    ({"writes": 40, "gate-chains": 100}, 0.4),
    ({"writes": 0, "gate-chains": 613}, 0.0),     # chains that write nothing
    ({"writes": 0, "gate-chains": None}, None),   # the parent's program
    ({"writes": 0, "gate-chains": 0}, None),      # no chain: the tier is off
    (None, None),                                 # another cell
])
def test_writes_per_gate_chain_on_counter_pairs(window, want):
    read = _reader("snapshot.writes_per_gate_chain").read
    got = read(_serve_ctx(window=window))
    assert got == (pytest.approx(want) if want is not None else None)
