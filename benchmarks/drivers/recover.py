"""Driver of a cold restart: a history host that was killed and is started
again over the write-ahead log it was writing, in the one process that owns
both the log and the chip.

Set-up makes the corpus from the seed (`recover_corpus.py`: every history
batch by batch, with the reference's verdict, in worker processes), writes
ONE log through the program's own writer (`open_durable_stores(path)` and
the `Stores` API: the domains, the shards' records, then for every committed
batch the `h` record and the `cur` record its commit logs) and runs one whole
recovery as warm-up. The timed entry is
`cadence_tpu.engine.durability.recover_stores(path)` with every argument at
its default: the log read and replayed into fresh stores, every run's state
rebuilt on the device and hydrated, upserted, then verified on the device.

Every timed recovery is cold. What a pass leaves is looked at OUTSIDE the
timed span (every execution's payload CRC, its history against what the log
was handed, its pointer and visibility record), then its stores are dropped,
their log handle closed and the garbage collected before the next pass
starts: no object of a pass lives into the next. `replay_events_per_s` is the
events of the window's recoveries over their own wall time; an event counts
once a pass, though the path replays it twice (rebuild, then verify).
"""
from __future__ import annotations

import gc
import os
import shutil
import time
import zlib
from typing import Dict, List, Optional, Tuple

import recover_corpus
from harness import Compared, CompileCounter, say

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: utils/metrics.SCOPE_TPU_RECOVER, spelled out: a program from before the
#: counters has no such name, and the cell runs on it too
RECOVER_SCOPE = "tpu.recover"
REPORT_COUNTS = ("executions_rebuilt", "open_workflows", "device_rebuilt",
                 "rebuild_fallback", "snapshot_hydrated", "device_verified",
                 "oracle_fallback")
REPORT_LISTS = ("divergent", "quarantined")
#: the legs that lie side by side under `recover.call`, as
#: `RecoveryReport.seconds` names them (`upsert` lies inside `rebuild`)
TOP_LEGS = ("log-replay", "rebuild", "verify", "reconcile")

Key = Tuple[str, str, str]


def recover(path: str):
    """The program's entry, alone in a function so that a test can break
    it underneath the harness."""
    from cadence_tpu.engine.durability import recover_stores

    return recover_stores(path)


def domain_id(d: int) -> str:
    return f"recover-domain-{d}-id"


def write_log(path: str, keys: List[Key], histories: List[dict],
              domains: int, shards: int) -> dict:
    """One log of the whole corpus through the program's own writer, every
    record one the commit path logs; what it holds, counted."""
    from cadence_tpu.core.codec import deserialize_history
    from cadence_tpu.engine.durability import (current_run_record,
                                               open_durable_stores)
    from cadence_tpu.engine.persistence import CurrentExecution, DomainInfo

    stores = open_durable_stores(path)
    for d in range(domains):
        stores.domain.register(DomainInfo(domain_id=domain_id(d),
                                          name=f"recover-domain-{d}"))
    for shard_id in range(shards):  # a host's acquire: owner and range id
        info = stores.shard.get_or_create(shard_id)
        held = info.range_id
        info.owner, info.range_id = "recover-host", held + 1
        stores.shard.update(info, expected_range_id=held)
    for key, history in zip(keys, histories):
        for blob, pointer in zip(history["blobs"], history["pointers"]):
            (batch,) = deserialize_history(blob, *key)
            stores.history.append_batch(*key, batch.events, blob=blob)
            stores.wal.append(current_run_record(
                key[0], key[1], CurrentExecution(key[2], *pointer)))
    stores.wal.close()
    batches = sum(len(h["blobs"]) for h in histories)
    return {"log_bytes": os.path.getsize(path),
            "history_bytes": sum(len(b) for h in histories
                                 for b in h["blobs"]),
            "history_batches": batches,
            "records": domains + shards + 2 * batches}


def file_crc(path: str) -> int:
    crc = 0
    with open(path, "rb") as fh:
        for block in iter(lambda: fh.read(1 << 22), b""):
            crc = zlib.crc32(block, crc)
    return crc


def report_fields(report) -> dict:
    out = {name: int(getattr(report, name)) for name in REPORT_COUNTS}
    out.update({name: len(getattr(report, name)) for name in REPORT_LISTS})
    return out


class Driver:
    # this process holds the chip

    def __init__(self, cell: dict, config: dict, traffic: dict, opts) -> None:
        self.cell, self.opts = cell, opts
        self.config, self.traffic = dict(config), dict(traffic)
        if opts.rehearse:
            self.config.update(config.get("rehearse", {}))
            self.traffic.update(traffic.get("rehearse", {}))
        self.suites = list(self.traffic["suites"])
        self.per_suite = int(self.config["workflows_per_suite"])
        self.target_events = int(self.config["target_events"])
        self.runs = len(self.suites) * self.per_suite
        self.passes: List[dict] = []
        self.traced_window_s = 0.0
        self.farm = None
        self.log_dir = os.path.join(
            ROOT, ".bench_out", "wal",
            f"{cell['name']}-{opts.seed}-{os.getpid()}")
        self.path = os.path.join(self.log_dir, "wal.jsonl")

    # -- set-up ------------------------------------------------------------

    def setup(self) -> dict:
        # the corpus workers start before this process touches JAX
        self.farm = recover_corpus.HistoryFarm(
            self.suites, self.per_suite, self.target_events, self.opts.seed,
            control=self.opts.control,
            slice_w=int(self.traffic.get("slice_workflows", 128)))
        import jax
        import numpy as np

        from cadence_tpu.utils import compile_cache
        from cadence_tpu.utils import metrics as m

        cache = compile_cache.enable()
        self.compiles = CompileCounter()
        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        say(driver="recover", device=device, compile_cache=cache,
            corpus_workers=self.farm.workers)
        want = int(self.cell["chips"])
        if not self.opts.rehearse and (device["platform"] != "tpu"
                                       or len(devices) < want):
            raise SystemExit(f"needs {want} TPU chip(s), JAX found {device}")
        self.jax, self.m, self.np = jax, m, np
        self.devices = devices[:want]
        t0 = time.perf_counter()
        histories = self.farm.collect()
        self.farm.close()
        t1 = time.perf_counter()
        domains = int(self.config["domains"])
        self.keys: List[Key] = []
        for j in range(self.runs):
            suite, i = recover_corpus.corpus_mod.corpus_key(self.suites, j)
            self.keys.append((domain_id(j % domains), f"{suite}-wf-{i}",
                              f"run-{i}"))
        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir)
        self.log = write_log(self.path, self.keys, histories, domains,
                             int(self.config["shards"]))
        self.log["crc"] = file_crc(self.path)
        # what the log was handed, and the reference's answers
        self.handed = [h["handed"] for h in histories]
        self.real_events = sum(n for h in self.handed for _first, n in h)
        self.want_crc = np.asarray([h["crc"] for h in histories],
                                   dtype=np.int64)
        self.control_crc = np.asarray(
            [h["control_crc"] for h in histories], dtype=np.int64) \
            if self.opts.control else None
        self.want_open = sum(h["open"] for h in histories)
        del histories
        say(driver="recover", corpus_s=t1 - t0,
            log_write_s=time.perf_counter() - t1, runs=self.runs,
            events=self.real_events, open_workflows=self.want_open,
            **self.log)
        # warm-up: one whole recovery, so that every program the window
        # uses (both rebuild chunk shapes, the verify, the pool's row
        # slices) is compiled or loaded before it
        warm = self._pass()
        say(driver="recover", warm_pass_s=warm["wall_s"],
            compiled=self.compiles.total, cache_hits=self.compiles.hits,
            report=warm["report"], seconds=warm["seconds"])
        return device

    def _pass(self, before=None, after=None) -> dict:
        """One cold recovery, timed, then looked at and dropped. `before`
        and `after` run just outside the timed span (the trace's start and
        stop)."""
        gc.collect()
        size0 = os.path.getsize(self.path)
        if before:
            before()
        t0 = time.perf_counter()
        stores, report = recover(self.path)
        t1 = time.perf_counter()
        if after:
            after()
        one = {"wall_s": t1 - t0, "traced": False,
               "events": self.real_events, "report": report_fields(report),
               "seconds": {k: float(v) for k, v in
                           (getattr(report, "seconds", None) or {}).items()},
               "log_bytes_changed":
                   int(os.path.getsize(self.path) != size0)}
        del report
        one.update(self._look(stores))
        stores.wal.close()
        del stores
        gc.collect()
        return one

    def _look(self, stores) -> dict:
        """What a pass left, against what the log was handed: every
        execution's payload CRC32 (-1 where it has none), the batches of
        its history that are missing or out of place, and the runs without
        their current-run pointer or their visibility record."""
        from cadence_tpu.core.checksum import crc32_of_row, payload_row

        np = self.np
        crcs = np.full(self.runs, -1, dtype=np.int64)
        rebuilt = set(stores.execution.list_executions())
        held = set(stores.history.list_runs())
        seen = set()
        for d in range(int(self.config["domains"])):
            for rec in (stores.visibility.list_open(domain_id(d))
                        + stores.visibility.list_closed(domain_id(d))):
                seen.add((rec.domain_id, rec.workflow_id, rec.run_id))
        missing = unpointed = 0
        for j, key in enumerate(self.keys):
            if key in rebuilt:
                try:
                    crcs[j] = crc32_of_row(payload_row(
                        stores.execution.get_workflow(*key))) & 0xFFFFFFFF
                except OverflowError:  # a state past the payload's tables
                    pass
            got = [(b[0].id, len(b)) for b in
                   stores.history.read_batches(*key)] if key in held else []
            want = self.handed[j]
            missing += len(want) - sum(
                1 for g, w in zip(got, want) if g == w) \
                + max(0, len(got) - len(want))
            try:
                pointed = stores.execution.get_current_run_id(
                    key[0], key[1]) == key[2]
            except Exception:  # no pointer for this workflow at all
                pointed = False
            unpointed += int(not pointed or key not in seen)
        return {"crcs": crcs, "acked_batches_missing": missing,
                "pointer_or_visibility_missing": unpointed}

    # -- the window --------------------------------------------------------

    def run_window(self, seconds: float, trace_dir: Optional[str]) -> None:
        compiles0 = self.compiles.total
        trace_after = int(self.traffic.get("trace_after_passes", 0))
        trace_passes = int(self.traffic.get("trace_passes", 1))
        at_least = int(self.traffic.get("passes_at_least", 1))
        marks: Dict[str, float] = {}

        def start_trace() -> None:
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir, exist_ok=True)
            options = self.jax.profiler.ProfileOptions()
            # the host's Python is the system under test, and its spans
            # are TraceAnnotations already
            options.python_tracer_level = 0
            self.jax.profiler.start_trace(trace_dir,
                                          profiler_options=options)
            marks["t0"] = time.perf_counter()

        def stop_trace() -> None:
            self.traced_window_s += time.perf_counter() - marks["t0"]
            self.jax.profiler.stop_trace()

        # the passes a traced run owes its trace before it may stop
        owed = trace_after + trace_passes if trace_dir else 0
        t_window = time.perf_counter()
        while True:
            n = len(self.passes)
            traced = trace_after <= n < owed
            one = self._pass(start_trace, stop_trace) if traced \
                else self._pass()
            one["traced"] = traced
            self.passes.append(one)
            if time.perf_counter() - t_window >= seconds \
                    and len(self.passes) >= max(at_least, owed):
                break
        #: the recoveries' own wall time: what lies between two passes is
        #: the benchmark's look at the last one, not recovery
        self.window_s = sum(p["wall_s"] for p in self.passes)
        self.compiles_in_window = self.compiles.total - compiles0

    def attempted_failed(self):
        return self.runs * len(self.passes), sum(
            max(0, self.runs - p["report"]["executions_rebuilt"])
            for p in self.passes)

    def end_to_end(self) -> Dict[str, float]:
        events = sum(p["events"] for p in self.passes)
        say(driver="recover", passes=len(self.passes), events=events,
            window_s=self.window_s,
            pass_s=[round(p["wall_s"], 4) for p in self.passes],
            report=self.passes[-1]["report"])
        for n, p in enumerate(self.passes):
            if p["seconds"]:
                # the top-level legs beside the call they should add up to
                legs = {leg: p["seconds"].get(leg, 0.0) for leg in TOP_LEGS}
                legs["rebuild-less-upsert"] = legs.pop("rebuild") \
                    - p["seconds"].get("upsert", 0.0)
                legs["upsert"] = p["seconds"].get("upsert", 0.0)
                call = p["seconds"]["call"]
                say(driver="recover", **{"pass": n}, traced=p["traced"],
                    call_s=call, legs_s=legs,
                    legs_over_call=sum(legs.values()) / call)
        return {"replay_events_per_s": events / self.window_s}

    def _counters(self) -> Dict[str, int]:
        counters, _gauges, _hists = self.m.DEFAULT_REGISTRY.raw_series()
        return {name: int(value) for (scope, name), value in counters.items()
                if scope == RECOVER_SCOPE}

    def context(self, device: dict, reduced_trace: Optional[dict]) -> dict:
        """What the per-layer readers are given."""
        ctx = {
            "kind": "recover", "device": device, "passes": self.passes,
            "window_s": self.window_s, "trace": reduced_trace,
            "traced_window_s": self.traced_window_s, "runs": self.runs,
            "history_bytes": self.log["history_bytes"],
            "counters": self._counters(),
            "rehearse": bool(self.opts.rehearse),
        }
        import _recover_common

        say(driver="recover", counters=ctx["counters"],
            traced_call=_recover_common.call_breakdown(ctx),
            device_modules=_recover_common.module_seconds(ctx))
        return ctx

    def memory_peak_bytes(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks) if peaks else 0

    def release(self) -> None:
        pass  # no pass's stores outlive it

    # -- what decides `correct` --------------------------------------------

    def check(self) -> List[Compared]:
        """Every execution of EVERY pass of the window against the plain
        reference and against what the log was handed, every pass's report
        against the reference's counts, and the faults of the run."""
        np = self.np
        mismatched = np.zeros(self.runs, dtype=bool)
        for p in self.passes:
            # the control in the program's place: its answers stand where
            # the recovered states' would
            got = self.control_crc if self.opts.control else p["crcs"]
            mismatched |= got != self.want_crc
        reports = [p["report"] for p in self.passes]
        changed = sum(p["log_bytes_changed"] for p in self.passes) \
            + int(os.path.getsize(self.path) != self.log["log_bytes"]
                  or file_crc(self.path) != self.log["crc"])
        say(driver="recover", executions_compared=self.runs,
            passes_compared=len(self.passes))
        return [
            Compared("state_crc_mismatch", int(mismatched.sum()), 0),
            Compared("acked_batches_missing", sum(
                p["acked_batches_missing"] for p in self.passes), 0),
            Compared("divergent", sum(r["divergent"] for r in reports), 0),
            Compared("executions_not_rebuilt", sum(
                abs(self.runs - r["executions_rebuilt"])
                for r in reports), 0),
            Compared("open_workflows_differing", sum(
                abs(r["open_workflows"] - self.want_open)
                for r in reports), 0),
            Compared("pointer_or_visibility_missing", sum(
                p["pointer_or_visibility_missing"]
                for p in self.passes), 0),
            Compared("rows_not_on_device", sum(
                abs(self.runs - r["device_rebuilt"])
                + abs(self.runs - r["device_verified"])
                + r["rebuild_fallback"] + r["oracle_fallback"]
                for r in reports), 0),
            Compared("compiles_in_window", self.compiles_in_window, 0),
            Compared("log_bytes_changed", changed, 0),
        ]

    def close(self) -> None:
        if self.farm is not None:
            self.farm.close()
        shutil.rmtree(self.log_dir, ignore_errors=True)
