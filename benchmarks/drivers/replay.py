"""Driver of a bulk-replay deployment: a recovering or promoted history host
getting its state back.

The timed entry is the program's `native/feeder.feed_serialized_wirec`
(native pack -> H2D -> device decode + replay + CRC -> readback) over a
corpus of serialized histories that set-up makes from the seed. The window
calls it over the whole corpus again and again; every call ends by reading
its CRCs and error flags back. One process: this one opens the chips.
"""
from __future__ import annotations

import os
import shutil
import time
from typing import Dict, List, Optional

import corpus as corpus_mod
from harness import Compared, CompileCounter, say

LEGS = ("pack", "pack-queue-wait", "h2d", "kernel", "readback")
LEG_SCOPE = "tpu.replay-engine"


def feed(blobs, max_events, chunk_workflows, mesh):
    """The program's entry, alone in a function so that a test can break
    it underneath the harness."""
    from cadence_tpu.native.feeder import feed_serialized_wirec

    return feed_serialized_wirec(blobs, max_events,
                                 chunk_workflows=chunk_workflows, mesh=mesh)


class Driver:
    # this process holds the chips

    def __init__(self, cell: dict, config: dict, traffic: dict, opts) -> None:
        self.cell, self.opts = cell, opts
        self.config, self.traffic = dict(config), dict(traffic)
        if opts.rehearse:
            self.config.update(config.get("rehearse", {}))
            self.traffic.update(traffic.get("rehearse", {}))
        self.suites = list(self.traffic["suites"])
        self.per_suite = int(self.config["workflows_per_suite"])
        self.target_events = int(self.config["target_events"])
        self.calls: List[dict] = []
        self.traced_window_s = 0.0
        self.farm = None
        self.mesh = None
        self.blobs = None

    # -- set-up ------------------------------------------------------------

    def setup(self) -> dict:
        # the corpus workers start before this process touches JAX
        self.farm = corpus_mod.CorpusFarm(
            self.suites, self.per_suite, self.target_events, self.opts.seed,
            slice_w=int(self.traffic.get("slice_workflows", 512)))
        import jax

        from cadence_tpu.utils import compile_cache
        from cadence_tpu.utils import metrics as m

        cache = compile_cache.enable()
        self.compiles = CompileCounter()
        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        say(driver="replay", device=device, compile_cache=cache,
            corpus_workers=self.farm.workers)
        want = int(self.cell["chips"])
        if not self.opts.rehearse and (device["platform"] != "tpu"
                                       or len(devices) < want):
            raise SystemExit(f"needs {want} TPU chip(s), JAX found {device}")
        self.jax, self.m = jax, m
        self.devices = devices[:want]
        n_mesh = int(self.config["mesh_devices"])
        if n_mesh > 1:
            from cadence_tpu.parallel.mesh import make_mesh

            if len(devices) < n_mesh:
                raise SystemExit(f"the mesh needs {n_mesh} devices, JAX "
                                 f"found {len(devices)}")
            self.mesh = make_mesh(devices[:n_mesh])
            self.devices = devices[:n_mesh]
        t0 = time.perf_counter()
        self.blobs, longest, self.real_events = self.farm.collect()
        self.farm.close()
        # the shape the feeder is handed is the traffic file's, the same
        # for every seed: no seed's longest history may pass it
        self.max_events = int(self.traffic["max_events"])
        if longest > self.max_events:
            raise SystemExit(
                f"seed {self.opts.seed} made a history of {longest} events: "
                f"over the traffic file's max_events, {self.max_events}")
        say(driver="replay", corpus_s=time.perf_counter() - t0,
            workflows=len(self.blobs), events=self.real_events,
            longest=longest, max_events=self.max_events,
            blob_bytes=sum(len(b) for b in self.blobs))
        # warm-up: one whole pass, so that every executable a profile
        # refit needs exists before the window
        t0 = time.perf_counter()
        warm = self._call()
        say(driver="replay", warm_pass_s=time.perf_counter() - t0,
            refits=warm["refits"], native=warm["native"],
            chunks=warm["chunks"], compiled=self.compiles.total,
            cache_hits=self.compiles.hits)
        self.calls.clear()
        return device

    def _leg_totals(self) -> Dict[str, float]:
        reg = self.m.DEFAULT_REGISTRY
        return {leg: reg.histogram(LEG_SCOPE, leg).total for leg in LEGS}

    def _call(self) -> dict:
        reg = self.m.DEFAULT_REGISTRY
        legs0 = self._leg_totals()
        py0 = reg.counter(self.m.SCOPE_TPU_NATIVE, self.m.M_NATIVE_PY_PACKS)
        t0 = time.perf_counter()
        crc, err, rep = feed(self.blobs, self.max_events,
                             int(self.config["chunk_workflows"]), self.mesh)
        t1 = time.perf_counter()
        legs1 = self._leg_totals()
        call = {
            "t0": t0, "t1": t1, "wall_s": t1 - t0, "crc": crc, "err": err,
            "workflows": int(rep.workflows), "events": int(rep.events),
            "chunks": int(rep.chunks), "wire_bytes": int(rep.wire_bytes),
            "pack_s": float(rep.pack_s),
            "pack_queue_wait_s": float(rep.pack_queue_wait_s),
            "h2d_s": float(rep.h2d_s), "refits": int(rep.profile_refits),
            "native": bool(rep.native_wirec),
            "python_packs": int(reg.counter(
                self.m.SCOPE_TPU_NATIVE, self.m.M_NATIVE_PY_PACKS) - py0),
            "legs": {leg: legs1[leg] - legs0[leg] for leg in LEGS},
        }
        self.calls.append(call)
        return call

    # -- the window --------------------------------------------------------

    def run_window(self, seconds: float, trace_dir: Optional[str]) -> None:
        compiles0 = self.compiles.total
        trace_after = int(self.traffic.get("trace_after_passes", 1))
        trace_passes = int(self.traffic.get("trace_passes", 2))
        tracing = False
        t0 = time.perf_counter()
        while True:
            n = len(self.calls)
            if trace_dir and not tracing and n == trace_after:
                shutil.rmtree(trace_dir, ignore_errors=True)
                os.makedirs(trace_dir, exist_ok=True)
                options = self.jax.profiler.ProfileOptions()
                # the pack threads and the consumer are the system under
                # test: their spans and the runtime's events are traced,
                # every Python call is not
                options.python_tracer_level = 0
                self.jax.profiler.start_trace(trace_dir,
                                              profiler_options=options)
                tracing, t_trace = True, time.perf_counter()
            call = self._call()
            call["traced"] = tracing
            if tracing and sum(c["traced"] for c in self.calls) >= trace_passes:
                self.traced_window_s = time.perf_counter() - t_trace
                self.jax.profiler.stop_trace()
                tracing, trace_dir = False, None
            now = time.perf_counter()
            if now - t0 >= seconds and not tracing:
                break
        self.window_s = now - t0
        self.compiles_in_window = self.compiles.total - compiles0

    def attempted_failed(self):
        return len(self.calls), sum(
            1 for c in self.calls if len(c["crc"]) != len(self.blobs))

    def end_to_end(self) -> Dict[str, float]:
        events = sum(c["events"] for c in self.calls)
        slowest = max(self.calls, key=lambda c: c["wall_s"])
        say(driver="replay", passes=len(self.calls), events=events,
            window_s=self.window_s,
            refits=sum(c["refits"] for c in self.calls),
            pass_s=[round(c["wall_s"], 4) for c in self.calls],
            # which of the program's legs the window's time went to
            legs_s={leg: sum(c["legs"][leg] for c in self.calls)
                    for leg in LEGS},
            # where a stalled pass spent its time, by the program's legs
            slowest=dict(
                {key: slowest[key] for key in (
                    "wall_s", "legs", "pack_s", "pack_queue_wait_s", "h2d_s",
                    "ladder_s") if key in slowest},
                **{"pass": self.calls.index(slowest)}))
        return {"replay_events_per_s": events / self.window_s}

    def context(self, device: dict, reduced_trace: Optional[dict]) -> dict:
        """What the per-layer readers are given."""
        return {
            "kind": "replay", "device": device, "calls": self.calls,
            "window_s": self.window_s, "trace": reduced_trace,
            "traced_window_s": self.traced_window_s,
            "kernel_modules": list(self.config["kernel_modules"]),
            "rehearse": bool(self.opts.rehearse),
        }

    def memory_peak_bytes(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks) if peaks else 0

    def release(self) -> None:
        self.blobs = None

    # -- what decides `correct` --------------------------------------------

    def check(self) -> List[Compared]:
        """Every answer of every call of the window against the plain
        reference on a sample drawn from the seed, every call against the
        first on every row, and the faults of the run."""
        import numpy as np

        n = len(self.suites) * self.per_suite
        sample = corpus_mod.draw_sample(
            self.suites, self.per_suite,
            int(self.traffic["reference_sample_per_suite"]), self.opts.seed)
        t0 = time.perf_counter()
        want = corpus_mod.reference_crcs(self.suites, sample, self.opts.seed,
                                         self.target_events)
        got_from = self.calls
        if self.opts.control:
            # the control in the program's place: its answers stand where
            # the timed path's would
            ctl = corpus_mod.reference_crcs(
                self.suites, sample, self.opts.seed, self.target_events,
                control=self.opts.control)
            row = np.zeros(n, dtype=np.uint32)
            for j, crc in ctl.items():
                row[j] = crc
            got_from = [{"crc": row, "err": np.zeros(n, dtype=np.int32)}]
        idx = np.asarray(sample)
        ref = np.asarray([want[j] for j in sample], dtype=np.uint32)
        mismatched = np.zeros(len(sample), dtype=bool)
        short = flags = disagree = 0
        first = got_from[0]
        for call in got_from:
            if len(call["crc"]) != n or len(call["err"]) != n:
                short += 1
                continue
            crc = np.asarray(call["crc"]).astype(np.uint32)
            mismatched |= crc[idx] != ref
            flags += int((np.asarray(call["err"]) != 0).sum())
            if call is not first and len(first["crc"]) == n \
                    and not self.opts.control:
                disagree += int((crc != np.asarray(first["crc"])
                                 .astype(np.uint32)).sum())
        say(driver="replay", reference_s=time.perf_counter() - t0,
            sample=len(sample), calls_compared=len(got_from))
        return [
            Compared("crc_mismatch_in_sample", int(mismatched.sum()), 0),
            Compared("error_flags", flags, 0),
            Compared("rows_differing_between_calls", disagree, 0),
            Compared("calls_short_of_rows", short, 0),
            Compared("compiles_in_window", self.compiles_in_window, 0),
            Compared("python_encoder_packs",
                     sum(c["python_packs"] for c in self.calls), 0),
        ]

    def close(self) -> None:
        if self.farm is not None:
            self.farm.close()
