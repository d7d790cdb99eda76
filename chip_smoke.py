#!/usr/bin/env python3
"""chip_smoke.py — the system's three device paths, once, on the chip.

    python chip_smoke.py [--seed N]       one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4        the four-chip mesh path, nothing else
    python chip_smoke.py --rehearse       tiny sizes, for a CPU rehearsal

Phases, each through the entry points a user would call and each checked
by the repo's own means (the Python oracle, the tiers' own counters):

  bulk        all five corpus suites at the suite width of the
              benchmark's replay cells (16,384
              distinct workflows x ~120 events, seeded), packed by the
              native wirec encoder and replayed through the serving
              executor (engine/executor.stream_wirec_mesh, and the dense
              replay_corpus_mesh beside it): device CRC32 per workflow
              against the oracle on a seeded sample, zero error flags;
              the `overflow` suite through the capacity-escalation
              ladder with no row left to the oracle; one chunk of the
              fused generator+replay+CRC kernel with oracle spot parity.
  serve       a real wire cluster (store server + one service host as OS
              processes, serving tier on) driven over sockets: start,
              decide, signal, complete — then the HOST's own counters:
              platform tpu, every handed transaction's ticket ok,
              divergence/failures/bypasses 0, resident entries > 0.
  visibility  a VisibilityStore with the device view on at >= 100,000
              rows: List/Scan/Count equal to host evaluation, served by
              the device, no fallback.
  mesh        (--chips 4 only) the bulk corpus on a mesh of four and of
              one: CRCs identical elementwise, rows dispatched to every
              device, the fused shard_map kernel and the cross-shard
              stats psum once each.

One process for each chip: this parent never initialises a JAX backend.
Every phase is a child that holds the chip alone and exits before the
next starts; in `serve` the child is only a launcher and the service
host is the process on the chip. The device identity in the last line
comes from the children's reports.

The last line of standard output is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`.
The exit code is 0 only with `"ok": true`: every phase passed, every
fallback counter named above is zero, and the device is a TPU. Seconds
printed on the way are this run's wall clock (first call = compile +
run, warm = run alone, both ended by reading the result back); they are
a record of the run, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time

#: everything must be over inside the driver's 1200 s, compiles included
DEADLINE_S = 1150.0

SIZES = {
    # the sizes the benchmark's replay cells and ROADMAP call real
    "full": dict(
        suite_w=16384, target_events=120, oracle_sample=256, chunks=4,
        gen_slice=1024, fused_w=16384, fused_events=1000, fused_sample=64,
        serve_workflows=512, serve_signal_every=4, serve_clients=16,
        # the host boots as any CADENCE_TPU_SERVING=1 host does: the
        # tier's default max_batch and warm-up buckets
        serve_env={},
        vis_rows=100_000, vis_pages=3),
    # a CPU rehearsal of the same control flow
    "tiny": dict(
        suite_w=256, target_events=24, oracle_sample=32, chunks=2,
        gen_slice=64, fused_w=64, fused_events=40, fused_sample=8,
        serve_workflows=24, serve_signal_every=4, serve_clients=4,
        # a short warm-up: flushes 8 wide, the two event buckets this
        # workload reaches
        serve_env={"CADENCE_TPU_SERVING_BATCH": "8",
                   "CADENCE_TPU_SERVING_WARM_EVENTS": "16,32"},
        vis_rows=3000, vis_pages=2),
}

PHASES_ONE_CHIP = ("bulk", "serve", "visibility")
PHASES_FOUR_CHIPS = ("mesh",)


def say(**fields) -> None:
    """One JSON line of the run's record."""
    print(json.dumps(fields), flush=True)


# ---------------------------------------------------------------------------
# Parent: runs the phases as children, never touches JAX
# ---------------------------------------------------------------------------


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0


def _run_phase(name: str, args, deadline: float):
    """Run one phase as a child in its own session; stream its lines;
    return its report (the JSON of its last line) or None. Whatever the
    child started dies with it: the whole process group is killed."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
           "--seed", str(args.seed), "--chips", str(args.chips)]
    if args.rehearse:
        cmd.append("--rehearse")
    env = dict(os.environ)
    if args.rehearse and args.chips > 1 \
            and env.get("JAX_PLATFORMS", "").lower() == "cpu" \
            and "xla_force_host_platform_device_count" not in \
            env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={args.chips}").strip()
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    last = [""]

    def pump():
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line:
                last[0] = line
                print(line, flush=True)

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    timed_out = False
    try:
        proc.wait(timeout=max(1.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        timed_out = True
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
        reader.join(timeout=10)
    if timed_out:
        say(phase=name, ok=False, failures=["ran past the smoke's deadline"])
        return None
    try:
        report = json.loads(last[0])
    except ValueError:
        report = None
    if not isinstance(report, dict) or report.get("phase") != name \
            or "ok" not in report \
            or bool(report["ok"]) != (proc.returncode == 0):
        say(phase=name, ok=False,
            failures=[f"child exited rc={proc.returncode} without a report"])
        return None
    return report


def main_parent(args) -> int:
    t_start = time.monotonic()
    deadline = t_start + DEADLINE_S
    phases = PHASES_FOUR_CHIPS if args.chips == 4 else PHASES_ONE_CHIP
    try:
        from cadence_tpu.utils.compile_cache import cache_dir
    except ImportError as exc:
        say(failure=f"chip_smoke.py runs from the root of the repo: {exc}")
        print(json.dumps({"ok": False, "device": None}), flush=True)
        return 1
    cache = cache_dir()
    say(smoke="start", seed=args.seed, chips=args.chips,
        sizes="tiny" if args.rehearse else "full", phases=list(phases),
        compile_cache=cache, cache_entries=_cache_entries(cache))
    ok = True
    device = None
    for name in phases:
        before = _cache_entries(cache)
        t0 = time.monotonic()
        report = _run_phase(name, args, deadline)
        say(phase=name, summary=True,
            ok=bool(report and report.get("ok")),
            seconds=round(time.monotonic() - t0, 1),
            cache_entries_written=_cache_entries(cache) - before)
        if not report or not report.get("ok"):
            ok = False
            if report and report.get("device") and device is None:
                device = report["device"]
            break  # a later phase proves nothing after a failed one
        if device is None:
            device = report.get("device")
        elif report.get("device") != device:
            say(failure=f"phase {name} ran on {report.get('device')}, "
                        f"an earlier phase on {device}")
            ok = False
    if not device or device.get("platform") != "tpu":
        say(failure=f"the device is not a TPU: {device}")
        ok = False
    elif device.get("count") != args.chips:
        say(failure=f"wanted {args.chips} chip(s), JAX reports "
                    f"{device.get('count')}")
        ok = False
    say(smoke="end", seconds=round(time.monotonic() - t_start, 1))
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# Shared by the phase children
# ---------------------------------------------------------------------------


class CompileLog(logging.Handler):
    """What this process compiled and what it took from the persistent
    cache, read off JAX's own compiler log: the proof that a second run
    on the same machine compiles nothing the first one did."""

    def __init__(self) -> None:
        super().__init__(logging.DEBUG)
        self.hits = 0
        self.compiled = {}
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.addHandler(self)

    def emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith("PERSISTENT COMPILATION CACHE MISS for"):
            name = msg.split("'")[1]
            self.compiled[name] = self.compiled.get(name, 0) + 1
        elif msg.startswith("Persistent compilation cache hit for"):
            self.hits += 1


class Checks:
    """The phase's verdict: every failed expectation is kept and printed;
    nothing is caught and passed over (an exception ends the child with
    a traceback and no report, which the parent counts as a failure)."""

    def __init__(self, phase: str) -> None:
        self.phase = phase
        self.failures = []
        self.compile_log = None

    def expect(self, cond: bool, what: str) -> bool:
        if not cond:
            self.failures.append(what)
            say(phase=self.phase, failed=what)
        return bool(cond)

    def report(self, device) -> int:
        if self.compile_log is not None:
            say(phase=self.phase, compile_cache_hits=self.compile_log.hits,
                compiled_here=self.compile_log.compiled)
        say(phase=self.phase, ok=not self.failures, device=device,
            failures=self.failures)
        return 0 if not self.failures else 1


def _open_device(checks: Checks, rehearse: bool, chips: int):
    """Initialise the backend (this process now holds the chip), switch
    the compile cache on, and say what JAX found. Outside a rehearsal a
    platform other than tpu ends the phase before any work is done."""
    import jax

    from cadence_tpu.utils import compile_cache

    cache = compile_cache.enable()
    checks.compile_log = CompileLog()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(phase=checks.phase, device=device, compile_cache=cache,
        jax=jax.__version__)
    if not rehearse and device["platform"] != "tpu":
        checks.expect(False, f"no accelerator: JAX found {device}")
        raise SystemExit(checks.report(device))
    checks.expect(len(devices) >= chips,
                  f"need {chips} device(s), JAX found {len(devices)}")
    return device


def _gen_init() -> None:
    # corpus workers never go near a device, whatever the machine holds
    os.environ["JAX_PLATFORMS"] = "cpu"


def _gen_slice(job):
    """Worker: generate + encode one slice of one suite, the oracle's
    CRC32 for the sampled workflows that fall inside it and, where the
    suite is fed as wire bytes, the serialized histories."""
    suite, seed, lo, hi, target_events, sampled, serialized = job
    import numpy as np

    from cadence_tpu.core.checksum import (
        STICKY_ROW_INDEX,
        crc32_of_row,
        payload_row,
    )
    from cadence_tpu.core.codec import serialize_corpus
    from cadence_tpu.gen.corpus import generate_history
    from cadence_tpu.ops.encode import encode_corpus
    from cadence_tpu.oracle.state_builder import StateBuilder

    histories = [generate_history(suite, seed, i, target_events)
                 for i in range(lo, hi)]
    oracle = {}
    for i in sampled:
        row = payload_row(StateBuilder().replay_history(histories[i - lo]))
        row[STICKY_ROW_INDEX] = 0
        oracle[i] = int(np.uint32(crc32_of_row(row)))
    blobs = serialize_corpus(histories) if serialized else []
    return suite, lo, encode_corpus(histories), oracle, blobs


class CorpusFarm:
    """Seeded corpora made in bulk by worker processes while the phase
    process drives the device: `suite(name)` returns ([W, E, L] int64
    lanes, {sampled index: oracle CRC32}) and leaves the suite's wire
    blobs, if it is one of `serialized`, in `blobs[name]`. Must be started
    BEFORE this process opens the chip and is safe after it: the workers
    are pinned to the CPU platform and only run numpy and the Python
    oracle."""

    def __init__(self, suites, size, seed: int, sample_all=(),
                 serialized=()) -> None:
        import multiprocessing
        import random
        from concurrent.futures import ProcessPoolExecutor

        W, step = size["suite_w"], size["gen_slice"]
        jobs = []
        for suite in suites:
            n = W if suite in sample_all else min(size["oracle_sample"], W)
            picked = sorted(random.Random(
                f"{seed}:{suite}:oracle-sample").sample(range(W), n))
            for lo in range(0, W, step):
                hi = min(lo + step, W)
                jobs.append((suite, seed, lo, hi, size["target_events"],
                             [i for i in picked if lo <= i < hi],
                             suite in serialized))
        workers = max(1, min(len(jobs), (os.cpu_count() or 2) - 1))
        self._pool = ProcessPoolExecutor(
            max_workers=workers,
            mp_context=multiprocessing.get_context("spawn"),
            initializer=_gen_init)
        self._futures = {}
        self.blobs = {}
        for job in jobs:
            self._futures.setdefault(job[0], []).append(
                self._pool.submit(_gen_slice, job))
        self.workers = workers

    def suite(self, name: str):
        import numpy as np

        from cadence_tpu.ops.encode import LANE_EVENT_TYPE, NUM_LANES

        parts = sorted((f.result() for f in self._futures.pop(name)),
                       key=lambda part: part[1])
        E = max(part[2].shape[1] for part in parts)
        W = sum(part[2].shape[0] for part in parts)
        events = np.zeros((W, E, NUM_LANES), dtype=np.int64)
        events[:, :, LANE_EVENT_TYPE] = -1  # padding rows, as encode_corpus
        oracle = {}
        self.blobs[name] = []
        for _suite, lo, lanes, crcs, blobs in parts:
            events[lo:lo + lanes.shape[0], :lanes.shape[1]] = lanes
            oracle.update(crcs)
            self.blobs[name].extend(blobs)
        return events, oracle

    def close(self) -> None:
        self._pool.shutdown(wait=True, cancel_futures=True)


def _timed(fn):
    """(result, seconds); `fn` must end by reading its result back."""
    t0 = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - t0, 3)


def _oracle_divergence(crcs, oracle) -> int:
    return sum(1 for i, want in oracle.items() if int(crcs[i]) != want)


def _replay_suite(checks: Checks, suite: str, events, oracle, mesh, layout,
                  size):
    """One suite through both serving-executor paths on `mesh`: the
    compressed stream (native pack -> wirec -> CRC on device) and the
    dense one (int64 lanes -> payload rows). Returns (crcs, errors,
    wirec corpus)."""
    import numpy as np

    from cadence_tpu.core.checksum import crc32_of_rows
    from cadence_tpu.engine.executor import (
        replay_corpus_mesh,
        stream_wirec_mesh,
    )
    from cadence_tpu.native.wirec import h2d_path, pack_wirec_auto
    from cadence_tpu.ops.encode import LANE_EVENT_ID
    from cadence_tpu.utils import metrics as m

    W = events.shape[0]
    n = int(mesh.devices.size)
    real = int((events[:, :, LANE_EVENT_ID] > 0).sum())
    registry = m.DEFAULT_REGISTRY  # where every callee below counts
    py_before = registry.counter(m.SCOPE_TPU_NATIVE, m.M_NATIVE_PY_PACKS)
    corpus, pack_s = _timed(lambda: pack_wirec_auto(events))
    native = registry.counter(m.SCOPE_TPU_NATIVE,
                              m.M_NATIVE_PY_PACKS) == py_before

    def stream():
        crc, err, _rep = stream_wirec_mesh(corpus, mesh, layout,
                                           n_chunks=size["chunks"])
        return crc, err

    (crc, err), first_s = _timed(stream)
    (crc2, err2), warm_s = _timed(stream)

    chunk = -(-W // size["chunks"])

    def dense():
        rows, errors, _branch, _rep = replay_corpus_mesh(
            events, mesh, layout, chunk_workflows=chunk)
        return rows, errors

    (rows, errors), dense_first_s = _timed(dense)
    (rows2, _errors2), dense_warm_s = _timed(dense)

    flagged = int((err != 0).sum())
    divergent = _oracle_divergence(crc, oracle)
    say(phase=checks.phase, suite=suite, devices=n, workflows=W,
        events=real, event_axis=int(events.shape[1]),
        encoder="native" if native else "python", h2d=h2d_path(),
        wire_bytes_per_event=round(corpus.bytes_per_event(), 2),
        pack_s=pack_s, wirec_first_s=first_s, wirec_warm_s=warm_s,
        dense_first_s=dense_first_s, dense_warm_s=dense_warm_s,
        error_flags=flagged, oracle_sample=len(oracle),
        oracle_divergent=divergent)
    checks.expect(native, f"{suite}: packed by the pure-Python encoder")
    checks.expect(bool((crc == crc2).all() and (err == err2).all()),
                  f"{suite}: two runs of the wirec stream disagree")
    checks.expect(bool((rows == rows2).all()),
                  f"{suite}: two runs of the dense replay disagree")
    checks.expect(bool((errors == err).all())
                  and bool((crc32_of_rows(rows)[err == 0]
                            == crc[err == 0]).all()),
                  f"{suite}: dense rows and wirec CRCs disagree")
    checks.expect(flagged == 0, f"{suite}: {flagged} kernel error flags")
    checks.expect(divergent == 0,
                  f"{suite}: {divergent} of {len(oracle)} sampled "
                  "workflows diverge from the oracle")
    return np.asarray(crc), np.asarray(err), corpus


def _fused_chunk(checks: Checks, mesh, layout, size, seed: int):
    """One chunk of the fused generator+replay+CRC kernel on `mesh`,
    checked against the oracle on a seeded sample of its rows."""
    import numpy as np

    from cadence_tpu.core.checksum import (
        STICKY_ROW_INDEX,
        crc32_of_row,
        payload_row,
    )
    from cadence_tpu.ops.encode import decode_lanes
    from cadence_tpu.ops.genkernel import (
        generate_and_replay_sharded_crc,
        generate_lanes,
    )
    from cadence_tpu.oracle.state_builder import StateBuilder

    W, E = size["fused_w"], size["fused_events"]

    def run():
        crc, err = generate_and_replay_sharded_crc(seed, 0, W, E, mesh,
                                                   layout)
        return np.asarray(crc).astype(np.uint32), np.asarray(err)

    (crc, err), first_s = _timed(run)
    (crc2, _err2), warm_s = _timed(run)
    sample = min(size["fused_sample"], W)
    lanes = np.asarray(generate_lanes(seed, 0, sample, E))
    divergent = 0
    for i in range(sample):
        row = payload_row(
            StateBuilder().replay_history(decode_lanes(lanes[i])), layout)
        row[STICKY_ROW_INDEX] = 0
        divergent += int(np.uint32(crc32_of_row(row)) != crc[i])
    flagged = int((err != 0).sum())
    say(phase=checks.phase, kernel="fused generate+replay+crc",
        devices=int(mesh.devices.size), workflows=W, events=W * E,
        first_s=first_s, warm_s=warm_s, error_flags=flagged,
        oracle_sample=sample, oracle_divergent=divergent)
    checks.expect(bool((crc == crc2).all()),
                  "fused kernel: two runs disagree")
    checks.expect(flagged == 0, f"fused kernel: {flagged} error flags")
    checks.expect(divergent == 0, f"fused kernel: {divergent} of {sample} "
                  "sampled workflows diverge from the oracle")
    return crc


# ---------------------------------------------------------------------------
# Phase: bulk
# ---------------------------------------------------------------------------


def phase_bulk(args, size) -> int:
    checks = Checks("bulk")
    from cadence_tpu.gen.corpus import SUITES

    farm = CorpusFarm(SUITES + ("overflow",), size, args.seed,
                      sample_all=("overflow",), serialized=("overflow",))
    try:
        device = _open_device(checks, args.rehearse, 1)
        import numpy as np

        from cadence_tpu.core.checksum import DEFAULT_LAYOUT as layout
        from cadence_tpu.native.feeder import feed_serialized_wirec
        from cadence_tpu.parallel.mesh import make_mesh, serving_mesh
        from cadence_tpu.utils import metrics as m

        registry = m.DEFAULT_REGISTRY
        mesh = serving_mesh()  # the unconfigured default: a mesh of one
        say(phase="bulk", corpus_workers=farm.workers,
            mesh_devices=int(mesh.devices.size))
        for suite in SUITES:
            events, oracle = farm.suite(suite)
            _replay_suite(checks, suite, events, oracle, mesh, layout, size)

        # overflow: ~2.7% of workflows exceed the device's pending
        # tables; the serialized feeder re-replays exactly those at
        # widened K, on device, inside the call (the path the benchmark's
        # replay.overflow-1chip times), and nothing may be left for the
        # host oracle
        events, oracle = farm.suite("overflow")
        chunk = -(-events.shape[0] // size["chunks"])

        def feed():
            return feed_serialized_wirec(farm.blobs["overflow"],
                                         events.shape[1],
                                         chunk_workflows=chunk,
                                         layout=layout)

        (crc, err, rep), first_s = _timed(feed)
        (crc2, err2, rep), warm_s = _timed(feed)
        flagged = len(rep.ladder_indices)
        residual = int((err != 0).sum())
        divergent = _oracle_divergence(crc, oracle)
        say(phase="bulk", suite="overflow", ladder=True,
            workflows=int(events.shape[0]), events=int(rep.events),
            chunks=int(rep.chunks),
            encoder="native" if rep.native_wirec else "python",
            flagged=flagged, resolved_on_device=int(rep.ladder_resolved),
            residual_oracle_rows=residual,
            ladder_rows=int(rep.ladder_rows),
            ladder_lanes=int(rep.ladder_lanes),
            ladder_events=int(rep.ladder_events), ladder_s=rep.ladder_s,
            feed_first_s=first_s, feed_warm_s=warm_s,
            oracle_checked=len(oracle), oracle_divergent=divergent)
        checks.expect(rep.native_wirec,
                      "overflow: packed by the pure-Python encoder")
        checks.expect(bool((crc == crc2).all() and (err == err2).all()),
                      "overflow: two runs of the feeder disagree")
        checks.expect(flagged >= max(1, events.shape[0] // 100),
                      "overflow: the suite flagged almost nothing, so the "
                      "ladder was not exercised")
        checks.expect(residual == 0 and rep.ladder_residual == 0,
                      f"overflow: {residual} rows left to the host oracle")
        checks.expect(divergent == 0, f"overflow: {divergent} workflows "
                      "diverge from the oracle after the ladder")

        _fused_chunk(checks, make_mesh(), layout, size, args.seed)

        snap = registry.snapshot()
        native = snap.get(m.SCOPE_TPU_NATIVE, {})
        executor = snap.get(m.SCOPE_TPU_EXECUTOR, {})
        say(phase="bulk", counters={
            "tpu.native": {k: native.get(k, 0) for k in (
                m.M_NATIVE_AVAILABLE, m.M_NATIVE_PACKS,
                m.M_NATIVE_PY_PACKS, m.M_NATIVE_DECODE_PASSES)},
            "tpu.executor": {m.M_EXEC_CHUNKS: executor.get(m.M_EXEC_CHUNKS)},
        })
        checks.expect(native.get(m.M_NATIVE_AVAILABLE) == 1.0,
                      "tpu.native/available is not 1")
        checks.expect(native.get(m.M_NATIVE_PY_PACKS, 0) == 0,
                      "the pure-Python encoder served a pack")
        checks.expect((executor.get(m.M_EXEC_CHUNKS) or 0) > 0,
                      "the serving executor dispatched no chunk")
    finally:
        farm.close()
    return checks.report(device)


# ---------------------------------------------------------------------------
# Phase: mesh (--chips 4)
# ---------------------------------------------------------------------------


def phase_mesh(args, size) -> int:
    checks = Checks("mesh")
    from cadence_tpu.gen.corpus import SUITES

    farm = CorpusFarm(SUITES, size, args.seed)
    try:
        device = _open_device(checks, args.rehearse, args.chips)
        import jax
        import numpy as np

        from cadence_tpu.core.checksum import DEFAULT_LAYOUT as layout
        from cadence_tpu.parallel.mesh import make_mesh, replay_sharded
        from cadence_tpu.utils import metrics as m

        registry = m.DEFAULT_REGISTRY
        devices = jax.devices()[:args.chips]
        mesh_n, mesh_1 = make_mesh(devices), make_mesh(devices[:1])
        last_events = None
        for suite in SUITES:
            events, oracle = farm.suite(suite)
            crc_n, err_n, _ = _replay_suite(checks, suite, events, oracle,
                                            mesh_n, layout, size)
            crc_1, err_1, _ = _replay_suite(checks, suite, events, oracle,
                                            mesh_1, layout, size)
            checks.expect(bool((crc_n == crc_1).all()
                               and (err_n == err_1).all()),
                          f"{suite}: a mesh of {args.chips} and a mesh of "
                          "1 give different CRCs")
            last_events = events

        rows = {d: registry.counter(m.SCOPE_TPU_EXECUTOR,
                                    m.device_metric(m.M_EXEC_ROWS, d))
                for d in range(args.chips)}
        say(phase="mesh", rows_dispatched_per_device=rows)
        checks.expect(all(v > 0 for v in rows.values()),
                      f"a device was dispatched no rows: {rows}")

        # the fused shard_map kernel once, across the whole mesh; the
        # oracle sample is what it is compared with
        _fused_chunk(checks, mesh_n, layout, size, args.seed)

        # the cross-shard collective: replay_sharded's stats are a psum
        # over the mesh; the same corpus on one device is the reference
        def stats_on(mesh):
            _rows, err, stats = replay_sharded(last_events, mesh, layout)
            return np.asarray(stats), np.asarray(err)

        (stats_n, err_n), psum_s = _timed(lambda: stats_on(mesh_n))
        stats_1, _err1 = stats_on(mesh_1)
        say(phase="mesh", collective="stats psum", devices=args.chips,
            stats_mesh_n=stats_n.tolist(), stats_mesh_1=stats_1.tolist(),
            first_s=psum_s)
        checks.expect(stats_n.tolist() == stats_1.tolist()
                      and int(stats_n[0]) == int((err_n != 0).sum()),
                      "the cross-shard stats differ from one device's")
    finally:
        farm.close()
    return checks.report(device)


# ---------------------------------------------------------------------------
# Phase: serve (this child is only the launcher; the host holds the chip)
# ---------------------------------------------------------------------------


def phase_serve(args, size) -> int:
    checks = Checks("serve")
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from cadence_tpu.core.enums import CloseStatus, DecisionType, EventType
    from cadence_tpu.engine.history_engine import Decision
    from cadence_tpu.rpc.cluster import launch

    domain, task_list = "smoke", "smoke-tl"
    n = size["serve_workflows"]
    ids = [f"smoke-{args.seed}-{i}" for i in range(n)]
    signalled = set(ids[::size["serve_signal_every"]])
    device = None
    cluster = launch(num_hosts=1, num_shards=8, env_extra={
        "CADENCE_TPU_SERVING": "1", **size["serve_env"]})
    try:
        host = sorted(cluster.hosts)[0]
        health_url = f"http://127.0.0.1:{cluster.http_ports[host]}/health"
        with urllib.request.urlopen(health_url, timeout=30) as resp:
            health = json.loads(resp.read())
        device = health.get("device")
        say(phase="serve", host=host, health_device=device)
        if not checks.expect(bool(device), "the host states no device on "
                             "/health"):
            return checks.report(device)
        if not args.rehearse and device["platform"] != "tpu":
            checks.expect(False, f"the service host is on {device}")
            return checks.report(device)

        t0 = time.monotonic()
        while True:
            doc = cluster.admin(host, "admin_cluster")
            if doc["serving_warmed"]:
                break
            if time.monotonic() - t0 > 900:
                raise TimeoutError("the host's serving warm-up never ended")
            time.sleep(0.5)
        say(phase="serve", warm_up_s=round(time.monotonic() - t0, 1),
            warm_error=doc["serving_warm_error"])
        checks.expect(not doc["serving_warm_error"],
                      f"serving warm-up failed: {doc['serving_warm_error']}")

        fe = cluster.frontend(host)
        fe.register_domain(domain)
        pool = ThreadPoolExecutor(max_workers=size["serve_clients"])

        def timed_all(fn, items):
            t = time.monotonic()
            list(pool.map(fn, items))
            return round(time.monotonic() - t, 2)

        start_s = timed_all(lambda wf: fe.start_workflow_execution(
            domain, wf, "smoke-type", task_list), ids)

        completed, kept_open = [], []
        lock = threading.Lock()

        def decide_one(_slot) -> bool:
            """Poll one decision task and answer it: a workflow in the
            signal subset stays open until its signal is in its history;
            every other decision completes the workflow."""
            resp = fe.poll_for_decision_task(domain, task_list,
                                             wait_seconds=1.0,
                                             identity="chip-smoke")
            if resp is None or resp.token is None:
                return False
            wf = resp.token.workflow_id
            got_signal = any(e.event_type == EventType.WorkflowExecutionSignaled
                             for e in resp.history)
            if wf in signalled and not got_signal:
                fe.respond_decision_task_completed(resp.token, [])
                with lock:
                    kept_open.append(wf)
            else:
                fe.respond_decision_task_completed(resp.token, [Decision(
                    DecisionType.CompleteWorkflowExecution,
                    {"result": b"smoke-done"})])
                with lock:
                    completed.append(wf)
            return True

        def decide_until(done, what: str) -> float:
            t = time.monotonic()
            while not done():
                if time.monotonic() - t > 600:
                    raise TimeoutError(f"decisions stalled: {what}")
                list(pool.map(decide_one, range(size["serve_clients"])))
            return round(time.monotonic() - t, 2)

        first_s = decide_until(
            lambda: len(completed) + len(kept_open) >= n,
            "first decision of every workflow")
        signal_s = timed_all(lambda wf: fe.signal_workflow_execution(
            domain, wf, "smoke-signal"), sorted(signalled))
        second_s = decide_until(lambda: len(completed) >= n,
                                "completion of the signalled workflows")
        pool.shutdown()
        say(phase="serve", workflows=n, signalled=len(signalled),
            completed=len(completed), start_s=start_s,
            first_decisions_s=first_s, signals_s=signal_s,
            last_decisions_s=second_s)
        checks.expect(len(set(completed)) == n,
                      f"{len(set(completed))} of {n} workflows completed")
        # the close lands in visibility through the host's transfer
        # queue, a beat after the decision's reply
        t0 = time.monotonic()
        while True:
            closed = fe.count_workflow_executions(
                domain, f"CloseStatus = {int(CloseStatus.Completed)}")
            if closed == n or time.monotonic() - t0 > 60:
                break
            time.sleep(0.2)
        checks.expect(closed == n, f"visibility counts {closed} completed "
                      f"workflows, not {n}")

        # RPC success proves nothing about the device: the oracle commits
        # first and a failed flush only resolves its ticket not-ok. The
        # host's own counters are the check — once the tier has settled.
        t0 = time.monotonic()
        while True:
            doc = cluster.admin(host, "admin_cluster")
            s = doc["serving"]
            if s["queue_depth"] == 0 and \
                    s["tickets_ok"] + s["tickets_failed"] >= s["transactions"]:
                break
            if time.monotonic() - t0 > 120:
                break
            time.sleep(0.2)
        snap = cluster.admin(host, "admin_metrics")["snapshot"]
        serving = snap.get("tpu.serving", {})
        native = snap.get("tpu.native", {})
        say(phase="serve", serving={k: s[k] for k in (
            "transactions", "tickets_ok", "tickets_failed",
            "batched_launches", "coalesced_appends", "coalescing_factor",
            "cold_admits", "suffix_appends", "exact_serves", "bypassed",
            "requeued", "busy_rejections", "parity_divergence",
            "max_batch")},
            handoff_failures=serving.get("handoff-failures"),
            resident_entries=doc["resident"]["entries"],
            resident_bytes=doc["resident"]["resident_bytes"],
            native_available=native.get("available"))
        checks.expect(s["transactions"] >= 2 * n,
                      f"only {s['transactions']} transactions reached the "
                      "serving tier")
        checks.expect(s["tickets_ok"] == s["transactions"],
                      f"{s['tickets_ok']} of {s['transactions']} tickets "
                      "resolved ok")
        for name in ("tickets_failed", "parity_divergence", "bypassed",
                     "busy_rejections"):
            checks.expect(s[name] == 0, f"tpu.serving {name} = {s[name]}")
        checks.expect(serving.get("handoff-failures") == 0,
                      "tpu.serving handoff-failures = "
                      f"{serving.get('handoff-failures')}")
        checks.expect(doc["resident"]["entries"] > 0,
                      "no resident entry on the device")
        checks.expect(native.get("available") == 1.0,
                      "the host's tpu.native/available is not 1")

        from jax._src import xla_bridge
        launcher_on_jax = xla_bridge.backends_are_initialized()
        say(phase="serve", launcher_initialised_a_backend=launcher_on_jax,
            store_server_jax_platforms=cluster._store_env["JAX_PLATFORMS"])
        checks.expect(cluster._store_env["JAX_PLATFORMS"] == "cpu",
                      "the store server was not pinned to the CPU backend")
        checks.expect(not launcher_on_jax,
                      "the launcher initialised a JAX backend")
    finally:
        cluster.stop()
    return checks.report(device)


# ---------------------------------------------------------------------------
# Phase: visibility
# ---------------------------------------------------------------------------


def phase_visibility(args, size) -> int:
    checks = Checks("visibility")
    import random

    n = size["vis_rows"]
    # the tier's own switches, set before the store is built: the view
    # on, parity on (its default), capacity sized to the table
    os.environ["CADENCE_TPU_VISIBILITY_CAPACITY"] = str(n)
    os.environ.pop("CADENCE_TPU_VISIBILITY_PARITY", None)
    device = _open_device(checks, args.rehearse, 1)

    from cadence_tpu.engine.persistence import (
        VisibilityRecord,
        VisibilityStore,
    )

    rng = random.Random(f"{args.seed}:visibility")
    store = VisibilityStore()
    base = 1_700_000_000_000_000_000
    t0 = time.perf_counter()
    for i in range(n):
        attrs = {}
        r = rng.random()
        if r < 0.5:
            attrs["Priority"] = rng.randrange(0, 10)
        elif r < 0.8:
            attrs["Tag"] = f"tag-{rng.randrange(4)}"
        store.record_started(VisibilityRecord(
            domain_id="smoke", workflow_id=f"wf-{i}", run_id=f"r-{i}",
            workflow_type=f"wt-{i % 8}", start_time=base + i * 1000,
            search_attrs=attrs))
        if rng.random() < 0.5:
            store.record_closed("smoke", f"wf-{i}", f"r-{i}",
                                close_time=base + i * 1000 + 7,
                                close_status=rng.randrange(0, 3))
    load_s = round(time.perf_counter() - t0, 2)

    cut = base + int(n * 0.999) * 1000
    counts = ["", "CloseStatus = -1", "WorkflowType = 'wt-3'",
              "Priority >= 9", f"StartTime > {cut}",
              "WorkflowType = 'wt-1' AND CloseStatus = 0 AND Priority < 2"]
    lists = ["WorkflowType = 'wt-3' AND CloseStatus = -1 AND Priority >= 8",
             "Tag = 'tag-2' AND CloseStatus = 1",
             f"StartTime > {cut}"]
    scans = ["CloseStatus = -1", "WorkflowType = 'wt-5' AND Priority < 5"]

    def walk(query):
        pages, token = [], None
        for _ in range(size["vis_pages"]):
            recs, token = store.query_page("smoke", query, 50, token)
            pages.append([(r.workflow_id, r.run_id) for r in recs])
            if token is None:
                break
        return pages

    def answers():
        return {
            "count": [store.count("smoke", q) for q in counts],
            "list": [sorted((r.workflow_id, r.run_id)
                            for r in store.query("smoke", q))
                     for q in lists],
            "scan": [walk(q) for q in scans],
        }

    # the host's evaluation first (tier off), then the device's
    os.environ["CADENCE_TPU_VISIBILITY"] = "0"
    host, host_s = _timed(answers)
    os.environ["CADENCE_TPU_VISIBILITY"] = "1"
    dev, first_s = _timed(answers)
    dev2, warm_s = _timed(answers)
    stats = store._device.stats()
    store._device.stop()
    say(phase="visibility", rows=n, capacity=stats["capacity"],
        load_s=load_s, queries={"count": len(counts), "list": len(lists),
                                "scan_pages": sum(len(p) for p in
                                                  dev["scan"])},
        count_answers=dev["count"],
        host_s=host_s, device_first_s=first_s, device_warm_s=warm_s,
        counters={k: stats[k] for k in (
            "queries", "device_served", "host_fallbacks", "parity_checks",
            "parity_divergence", "topk_serves", "bitmap_scans",
            "topk_escalations", "quarantined", "compile_cache_misses")})
    checks.expect(dev == host, "the device's answers differ from the "
                  "host's evaluation")
    checks.expect(dev2 == host, "the device's second answers differ")
    checks.expect(stats["rows"] >= n, f"the view holds {stats['rows']} rows")
    checks.expect(stats["device_served"] > 0, "no query was device-served")
    checks.expect(stats["device_served"] == stats["queries"],
                  f"{stats['queries'] - stats['device_served']} queries "
                  "were not served by the device")
    for name in ("host_fallbacks", "parity_divergence"):
        checks.expect(stats[name] == 0, f"tpu.visibility {name} = "
                      f"{stats[name]}")
    checks.expect(not stats["quarantined"], "the view is quarantined")
    checks.expect(stats["parity_checks"] > 0, "no parity check ran")
    checks.expect(stats["topk_serves"] > 0 and stats["bitmap_scans"] > 0,
                  "the top-K or the bitmap kernel never served")
    return checks.report(device)


PHASE_FNS = {"bulk": phase_bulk, "serve": phase_serve,
             "visibility": phase_visibility, "mesh": phase_mesh}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="seed of every corpus, sample and workload")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 = the mesh phase alone, on four chips")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes, to rehearse the control flow on a "
                        "CPU; the platform check at the end still fails")
    p.add_argument("--phase", choices=sorted(PHASE_FNS),
                   help=argparse.SUPPRESS)  # a child of this script
    args = p.parse_args(argv)
    if args.phase is None:
        return main_parent(args)
    return PHASE_FNS[args.phase](args, SIZES["tiny" if args.rehearse
                                             else "full"])


if __name__ == "__main__":
    sys.exit(main())
