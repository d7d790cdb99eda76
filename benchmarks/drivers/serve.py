"""Driver of a served deployment: a wire cluster (store server + one service
host as OS processes, serving tier on) under an open-loop mix of frontend
operations.

This process is the launcher and the load: it never initialises a JAX
backend. The service host holds the chip; it is started through the
benchmark's wrapper (`serve_host.py`), which is the program's own
`rpc.server.main` plus a thread that can trace the chip and read its
memory. The store server is pinned to the CPU by its role.

The timed entry is `rpc/cluster.FrontendClient` over sockets. What was
acknowledged is read back from the store through `rpc/client.RemoteStores`
once the window has closed, replayed by the plain reference, and compared
with the device twin's resident rows as the host reports them.
"""
from __future__ import annotations

import json
import os
import random
import shutil
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Dict, List, Optional

import loadgen
from harness import Compared, percentile, say

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SERVING_COUNTERS = (
    "transactions", "tickets_ok", "tickets_failed", "batched_launches",
    "coalesced_appends", "cold_admits", "suffix_appends", "exact_serves",
    "bypassed", "requeued", "busy_rejections", "parity_divergence")
#: a failed op has missed every limit: it counts with this latency
FAILED_OP_LATENCY_S = 60.0


class HostControl:
    """Requests to the wrapper's thread inside the service host."""

    def __init__(self, ctl_dir: str) -> None:
        self.dir, self._n, self._lock = ctl_dir, 0, threading.Lock()

    def ask(self, timeout: float = 240.0, **req) -> dict:
        with self._lock:
            self._n += 1
            base = os.path.join(self.dir, f"{self._n:06d}")
        with open(base + ".tmp", "w") as f:
            json.dump(req, f)
        os.replace(base + ".tmp", base + ".req")
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if os.path.exists(base + ".rsp"):
                with open(base + ".rsp") as f:
                    rsp = json.load(f)
                if not rsp.get("ok"):
                    raise RuntimeError(f"host refused {req}: {rsp}")
                return rsp
            time.sleep(0.02)
        raise TimeoutError(f"the host never answered {req}")


def _wait_listening(port: int, proc, timeout: float = 180.0) -> None:
    from cadence_tpu.rpc.wire import call

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SystemExit(f"process exited rc={proc.returncode} before "
                             "it listened")
        try:
            call(("127.0.0.1", port), ("ping",), timeout=2)
            return
        except Exception:
            time.sleep(0.05)
    raise TimeoutError(f"port {port} not serving after {timeout}s")


def reference_crc(batches, control: str = "") -> int:
    """The plain reference's answer for a history read back from the store:
    the history as plain data, replayed by `refimpl/replay.py`."""
    from refimpl import replay as reference

    return reference.crc_of_history(reference.plain(batches), control)


class Driver:
    # the service host, a child, holds the chip: the launcher stays off JAX

    def __init__(self, cell: dict, config: dict, traffic: dict, opts) -> None:
        self.cell, self.opts = cell, opts
        self.config, self.traffic = dict(config), dict(traffic)
        if opts.rehearse:
            self.config.update(config.get("rehearse", {}))
            self.traffic.update(traffic.get("rehearse", {}))
        self.cluster = None
        self.completers = None
        self.out_dir = os.path.join(ROOT, ".bench_out", "serve",
                                    cell["name"])

    # -- the cluster -------------------------------------------------------

    def _launch(self):
        from cadence_tpu.rpc.cluster import (
            Cluster,
            check_one_process_per_chip,
            child_env,
            free_port,
        )

        shutil.rmtree(self.out_dir, ignore_errors=True)
        ctl = os.path.join(self.out_dir, "ctl")
        os.makedirs(ctl)
        self.host_ctl = HostControl(ctl)
        host, cfg = "host-0", self.config
        extra = {"CADENCE_TPU_SERVING": "1", "BENCH_HOST_CTL": ctl,
                 **cfg.get("host_env", {})}
        envs = {host: ("host", child_env(host, "host", extra)),
                "store": ("store", child_env("store", "store", {}))}
        check_one_process_per_chip(envs)
        store_port, port, http_port = free_port(), free_port(), free_port()
        store_cmd = [sys.executable, "-m", "cadence_tpu.rpc.storeserver",
                     "--port", str(store_port)]
        store_proc = subprocess.Popen(store_cmd, env=envs["store"][1])
        _wait_listening(store_port, store_proc)
        cmd = [sys.executable, os.path.join(HERE, "serve_host.py"),
               "--name", host, "--port", str(port),
               "--store", f"127.0.0.1:{store_port}",
               "--num-shards", str(cfg["num_shards"]),
               "--hb-interval", str(cfg["hb_interval"]),
               "--ttl", str(cfg["ttl"]), "--cluster-name", "primary",
               "--http-port", str(http_port)]
        proc = subprocess.Popen(cmd, env=envs[host][1])
        self.cluster = Cluster(store_port, {host: port}, {host: proc},
                               store_proc, http_ports={host: http_port},
                               store_cmd=store_cmd,
                               store_env=envs["store"][1])
        _wait_listening(port, proc)
        self.host = host
        self.store_pinned = envs["store"][1].get("JAX_PLATFORMS") == "cpu"

    def _client(self):
        return self.cluster.frontend(self.host)

    def _serving(self) -> dict:
        return self.cluster.admin(self.host, "admin_cluster")["serving"]

    def _plans(self, rate: float) -> List[loadgen.DomainPlan]:
        t = self.traffic
        draw = t.get("pool_draw", {"kind": "uniform"})
        return [loadgen.DomainPlan(
            domain=d["name"], rps=rate * d["share"], weights=dict(t["mix"]),
            pool_size=int(t["pool_size"]), arrival=t.get("arrival", "poisson"),
            pool_draw=draw["kind"], zipf_theta=float(draw.get("theta", 0.99)),
            fixed_set=bool(t.get("fixed_set", False)),
            reset_target=t.get("reset_target", "same"))
            for d in t["domains"]]

    # -- set-up ------------------------------------------------------------

    def setup(self) -> dict:
        t0 = time.perf_counter()
        self._launch()
        http = self.cluster.http_ports[self.host]
        with urllib.request.urlopen(f"http://127.0.0.1:{http}/health",
                                    timeout=30) as resp:
            device = json.loads(resp.read()).get("device")
        say(driver="serve", host_listening_s=time.perf_counter() - t0,
            health_device=device, store_pinned_to_cpu=self.store_pinned)
        if not device:
            raise SystemExit("the service host states no device on /health")
        if not self.opts.rehearse and device["platform"] != "tpu":
            raise SystemExit(f"the service host is on {device}, not a TPU")
        if not self.store_pinned:
            raise SystemExit("the store server is not pinned to the CPU")
        t1 = time.perf_counter()
        while True:
            doc = self.cluster.admin(self.host, "admin_cluster")
            if doc["serving_warmed"]:
                break
            if time.perf_counter() - t1 > 1000:
                raise TimeoutError("the host's serving warm-up never ended")
            time.sleep(0.25)
        if doc["serving_warm_error"]:
            raise SystemExit(f"serving warm-up failed: "
                             f"{doc['serving_warm_error']}")
        stats = self.host_ctl.ask(op="stats")
        say(driver="serve", boot_warm_up_s=time.perf_counter() - t1,
            host_compiles=stats["compiles"], cache_hits=stats["cache_hits"],
            max_batch=doc["serving"]["max_batch"])

        self.rate = float(self.traffic["rate_ops_per_s"])
        self.plans = self._plans(self.rate)
        seeded = loadgen.seed_pools(
            self._client, self.plans,
            clients=int(self.traffic.get("seed_clients", 16)),
            warm_resets=int(self.traffic.get("warm_resets_per_domain", 0)))
        say(driver="serve", seeded=seeded)
        self.completers = loadgen.DecisionCompleters(
            self._client, [p.domain for p in self.plans],
            per_domain=int(self.traffic["completers_per_domain"]))
        self.completers.start()
        # warm-up traffic: the window's own mix and rate for a few seconds,
        # on ids of its own, so that every op kind has run before the window
        warm_s = float(self.traffic.get("warm_traffic_s", 4.0))
        t2 = time.perf_counter()
        warm = loadgen.Sender(
            self._client,
            loadgen.build_schedule(self.plans, warm_s,
                                   f"{self.opts.seed}:warm", id_salt="w"),
            threads=int(self.traffic["sender_threads"]),
            longpoll_timeout_s=float(self.traffic["longpoll_timeout_s"]),
            request_salt="warm-")
        warm.run()
        bad = [s.outcome for s in warm.samples if s.outcome != "ok"]
        say(driver="serve", warm_traffic_s=time.perf_counter() - t2,
            warm_ops=len(warm.samples), warm_failed=len(bad),
            warm_failures=sorted(set(bad))[:5])
        self._settle()
        return device

    def _settle(self, timeout: float = 120.0) -> dict:
        """Wait until the serving tier has resolved every ticket."""
        t0 = time.monotonic()
        while True:
            s = self._serving()
            if s["queue_depth"] == 0 and \
                    s["tickets_ok"] + s["tickets_failed"] >= s["transactions"]:
                return s
            if time.monotonic() - t0 > timeout:
                return s
            time.sleep(0.1)

    # -- the window --------------------------------------------------------

    def run_window(self, seconds: float, trace_dir: Optional[str]) -> None:
        t = self.traffic
        self.schedule = loadgen.build_schedule(self.plans, seconds,
                                               self.opts.seed)
        say(driver="serve", scheduled=len(self.schedule), rate=self.rate,
            digest=loadgen.trace_digest(self.schedule)[:16])
        self.sender = loadgen.Sender(
            self._client, self.schedule, threads=int(t["sender_threads"]),
            longpoll_timeout_s=float(t["longpoll_timeout_s"]))
        self.serving_before = self._serving()
        self.host_before = self.host_ctl.ask(op="stats")
        self.traced_window_s = 0.0
        # a trace covers the window's last `trace_s` seconds and is stopped
        # once the window's last answer is in: collecting it takes the host
        # tens of seconds, which no op may wait through. The host-clock
        # readers are given the ops answered before it started.
        self.trace_from_s = float("inf")
        tracer = None
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir, exist_ok=True)
            start_at = max(seconds / 2,
                           seconds - float(t.get("trace_s", 5.0)))

            def trace():
                time.sleep(start_at)
                self.trace_from_s = time.perf_counter() - self.sender.t0
                self.host_ctl.ask(op="trace_start", dir=trace_dir)

            tracer = threading.Thread(target=trace, name="bench-tracer")
            tracer.start()
        n_completed = len(self.completers.completed)
        t0 = self.sender.run()
        t_end = time.perf_counter()
        self.window_s = t_end - t0
        # the backlog the window leaves: churn workflows it started that no
        # worker had completed when the last reply came
        started = {s.op.workflow_id for s in self.sender.samples
                   if s.op.kind == loadgen.OP_START and s.outcome == "ok"}

        def done() -> set:
            return {wf for _t, _d, wf in
                    self.completers.completed[n_completed:]}

        self.open_backlog = len(started - done())
        # wait for the answers that are due, a minute past the close if
        # need be: a decision that comes late is late, not wrong
        deadline = time.monotonic() + 60
        while started - done() and time.monotonic() < deadline:
            time.sleep(0.1)
        self.never_completed = len(started - done())
        self.never_completed_ids = sorted(started - done())[:5]
        self.drain_s = time.perf_counter() - t_end
        self.serving_after = self._settle()
        self.serving_delta = {k: self.serving_after[k] - self.serving_before[k]
                              for k in SERVING_COUNTERS}
        self.host_after = self.host_ctl.ask(op="stats")
        self.host_compiles = self.host_after["compiles"] \
            - self.host_before["compiles"]
        # only now, with every answer in, the tier's queue empty and the
        # window's counters read, is the trace stopped: collecting it stalls
        # the host's Python for tens of seconds, and what the host does in
        # and after that stall is no part of the window
        if tracer is not None:
            tracer.join()
            t_stop = time.perf_counter()
            self.traced_window_s = self.host_ctl.ask(
                op="trace_stop")["window_s"]
            say(driver="serve", trace_from_s=self.trace_from_s,
                traced_window_s=self.traced_window_s,
                trace_stop_s=time.perf_counter() - t_stop)
        self.completers.stop()

    def _measured(self) -> List[loadgen.Sample]:
        kinds = set(self.traffic["measured_ops"])
        return [s for s in self.sender.samples if s.op.kind in kinds]

    @staticmethod
    def _latency_s(measured: List[loadgen.Sample]) -> List[float]:
        """What each measured op's client waited, from the intended send
        time; a failed op has missed every limit."""
        return [s.latency_s if s.outcome == "ok"
                else max(s.latency_s, FAILED_OP_LATENCY_S)
                for s in measured]

    def attempted_failed(self):
        samples = self.sender.samples
        return len(samples), sum(1 for s in samples if s.outcome != "ok")

    def end_to_end(self) -> Dict[str, float]:
        lat = self._latency_s(self._measured())
        by_outcome: Dict[str, int] = {}
        for s in self.sender.samples:
            by_outcome[s.outcome] = by_outcome.get(s.outcome, 0) + 1
        say(driver="serve", failed_ops=[
            (s.op.kind, s.outcome, s.op.workflow_id, round(s.op.at_s, 3))
            for s in self.sender.samples if s.outcome != "ok"][:40])
        say(driver="serve", window_s=self.window_s, ops=len(self.sender.samples),
            measured_ops=len(lat), outcomes=by_outcome,
            open_backlog=self.open_backlog, drain_s=self.drain_s,
            never_completed=self.never_completed,
            completer_errors=self.completers.errors,
            conflict_retries=self.sender.conflict_retries,
            serving_delta=self.serving_delta,
            host_compiles_in_window=self.host_compiles)
        return {"op_p50_ms": percentile(lat, 50) * 1e3}

    def context(self, device: dict, reduced_trace: Optional[dict]) -> dict:
        """What the per-layer readers are given. Samples and latencies are
        those of the ops answered before the trace started, so that no
        host-clock metric is read through the profiler."""
        def untraced(samples):
            return [s for s in samples if s.done_s < self.trace_from_s]

        measured = untraced(self._measured())
        return {
            "kind": "serve", "device": device, "window_s": self.window_s,
            "samples": untraced(self.sender.samples), "measured": measured,
            "measured_latency_s": self._latency_s(measured),
            "open_backlog": self.open_backlog,
            "serving_before": self.serving_before,
            "serving_after": self.serving_after,
            "trace": reduced_trace, "traced_window_s": self.traced_window_s,
            "rehearse": bool(self.opts.rehearse),
        }

    def memory_peak_bytes(self) -> int:
        return int(self.host_after["memory_peak_bytes"])

    def release(self) -> None:
        pass  # the cluster stays up: the check reads it back

    # -- what decides `correct` --------------------------------------------

    def check(self) -> List[Compared]:
        """What the window acknowledged, read back from the store by a
        launcher that is off the device; the persisted histories replayed
        by the plain reference against the device twin's resident rows;
        and the faults of the run."""
        from cadence_tpu.rpc.client import RemoteStores

        t0 = time.perf_counter()
        stores = RemoteStores(("127.0.0.1", self.cluster.store_port))
        domain_id = {info.name: info.domain_id
                     for info in stores.domain.list_domains()}
        runs: Dict[tuple, List[str]] = {}
        for did, wf, run in stores.execution.list_executions():
            runs.setdefault((did, wf), []).append(run)
        acked: Dict[tuple, dict] = {}
        for s in self.sender.samples:
            if s.outcome != "ok":
                continue
            entry = acked.setdefault((s.op.domain, s.op.workflow_id),
                                     {"started": False, "signals": set()})
            if s.op.kind in loadgen.START_OPS:
                entry["started"] = True
            elif s.op.kind in (loadgen.OP_SIGNAL,
                               loadgen.OP_SIGNAL_WITH_START):
                entry["signals"].add(s.op.arg)
        # the sample: drawn from the seed, the most signalled workflow in it
        keys = sorted(k for k, e in acked.items()
                      if e["started"] or e["signals"])
        rng = random.Random(f"{self.opts.seed}:serve-sample")
        n = min(len(keys), int(self.traffic["readback_sample"]))
        sample = set(rng.sample(keys, n)) if n else set()
        if keys:
            sample.add(max(keys, key=lambda k: len(acked[k]["signals"])))
        for did, wf in [k for k in runs if k[1] in self.never_completed_ids]:
            say(driver="serve", never_completed=wf, runs=[
                [ev.event_type.name for batch in
                 stores.history.as_history_batches(did, wf, run)
                 for ev in batch.events] for run in runs[(did, wf)]])

        def resident_rows() -> dict:
            return self.cluster.admin(self.host, "admin_cluster", True,
                                      timeout=120)["resident_rows"]

        rows = resident_rows()
        missing = mismatched = compared = no_row = 0
        behind: List[tuple] = []   # twin and store not at the same batch

        def compare(key, batches) -> bool:
            """True once the twin's row of `key` has been compared, at the
            same batch count as the history read back."""
            nonlocal compared, mismatched
            crc, _branch, (n_batches, _tail) = rows[key]
            if n_batches != len(batches):
                return False
            compared += 1
            if reference_crc(batches, self.opts.control) != crc:
                mismatched += 1
            return True

        for domain, wf in sorted(sample):
            did = domain_id[domain]
            want = acked[(domain, wf)]
            seen_signals, has_start = set(), False
            current = stores.execution.get_current_run_id(did, wf) \
                if runs.get((did, wf)) else None
            for run in runs.get((did, wf), []):
                batches = stores.history.as_history_batches(did, wf, run)
                for batch in batches:
                    for ev in batch.events:
                        name = ev.event_type.name
                        if name == "WorkflowExecutionStarted":
                            has_start = True
                        elif name == "WorkflowExecutionSignaled":
                            seen_signals.add(ev.attrs.get("signal_name"))
                if run != current:
                    continue
                if (did, wf, run) not in rows:
                    no_row += 1   # not resident: the twin states nothing
                elif not compare((did, wf, run), batches):
                    behind.append((did, wf, run))
            if want["started"] and not has_start:
                missing += 1
            missing += len(want["signals"] - seen_signals)
        # a row and a history that were read a moment apart may differ by a
        # transaction that a timer fired in between: read both again, until
        # the twin has had `twin_settle_s` to catch up. What is still
        # behind then is a stale twin.
        deadline = time.monotonic() + float(
            self.traffic.get("twin_settle_s", 15.0))
        first_behind = len(behind)
        while behind and time.monotonic() < deadline:
            time.sleep(0.5)
            self._settle()
            rows = resident_rows()
            behind = [key for key in behind if key not in rows or not compare(
                key, stores.history.as_history_batches(*key))]
        delta = self.serving_delta
        unresolved = delta["transactions"] - delta["tickets_ok"] \
            - delta["tickets_failed"]
        say(driver="serve", check_s=time.perf_counter() - t0,
            sampled=len(sample), twin_rows_compared=compared,
            twin_rows_behind_at_first=first_behind,
            twin_rows_behind=len(behind), sampled_without_row=no_row,
            resident_rows=len(rows),
            parity_divergence=delta["parity_divergence"],
            bypassed=delta["bypassed"], requeued=delta["requeued"])
        want_rows = int(self.traffic["twin_rows_at_least"])
        return [
            Compared("acked_missing_from_history", missing, 0),
            Compared("twin_crc_mismatch", mismatched, 0),
            Compared("twin_rows_behind", len(behind), 0),
            Compared("sampled_without_row", no_row,
                     int(self.traffic["sampled_without_row_at_most"])),
            Compared("twin_rows_short_of_sample",
                     max(0, want_rows - compared), 0),
            # a ticket the tier resolves not-ok is its stated way out of a
            # hand-off it cannot prove (the entry is dropped, the next
            # transaction admits it anew): a count with a limit, set in the
            # traffic file from sound runs and from the program at fault.
            # A parity divergence fails its ticket, so it is counted here.
            Compared("tickets_failed", delta["tickets_failed"],
                     int(self.traffic["tickets_failed_at_most"])),
            Compared("tickets_unresolved", unresolved, 0),
            Compared("churn_never_completed", self.never_completed, 0),
            Compared("host_compiles_in_window", self.host_compiles, 0),
        ]

    def close(self) -> None:
        if self.completers is not None:
            self.completers.stop()
        if self.cluster is not None:
            self.cluster.stop()
