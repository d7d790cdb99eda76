#!/usr/bin/env python3
"""The service host of a served cell, started through the benchmark.

The same `cadence_tpu.rpc.server.main`, same flags, same environment as
`rpc/cluster.launch` gives a host. Added, because only the process that
holds the chip can trace it and the program has no verb for it: one
thread that answers the launcher's requests, handed over as files in the
directory `BENCH_HOST_CTL` names —

    <n>.req   {"op": "trace_start", "dir": ...} | {"op": "trace_stop"} |
              {"op": "stats"}
    <n>.rsp   the answer, written whole and then renamed into place

`stats` gives the peak of each device's memory and the count of XLA
compilations this process has made or fetched so far (`CompileCounter`).
Both kinds of run go through this wrapper, so one path is measured.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))):
    if path not in sys.path:
        sys.path.insert(0, path)


def _answer(req: dict, state: dict) -> dict:
    import jax

    op = req["op"]
    if op == "trace_start":
        options = jax.profiler.ProfileOptions()
        # the host's Python is the system under test: do not slow it by
        # tracing every Python call
        options.python_tracer_level = 0
        jax.profiler.start_trace(req["dir"], profiler_options=options)
        state["trace_t0"] = time.perf_counter()
        return {"ok": True}
    if op == "trace_stop":
        window_s = time.perf_counter() - state.pop("trace_t0")
        jax.profiler.stop_trace()
        return {"ok": True, "window_s": window_s}
    if op == "stats":
        peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in jax.devices()]
        counter = state["compiles"]
        return {"ok": True, "memory_peak_bytes": max(peaks) if peaks else 0,
                "compiles": counter.total, "cache_hits": counter.hits}
    return {"ok": False, "error": f"unknown op {op!r}"}


def _control_loop(ctl: str, state: dict) -> None:
    done = set()
    while True:
        for name in sorted(os.listdir(ctl)):
            if not name.endswith(".req") or name in done:
                continue
            done.add(name)
            try:
                with open(os.path.join(ctl, name)) as f:
                    rsp = _answer(json.load(f), state)
            except Exception as exc:
                rsp = {"ok": False, "error": f"{type(exc).__name__}: {exc}"}
            out = os.path.join(ctl, name[:-4] + ".rsp")
            with open(out + ".tmp", "w") as f:
                json.dump(rsp, f)
            os.replace(out + ".tmp", out)
        time.sleep(0.05)


def main(argv=None) -> int:
    from harness import CompileCounter

    from cadence_tpu.rpc.server import main as host_main

    ctl = os.environ.get("BENCH_HOST_CTL")
    if ctl:
        state = {"compiles": CompileCounter()}
        threading.Thread(target=_control_loop, args=(ctl, state),
                         daemon=True, name="bench-host-ctl").start()
    return host_main(argv)


if __name__ == "__main__":
    sys.exit(main())
