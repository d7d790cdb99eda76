"""95th percentile of the measured ops' latency from the INTENDED send
time, over all of them (a failed op counts with 60 s): the tail a client
sees. It stands here and not among the end-to-end metrics because its
run-to-run spread on the v5e (18 % at 40 ops/s, PR 24) is wider than any
bound the benchmark may set."""
from harness import percentile


def read(ctx):
    latencies = ctx.get("measured_latency_s")
    if not latencies:
        return None
    return percentile(latencies, 95) * 1e3
