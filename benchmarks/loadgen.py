"""The benchmark's load generator for a served deployment: a seeded
open-loop schedule, a sender that keeps every sample, and the workers that
complete decisions.

A copy of the program's `loadgen/mixes.py` (`build_schedule`,
`trace_digest`) and `loadgen/generator.py` (`LoadGenerator`,
`DecisionCompleters`), kept here so that no later PR can change what the
load is. What differs from the originals:

- every sample is kept raw (latency from the INTENDED send time, service
  time from the actual send, how late the send was), so a percentile is a
  percentile of samples and not of a bucket histogram;
- a pool target is drawn as the traffic file says: `uniform` (the
  original, and with it the schedule is the original's, digest for digest)
  or `zipf` with exponent theta over the pool, rank 0 the hottest;
- `fixed_set`: every seed sends the same multiset of inter-arrival gaps
  (the quantiles of the exponential distribution at the plan's rate), of
  op kinds (each kind's share of the count, to the nearest op) and of pool
  ranks, in an order the seed shuffles; so two seeds differ in order and
  never in the amount of work;
- `reset_target: cold-half` takes a reset's target from the colder half of
  the pool in turn (see `DomainPlan`);
- a write conflict (`ConditionFailedError`) is retried by the sender, up to
  three sends, and counted;
- the pools are seeded by parallel clients.
"""
from __future__ import annotations

import bisect
import hashlib
import math
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Sequence

OP_START = "start"
OP_CRON_START = "cron-start"
OP_RETRY_START = "retry-start"
OP_SIGNAL = "signal"
OP_SIGNAL_WITH_START = "signal-with-start"
OP_QUERY = "query"
OP_LONGPOLL = "longpoll"
OP_RESET = "reset"
OP_LIST = "list"
OP_SCAN = "scan"
OP_COUNT = "count"

#: kinds that target the long-lived pool population
POOL_OPS = (OP_SIGNAL, OP_QUERY, OP_LONGPOLL, OP_RESET)
#: kinds that carry a visibility query string in `arg`
VIS_OPS = (OP_LIST, OP_SCAN, OP_COUNT)
START_OPS = (OP_START, OP_CRON_START, OP_RETRY_START)

VIS_QUERIES = (
    "WorkflowType = 'lg-churn'",
    "WorkflowType = 'lg-pool' AND CloseStatus = -1",
    "CloseStatus = 0",
    "CloseStatus = -1",
    "CloseStatus = 0 OR CloseStatus = -1",
    "WorkflowType = 'lg-churn' AND StartTime > 0",
    "WorkflowType != 'lg-pool' AND (CloseStatus = 0 OR CloseStatus = 5)",
    "StartTime > 0 AND CloseTime >= 0",
)

#: how often a sender sends one op that keeps losing a write conflict
CONFLICT_TRIES = 3

CHURN_TYPE = "lg-churn"
POOL_TYPE = "lg-pool"


def churn_task_list(domain: str) -> str:
    return f"lg-churn-{domain}"


def pool_task_list(domain: str) -> str:
    return f"lg-pool-{domain}"


@dataclass(frozen=True)
class ScheduledOp:
    index: int
    at_s: float
    kind: str
    domain: str
    workflow_id: str
    arg: str = ""


@dataclass(frozen=True)
class DomainPlan:
    """One domain's traffic: its arrival rate, its mix (kind -> weight),
    its pool and how a pool target is drawn."""

    domain: str
    rps: float
    weights: Dict[str, float] = field(default_factory=dict)
    pool_size: int = 8
    arrival: str = "poisson"      # or "uniform": a 1/rps lattice
    pool_draw: str = "uniform"    # or "zipf"
    zipf_theta: float = 0.99
    fixed_set: bool = False
    #: "same": a reset draws its target as a signal does (the original);
    #: "cold-half": the domain's resets walk the colder half of the pool
    #: in turn, so that a reset, which closes the run under every op that
    #: races it, rarely meets the hot workflows' signals or another reset
    reset_target: str = "same"

    def normalized(self) -> List[tuple]:
        items = [(k, w) for k, w in sorted(self.weights.items()) if w > 0]
        total = sum(w for _, w in items)
        if not items or total <= 0 or not self.rps > 0:
            raise ValueError(f"plan {self.domain!r}: no positive weights "
                             f"or rate")
        return [(k, w / total) for k, w in items]


def pool_workflow_ids(plan: DomainPlan) -> List[str]:
    return [f"lg-{plan.domain}-pool-{i}" for i in range(plan.pool_size)]


def _draw_kind(rng: random.Random, normalized: Sequence[tuple]) -> str:
    r = rng.random()
    acc = 0.0
    for kind, w in normalized:
        acc += w
        if r < acc:
            return kind
    return normalized[-1][0]


def zipf_cdf(n: int, theta: float) -> List[float]:
    """Cumulative shares of ranks 0..n-1 under p(rank) ~ 1/(rank+1)^theta."""
    weights = [1.0 / (r + 1) ** theta for r in range(n)]
    total, acc, out = sum(weights), 0.0, []
    for w in weights:
        acc += w
        out.append(acc / total)
    return out


def _apportion(shares: Sequence[float], n: int) -> List[int]:
    """`n` items split by `shares` (which sum to 1), largest remainder."""
    raw = [s * n for s in shares]
    counts = [int(x) for x in raw]
    by_rest = sorted(range(len(raw)), key=lambda i: -(raw[i] - counts[i]))
    for i in by_rest[:n - sum(counts)]:
        counts[i] += 1
    return counts


def _fixed_stream(plan: DomainPlan, duration_s: float, rng: random.Random):
    """(time, kind, pool rank, query index) of every op of one domain: the
    same multisets for every seed, in an order the seed shuffles."""
    n = int(round(plan.rps * duration_s))
    if plan.arrival == "uniform":
        gaps = [1.0 / plan.rps] * n
    else:  # the exponential's quantiles: a Poisson stream's gaps, all of them
        gaps = [-math.log(1.0 - (k + 0.5) / n) / plan.rps for k in range(n)]
        scale = (duration_s * n / (n + 1.0)) / sum(gaps)
        gaps = [g * scale for g in gaps]
    normalized = plan.normalized()
    kinds: List[str] = []
    for (kind, _w), count in zip(
            normalized, _apportion([w for _k, w in normalized], n)):
        kinds.extend([kind] * count)
    if plan.pool_draw == "zipf":
        cdf = zipf_cdf(plan.pool_size, plan.zipf_theta)
        shares = [cdf[0]] + [cdf[i] - cdf[i - 1] for i in range(1, len(cdf))]
    else:
        shares = [1.0 / plan.pool_size] * plan.pool_size
    ranks: List[int] = []
    for rank, count in enumerate(_apportion(shares, n)):
        ranks.extend([rank] * count)
    for seq in (gaps, kinds, ranks):
        rng.shuffle(seq)
    t, out = 0.0, []
    for gap, kind, rank in zip(gaps, kinds, ranks):
        t += gap
        out.append((t, kind, rank, rank % len(VIS_QUERIES)))
    return out


def _drawn_stream(plan: DomainPlan, duration_s: float, rng: random.Random):
    """The original's stream: every gap, kind and target a draw."""
    normalized = plan.normalized()
    cdf = zipf_cdf(plan.pool_size, plan.zipf_theta) \
        if plan.pool_draw == "zipf" else None
    t, out = 0.0, []
    while True:
        if plan.arrival == "uniform":
            t += 1.0 / plan.rps
        else:
            t += rng.expovariate(plan.rps)
        if t >= duration_s:
            return out
        kind = _draw_kind(rng, normalized)
        rank = None
        if kind in POOL_OPS or kind == OP_SIGNAL_WITH_START:
            rank = (rng.randrange(plan.pool_size) if cdf is None else
                    min(bisect.bisect_left(cdf, rng.random()),
                        plan.pool_size - 1))
        vis = (rng.randrange(len(VIS_QUERIES)) if kind in VIS_OPS else None)
        out.append((t, kind, rank, vis))


def build_schedule(plans: Sequence[DomainPlan], duration_s: float,
                   seed, id_salt: str = "") -> List[ScheduledOp]:
    """The full open-loop schedule: per-domain seeded streams (seeded by
    (seed, domain)), merged by intended time and re-indexed. `id_salt`
    keeps the churn ids of two schedules on one cluster apart (the warm-up
    traffic and the window's)."""
    ops: List[ScheduledOp] = []
    for plan in plans:
        rng = random.Random(f"{seed}:{plan.domain}")
        stream = (_fixed_stream if plan.fixed_set else _drawn_stream)(
            plan, duration_s, rng)
        for i, (t, kind, rank, vis) in enumerate(stream):
            if kind == OP_RESET and plan.reset_target == "cold-half":
                cold = plan.pool_size - 1 - i % max(1, plan.pool_size // 2)
                wf = f"lg-{plan.domain}-pool-{cold}"
            elif kind in POOL_OPS:
                wf = f"lg-{plan.domain}-pool-{rank}"
            elif kind == OP_SIGNAL_WITH_START:
                wf = f"lg-{plan.domain}-sws-{rank}"
            elif kind in VIS_OPS:
                wf = f"lg-{plan.domain}-vis"
            else:  # start-shaped: a unique churn id
                wf = f"lg-{plan.domain}-{kind}-{id_salt}{i}"
            if kind in (OP_SIGNAL, OP_SIGNAL_WITH_START):
                arg = f"sig-{id_salt}{i}"
            elif kind in VIS_OPS:
                arg = VIS_QUERIES[vis]
            else:
                arg = ""
            ops.append(ScheduledOp(index=0, at_s=round(t, 6), kind=kind,
                                   domain=plan.domain, workflow_id=wf,
                                   arg=arg))
    ops.sort(key=lambda op: (op.at_s, op.domain, op.workflow_id))
    return [ScheduledOp(index=j, at_s=op.at_s, kind=op.kind,
                        domain=op.domain, workflow_id=op.workflow_id,
                        arg=op.arg)
            for j, op in enumerate(ops)]


def trace_digest(schedule: Sequence[ScheduledOp]) -> str:
    h = hashlib.sha256()
    for op in schedule:
        h.update(f"{op.index}|{op.at_s:.6f}|{op.kind}|{op.domain}|"
                 f"{op.workflow_id}|{op.arg}\n".encode())
    return h.hexdigest()


# -- the run ----------------------------------------------------------------


@dataclass
class Sample:
    """One op as it went: seconds relative to the window's start."""

    op: ScheduledOp
    sent_s: float       # actual send
    done_s: float       # reply (or failure)
    outcome: str        # "ok" | "shed" | "busy" | the error's type name

    @property
    def latency_s(self) -> float:   # what the user waited, from due time
        return self.done_s - self.op.at_s

    @property
    def service_s(self) -> float:
        return self.done_s - self.sent_s

    @property
    def late_s(self) -> float:
        return max(0.0, self.sent_s - self.op.at_s)


class DecisionCompleters:
    """The worker fleet for the churn population: per-domain poller threads
    that complete every decision with CompleteWorkflowExecution."""

    def __init__(self, client_factory: Callable[[], object],
                 domains: Sequence[str], per_domain: int = 2,
                 poll_wait: float = 0.3) -> None:
        self._factory = client_factory
        self._domains = list(domains)
        self._per_domain = per_domain
        self._poll_wait = poll_wait
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._lock = threading.Lock()
        self.completed: List[tuple] = []   # (monotonic time, domain, id)
        self.errors = 0

    def start(self) -> None:
        for domain in self._domains:
            for i in range(self._per_domain):
                t = threading.Thread(target=self._loop, args=(domain,),
                                     daemon=True,
                                     name=f"lg-completer-{domain}-{i}")
                t.start()
                self._threads.append(t)

    def _loop(self, domain: str) -> None:
        from cadence_tpu.core.enums import DecisionType
        from cadence_tpu.engine.history_engine import Decision
        client = self._factory()
        tl = churn_task_list(domain)
        while not self._stop.is_set():
            try:
                resp = client.poll_for_decision_task(
                    domain, tl, wait_seconds=self._poll_wait,
                    identity="bench-completer")
                if resp is None or resp.token is None:
                    continue
                client.respond_decision_task_completed(resp.token, [
                    Decision(DecisionType.CompleteWorkflowExecution,
                             {"result": b"lg-done"})])
                with self._lock:
                    self.completed.append((time.perf_counter(), domain,
                                           resp.token.workflow_id))
            except Exception:
                with self._lock:
                    self.errors += 1
                time.sleep(0.05)

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10)


def seed_pools(client_factory: Callable[[], object],
               plans: Sequence[DomainPlan], clients: int = 16,
               deadline_s: float = 240.0, warm_resets: int = 0) -> dict:
    """Register the domains and seed every pool workflow: started on the
    pool task list with exactly ONE decision completed (empty decision
    list: it stays open, nothing pending), so a reset always has the
    event-4 decision boundary to fork at and a signal always lands. Then
    `warm_resets` pool workflows a domain are reset once and re-decided,
    so that what the first reset of a process pays (lazy runtime set-up, a
    compile) is paid before the window."""
    first = client_factory()
    for plan in plans:
        try:
            first.register_domain(plan.domain)
        except Exception:
            pass  # already registered
    t0 = time.perf_counter()
    stop_at = time.monotonic() + deadline_s
    local = threading.local()

    def client():
        if not hasattr(local, "client"):
            local.client = client_factory()
        return local.client

    def start(item):
        plan, wf = item
        client().start_workflow_execution(
            plan.domain, wf, POOL_TYPE, pool_task_list(plan.domain),
            execution_timeout=24 * 3600)

    def decide_until_empty(plan: DomainPlan, pending: set, lock) -> None:
        while time.monotonic() < stop_at:
            with lock:
                if not pending:
                    return
            resp = client().poll_for_decision_task(
                plan.domain, pool_task_list(plan.domain), wait_seconds=0.2,
                identity="bench-seeder")
            if resp is None or resp.token is None:
                continue
            client().respond_decision_task_completed(resp.token, [])
            with lock:
                pending.discard(resp.token.workflow_id)

    def decide_all(targets: Dict[str, set]) -> None:
        lock = threading.Lock()
        per = max(1, clients // max(1, len(plans)))
        futures = [pool.submit(decide_until_empty, plan, targets[plan.domain],
                               lock)
                   for plan in plans for _ in range(per)]
        for f in futures:
            f.result()
        left = {d: sorted(p)[:3] for d, p in targets.items() if p}
        if left:
            raise TimeoutError(f"pool workflows never decided: {left}")

    with ThreadPoolExecutor(max_workers=clients) as pool:
        list(pool.map(start, [(plan, wf) for plan in plans
                              for wf in pool_workflow_ids(plan)]))
        started_s = time.perf_counter() - t0
        decide_all({plan.domain: set(pool_workflow_ids(plan))
                    for plan in plans})
        decided_s = time.perf_counter() - t0
        reset = {plan.domain: set(pool_workflow_ids(plan)[-warm_resets:])
                 if warm_resets and plan.weights.get(OP_RESET, 0) > 0
                 else set() for plan in plans}

        def warm_reset(item):
            domain, wf = item
            client().reset_workflow_execution(
                domain, wf, decision_finish_event_id=4, reason="bench-warmup")

        list(pool.map(warm_reset, [(d, wf) for d, wfs in reset.items()
                                   for wf in sorted(wfs)]))
        decide_all(reset)
    return {"pool_workflows": sum(p.pool_size for p in plans),
            "start_s": started_s, "decide_s": decided_s - started_s,
            "reset_warm_s": time.perf_counter() - t0 - decided_s}


class Sender:
    """Drives one schedule against frontend clients, open loop: each op is
    sent at its intended time by whichever thread is free, and timed from
    that intended time whenever it was sent."""

    def __init__(self, client_factory: Callable[[], object],
                 schedule: Sequence[ScheduledOp], threads: int = 16,
                 longpoll_timeout_s: float = 0.25,
                 request_salt: str = "") -> None:
        self._factory = client_factory
        self.schedule = list(schedule)
        self.threads = threads
        self.longpoll_timeout_s = longpoll_timeout_s
        self.request_salt = request_salt
        self.samples: List[Sample] = []
        self.conflict_retries = 0
        self._cursor = 0
        self._lock = threading.Lock()

    def run(self) -> float:
        """Send the schedule; returns the window's start on
        `time.perf_counter()`. Ends when every op has its reply."""
        workers = [threading.Thread(target=self._loop, daemon=True,
                                    name=f"lg-sender-{i}")
                   for i in range(self.threads)]
        self.t0 = time.perf_counter()
        for t in workers:
            t.start()
        for t in workers:
            t.join()
        return self.t0

    def _loop(self) -> None:
        from cadence_tpu.utils.circuitbreaker import ServiceBusy
        from cadence_tpu.utils.quotas import ServiceBusyError

        client = self._factory()
        n = len(self.schedule)
        while True:
            with self._lock:
                idx = self._cursor
                if idx >= n:
                    return
                self._cursor = idx + 1
            op = self.schedule[idx]
            wait = self.t0 + op.at_s - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent = time.perf_counter()
            try:
                self._execute_retrying(client, op)
                outcome = "ok"
            except ServiceBusyError:
                outcome = "shed"
            except ServiceBusy:
                outcome = "busy"
            except Exception as exc:
                outcome = type(exc).__name__
            done = time.perf_counter()
            sample = Sample(op, sent - self.t0, done - self.t0, outcome)
            with self._lock:
                self.samples.append(sample)

    def _execute_retrying(self, client, op: ScheduledOp) -> None:
        """A transaction that lost the race for its workflow to another
        (`ConditionFailedError`: two signals to one hot workflow at once)
        is sent again, as a client library would; the op's latency runs
        on through the retries."""
        from cadence_tpu.engine.persistence import ConditionFailedError

        for attempt in range(CONFLICT_TRIES):
            try:
                return self._execute(client, op)
            except ConditionFailedError:
                if attempt == CONFLICT_TRIES - 1:
                    raise
                with self._lock:
                    self.conflict_retries += 1

    def _execute(self, client, op: ScheduledOp) -> None:
        from cadence_tpu.core.events import RetryPolicy
        if op.kind == OP_START:
            client.start_workflow_execution(
                op.domain, op.workflow_id, CHURN_TYPE,
                churn_task_list(op.domain))
        elif op.kind == OP_CRON_START:
            client.start_workflow_execution(
                op.domain, op.workflow_id, CHURN_TYPE,
                churn_task_list(op.domain), cron_schedule="* * * * *")
        elif op.kind == OP_RETRY_START:
            client.start_workflow_execution(
                op.domain, op.workflow_id, CHURN_TYPE,
                churn_task_list(op.domain),
                retry_policy=RetryPolicy(initial_interval_seconds=1,
                                         backoff_coefficient=2.0,
                                         maximum_interval_seconds=10,
                                         maximum_attempts=3))
        elif op.kind == OP_SIGNAL:
            client.signal_workflow_execution(
                op.domain, op.workflow_id, op.arg,
                request_id=(f"lg-req-{self.request_salt}"
                            f"{op.domain}-{op.index}"))
        elif op.kind == OP_SIGNAL_WITH_START:
            client.signal_with_start_workflow_execution(
                op.domain, op.workflow_id, op.arg, POOL_TYPE,
                pool_task_list(op.domain))
        elif op.kind == OP_QUERY:
            client.describe_workflow_execution(op.domain, op.workflow_id)
        elif op.kind == OP_LONGPOLL:
            client.get_workflow_execution_history(
                op.domain, op.workflow_id, wait_for_new_event=True,
                last_event_id=1_000_000, timeout=self.longpoll_timeout_s)
        elif op.kind == OP_RESET:
            client.reset_workflow_execution(
                op.domain, op.workflow_id, decision_finish_event_id=4,
                reason=f"bench-{op.index}")
        elif op.kind == OP_LIST:
            client.list_workflow_executions(op.domain, op.arg)
        elif op.kind == OP_SCAN:
            client.scan_workflow_executions(op.domain, op.arg)
        elif op.kind == OP_COUNT:
            client.count_workflow_executions(op.domain, op.arg)
        else:
            raise ValueError(f"unknown op kind {op.kind!r}")
