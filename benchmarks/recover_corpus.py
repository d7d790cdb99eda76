"""Seeded workflow histories for a recovery cell, one transaction at a time,
each with the plain reference's verdict on it.

What a write-ahead log holds is not a history's blob but one record for
each committed transaction, so a worker hands back every history as the
serialized blob of each of its batches, beside what the commit of that batch
leaves in the current-run pointer (the workflow's state and close status)
and the reference's answers: the CRC32 of the final state's canonical
payload (`refimpl/replay.py`) and whether the run ends open. The workers
are pinned to the CPU platform and import nothing of the program; they must
be started BEFORE this process opens the chip. The reference runs here, in
the workers, off the timed path.

Workflow `j` is history `j // len(suites)` of suite `suites[j %
len(suites)]`, as in `corpus.py`.
"""
from __future__ import annotations

import os
from typing import List, Sequence

import corpus as corpus_mod

#: persistence.WorkflowCloseStatus* of the event that closes a run
CLOSE_STATUS = {"WorkflowExecutionCompleted": 1,
                "WorkflowExecutionFailed": 2,
                "WorkflowExecutionCanceled": 3,
                "WorkflowExecutionTerminated": 4,
                "WorkflowExecutionContinuedAsNew": 5,
                "WorkflowExecutionTimedOut": 6}


def _make_slice(job):
    """Worker: histories [lo, hi) of one suite, batch by batch."""
    suite, seed, lo, hi, target_events, control = job
    from refimpl import replay as reference
    from refimpl.core.codec import serialize_history
    from refimpl.gen.corpus import generate_history

    out = []
    for i in range(lo, hi):
        history = generate_history(suite, seed, i, target_events)
        plain = reference.plain(history)
        # the reference's table one transaction at a time, for the
        # workflow state that each commit leaves in its pointer record
        # (the verdict below is `crc_of_history`, whole)
        st, close_status, pointers = reference.new_state(), 0, []
        for batch in plain:
            for event in batch:
                reference.apply_event(st, event)
                close_status = CLOSE_STATUS.get(event[1], close_status)
            pointers.append((st["state"], close_status))
        out.append({
            "blobs": [serialize_history([batch]) for batch in history],
            # (first event id, events) of each batch: what the log is handed
            "handed": [(batch[0][0], len(batch)) for batch in plain],
            "pointers": pointers,
            "crc": reference.crc_of_history(plain),
            "control_crc": (reference.crc_of_history(plain, control)
                            if control else None),
            "open": st["state"] != reference.COMPLETED,
        })
    return suite, lo, out


class HistoryFarm:
    """`collect()` returns one record for each workflow of the corpus,
    interleaved over the suites."""

    def __init__(self, suites: Sequence[str], per_suite: int,
                 target_events: int, seed: int, control: str = "",
                 slice_w: int = 128, workers: int = 0) -> None:
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor

        self.suites, self.per_suite = list(suites), per_suite
        jobs = [(suite, seed, lo, min(lo + slice_w, per_suite),
                 target_events, control)
                for lo in range(0, per_suite, slice_w) for suite in suites]
        self.workers = workers or max(1, min(len(jobs),
                                             (os.cpu_count() or 2) - 1))
        self._pool = ProcessPoolExecutor(
            max_workers=self.workers,
            mp_context=multiprocessing.get_context("spawn"),
            initializer=corpus_mod._worker_init)
        self._futures = [self._pool.submit(_make_slice, job) for job in jobs]

    def collect(self) -> List[dict]:
        n_suites = len(self.suites)
        histories: List[dict] = [{}] * (n_suites * self.per_suite)
        for future in self._futures:
            suite, lo, part = future.result()
            s = self.suites.index(suite)
            histories[lo * n_suites + s:
                      (lo + len(part)) * n_suites + s:n_suites] = part
        self._futures = []
        return histories

    def close(self) -> None:
        self._pool.shutdown(wait=True, cancel_futures=True)
