"""Seeded corpora of serialized workflow histories, made in bulk.

A copy of `chip_smoke.CorpusFarm`, cut to what a replay cell needs: wire
blobs (the feeder's input), made by worker processes that are pinned to
the CPU platform and import nothing of the program — the generator, the
event types and the codec are the benchmark's own copies under `refimpl/`
(they make the input; `refimpl/replay.py`, which judges the answers, is no
copy of anything). The farm must be started BEFORE this process opens the
chip.

Workflow `j` of the corpus is history `j // len(suites)` of suite
`suites[j % len(suites)]`: the suites are interleaved, so every chunk of
the feeder holds the same mix.
"""
from __future__ import annotations

import os
import random
from typing import Dict, List, Sequence, Tuple


def corpus_key(suites: Sequence[str], j: int) -> Tuple[str, int]:
    """(suite, index within the suite) of corpus workflow `j`."""
    return suites[j % len(suites)], j // len(suites)


def _worker_init() -> None:
    # corpus workers never go near a device, whatever the machine holds
    os.environ["JAX_PLATFORMS"] = "cpu"


def _make_slice(job):
    """Worker: generate and serialize histories [lo, hi) of one suite."""
    suite, seed, lo, hi, target_events = job
    from refimpl.core.codec import serialize_history
    from refimpl.gen.corpus import generate_history

    blobs, longest, events = [], 0, 0
    for i in range(lo, hi):
        history = generate_history(suite, seed, i, target_events)
        n = sum(len(batch.events) for batch in history)
        longest = max(longest, n)
        events += n
        blobs.append(serialize_history(history))
    return suite, lo, blobs, longest, events


class CorpusFarm:
    """`collect()` returns (blobs interleaved over the suites, the longest
    history's event count, the real events in all of them)."""

    def __init__(self, suites: Sequence[str], per_suite: int,
                 target_events: int, seed: int, slice_w: int = 512,
                 workers: int = 0) -> None:
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor

        self.suites, self.per_suite = list(suites), per_suite
        jobs = [(suite, seed, lo, min(lo + slice_w, per_suite), target_events)
                for lo in range(0, per_suite, slice_w) for suite in suites]
        self.workers = workers or max(1, min(len(jobs),
                                             (os.cpu_count() or 2) - 1))
        self._pool = ProcessPoolExecutor(
            max_workers=self.workers,
            mp_context=multiprocessing.get_context("spawn"),
            initializer=_worker_init)
        self._futures = [self._pool.submit(_make_slice, job) for job in jobs]

    def collect(self) -> Tuple[List[bytes], int, int]:
        n_suites = len(self.suites)
        blobs: List[bytes] = [b""] * (n_suites * self.per_suite)
        longest = events = 0
        for future in self._futures:
            suite, lo, part, part_longest, part_events = future.result()
            s = self.suites.index(suite)
            blobs[lo * n_suites + s:(lo + len(part)) * n_suites + s:n_suites] \
                = part
            longest = max(longest, part_longest)
            events += part_events
        self._futures = []
        return blobs, longest, events

    def close(self) -> None:
        self._pool.shutdown(wait=True, cancel_futures=True)


def draw_sample(suites: Sequence[str], per_suite: int, n_per_suite: int,
                seed: int) -> List[int]:
    """Corpus indices whose answers are compared with the reference: the
    same number from every suite, drawn from the seed."""
    picked: List[int] = []
    for s, suite in enumerate(suites):
        rng = random.Random(f"{seed}:{suite}:reference-sample")
        for i in rng.sample(range(per_suite), min(n_per_suite, per_suite)):
            picked.append(i * len(suites) + s)
    return sorted(picked)


def reference_crcs(suites: Sequence[str], indices: Sequence[int], seed: int,
                   target_events: int, control: str = "") -> Dict[int, int]:
    """The plain reference (`refimpl/replay.py`) over each sampled history,
    made again from the seed: the CRC32 of its canonical payload.
    `control` names a broken guarantee (see `configs/*.json`,
    "guarantees")."""
    from refimpl import replay as reference
    from refimpl.gen.corpus import generate_history

    out: Dict[int, int] = {}
    for j in indices:
        suite, i = corpus_key(suites, j)
        history = reference.plain(
            generate_history(suite, seed, i, target_events))
        out[j] = reference.crc_of_history(history, control)
    return out
