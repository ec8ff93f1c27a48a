"""Wall-clock open-loop micro-batcher in front of ``batch_query``.

Copies the batching policy of ``repro.runtime.scheduler.OnlineRuntime`` onto
the wall clock: requests are due at times drawn ahead from the seed (open
loop: a slow server does not slow the arrivals), a batch flushes when 64
requests wait or when the oldest has waited 5 ms, and one serial server runs
the batches, each call returning only once its results are on the host.  A
request's latency runs from its due time to the end of its batch.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import time
import traceback
from typing import Callable, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class Batch:
    start: float        # window clock, seconds
    end: float
    rows: np.ndarray    # schedule rows served by the call
    plan_s: float       # the program's planning time for the call


@dataclasses.dataclass
class Served:
    done: np.ndarray            # (R,) completion time, NaN if never served
    ids: np.ndarray             # (R, k) served ids, -1 padded
    dists: np.ndarray           # (R, k) served distances
    exact: np.ndarray           # (R,) every clause of the plan exact (pre/ipre)
    n_post_clauses: np.ndarray  # (R,) clauses planned post
    n_clauses: np.ndarray       # (R,)
    batches: List[Batch]
    errors: int

    def latency(self, due: np.ndarray) -> np.ndarray:
        return self.done - due


def serve(call: Callable, queries: np.ndarray, preds: Sequence, due: np.ndarray,
          k: int, close: float, max_batch: int = 64, max_wait: float = 0.005,
          drain: Optional[np.ndarray] = None, annotate=None,
          clock: Callable[[], float] = time.perf_counter) -> Served:
    """Serve requests due at ``due`` (sorted, window seconds) through
    ``call(queries, preds, k) -> [PlannedResult]``.  Before ``close`` the
    policy above holds.  After it, the rows in ``drain`` (all when None)
    that are still waiting are served in full batches, so each one gets an
    answer; their completions fall after the close."""
    from repro.core.plan import STRATEGY_NAMES

    r = int(due.size)
    ann = annotate or (lambda name: contextlib.nullcontext())
    out = Served(np.full(r, np.nan), np.full((r, k), -1, np.int32),
                 np.full((r, k), np.inf, np.float32), np.zeros(r, bool),
                 np.zeros(r, np.int32), np.zeros(r, np.int32), [], 0)

    def run(rows: np.ndarray, t0: float) -> None:
        start = clock() - t0
        try:
            with ann("bench:serve"):
                res = call(queries[rows], [preds[i] for i in rows], k)
        except Exception:  # a failed call fails its rows; the run goes on
            traceback.print_exc(file=sys.stderr)
            out.errors += len(rows)
            res = None
        end = clock() - t0
        plan_s = 0.0
        if res is not None:
            for i, pr in zip(rows, res):
                out.done[i] = end
                kk = min(k, pr.result.ids.shape[1])
                out.ids[i, :kk] = pr.result.ids[0, :kk]
                out.dists[i, :kk] = pr.result.dists[0, :kk]
                names = [STRATEGY_NAMES[c.decision] for c in pr.plan.clauses]
                out.exact[i] = all(n in ("pre", "ipre") for n in names)
                out.n_post_clauses[i] = sum(n == "post" for n in names)
                out.n_clauses[i] = len(names)
                plan_s += pr.plan_overhead
        out.batches.append(Batch(start, end, rows, plan_s))

    t0 = clock()
    nxt = 0
    while nxt < r:
        now = clock() - t0
        if now >= close:
            break
        n_due = int(np.searchsorted(due, now, side="right")) - nxt
        full_at = due[nxt + max_batch - 1] if nxt + max_batch - 1 < r else np.inf
        flush_at = min(due[nxt] + max_wait, full_at)
        if n_due > 0 and now >= flush_at:
            b = min(n_due, max_batch)
            run(np.arange(nxt, nxt + b), t0)
            nxt += b
            continue
        wake = min(flush_at, close)
        with ann("bench:wait"):
            time.sleep(max(0.0, wake - (clock() - t0)))
    left = np.arange(nxt, r)
    if drain is not None:
        left = left[np.isin(left, drain)]
    for s in range(0, left.size, max_batch):
        rows = left[s:s + max_batch]
        run(rows, t0)
    return out
