"""Arithmetic shared by the metric readers in ``bench/metrics/``.

Each reader takes the run's ``RunView`` and returns a number, or ``None``
where the run holds nothing to read (the harness then leaves the metric out
of the result line).  A share of a roofline is never returned as 0.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from .roofline import least_seconds, scan_work

EXACT = ("pre", "ipre")
# the XLA programs that make up the masked top-k scan: the Pallas kernel's
# wrapper, the XLA top-k it falls back to, and the on-device subset gather
SCAN_MODULES = ("masked_l2_topk", "l2_topk", "_take_rows")


def latency_ms(v, q: float) -> float:
    """The ``q``-th percentile of latency from due time over every request
    due in the window, ms; a request never answered counts as infinitely
    late."""
    lat = v.served.latency(v.sched.due)
    with np.errstate(invalid="ignore"):
        return float(np.percentile(np.where(np.isnan(lat), np.inf, lat), q) * 1e3)


def recall_at_10(v) -> float:
    """Mean recall@k of the sampled answers against the reference."""
    return v.readings.recall


def setup_s(v) -> float:
    """Process start to window start."""
    return v.setup_s


def queue_wait_ms(v) -> Optional[float]:
    """Median of (batch start - due time) over the window's requests."""
    w = [b.start - v.sched.due[b.rows] for b in v.window_batches()]
    return float(np.median(np.concatenate(w)) * 1e3) if w else None


def batch_size(v) -> Optional[float]:
    b = v.window_batches()
    return float(np.mean([x.rows.size for x in b])) if b else None


def plan_ms(v) -> Optional[float]:
    """Mean planning time of one ``batch_query`` call (the program's own
    ``PlannedResult.plan_overhead``, summed over the call's rows)."""
    b = v.window_batches()
    return float(np.mean([x.plan_s for x in b]) * 1e3) if b else None


def post_clause_share(v) -> Optional[float]:
    rows = np.concatenate([b.rows for b in v.window_batches()] or [np.zeros(0, int)])
    n = int(v.served.n_clauses[rows].sum())
    return 100.0 * float(v.served.n_post_clauses[rows].sum()) / n if n else None


def group_ms(v, decisions) -> Optional[float]:
    walls = [s["wall_s"] for s in v.spans if s["decision"] in decisions]
    return float(np.mean(walls) * 1e3) if walls else None


def scan_roofline(v) -> Optional[float]:
    """Least time of the exact groups' masked top-k work over the device time
    of the scan's programs, in %."""
    if v.reduced is None or not v.peaks:
        return None
    least = 0.0
    for s in v.spans:
        if s["decision"] in EXACT and s["n_candidates"]:
            fl, nb = scan_work(int(s["n_rows"]), int(s["n_candidates"]), v.dim, v.k)
            least += least_seconds(fl, nb, v.peaks)[0]
    kernel = sum(v.reduced.module_s.get(m, 0.0) for m in SCAN_MODULES)
    return 100.0 * least / kernel if least > 0 and kernel > 0 else None


def idle_share(v) -> Optional[float]:
    """1 - (union of device-busy intervals) / (traced window), in %."""
    if v.reduced is None or v.reduced.busy_s <= 0 or v.window_s <= 0:
        return None
    return 100.0 * (1.0 - v.reduced.busy_s / v.window_s)


def window_compiles(v) -> float:
    return float(v.compiles)


def tail_p99_ms(v) -> Optional[float]:
    """99th percentile latency from due time over every request due in the
    window.  It stands in for an end-to-end ``p99_ms``, which no bound could
    hold (``PERF.md``): post-filter widenings on the host make it."""
    p99 = latency_ms(v, 99)
    return p99 if np.isfinite(p99) else None
