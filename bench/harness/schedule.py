"""Open-loop request schedules, drawn ahead of the run from the seed.

Copies the model of ``repro.runtime.queue.poisson_trace`` / ``_assemble``:
Poisson arrivals at a fixed rate, and each request's predicate drawn from
the pool with Zipf popularity (rank r with probability ~ 1/r^a).  The count
of requests is fixed, ``round(rate * seconds)``, and their due times are
sorted uniform draws over the window: a Poisson process conditioned on its
count.  Each predicate's count is fixed too, its Zipf share of the window
rounded by largest remainder, and only their order is drawn: every seed
offers the same requests' predicates, in another order, with fresh query
vectors.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .datagen import query_vectors


@dataclasses.dataclass
class Schedule:
    due: np.ndarray        # (R,) seconds from the window's start, sorted
    pred: np.ndarray       # (R,) index into the pool
    queries: np.ndarray    # (R, d) float32, one fresh query vector each
    seconds: float
    rate: float

    def __len__(self) -> int:
        return int(self.due.size)


def zipf_probs(n: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** a
    return p / p.sum()


def zipf_counts(n: int, n_pool: int, a: float) -> np.ndarray:
    """How many of ``n`` requests each rank gets: its Zipf share, rounded by
    largest remainder so that the counts sum to ``n``."""
    share = zipf_probs(n_pool, a) * n
    counts = np.floor(share).astype(np.int64)
    counts[np.argsort(counts - share, kind="stable")[: n - int(counts.sum())]] += 1
    return counts


def make_schedule(n_pool: int, vectors: np.ndarray, query_scale: float,
                  rate: float, seconds: float, zipf_a: float,
                  rng: np.random.Generator) -> Schedule:
    n = max(1, int(round(rate * seconds)))
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    pred = rng.permutation(np.repeat(np.arange(n_pool), zipf_counts(n, n_pool, zipf_a)))
    return Schedule(due, pred, query_vectors(vectors, query_scale, n, rng),
                    float(seconds), float(rate))
