"""Execution strategies for filtered ANN queries (paper §4.1 Methods).

* :class:`PreFilterExec`        — filter first, brute-force exact KNN over
  the passing subset (the paper implements pre-filtering with brute force;
  §4.1).  The predicate mask comes from an O(N·leaves) columnar scan.
* :class:`IndexedPreFilterExec` — the same exact subset top-k, but the mask
  comes from the bitmap attribute index (``repro.filter``): compiled DNF
  bitmaps, LRU-cached across serving traffic, expanded to the bool mask the
  kernels consume.  Identical results to :class:`PreFilterExec` by
  construction (same mask, same execution core), minus the scan.
* :class:`PostFilterExec`       — search the global IVF index for α·k
  candidates, filter, and double α (and widen nprobe) until ≥ k valid
  results survive.

ACORN (and any other registered ANN backend) is reached through the backend
registry (``repro.index.registry``) rather than a bespoke executor: routed
rows compute the candidate mask once and call the backend's uniform
``search_masked`` surface.

All return ``SearchResult`` with global ids (-1 padded), squared-L2
distances, wall time, and strategy bookkeeping used to label planner
training data.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..index.ivf import IVFIndex
from ..kernels.ops import fused_masked_topk
from .predicates import AnyPredicate
from .util import next_pow2

__all__ = [
    "SearchResult",
    "PreFilterExec",
    "IndexedPreFilterExec",
    "PostFilterExec",
    "recall_at_k",
]


@jax.jit
def _take_rows(corpus: jax.Array, idx: jax.Array) -> jax.Array:
    """Rows ``idx`` of a device-resident corpus, gathered where it lives."""
    return jnp.take(corpus, idx, axis=0, mode="clip")


@dataclasses.dataclass
class SearchResult:
    dists: np.ndarray      # (B, k)
    ids: np.ndarray        # (B, k), -1 padded
    elapsed: float         # end-to-end seconds (filter + search + expansion)
    strategy: str
    n_expansions: int = 0  # post-filter α-doubling rounds
    backend: str = ""      # routed backend name ("" until packaging fills it)
    knob: str = ""         # routed knob-tier name


def recall_at_k(result_ids: np.ndarray, truth_ids: np.ndarray) -> float:
    """Mean fraction of ground-truth neighbours recovered (recall@k)."""
    b, k = truth_ids.shape
    hits = 0
    denom = 0
    for i in range(b):
        t = set(int(x) for x in truth_ids[i] if x >= 0)
        if not t:
            continue
        r = set(int(x) for x in result_ids[i] if x >= 0)
        hits += len(t & r)
        denom += len(t)
    return hits / denom if denom else 1.0


class PreFilterExec:
    """Filter -> brute-force KNN over the subset (100 % recall).

    The mask-to-top-k core (:meth:`search_masked`) is shared with
    :class:`IndexedPreFilterExec` — the two strategies differ ONLY in how
    the candidate mask is produced (columnar scan vs compiled bitmap), so
    their results are identical by construction.
    """

    strategy_name = "pre"
    # Above this passing fraction, gathering the subset costs more than it
    # saves: run the fused masked top-k over the FULL corpus instead (the
    # warmed full-corpus shape) — the "bitmap-masked fused top-k" large-set
    # path.  Below it, gather + pow2-padded subset scan.
    FULL_SCAN_FRAC = 0.25

    def __init__(self, vectors: np.ndarray, cat: np.ndarray, num: np.ndarray,
                 device: Optional[jax.Device] = None):
        self.vectors = np.ascontiguousarray(vectors, np.float32)
        self.cat, self.num = cat, num
        # the corpus is copied to the scan device once (``device``, else the
        # default one) and every scan over it runs there: a full scan ships
        # only queries and the mask, a subset scan only the passing row ids,
        # and a corpus shard per chip scans on its own chip.
        self.device = device if device is not None else jax.devices()[0]
        self.scan_vectors = jax.device_put(self.vectors, self.device)

    def _scan_subset(self, qp, idx, valid_rows, kk):
        """The gathered-subset scan: rows ``idx`` (a pow2 bucket, padding
        rows masked off by ``valid_rows``) are gathered from the resident
        corpus on its device and scanned there.  A host gather of the rows
        cost ~120 ms for 37k rows at 1M x 384 on a v5e host, 60x the scan.
        ``warm`` goes through here too, so it compiles exactly the shapes a
        search reuses."""
        sub = _take_rows(self.scan_vectors, idx)
        return fused_masked_topk(qp, sub, valid_rows, kk)

    def warm(self, k: int) -> None:
        """Compile every scan shape ``search_masked`` dispatches for a
        floor-8 query batch: each pow2 subset bucket the gathered path can
        reach (passing sets up to ``FULL_SCAN_FRAC`` of the corpus) and the
        full-corpus masked scan.  Keeps one-off compiles out of the timed
        strategy races that label the planner's training rows (§3.1)."""
        n, d = self.vectors.shape
        q = np.zeros((8, d), np.float32)
        p_max = next_pow2(max(int(self.FULL_SCAN_FRAC * n), 1), floor=16)
        p = 16
        while p <= p_max:
            self._scan_subset(q, np.zeros(p, np.int32), np.ones(p, bool), min(k, p))
            p *= 2
        fused_masked_topk(q, self.scan_vectors, np.ones(n, bool), min(k, n))

    def candidate_mask(self, pred: AnyPredicate) -> np.ndarray:
        """(N,) bool predicate mask — the columnar scan."""
        return pred.eval(self.cat, self.num)

    def search(self, queries: np.ndarray, pred: AnyPredicate, k: int) -> SearchResult:
        t0 = time.perf_counter()
        mask = self.candidate_mask(pred)
        return self.search_masked(queries, mask, k, t0=t0)

    def search_masked(
        self, queries: np.ndarray, mask: np.ndarray, k: int,
        t0: Optional[float] = None,
    ) -> SearchResult:
        """Exact subset top-k under a precomputed candidate mask."""
        if t0 is None:
            t0 = time.perf_counter()
        b = queries.shape[0]
        n = self.vectors.shape[0]
        n_pass = int(mask.sum())
        if n_pass == 0:
            return SearchResult(
                np.full((b, k), np.inf, np.float32),
                np.full((b, k), -1, np.int32),
                time.perf_counter() - t0,
                self.strategy_name,
            )
        bp = next_pow2(b, floor=8)
        qp = np.zeros((bp, self.vectors.shape[1]), np.float32)
        qp[:b] = np.asarray(queries, np.float32)
        if n_pass > self.FULL_SCAN_FRAC * n:
            # large passing set: masked fused top-k over the whole corpus —
            # ids come back global already
            kk = min(k, n)
            d, gids = fused_masked_topk(qp, self.scan_vectors, mask, kk)
            d, gids = np.asarray(d)[:b], np.asarray(gids)[:b]
            ids = np.full((b, k), -1, np.int32)
            dist = np.full((b, k), np.inf, np.float32)
            valid = gids >= 0
            ids[:, :kk] = np.where(valid, gids, -1)
            dist[:, :kk] = np.where(valid, d, np.inf)
            return SearchResult(dist, ids, time.perf_counter() - t0, self.strategy_name)
        # small passing set: gather the compacted subset, padded to the next
        # power of two so the jit'd top-k sees O(log N) distinct shapes, not
        # one per query (otherwise recompilation time pollutes the utility
        # labels the planner learns from).  The query batch pads the same way
        # (floor 8): the batched serving path stacks all queries sharing a
        # predicate into ONE fused call, and pow2 query shapes keep the
        # compile set O(log B) — with the floor making single-query and
        # small-group calls share one shape (identical per-row results by
        # construction).
        idx = np.nonzero(mask)[0]
        p = next_pow2(n_pass, floor=16)
        idx_p = np.zeros(p, np.int32)
        idx_p[:n_pass] = idx
        valid_rows = np.zeros(p, bool)
        valid_rows[:n_pass] = True
        # k is capped by the padded bucket, not the passing count: a tiny
        # passing set reuses the bucket's warmed shape (padding rows come
        # back as id -1)
        kk = min(k, p)
        d, local = self._scan_subset(qp, idx_p, valid_rows, kk)
        d, local = np.asarray(d)[:b], np.asarray(local)[:b]
        ids = np.full((b, k), -1, np.int32)
        dist = np.full((b, k), np.inf, np.float32)
        valid = local >= 0
        ids[:, :kk] = np.where(valid, idx[np.minimum(np.maximum(local, 0), n_pass - 1)], -1)
        dist[:, :kk] = np.where(valid, d, np.inf)
        return SearchResult(dist, ids, time.perf_counter() - t0, self.strategy_name)


class IndexedPreFilterExec(PreFilterExec):
    """Pre-filtering with the candidate mask answered by the bitmap
    attribute index instead of a columnar scan (``repro.filter``).

    The compiled-bitmap cache is shared with the engine's selectivity
    estimator, so a predicate that was planned (exact popcount selectivity)
    executes from the same compilation; repeated serving predicates skip
    compilation AND mask expansion (both cached).  Predicates whose leaves
    reference unindexed attributes fall back to the scan — same answer,
    scan price.
    """

    strategy_name = "ipre"

    def __init__(self, vectors: np.ndarray, cat: np.ndarray, num: np.ndarray,
                 index, cache, device: Optional[jax.Device] = None):
        super().__init__(vectors, cat, num, device)
        self.index = index          # repro.filter.AttributeIndex
        self.cache = cache          # repro.filter.PredicateCache

    def candidate_mask(self, pred: AnyPredicate) -> np.ndarray:
        if self.index is not None and self.index.covers(pred):
            # two-tier cache: compiled words (capacity) + a smaller LRU of
            # expanded masks (mask_capacity), so repeat predicates skip both
            # compilation and expansion without pinning a mask per entry
            return self.cache.mask(pred, self.index)
        return pred.eval(self.cat, self.num)


class PostFilterExec:
    """Global-index ANN -> filter -> α-doubling expansion (paper §4.1(2))."""

    # The first probe covers at least this share of the lists, whatever
    # ``nprobe0`` says: a fixed count loses recall as the list count grows.
    # On the arXiv shape at 1M rows (1000 lists) post-filter recall@10 was
    # 0.60 at 8 probes and 0.98 at 64 (``benchmarks/chip_scan_diag.py``).
    MIN_PROBE_FRAC = 1 / 16

    def __init__(
        self,
        index: IVFIndex,
        cat: np.ndarray,
        num: np.ndarray,
        alpha0: int = 4,
        nprobe0: int = 8,
        max_rounds: int = 8,
    ):
        self.index = index
        self.cat, self.num = cat, num
        self.alpha0, self.nprobe0, self.max_rounds = alpha0, nprobe0, max_rounds

    def initial_params(self, k: int, est_selectivity: Optional[float] = None) -> Tuple[int, int]:
        """Initial ``(candidate budget, nprobe)`` for one query.

        ``est_selectivity`` (from the planner's estimator) sizes BOTH knobs:
        to surface ~alpha0*k predicate-passing candidates the scan must cover
        ~alpha0*k/selectivity corpus points, so nprobe ~ alpha0*k*L/(sel*N)
        AND the candidate request itself must be ~alpha0*k/sel — a budget of
        only alpha0*k at low selectivity loses most candidates to the filter
        and pays extra doubling rounds (or recall at the round cap).  Both
        values round up to powers of two so a batch of queries collapses into
        a handful of shared (budget, nprobe) groups — the grouping the
        batched executor exploits.
        """
        n, n_lists = self.index.n, self.index.n_lists
        want = self.alpha0 * k
        nprobe = max(self.nprobe0, int(np.ceil(self.MIN_PROBE_FRAC * n_lists)))
        if est_selectivity is not None and est_selectivity > 0:
            want_points = self.alpha0 * k / est_selectivity
            nprobe_sel = int(np.ceil(want_points * n_lists / n))
            nprobe = int(np.clip(nprobe_sel, nprobe, n_lists))
            want = max(want, int(np.ceil(want_points)))
        return min(next_pow2(want), n), min(next_pow2(nprobe), n_lists)

    def search(
        self,
        queries: np.ndarray,
        pred: AnyPredicate,
        k: int,
        est_selectivity: Optional[float] = None,
        alive: Optional[np.ndarray] = None,
    ) -> SearchResult:
        """Single-predicate entry point; delegates to the row-faithful batched
        core so the per-query and batched serving paths share one
        implementation (and therefore return identical ids)."""
        t0 = time.perf_counter()
        q = np.asarray(queries, np.float32)
        b = q.shape[0]
        out_d, out_i, rounds = self.search_rows(
            q, [pred] * b, k, [est_selectivity] * b, alive=alive)
        n_exp = int(rounds.max()) if rounds.size else 0
        return SearchResult(out_d, out_i, time.perf_counter() - t0, "post", n_exp)

    def search_rows(
        self,
        q: np.ndarray,
        preds: Sequence[AnyPredicate],
        k: int,
        ests: Sequence[Optional[float]],
        alive: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Row-faithful batched post-filter search (per-row predicates).

        ``alive``, when given, is a bool mask over the INDEX's rows; a
        retrieved candidate whose bit is clear (tombstoned under a live
        corpus) is filtered exactly like a predicate miss, so it both drops
        from the results and still counts against the α budget — the same
        accounting a predicate-failing candidate gets.

        Every row runs exactly the (budget, nprobe) doubling schedule a
        dedicated ``search`` call would run — rows whose current parameters
        coincide share one IVF dispatch, and candidate filtering is a single
        vectorised predicate evaluation per distinct predicate instead of a
        Python loop over rows.  Because ``IVFIndex.search`` is row-independent,
        batched results are identical to B independent calls, only cheaper.
        Returns ``(dists (B, k), ids (B, k), expansion_rounds (B,))``.
        """
        b = q.shape[0]
        n, n_lists = self.index.n, self.index.n_lists
        params = [self.initial_params(k, e) for e in ests]
        want = np.array([w for w, _ in params], np.int64)
        nprobe = np.array([p for _, p in params], np.int64)
        rounds = np.zeros(b, np.int64)
        out_d = np.full((b, k), np.inf, np.float32)
        out_i = np.full((b, k), -1, np.int32)
        # a row pays at most max_rounds IVF dispatches: the initial search
        # plus up to max_rounds - 1 doubling rounds
        pending = np.arange(b) if self.max_rounds > 0 else np.empty(0, np.int64)
        # predicates evaluated lazily on retrieved candidates only
        while pending.size:
            groups: dict = {}
            for qi in pending:
                groups.setdefault((int(want[qi]), int(nprobe[qi])), []).append(int(qi))
            for (w, npb), rows_l in groups.items():
                rows = np.asarray(rows_l)
                d, ids = self.index.search(q[rows], w, nprobe=npb)
                # one predicate evaluation per distinct predicate in the group
                keep = np.zeros(ids.shape, bool)
                bypred: dict = {}
                for j, qi in enumerate(rows_l):
                    bypred.setdefault(preds[qi], []).append(j)
                for p, js in bypred.items():
                    flat = ids[js].reshape(-1)
                    pos = flat >= 0
                    kp = np.zeros(flat.size, bool)
                    if pos.any():
                        kp[pos] = p.eval(self.cat[flat[pos]], self.num[flat[pos]])
                        if alive is not None:
                            kp[pos] &= alive[flat[pos]]
                    keep[js] = kp.reshape(len(js), -1)
                # first k passing candidates per row, in distance order: a
                # stable argsort of ~keep floats passing slots to the front
                # without reordering among themselves
                kk = min(k, ids.shape[1])
                order = np.argsort(~keep, axis=1, kind="stable")[:, :kk]
                sel_i = np.take_along_axis(ids, order, axis=1)
                sel_d = np.take_along_axis(d, order, axis=1)
                sel_keep = np.take_along_axis(keep, order, axis=1)
                blk_i = np.full((rows.size, k), -1, np.int32)
                blk_d = np.full((rows.size, k), np.inf, np.float32)
                blk_i[:, :kk] = np.where(sel_keep, sel_i, -1)
                blk_d[:, :kk] = np.where(sel_keep, sel_d, np.inf)
                out_i[rows] = blk_i
                out_d[rows] = blk_d
            got = (out_i[pending] >= 0).sum(1)
            exhausted = (want[pending] >= n) & (nprobe[pending] >= n_lists)
            more = (got < k) & ~exhausted & (rounds[pending] + 1 < self.max_rounds)
            pending = pending[more]
            if pending.size:
                want[pending] = np.minimum(want[pending] * 2, n)   # paper: double α
                nprobe[pending] = np.minimum(nprobe[pending] * 2, n_lists)
                rounds[pending] += 1
        return out_d, out_i, rounds
