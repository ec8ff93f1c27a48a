"""FilteredANNEngine — the public API tying the paper's pieces together.

Workflow (paper Fig. 1): query -> selectivity estimator -> core planner ->
selected executor -> results.  The engine owns the dataset statistics, the
global IVF index (post-filter backend), the estimator, the planner, and the
executors; ``fit()`` runs the paper's §3.1 training-data preparation.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

# NOTE: repro.filter is imported lazily inside build_stats/shard_corpus —
# filter's compiler depends on core.predicates, so a module-level import
# here would make `import repro.filter` (filter-first order) hit a
# partially-initialised package.
from ..index.flat import l2_topk
from ..index.ivf import IVFIndex
from ..index.registry import BackendSet
from ..obs.trace import NULL_TRACER
from .corpus import CompactionPolicy, LiveCorpus
from .executors import (
    IndexedPreFilterExec,
    PostFilterExec,
    PreFilterExec,
    SearchResult,
    recall_at_k,
)
from .plan import (
    ClausePlan,
    ExecutionPlan,
    NO_ROUTE,
    STRATEGY_NAMES,
    clause_predicates,
    collapse_clause_results,
    default_route_name,
    expand_for_execution,
    format_plan,
)
from .planner import CorePlanner, PlannerFeatures, INDEXED_PRE, POST_FILTER, PRE_FILTER
from .predicates import AnyPredicate, Or
from .selectivity import SelEstimate, SelectivityEstimator
from .stats import DatasetStats

__all__ = ["FilteredANNEngine", "EngineConfig", "PlannedResult", "QueryResult",
           "CorpusShard", "PlanCache", "QueryLabel", "ExecutionPlan",
           "ClausePlan"]

# legacy spelling, kept for downstream imports (serve, tests)
_default_route_name = default_route_name


@dataclasses.dataclass
class EngineConfig:
    n_lists: Optional[int] = None      # IVF lists (default sqrt(N))
    sample_frac: float = 0.02          # stats sample (paper: 1-5 %)
    alpha0: int = 4                    # initial post-filter expansion
    nprobe0: int = 8                   # post-filter first probe count; the
                                       # executor floors it at 1/16 of the lists
    seed: int = 0
    default_k: int = 10                # warmed-up k for the jit'd searches
    attr_index: bool = True            # build the bitmap/range attribute index
    range_buckets: int = 128           # filter.ranges.DEFAULT_BUCKETS
    pred_cache_size: int = 256         # compiled-predicate LRU entries
    plan_cache_size: int = 1024        # memoised (predicate, k) plan entries
    # registered ANN backends to race/route over (repro.index.registry
    # names).  None keeps the legacy plan-only engine: no BackendSet is
    # built, the decision space stays (pre, post, ipre), and every code
    # path is bit-identical to before the routing extension existed.
    backends: Optional[Tuple[str, ...]] = None
    # recall@k a (backend, knob) class must hit on a training query before
    # utility gets a say in the routing label; below it, max-recall wins.
    route_recall_target: float = 0.9
    # live-corpus compaction thresholds (see core.corpus.CompactionPolicy):
    # churn past any of these makes needs_compaction()/maybe_compact() fold
    # segment + tombstones into a rebuilt index
    max_tombstone_frac: float = 0.20
    max_segment_frac: float = 0.20
    max_list_drift: float = 1.75


@dataclasses.dataclass
class PlannedResult:
    """One served query: the executed :class:`SearchResult` plus the
    structured :class:`ExecutionPlan` it ran under.  The historical scalar
    surface (``est_selectivity`` / ``decision``) reads through to the plan."""

    result: SearchResult
    plan: ExecutionPlan
    plan_overhead: float               # seconds spent estimating + deciding

    @property
    def est_selectivity(self) -> float:
        return self.plan.est

    @property
    def decision(self) -> int:
        return self.plan.decision


#: public alias — "the thing a query returns" in docs and the API snapshot
QueryResult = PlannedResult


@dataclasses.dataclass
class QueryLabel:
    """Outcome of one §3.1 utility race (see :meth:`label_query`).

    ``route`` is the argmax (backend, knob-tier) class when a BackendSet was
    raced, else -1; ``route_utils`` holds the per-class utilities.  For DNF
    predicates ``clauses`` carries one :class:`QueryLabel` per unique
    conjunctive disjunct (first-occurrence order) — the per-clause
    decomposition the planner and feedback loop train on."""

    label: int                         # PRE_FILTER or POST_FILTER
    true_sel: float
    u_pre: float
    u_post: float
    route: int = -1
    route_utils: Optional[np.ndarray] = None
    clauses: Optional[Tuple["QueryLabel", ...]] = None


def _kernel_snapshot() -> dict:
    """Current dispatch counts of the process-global kernel ledger — an
    execute span annotates the DELTA across its body, so the span carries
    exactly its own dispatches."""
    from ..kernels import ops

    return ops.dispatch_counts()


def _annotate_kernel_delta(tracer, counts0: dict) -> None:
    """Attach per-kernel dispatch deltas since ``counts0`` to the open span
    (``kernel_<name>`` attrs, on the deterministic ledger)."""
    from ..kernels import ops

    for name, n in ops.dispatch_counts().items():
        d = n - counts0.get(name, 0)
        if d:
            tracer.annotate(**{f"kernel_{name}": d})


def package_results(
    d: np.ndarray,
    ids: np.ndarray,
    rounds: np.ndarray,
    plans: Sequence[ExecutionPlan],
    share: float,
    plan_share: float,
) -> List[PlannedResult]:
    """Wrap batched (B, k) arrays into per-row PlannedResults — one
    packaging convention for the flat and sharded batch paths (``share`` is
    the batch wall time split evenly across rows, plan overhead included).
    The strategy / backend / knob labels on each row come from its
    :class:`ExecutionPlan` (DNF rows report the synthetic ``dnf`` class)."""
    out = []
    for j, plan in enumerate(plans):
        out.append(PlannedResult(
            SearchResult(d[j : j + 1], ids[j : j + 1], share,
                         plan.strategy,
                         n_expansions=int(rounds[j]),
                         backend=plan.backend, knob=plan.knob),
            plan, plan_share,
        ))
    return out


def _execute_grouped(
    pre_exec: PreFilterExec,
    ipre_exec: Optional[IndexedPreFilterExec],
    post_exec: PostFilterExec,
    queries: np.ndarray,
    preds: Sequence[AnyPredicate],
    k: int,
    decisions: np.ndarray,
    ests: np.ndarray,
    routes: Optional[np.ndarray] = None,
    backend_set: Optional[BackendSet] = None,
    tracer=None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Decision-grouped batch execution — the ONE implementation behind both
    the flat (`FilteredANNEngine.batch_query`) and sharded
    (`CorpusShard.search_batch`) paths.

    The two pre-filter groups (scan-masked and bitmap-masked) each evaluate
    every distinct predicate's mask once and run one fused masked top-k over
    all queries sharing it; un-routed post-filter rows run one row-faithful
    batched IVF search.  With ``routes``/``backend_set``, post-filter rows
    carrying a routing class >= 0 group by (class, predicate): each group
    evaluates its predicate mask once (through the bitmap index when
    covered) and runs ONE ``search_masked`` call on the routed backend —
    the (decision, backend, knob) extension of PR 2's decision grouping.
    Returns ``(dists (B, k), ids (B, k) local, expansion_rounds (B,))``.
    """
    tr = tracer if tracer is not None else NULL_TRACER
    b = len(preds)
    out_d = np.full((b, k), np.inf, np.float32)
    out_i = np.full((b, k), -1, np.int32)
    rounds = np.zeros(b, np.int64)
    for decision, ex in ((PRE_FILTER, pre_exec), (INDEXED_PRE, ipre_exec or pre_exec)):
        groups: dict = {}
        for i in range(b):
            if decisions[i] == decision:
                groups.setdefault(preds[i], []).append(i)
        for pred, rows in groups.items():
            bk, knob = _default_route_name(decision)
            with tr.span("group", decision=STRATEGY_NAMES[decision],
                         backend=bk, knob=knob, n_rows=len(rows)):
                # split of ex.search(): mask once, then the fused masked
                # top-k — bit-identical (search() is exactly this pair),
                # but the mask stays visible for the candidate-count attr
                with tr.span("mask"):
                    m = ex.candidate_mask(pred)
                res = ex.search_masked(queries[rows], m, k, tracer=tr)
                if tr.enabled:
                    tr.annotate(n_candidates=int(m.sum()))
                out_d[rows], out_i[rows] = res.dists, res.ids
    routed = routes is not None and backend_set is not None
    post_rows = [
        i for i in range(b)
        if decisions[i] == POST_FILTER and not (routed and routes[i] >= 0)
    ]
    if post_rows:
        with tr.span("group", decision="post", backend="ivf", knob="adapt",
                     n_rows=len(post_rows)):
            d, ids, rnd = post_exec.search_rows(
                queries[post_rows], [preds[i] for i in post_rows], k,
                [float(ests[i]) for i in post_rows], tracer=tr,
            )
            out_d[post_rows], out_i[post_rows] = d, ids
            rounds[post_rows] = rnd
            if tr.enabled:
                tr.annotate(expansion_rounds=int(np.asarray(rnd).sum()))
    if routed:
        groups = {}
        for i in range(b):
            if decisions[i] == POST_FILTER and routes[i] >= 0:
                groups.setdefault((int(routes[i]), preds[i]), []).append(i)
        mask_ex = ipre_exec or pre_exec
        masks: dict = {}
        for (ci, pred), rows in groups.items():
            bk, knob = backend_set.classes()[ci]
            with tr.span("group", decision="post", backend=str(bk),
                         knob=str(knob), n_rows=len(rows)):
                if pred not in masks:
                    with tr.span("mask"):
                        masks[pred] = mask_ex.candidate_mask(pred)
                d, ids = backend_set.search_class(ci, queries[rows], masks[pred], k)
                if tr.enabled:
                    tr.annotate(n_candidates=int(masks[pred].sum()))
                out_d[rows], out_i[rows] = d[:, :k], ids[:, :k]
    return out_d, out_i, rounds


def _live_execute_grouped(
    pre_exec: PreFilterExec,
    ipre_exec: Optional[IndexedPreFilterExec],
    post_exec: PostFilterExec,
    queries: np.ndarray,
    preds: Sequence[AnyPredicate],
    k: int,
    decisions: np.ndarray,
    ests: np.ndarray,
    live: LiveCorpus,
    routes: Optional[np.ndarray] = None,
    backend_set: Optional[BackendSet] = None,
    tracer=None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Tombstone/segment-composing twin of ``_execute_grouped`` — the one
    batch executor every query takes once the corpus mutated.

    Per decision group the base corpus answers exactly as before, except
    every candidate mask is ANDed with the live bitmap (tombstoned rows can
    never surface) and masks from the extended attribute index (length
    ``n_total``) are sliced back to the base rows the executors hold.  The
    append segment is searched by a plain masked scan (it stays small
    between compactions) through the SAME ``PreFilterExec`` kernel path the
    base uses, and the two parts merge with ``merge_topk`` — base part
    stacked first, so the composite column tie-break keeps handle order,
    which is the bit-equality invariant against a fresh build of the
    equivalent post-mutation corpus (handle -> compacted-position maps are
    monotone).
    """
    from ..dist.collectives import merge_topk

    tr = tracer if tracer is not None else NULL_TRACER
    b = len(preds)
    out_d = np.full((b, k), np.inf, np.float32)
    out_i = np.full((b, k), -1, np.int32)
    rounds = np.zeros(b, np.int64)
    alive = live.alive_mask()
    base_n = live.base_n
    alive_base, alive_seg = alive[:base_n], alive[base_n:]
    seg_exec = (
        PreFilterExec(live.seg_vectors(), live.seg_cat(), live.seg_num())
        if live.seg_n else None
    )
    seg_masks: dict = {}

    def seg_mask(pred) -> np.ndarray:
        if pred not in seg_masks:
            with tr.span("mask", segment=True):
                seg_masks[pred] = pred.eval(live.seg_cat(), live.seg_num()) & alive_seg
        return seg_masks[pred]

    def finish(rows, pred, bd, bi):
        if seg_exec is not None and seg_mask(pred).any():
            res = seg_exec.search_masked(queries[rows], seg_mask(pred), k, tracer=tr)
            si = np.where(res.ids >= 0, res.ids + base_n, -1).astype(np.int32)
            bd, bi = merge_topk(np.stack([bd, res.dists]), np.stack([bi, si]), k)
        out_d[rows], out_i[rows] = bd, bi

    for decision, ex in ((PRE_FILTER, pre_exec), (INDEXED_PRE, ipre_exec or pre_exec)):
        groups: dict = {}
        for i in range(b):
            if decisions[i] == decision:
                groups.setdefault(preds[i], []).append(i)
        for pred, rows in groups.items():
            bk, knob = _default_route_name(decision)
            with tr.span("group", decision=STRATEGY_NAMES[decision],
                         backend=bk, knob=knob, n_rows=len(rows), live=True):
                with tr.span("mask"):
                    mm = ex.candidate_mask(pred)[:base_n] & alive_base
                res = ex.search_masked(queries[rows], mm, k, tracer=tr)
                if tr.enabled:
                    tr.annotate(n_candidates=int(mm.sum()))
                finish(rows, pred, res.dists, res.ids)
    routed = routes is not None and backend_set is not None
    post_rows = [
        i for i in range(b)
        if decisions[i] == POST_FILTER and not (routed and routes[i] >= 0)
    ]
    if post_rows:
        with tr.span("group", decision="post", backend="ivf", knob="adapt",
                     n_rows=len(post_rows), live=True):
            d, ids, rnd = post_exec.search_rows(
                queries[post_rows], [preds[i] for i in post_rows], k,
                [float(ests[i]) for i in post_rows], alive=alive_base, tracer=tr,
            )
            rounds[post_rows] = rnd
            groups = {}
            for j, i in enumerate(post_rows):
                groups.setdefault(preds[i], []).append(j)
            for pred, js in groups.items():
                finish([post_rows[j] for j in js], pred, d[js], ids[js])
            if tr.enabled:
                tr.annotate(expansion_rounds=int(np.asarray(rnd).sum()))
    if routed:
        groups = {}
        for i in range(b):
            if decisions[i] == POST_FILTER and routes[i] >= 0:
                groups.setdefault((int(routes[i]), preds[i]), []).append(i)
        mask_ex = ipre_exec or pre_exec
        base_masks: dict = {}
        for (ci, pred), rows in groups.items():
            bk, knob = backend_set.classes()[ci]
            with tr.span("group", decision="post", backend=str(bk),
                         knob=str(knob), n_rows=len(rows), live=True):
                if pred not in base_masks:
                    with tr.span("mask"):
                        base_masks[pred] = mask_ex.candidate_mask(pred)[:base_n] & alive_base
                d, ids = backend_set.search_class(ci, queries[rows], base_masks[pred], k)
                if tr.enabled:
                    tr.annotate(n_candidates=int(base_masks[pred].sum()))
                finish(rows, pred, d[:, :k], ids[:, :k])
    return out_d, out_i, rounds


class PlanCache:
    """LRU memo of ``(canonical predicate key, k) -> ExecutionPlan``.

    Serving traffic repeats predicates constantly; planning the same
    predicate is pure — the decision depends only on predicate + dataset
    statistics + the current planner head — so repeats can skip the
    estimator and the MLP dispatch entirely.  Invalidation is tied to the
    things a cached plan DOES depend on, via :meth:`validate_epoch`
    against ``(planner_version, planner.generation,
    estimator.generation)`` on every lookup: a planner swap, a planner or
    estimator refit — even one invoked directly on ``engine.planner`` /
    ``engine.estimator`` — empties the memo before it can serve a stale
    plan.
    """

    def __init__(self, capacity: int = 1024):
        assert capacity >= 1
        self.capacity = capacity
        self._store: "OrderedDict[Tuple, ExecutionPlan]" = OrderedDict()
        self.epoch: Tuple = ()        # engine._plan_epoch() the memo is valid under
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0

    def validate_epoch(self, epoch: Tuple) -> None:
        """Drop every entry if the (planner head, estimator, corpus
        generation) tuple the cached plans were computed under has changed —
        catches direct ``estimator.fit()`` calls that bypass the engine's
        own clear hooks, and (since the corpus generation joined the epoch)
        any live-corpus mutation, whose tombstones/appends change exact
        selectivities.  Epoch-mismatch drops are counted separately from
        capacity evictions so mutation-driven churn is observable in
        ``stats()``."""
        if epoch != self.epoch:
            if self.epoch:
                self.invalidations += 1
            self._store.clear()
            self.epoch = epoch

    def __len__(self) -> int:
        return len(self._store)

    def get(self, key) -> Optional[ExecutionPlan]:
        hit = self._store.get(key)
        if hit is None:
            self.misses += 1
            return None
        self.hits += 1
        self._store.move_to_end(key)
        return hit

    def put(self, key, value: ExecutionPlan) -> None:
        self._store[key] = value
        if len(self._store) > self.capacity:
            self._store.popitem(last=False)
            self.evictions += 1

    def clear(self) -> None:
        self._store.clear()

    def stats(self) -> Dict[str, int]:
        return {
            "size": len(self._store), "capacity": self.capacity,
            "hits": self.hits, "misses": self.misses, "evictions": self.evictions,
            "invalidations": self.invalidations,
        }


@dataclasses.dataclass
class CorpusShard:
    """One partition of the corpus with its own pre-/post-filter executors.

    Produced by :meth:`FilteredANNEngine.shard_corpus`.  Executors operate
    on shard-local row numbers; :meth:`search` maps results back to global
    ids so shard outputs merge directly (``repro.dist.collectives.merge_topk``).
    Each shard carries its OWN attribute index + predicate cache (bitmaps
    are positional, so they cannot be sliced from the global index).
    """

    shard_id: int
    ids: np.ndarray                    # (n_local,) global row ids
    pre_exec: PreFilterExec
    post_exec: PostFilterExec
    ipre_exec: Optional[IndexedPreFilterExec] = None
    backend_set: Optional[BackendSet] = None   # per-shard backend instances
    live: Optional[LiveCorpus] = None          # created on first mutation

    # ------------------------------------------------------------------
    def ensure_live(self) -> LiveCorpus:
        if self.live is None:
            self.live = LiveCorpus(self.pre_exec.vectors,
                                   self.pre_exec.cat, self.pre_exec.num)
        return self.live

    def upsert_local(
        self,
        vectors: np.ndarray,
        cat: np.ndarray,
        num: np.ndarray,
        global_ids: np.ndarray,
        local_ids: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Append rows to this shard's live view and extend the local ->
        global map (``global_ids``, one per row, assigned by the sharded
        engine's placement rule).  ``local_ids`` tombstones replaced LOCAL
        handles first.  Returns the new local handles."""
        live = self.ensure_live()
        c = np.atleast_2d(np.asarray(cat))
        m = np.atleast_2d(np.asarray(num))
        handles = live.upsert(vectors, c, m, ids=local_ids)
        if self.ipre_exec is not None:
            self.ipre_exec.index.extend(c, m)
            self.ipre_exec.cache.invalidate()
        self.ids = np.concatenate(
            [self.ids, np.asarray(global_ids, self.ids.dtype)]
        )
        return handles

    def delete_local(self, local_ids: np.ndarray) -> np.ndarray:
        """Tombstone shard-local handles; returns the newly dead ones."""
        return self.ensure_live().delete(local_ids)

    def search(
        self,
        q: np.ndarray,
        pred: AnyPredicate,
        k: int,
        decision: int,
        est_selectivity: Optional[float] = None,
        route: int = NO_ROUTE,
    ) -> SearchResult:
        """Run the planned executor on this shard; returns GLOBAL ids.
        ``route >= 0`` sends a post-filter row to that (backend, knob-tier)
        class of the shard's BackendSet instead of the lazy post path."""
        if self.live is not None and self.live.dirty:
            t0 = time.perf_counter()
            decisions = np.array([decision], np.int32)
            routes_arr = np.array([route], np.int32)
            d, ids, rounds = _live_execute_grouped(
                self.pre_exec, self.ipre_exec, self.post_exec,
                q, [pred], k, decisions,
                np.array([0.0 if est_selectivity is None else est_selectivity]),
                self.live, routes=routes_arr, backend_set=self.backend_set,
            )
            res = SearchResult(d, ids, time.perf_counter() - t0,
                               STRATEGY_NAMES[decision],
                               n_expansions=int(rounds[0]))
            if route >= 0 and decision == POST_FILTER and self.backend_set is not None:
                res.backend, res.knob = self.backend_set.classes()[route]
            res.ids = self._to_global(res.ids)
            return res
        if decision == INDEXED_PRE:
            res = (self.ipre_exec or self.pre_exec).search(q, pred, k)
        elif decision == PRE_FILTER:
            res = self.pre_exec.search(q, pred, k)
        elif route >= 0 and self.backend_set is not None:
            t0 = time.perf_counter()
            mask = (self.ipre_exec or self.pre_exec).candidate_mask(pred)
            d, ids = self.backend_set.search_class(route, q, mask, k)
            bk, knob = self.backend_set.classes()[route]
            res = SearchResult(d, ids, time.perf_counter() - t0, "post",
                               backend=bk, knob=knob)
        else:
            res = self.post_exec.search(q, pred, k, est_selectivity=est_selectivity)
        res.ids = self._to_global(res.ids)
        return res

    def _to_global(self, ids: np.ndarray) -> np.ndarray:
        valid = ids >= 0
        return np.where(valid, self.ids[np.maximum(ids, 0)], -1).astype(np.int32)

    def search_batch(
        self,
        queries: np.ndarray,
        preds: Sequence[AnyPredicate],
        k: int,
        decisions: np.ndarray,
        ests: np.ndarray,
        routes: Optional[np.ndarray] = None,
        tracer=None,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Run a whole planned batch on this shard (decision-grouped, same
        shared ``_execute_grouped`` core as
        :meth:`FilteredANNEngine.batch_query`).  Returns
        ``(dists (B, k), ids (B, k) GLOBAL, expansion_rounds (B,))`` ready to
        stack across shards for one batched ``merge_topk``."""
        if self.live is not None and self.live.dirty:
            out_d, out_i, rounds = _live_execute_grouped(
                self.pre_exec, self.ipre_exec, self.post_exec,
                queries, preds, k, decisions, ests, self.live,
                routes=routes, backend_set=self.backend_set, tracer=tracer,
            )
        else:
            out_d, out_i, rounds = _execute_grouped(
                self.pre_exec, self.ipre_exec, self.post_exec,
                queries, preds, k, decisions, ests,
                routes=routes, backend_set=self.backend_set, tracer=tracer,
            )
        return out_d, self._to_global(out_i), rounds


class FilteredANNEngine:
    def __init__(
        self,
        vectors: np.ndarray,
        cat: np.ndarray,
        num: np.ndarray,
        config: EngineConfig = EngineConfig(),
    ):
        self.vectors = np.ascontiguousarray(vectors, np.float32)
        self.cat, self.num = cat, num
        self.config = config
        self.build_time_: dict = {}

    # ------------------------------------------------------------------
    def build_stats(self) -> "FilteredANNEngine":
        """Planning-only build: statistics, estimator, planner, features.

        Skips the global IVF index, local executors, and jit warmup — all
        a sharded deployment pays for but never uses (every query runs on
        per-shard executors from :meth:`shard_corpus`).  Enough for
        :meth:`plan` and :meth:`shard_corpus`; :meth:`fit` and the
        unsharded :meth:`query` need the full :meth:`build`.
        """
        t0 = time.perf_counter()
        self.dataset_stats = DatasetStats.build(
            self.vectors, self.cat, self.num,
            sample_frac=self.config.sample_frac, seed=self.config.seed,
        )
        t1 = time.perf_counter()
        # bitmap/range attribute index + shared compiled-predicate cache:
        # the estimator's exact fast path and the indexed pre-filter
        # executor compile each predicate once between them
        from ..filter import AttributeIndex, PredicateCache
        from ..filter.cache import canonical_key

        self.attr_index = (
            AttributeIndex.build(self.cat, self.num, self.config.range_buckets)
            if self.config.attr_index else None
        )
        self.pred_cache = PredicateCache(self.config.pred_cache_size)
        # memoised plans for repeat predicates (pure in predicate + stats +
        # planner head; cleared on fit/swap_planner)
        self.plan_cache = PlanCache(self.config.plan_cache_size)
        self._plan_key = canonical_key
        self.planner_version = 0
        t2 = time.perf_counter()
        self.estimator = SelectivityEstimator(
            self.dataset_stats, index=self.attr_index, cache=self.pred_cache
        )
        self.planner = CorePlanner(seed=self.config.seed)
        self.feat = PlannerFeatures(self.dataset_stats)
        self.backend_set: Optional[BackendSet] = None   # built by build()
        # live-corpus mutation layer: every upsert/delete flows through
        # self.live; the estimator composes its tombstones into the exact
        # fast path, and corpus_generation joins the plan epoch so memoised
        # plans from a previous corpus version invalidate on lookup.
        # (corpus_generation is engine-level and monotone ACROSS compactions
        # — a fresh LiveCorpus restarts its own generation at 0.)
        self.live = LiveCorpus(self.vectors, self.cat, self.num)
        self.estimator.live = self.live
        self.corpus_generation = 0
        self.n_compactions = 0
        self.compaction_policy = CompactionPolicy(
            max_tombstone_frac=self.config.max_tombstone_frac,
            max_segment_frac=self.config.max_segment_frac,
            max_list_drift=self.config.max_list_drift,
        )
        # observability: the no-op tracer by default, an installed one kept
        # across compaction rebuilds (compact() re-runs build_stats) the
        # same way the trained heads survive
        self.tracer = getattr(self, "tracer", NULL_TRACER)
        self.build_time_["stats"] = t1 - t0
        self.build_time_["attr_index"] = t2 - t1
        return self

    def build(self) -> "FilteredANNEngine":
        """Offline phase: statistics + global index (paper Table 2 costs)."""
        self.build_stats()
        t1 = time.perf_counter()
        self.ivf = IVFIndex(self.vectors, self.config.n_lists, seed=self.config.seed).build()
        t2 = time.perf_counter()
        self.pre_exec = PreFilterExec(self.vectors, self.cat, self.num)
        self.ipre_exec = IndexedPreFilterExec(
            self.vectors, self.cat, self.num, self.attr_index, self.pred_cache
        )
        self.post_exec = PostFilterExec(
            self.ivf, self.cat, self.num,
            alpha0=self.config.alpha0, nprobe0=self.config.nprobe0,
        )
        if self.config.backends:
            t_b = time.perf_counter()
            self.backend_set = BackendSet.build(
                self.vectors, self.config.backends, seed=self.config.seed
            )
            self.build_time_["backends"] = time.perf_counter() - t_b
        # warm the jit'd pre-filter bucket shapes: per-query utility timings
        # (planner training labels, §3.1) must not include one-off XLA
        # compiles — a cold bucket inflates T_search by ~100x and mislabels
        # the query
        self._warm_buckets(self.config.default_k)
        t3 = time.perf_counter()
        self.build_time_.update({"ivf": t2 - t1, "warmup": t3 - t2})
        return self

    def _warm_buckets(self, k: int):
        """Compile every scan shape the pre-filter executors dispatch
        (``PreFilterExec.warm``: the Pallas kernel on a TPU, XLA elsewhere),
        plus the ground-truth shapes."""
        n, d = self.vectors.shape
        self.pre_exec.warm(k)
        q1 = np.zeros((1, d), np.float32)
        l2_topk(q1, self.vectors, k)                      # ground-truth shapes
        l2_topk(q1, self.vectors, k, np.ones(n, bool))
        # fit()'s oracle scans the corpus the pre-filter keeps on device
        l2_topk(q1, self.pre_exec.scan_vectors, k, np.ones(n, bool))

    # ------------------------------------------------------------------
    def label_query(self, q: np.ndarray, pred: AnyPredicate, k: int = 10,
                    ) -> QueryLabel:
        """Paper §3.1 utility labelling — the ONE definition shared by the
        offline :meth:`fit` loop, the online feedback loop's shadow
        labeller, and the benchmarks' oracle: run BOTH strategies against
        the exact masked top-k and pick the winner by utility
        U = recall@k / T_search.

        With a built BackendSet, every registered (backend, knob-tier)
        class is raced under the same rule (mask evaluation charged to each
        contender, since routed execution pays it at serve time); the
        winning class — highest utility among classes whose measured recall
        meets ``config.route_recall_target``, max-recall when none do —
        becomes the routing label and its utility competes as the post-side
        champion, so a backend that beats BOTH the exact scan and the lazy
        post path wins the plan decision too.

        DNF predicates additionally race every unique conjunctive disjunct
        on its own (``QueryLabel.clauses``, first-occurrence order — the
        same enumeration the per-disjunct planner uses), so planner /
        estimator / routing training sees clause-level rows for DNF
        traffic while the whole-predicate label stays available."""
        q = np.atleast_2d(q)
        clauses = None
        if isinstance(pred, Or):
            seen, cls = set(), []
            for t in pred.terms:
                key = self._plan_key(t)
                if key in seen:
                    continue
                seen.add(key)
                cls.append(self.label_query(q, t, k))
            clauses = tuple(cls)
        t_m0 = time.perf_counter()
        mask = pred.eval(self.cat, self.num)
        live = getattr(self, "live", None)
        live_dirty = live is not None and live.dirty
        alive_base = live.alive_mask()[: live.base_n] if live_dirty else None
        if live_dirty:
            # race strategies over the same live candidate set: tombstones
            # compose into mask, ground truth, and the post path alike (the
            # segment sits out the race — both contenders skip it equally)
            mask = mask & alive_base
        t_mask = time.perf_counter() - t_m0
        true_sel = float(mask.mean())
        # exact ground truth, over the corpus the pre-filter keeps on device
        _, ti = l2_topk(q, self.pre_exec.scan_vectors, k, mask)
        ti = np.asarray(ti)
        if live_dirty:
            r_pre = self.pre_exec.search_masked(q, mask, k)
            r_pre.elapsed += t_mask          # charge mask eval, like search()
        else:
            r_pre = self.pre_exec.search(q, pred, k)
        r_post = self.post_exec.search(q, pred, k, est_selectivity=true_sel,
                                       alive=alive_base)
        u_pre = recall_at_k(r_pre.ids, ti) / max(r_pre.elapsed, 1e-7)
        u_post = recall_at_k(r_post.ids, ti) / max(r_post.elapsed, 1e-7)
        route, route_utils = NO_ROUTE, None
        if self.backend_set is not None:
            classes = self.backend_set.classes()
            n_c = len(classes)
            route_utils = np.zeros(n_c, np.float64)
            recalls = np.zeros(n_c, np.float64)
            for ci in range(n_c):
                t0 = time.perf_counter()
                _, ids = self.backend_set.search_class(ci, q, mask, k)
                dt = time.perf_counter() - t0 + t_mask
                recalls[ci] = recall_at_k(ids, ti)
                route_utils[ci] = recalls[ci] / max(dt, 1e-7)
            # Constrained pick (Faiss-autotune style): utility only decides
            # among classes meeting the recall target.  A raw utility argmax
            # lets wall-clock noise during fit route queries to a fast
            # low-recall tier, collapsing served recall run-to-run.
            ok = recalls >= self.config.route_recall_target
            if ok.any():
                route = int(np.argmax(np.where(ok, route_utils, -1.0)))
            else:
                route = int(np.argmax(recalls + 1e-9 * route_utils))
            u_post = max(u_post, float(route_utils[route]))
        label = PRE_FILTER if u_pre >= u_post else POST_FILTER
        return QueryLabel(label, true_sel, u_pre, u_post, route, route_utils,
                          clauses=clauses)

    def fit(
        self,
        train_queries: Sequence[np.ndarray],
        train_preds: Sequence[AnyPredicate],
        k: int = 10,
        verbose: bool = False,
    ) -> "FilteredANNEngine":
        """Paper §3.1: execute both strategies per training query, label by
        utility U = recall@k / T_search, train estimator GBM + planner MLP.

        DNF training queries decompose: the planner, routing head, and
        estimator GBM only ever decide/serve *conjunctions* (the per-disjunct
        planner plans each clause of an ``Or`` independently), so an ``Or``
        contributes one training row per unique disjunct — features of the
        disjunct, label/route from its own §3.1 race — instead of one
        whole-predicate row the heads could never act on."""
        t0 = time.perf_counter()
        fit_preds, labels, true_sels, route_labels = [], [], [], []
        for q, pred in zip(train_queries, train_preds):
            lab = self.label_query(q, pred, k)
            if verbose:
                print(f"  {pred}: sel={lab.true_sel:.4f} "
                      f"U_pre={lab.u_pre:.1f} U_post={lab.u_post:.1f}")
            if lab.clauses:
                seen: set = set()
                uniq = [t for t in pred.terms
                        if not (self._plan_key(t) in seen
                                or seen.add(self._plan_key(t)))]
                for t, cl in zip(uniq, lab.clauses):
                    fit_preds.append(t)
                    labels.append(cl.label)
                    true_sels.append(cl.true_sel)
                    route_labels.append(cl.route)
            else:
                fit_preds.append(pred)
                labels.append(lab.label)
                true_sels.append(lab.true_sel)
                route_labels.append(lab.route)
        # selectivity estimator GBM trains on the same (clause) rows
        self.estimator.fit(fit_preds, true_sels)
        # re-extract features with the trained estimator so train/test match
        feats = []
        for p in fit_preds:
            se = self.estimator.estimate(p)
            feats.append(self.feat.vector(p, se.sel, k, se.is_exact))
        self.planner.fit(np.stack(feats), np.asarray(labels))
        if self.backend_set is not None:
            # routing head on the same features: argmax-utility class labels
            self.planner.fit_routing(
                np.stack(feats), np.asarray(route_labels),
                self.backend_set.class_names(),
            )
        # warm the single-query predict shape: the first live query must not
        # pay the (1, F) jit compile (~150 ms) inside its latency budget
        self.planner.decide(feats[0])
        # estimator AND head both changed: memoised plans are stale
        self.plan_cache.clear()
        self.planner_version += 1
        self.build_time_["fit"] = time.perf_counter() - t0
        return self

    def swap_planner(self, planner: CorePlanner) -> "FilteredANNEngine":
        """Atomically install a refit planner head (the online feedback
        loop's hook).  Clears the plan cache — memoised decisions belong to
        the old head — and pre-warms the new head's (1, F) predict shape so
        the first live query after a swap pays no jit compile."""
        self.planner = planner
        self.plan_cache.clear()
        self.planner_version += 1
        if planner.params is not None:
            planner.decide(np.zeros(planner.n_features, np.float32))
        return self

    def set_tracer(self, tracer) -> "FilteredANNEngine":
        """Install an :class:`repro.obs.trace.Tracer` on every serving path
        (``None`` restores the no-op default)."""
        self.tracer = tracer if tracer is not None else NULL_TRACER
        return self

    @staticmethod
    def _hit_ratio(hits: int, misses: int) -> float:
        total = hits + misses
        return round(hits / total, 6) if total else 0.0

    def stats(self) -> dict:
        """Public serving-counter accessor: predicate-cache hit/miss/eviction
        stats, plan-cache stats, and the planner head version — previously
        only reachable by poking engine internals.  (Dataset statistics
        live on ``self.dataset_stats``.)

        ``kernel_dispatch`` mirrors the process-global per-kernel dispatch
        counts (``repro.kernels.ops``) — cumulative across every engine in
        the process, so tests diff it around the call under measurement —
        and ``cache_hit_ratio`` summarises the three serving caches."""
        out: dict = {"planner_version": getattr(self, "planner_version", 0)}
        ratios: dict = {}
        pred_cache = getattr(self, "pred_cache", None)
        if pred_cache is not None:
            s = pred_cache.stats()
            out["pred_cache"] = s
            ratios["pred_cache"] = self._hit_ratio(s["hits"], s["misses"])
            ratios["mask_tier"] = self._hit_ratio(s["mask_hits"], s["mask_misses"])
        plan_cache = getattr(self, "plan_cache", None)
        if plan_cache is not None:
            s = plan_cache.stats()
            out["plan_cache"] = s
            ratios["plan_cache"] = self._hit_ratio(s["hits"], s["misses"])
        out["cache_hit_ratio"] = ratios
        from ..kernels import ops as _kops

        out["kernel_dispatch"] = _kops.dispatch_counts()
        out["corpus_generation"] = getattr(self, "corpus_generation", 0)
        out["n_compactions"] = getattr(self, "n_compactions", 0)
        live = getattr(self, "live", None)
        if live is not None:
            out["live"] = live.stats()
        return out

    # ------------------------------------------------------------------
    # live-corpus mutations
    # ------------------------------------------------------------------
    def upsert(
        self,
        vectors: np.ndarray,
        cat: np.ndarray,
        num: np.ndarray,
        ids: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Stream rows into the live corpus; returns their (stable, never
        reused) handles.  ``ids`` replaces existing handles: old rows are
        tombstoned, new versions appended under fresh handles.

        Incremental refresh instead of rebuild: label bitmaps extend and
        stay exact; the equi-depth range index goes stale (fails closed out
        of ``covers()``, so range predicates demote to the scan path and
        estimated selectivity); dataset statistics fold the delta in;
        compiled-predicate entries invalidate (their word count is stale);
        the plan epoch bumps so memoised plans re-plan on next lookup."""
        v = np.atleast_2d(np.asarray(vectors, np.float32))
        c = np.atleast_2d(np.asarray(cat))
        m = np.atleast_2d(np.asarray(num))
        tr = getattr(self, "tracer", NULL_TRACER)
        with tr.span("write", op="upsert", n_rows=int(v.shape[0])):
            removed_cat = removed_num = None
            if ids is not None:
                old = np.unique(np.asarray(ids, np.int64))
                old = old[~self.live.is_deleted(old)]
                if old.size:      # attrs of the rows about to be tombstoned
                    removed_cat, removed_num = self.live.row_attrs(old)
            handles = self.live.upsert(v, c, m, ids=ids)
            if self.attr_index is not None:
                self.attr_index.extend(c, m)
                self.pred_cache.invalidate()
            self.dataset_stats.apply_delta(
                added_cat=c, added_num=m,
                removed_cat=removed_cat, removed_num=removed_num,
            )
            ivf = getattr(self, "ivf", None)
            if ivf is not None:   # keep the drift trigger's assignments current
                self.live.assign_new(ivf.centroids)
            self.corpus_generation += 1
            tr.annotate(corpus_generation=self.corpus_generation)
        return handles

    def delete(self, ids: np.ndarray) -> np.ndarray:
        """Tombstone handles (idempotent); returns the newly dead ones.
        No index structure is rewritten — the tombstone bitmap composes
        into every candidate mask, backend call, and exact-selectivity
        popcount at query time — but statistics fold the removal in and
        the plan epoch bumps."""
        tr = getattr(self, "tracer", NULL_TRACER)
        with tr.span("write", op="delete"):
            fresh = self.live.delete(ids)
            if fresh.size:
                rc, rn = self.live.row_attrs(fresh)
                self.dataset_stats.apply_delta(removed_cat=rc, removed_num=rn)
            self.corpus_generation += 1
            tr.annotate(n_dead=int(fresh.size),
                        corpus_generation=self.corpus_generation)
        return fresh

    def list_drift(self) -> float:
        """IVF list-balance drift if the segment were folded in: max list
        count (base + incrementally assigned segment rows) over the
        build-time max.  1.0 when there is nothing to fold."""
        ivf = getattr(self, "ivf", None)
        live = getattr(self, "live", None)
        if ivf is None or live is None or not live.seg_n:
            return 1.0
        assign = live.assign_new(ivf.centroids)
        counts = ivf.list_counts + np.bincount(assign, minlength=ivf.n_lists)
        return float(counts.max() / max(int(ivf.list_counts.max()), 1))

    def needs_compaction(self) -> bool:
        return self.compaction_policy.due(
            self.live.tombstone_frac, self.live.segment_frac, self.list_drift()
        )

    def maybe_compact(self) -> Optional[np.ndarray]:
        """Compact iff churn crossed a :class:`CompactionPolicy` threshold;
        returns the handle -> new-position id_map, or None if not due."""
        live = getattr(self, "live", None)
        if live is not None and live.dirty and self.needs_compaction():
            return self.compact()
        return None

    def compact(self) -> np.ndarray:
        """Fold segment + tombstones into a rebuilt engine, in place.

        Live rows land in handle order (monotone map), the full build
        pipeline reruns over the folded arrays, and the trained planner /
        estimator heads survive the rebuild (only corpus-derived state is
        re-derived).  Generation counters bump so every cache invalidates.
        Returns ``id_map``: old handle -> new position (-1 for dead)."""
        t0 = time.perf_counter()
        tr = getattr(self, "tracer", NULL_TRACER)
        with tr.span("compact"):
            vectors, cat, num, id_map = self.live.compacted()
            planner, head_version = self.planner, self.planner_version
            est_model, est_gen = self.estimator.model, self.estimator.generation
            gen, n_comp = self.corpus_generation, self.n_compactions
            full = getattr(self, "pre_exec", None) is not None
            self.vectors, self.cat, self.num = vectors, cat, num
            if full:
                self.build()
            else:
                self.build_stats()  # planning-only engines stay planning-only
            self.planner = planner
            self.planner_version = head_version + 1
            self.estimator.model = est_model
            self.estimator.generation = est_gen + 1
            self.corpus_generation = gen + 1
            self.n_compactions = n_comp + 1
            tr.annotate(n_rows=int(vectors.shape[0]),
                        n_compactions=self.n_compactions,
                        corpus_generation=self.corpus_generation)
        self.build_time_["compaction"] = time.perf_counter() - t0
        return id_map

    def mutation_state(self) -> dict:
        """Array-only pytree of the mutable corpus state — what
        ``repro.ckpt.Checkpointer`` snapshots between compactions."""
        return self.live.state_tree()

    def load_mutation_state(self, tree) -> "FilteredANNEngine":
        """Restore a :meth:`mutation_state` snapshot onto a freshly built
        engine over the SAME base corpus.  Replays through the public
        upsert/delete APIs, so the attribute index, statistics deltas,
        caches, and generations all end consistent with having taken the
        writes live."""
        base_n = int(np.asarray(tree["base_n"]))
        if base_n != self.live.base_n or self.live.dirty:
            raise ValueError(
                "load_mutation_state needs a clean engine built over the "
                "same base corpus"
            )
        sv = np.asarray(tree["seg_vectors"])
        if sv.shape[0]:
            self.upsert(sv, np.asarray(tree["seg_cat"]),
                        np.asarray(tree["seg_num"]))
        from ..filter.bitmap import expand_words

        tomb = np.asarray(tree["tomb"], np.uint32)
        dead = np.nonzero(expand_words(tomb, self.live.n_total))[0]
        if dead.size:
            self.delete(dead)
        return self

    # ------------------------------------------------------------------
    def make_plan(self, pred: AnyPredicate, k: int = 10,
                  ) -> Tuple[ExecutionPlan, float]:
        """Plan one predicate into a structured :class:`ExecutionPlan`,
        without executing.

        Conjunctions get a single-clause plan (bit-identical decisions to
        the historical scalar path).  ``Or`` predicates plan *per disjunct*:
        each unique conjunctive clause gets its own decision / routing
        class, and the plan's ``"union"`` merge spec tells execution to run
        the clauses as ordinary decision groups and merge with cross-clause
        de-duplication.  The plan depends only on predicate and dataset
        statistics — not on which corpus rows are local — so a sharded
        deployment plans ONCE and broadcasts it to every shard.  Repeat
        predicates hit the plan cache and skip both the estimator and the
        MLP dispatch (same plan by purity, just cheaper).  Returns
        ``(plan, plan_overhead_s)``."""
        t0 = time.perf_counter()
        tr = getattr(self, "tracer", NULL_TRACER)
        with tr.span("plan", k=int(k)):
            self.plan_cache.validate_epoch(self._plan_epoch())
            key = (self._plan_key(pred), int(k))
            hit = self.plan_cache.get(key)
            if hit is not None:
                tr.annotate(plan_cache="hit", decision=hit.strategy,
                            route=int(hit.route), n_clauses=hit.n_clauses)
                return hit, time.perf_counter() - t0
            plan = self._plan_cold(pred, k)
            self.plan_cache.put(key, plan)
            tr.annotate(plan_cache="miss", decision=plan.strategy,
                        route=int(plan.route), n_clauses=plan.n_clauses)
        return plan, time.perf_counter() - t0

    def explain(self, pred: AnyPredicate, k: int = 10) -> str:
        """Pretty-print the :class:`ExecutionPlan` for ``(pred, k)`` without
        executing — one line per clause with decision, backend class, and
        the selectivity estimate the choice was made under."""
        plan, _ = self.make_plan(pred, k)
        return format_plan(plan, pred)

    def plan(self, pred: AnyPredicate, k: int = 10) -> Tuple[float, int, float]:
        """Scalar spelling of :meth:`make_plan`: returns
        ``(est_selectivity, decision, plan_overhead_s)``.  For DNF plans the
        decision is the dominant clause's (see ``ExecutionPlan.decision``)."""
        plan, overhead = self.make_plan(pred, k)
        return plan.est, plan.decision, overhead

    def plan_ex(self, pred: AnyPredicate, k: int = 10) -> Tuple[float, int, int, float]:
        """:meth:`plan` plus the routing class: returns
        ``(est_selectivity, decision, route, plan_overhead_s)`` where
        ``route`` is the (backend, knob-tier) class index for post-filter
        rows when the routing head is active, else ``NO_ROUTE``."""
        plan, overhead = self.make_plan(pred, k)
        return plan.est, plan.decision, plan.route, overhead

    def _class_names(self) -> Optional[Tuple[str, ...]]:
        """This engine's (backend, knob-tier) class enumeration.  Derived
        from the built BackendSet when present, else from the configured
        backend roster (knob grids are static per backend class, so a
        planning-only ``build_stats`` engine — the sharded deployment's
        planner — enumerates the same classes its shards build)."""
        bs = getattr(self, "backend_set", None)
        if bs is not None:
            return bs.class_names()
        if self.config.backends:
            from ..index.registry import _REGISTRY
            return tuple(
                f"{nm}:{tier.name}"
                for nm in self.config.backends
                for tier in _REGISTRY[nm](seed=0).knob_grid()
            )
        return None

    def _routing_active(self) -> bool:
        """Routing applies only when the planner's routing head was fitted
        over EXACTLY this engine's (backend, knob-tier) class enumeration —
        a head trained under a different backend roster (e.g. restored from
        a checkpoint of another deployment) is ignored, not misapplied."""
        expected = self._class_names()
        if expected is None:
            return False
        rc = self.planner.route_classes
        return rc is not None and rc == expected

    def _plan_epoch(self) -> Tuple[int, int, int, int]:
        """What a cached plan is valid under: the installed head
        (``planner_version``, bumped by fit/swap_planner), that head's own
        fit generation, the estimator's fit generation — the latter two
        catch direct ``eng.planner.fit()`` / ``eng.estimator.fit()`` calls
        that retrain in place without going through the engine's hooks —
        and the corpus generation, which every live upsert/delete/compaction
        bumps (mutations change exact selectivities, hence plans)."""
        return (self.planner_version, self.planner.generation,
                self.estimator.generation,
                getattr(self, "corpus_generation", 0))

    def _route_pair(self, decision: int, route: int) -> Tuple[str, str]:
        """Resolve a (decision, routing class) pair to its (backend, knob)
        execution class — routed post rows name their BackendSet class, all
        other rows the default class implied by the decision."""
        if decision == POST_FILTER and route >= 0:
            bs = getattr(self, "backend_set", None)
            if bs is not None:
                return bs.classes()[route]
            names = self._class_names()
            if names is not None and route < len(names):
                bk, _, knob = names[route].partition(":")
                return bk, knob
        return default_route_name(decision)

    def _decide_clauses(self, preds: Sequence, ests: np.ndarray,
                        exact: np.ndarray, k: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """One feature matrix + one planner dispatch over conjunction rows:
        returns per-row ``(decisions, routes)``."""
        fm = self.feat.matrix(list(preds), ests, k, exact)
        if self.planner.params:
            decisions = self.planner.decide(fm).astype(np.int32)
        else:
            # untrained fallback mirrors the planner's cost heuristic: the
            # selectivity threshold picks pre vs post, coverage upgrades
            # pre to the indexed variant
            decisions = np.where(ests < 0.05, PRE_FILTER, POST_FILTER).astype(np.int32)
            decisions = np.where(
                (decisions == PRE_FILTER) & exact, INDEXED_PRE, decisions
            ).astype(np.int32)
        routes = np.full(len(preds), NO_ROUTE, np.int32)
        if self._routing_active():
            r = self.planner.route(fm)
            if r is not None:
                routes = np.where(decisions == POST_FILTER, r, NO_ROUTE).astype(np.int32)
        return decisions, routes

    def _single_plan(self, pred, est: float, exact: bool, decision: int,
                     route: int) -> ExecutionPlan:
        bk, knob = self._route_pair(decision, route)
        cl = ClausePlan(self._plan_key(pred), int(decision), bk, knob,
                        float(est), int(route), bool(exact))
        return ExecutionPlan((cl,), float(est), bool(exact), "none")

    def _plan_dnf(self, pred: Or, k: int, se: SelEstimate) -> ExecutionPlan:
        """Per-disjunct planning: each unique conjunctive clause of the DNF
        is decided and routed independently (one batched head dispatch over
        the clause feature rows), producing a ``"union"``-merge plan."""
        tr = getattr(self, "tracer", NULL_TRACER)
        seen: set = set()
        terms, ests = [], []
        for t, ce in zip(pred.terms, se.per_clause):
            key = self._plan_key(t)
            if key in seen:
                continue
            seen.add(key)
            terms.append(t)
            ests.append(ce)
        if not terms:                       # empty Or: matches nothing
            return ExecutionPlan((), 0.0, True, "union")
        sels = np.asarray([c.sel for c in ests], np.float64)
        exact = np.asarray([c.is_exact for c in ests], bool)
        decisions, routes = self._decide_clauses(terms, sels, exact, k)
        clauses = []
        for j, t in enumerate(terms):
            bk, knob = self._route_pair(int(decisions[j]), int(routes[j]))
            clauses.append(ClausePlan(
                self._plan_key(t), int(decisions[j]), bk, knob,
                float(sels[j]), int(routes[j]), bool(exact[j])))
            if tr.enabled:
                with tr.span("clause", index=j,
                             decision=STRATEGY_NAMES[int(decisions[j])],
                             backend=bk, knob=knob, route=int(routes[j])):
                    tr.annotate(est=round(float(sels[j]), 6),
                                exact=bool(exact[j]))
        return ExecutionPlan(tuple(clauses), float(se.sel),
                             bool(se.is_exact), "union")

    def _plan_cold(self, pred: AnyPredicate, k: int) -> ExecutionPlan:
        tr = getattr(self, "tracer", NULL_TRACER)
        with tr.span("predicate_compile"):
            pc = getattr(self, "pred_cache", None)
            m0 = pc.misses if pc is not None else 0
            se = self.estimator.estimate(pred)
            if tr.enabled:
                tr.annotate(estimator="exact" if se.is_exact else "gbm")
                if pc is not None:
                    miss = pc.misses - m0
                    n_words = (self.vectors.shape[0] + 31) // 32
                    tr.annotate(pred_cache="miss" if miss else "hit",
                                bitmap_words=miss * n_words)
        if isinstance(pred, Or):
            return self._plan_dnf(pred, k, se)
        fv = self.feat.vector(pred, se.sel, k, se.is_exact)
        if self.planner.params:
            decision = int(self.planner.decide(fv)[0])
        else:
            decision = PRE_FILTER if se.sel < 0.05 else POST_FILTER
            if decision == PRE_FILTER and se.is_exact:
                decision = INDEXED_PRE
        route = NO_ROUTE
        if decision == POST_FILTER and self._routing_active():
            r = self.planner.route(fv)
            if r is not None:
                route = int(r[0])
        return self._single_plan(pred, se.sel, se.is_exact, decision, route)

    def make_plan_batch(
        self, preds: Sequence[AnyPredicate], k: int = 10
    ) -> Tuple[List[ExecutionPlan], float]:
        """Batched :meth:`make_plan`: one selectivity pass, one (rows, F)
        feature matrix over every conjunction AND every DNF clause in the
        batch, ONE planner jit dispatch instead of B.

        Returns ``(plans (B,), plan_overhead_s)`` where the overhead covers
        the whole batch.  Rows whose (predicate, k) was planned before
        resolve from the plan cache; only the misses pay the estimator pass
        and the MLP dispatch.
        """
        t0 = time.perf_counter()
        tr = getattr(self, "tracer", NULL_TRACER)
        b = len(preds)
        with tr.span("plan", n_preds=b, k=int(k)):
            self.plan_cache.validate_epoch(self._plan_epoch())
            plans: List[Optional[ExecutionPlan]] = [None] * b
            keys = [(self._plan_key(p), int(k)) for p in preds]
            miss = []
            for i, key in enumerate(keys):
                hit = self.plan_cache.get(key)
                if hit is None:
                    miss.append(i)
                else:
                    plans[i] = hit
            if miss:
                sub = [preds[i] for i in miss]
                with tr.span("predicate_compile", n_preds=len(miss)):
                    pc = getattr(self, "pred_cache", None)
                    m0 = pc.misses if pc is not None else 0
                    ses = self.estimator.estimate_batch(sub)
                    if tr.enabled:
                        n_ex = sum(s.is_exact for s in ses)
                        tr.annotate(estimator_exact=int(n_ex),
                                    estimator_gbm=len(miss) - int(n_ex))
                        if pc is not None:
                            md = pc.misses - m0
                            n_words = (self.vectors.shape[0] + 31) // 32
                            tr.annotate(pred_cache_misses=md,
                                        bitmap_words=md * n_words)
                # pool every decidable row — conjunctions as themselves, DNF
                # rows as their unique clauses — into ONE head dispatch
                spec_pred, spec_est, spec_exact = [], [], []
                spec_owner: List[Tuple[int, bool]] = []   # (miss slot, is_clause)
                for j, (p, se) in enumerate(zip(sub, ses)):
                    if isinstance(p, Or):
                        seen: set = set()
                        for t, ce in zip(p.terms, se.per_clause):
                            tk = self._plan_key(t)
                            if tk in seen:
                                continue
                            seen.add(tk)
                            spec_pred.append(t)
                            spec_est.append(ce.sel)
                            spec_exact.append(ce.is_exact)
                            spec_owner.append((j, True))
                    else:
                        spec_pred.append(p)
                        spec_est.append(se.sel)
                        spec_exact.append(se.is_exact)
                        spec_owner.append((j, False))
                if spec_pred:
                    decisions, routes = self._decide_clauses(
                        spec_pred, np.asarray(spec_est, np.float64),
                        np.asarray(spec_exact, bool), k)
                else:
                    decisions = routes = np.zeros(0, np.int32)
                by_owner: Dict[int, List[int]] = {}
                for r, (j, _) in enumerate(spec_owner):
                    by_owner.setdefault(j, []).append(r)
                n_dnf = 0
                for j, (p, se) in enumerate(zip(sub, ses)):
                    rows = by_owner.get(j, [])
                    if isinstance(p, Or):
                        n_dnf += 1
                        clauses = tuple(
                            ClausePlan(
                                self._plan_key(spec_pred[r]),
                                int(decisions[r]),
                                *self._route_pair(int(decisions[r]), int(routes[r])),
                                float(spec_est[r]), int(routes[r]),
                                bool(spec_exact[r]))
                            for r in rows)
                        plans[miss[j]] = ExecutionPlan(
                            clauses, float(se.sel), bool(se.is_exact), "union")
                    else:
                        r = rows[0]
                        plans[miss[j]] = self._single_plan(
                            p, se.sel, se.is_exact,
                            int(decisions[r]), int(routes[r]))
                    self.plan_cache.put(keys[miss[j]], plans[miss[j]])
                if tr.enabled and n_dnf:
                    tr.annotate(n_dnf=n_dnf)
            tr.annotate(plan_cache_hits=b - len(miss),
                        plan_cache_misses=len(miss))
        return plans, time.perf_counter() - t0

    def plan_batch(
        self, preds: Sequence[AnyPredicate], k: int = 10
    ) -> Tuple[np.ndarray, np.ndarray, float]:
        """Scalar spelling of :meth:`make_plan_batch`: returns
        ``(est_selectivities (B,), decisions (B,), plan_overhead_s)``."""
        plans, overhead = self.make_plan_batch(preds, k)
        return (np.asarray([p.est for p in plans], np.float64),
                np.asarray([p.decision for p in plans], np.int32), overhead)

    def plan_batch_ex(
        self, preds: Sequence[AnyPredicate], k: int = 10
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, float]:
        """Batched :meth:`plan_ex`: additionally returns per-row routing
        classes (``NO_ROUTE`` for non-post rows or when routing is off)."""
        plans, overhead = self.make_plan_batch(preds, k)
        return (np.asarray([p.est for p in plans], np.float64),
                np.asarray([p.decision for p in plans], np.int32),
                np.asarray([p.route for p in plans], np.int32), overhead)

    def shard_corpus(self, n_shards: int, n_lists: Optional[int] = None) -> List[CorpusShard]:
        """Partition the corpus into ``n_shards`` contiguous shards, each with
        its own pre-filter executor and post-filter IVF index.

        This is the hook the distribution layer builds on: shards map 1:1
        onto data-axis hosts, every shard answers the same planned query
        over its rows, and the per-shard top-k results merge exactly
        (``repro.dist.collectives.merge_topk``).  Per-shard IVF lists
        default to sqrt(n_local) as in the global build, clamped to the
        shard's row count; empty shards (more shards than rows) are
        dropped rather than built.
        """
        assert n_shards >= 1
        from ..filter import AttributeIndex, PredicateCache

        parts = np.array_split(np.arange(self.vectors.shape[0]), n_shards)
        devices = jax.devices()
        shards = []
        for s, ids in enumerate(parts):
            if ids.size == 0:
                continue
            # shard s scans on device s (round-robin when shards outnumber
            # devices): its corpus is committed there once
            dev = devices[s % len(devices)]
            v = np.ascontiguousarray(self.vectors[ids])
            c, m = self.cat[ids], self.num[ids]
            lists = min(n_lists or max(1, int(np.sqrt(ids.size))), ids.size)
            ivf = IVFIndex(v, lists, seed=self.config.seed + s).build()
            # per-shard attribute index + cache: bitmaps address shard-local
            # row positions, so each shard compiles its own
            ipre = None
            if self.config.attr_index:
                ipre = IndexedPreFilterExec(
                    v, c, m,
                    AttributeIndex.build(c, m, self.config.range_buckets),
                    PredicateCache(self.config.pred_cache_size),
                    device=dev,
                )
            # per-shard backend instances: backends index shard-local row
            # positions, so (like the attribute index) each shard builds its
            # own from its slice of the corpus
            bset = None
            if self.config.backends:
                bset = BackendSet.build(v, self.config.backends,
                                        seed=self.config.seed + s)
            shards.append(CorpusShard(
                shard_id=s,
                ids=ids,
                pre_exec=PreFilterExec(v, c, m, device=dev),
                post_exec=PostFilterExec(
                    ivf, c, m,
                    alpha0=self.config.alpha0, nprobe0=self.config.nprobe0,
                ),
                ipre_exec=ipre,
                backend_set=bset,
            ))
        return shards

    # ------------------------------------------------------------------
    def query(self, q: np.ndarray, pred: AnyPredicate, k: int = 10) -> PlannedResult:
        """Plan + execute one filtered ANN query."""
        q = np.atleast_2d(q)
        plan, plan_overhead = self.make_plan(pred, k)
        tr = getattr(self, "tracer", NULL_TRACER)
        live = getattr(self, "live", None)
        dirty = live is not None and live.dirty
        if plan.is_dnf:
            return self._query_dnf(q, pred, k, plan, plan_overhead)
        est, decision, route = plan.est, plan.decision, plan.route
        if dirty:
            # mutated corpus: the tombstone/segment-composing executor
            t0 = time.perf_counter()
            decisions = np.array([decision], np.int32)
            routes = np.array([route], np.int32)
            with tr.span("execute", n_queries=1, k=int(k), live=True):
                kc0 = _kernel_snapshot() if tr.enabled else {}
                d, ids, rounds = _live_execute_grouped(
                    self.pre_exec, self.ipre_exec, self.post_exec,
                    q, [pred], k, decisions, np.array([est]), live,
                    routes=routes, backend_set=self.backend_set, tracer=tr,
                )
                if tr.enabled:
                    _annotate_kernel_delta(tr, kc0)
            share = time.perf_counter() - t0 + plan_overhead
            return package_results(d, ids, rounds, [plan], share,
                                   plan_overhead)[0]
        with tr.span("execute", n_queries=1, k=int(k), live=False,
                     decision=STRATEGY_NAMES[decision]):
            kc0 = _kernel_snapshot() if tr.enabled else {}
            if decision == INDEXED_PRE:
                res = self.ipre_exec.search(q, pred, k)
            elif decision == PRE_FILTER:
                res = self.pre_exec.search(q, pred, k)
            elif route >= 0 and self.backend_set is not None:
                # routed: mask once (bitmap-indexed when covered), then the
                # chosen backend's masked search at the chosen knob tier
                t0 = time.perf_counter()
                mask = self.ipre_exec.candidate_mask(pred)
                d, ids = self.backend_set.search_class(route, q, mask, k)
                res = SearchResult(d, ids, time.perf_counter() - t0, "post")
            else:
                # the estimate also *parameterises* the chosen executor
                res = self.post_exec.search(q, pred, k, est_selectivity=est)
            if tr.enabled:
                _annotate_kernel_delta(tr, kc0)
        if not res.backend:
            res.backend, res.knob = plan.backend, plan.knob
        res.elapsed += plan_overhead   # end-to-end includes planning (paper §4.1)
        return PlannedResult(res, plan, plan_overhead)

    def _query_dnf(self, q: np.ndarray, pred: AnyPredicate, k: int,
                   plan: ExecutionPlan, plan_overhead: float) -> PlannedResult:
        """Per-disjunct execution of one DNF query: the clauses run as
        ordinary decision-group rows through the shared batch executor, then
        merge with cross-clause de-duplication."""
        tr = getattr(self, "tracer", NULL_TRACER)
        live = getattr(self, "live", None)
        dirty = live is not None and live.dirty
        exp_rows, exp_preds, decisions, ests, routes, row_map = (
            expand_for_execution([pred], [plan]))
        t0 = time.perf_counter()
        with tr.span("execute", n_queries=1, k=int(k), live=dirty,
                     decision="dnf", n_clauses=plan.n_clauses):
            kc0 = _kernel_snapshot() if tr.enabled else {}
            qq = q[exp_rows]
            if dirty:
                d, ids, rounds = _live_execute_grouped(
                    self.pre_exec, self.ipre_exec, self.post_exec,
                    qq, exp_preds, k, decisions, ests, live,
                    routes=routes, backend_set=self.backend_set, tracer=tr,
                )
            else:
                d, ids, rounds = _execute_grouped(
                    self.pre_exec, self.ipre_exec, self.post_exec,
                    qq, exp_preds, k, decisions, ests,
                    routes=routes, backend_set=self.backend_set, tracer=tr,
                )
            d, ids, rounds = collapse_clause_results(d, ids, rounds, row_map, k)
            if tr.enabled:
                _annotate_kernel_delta(tr, kc0)
        share = time.perf_counter() - t0 + plan_overhead
        return package_results(d, ids, rounds, [plan], share, plan_overhead)[0]

    def batch_query(
        self, queries: np.ndarray, preds: Sequence[AnyPredicate], k: int = 10
    ) -> List[PlannedResult]:
        """Batched plan -> group-by-decision -> execute.

        Plans the whole batch in one pass (:meth:`plan_batch`), then runs the
        shared decision-grouped executor (``_execute_grouped``): the
        pre-filter group evaluates each distinct predicate's mask ONCE and
        runs one fused masked top-k over all queries sharing it; the
        post-filter group runs one row-faithful batched IVF search with
        vectorised candidate filtering.  Results are identical to B
        independent :meth:`query` calls (same executors, same per-row
        parameters), only with the per-query Python/jit dispatch overhead
        amortised; per-result ``elapsed`` is the batch wall time split
        evenly across rows.
        """
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        b = len(preds)
        live = getattr(self, "live", None)
        tr = getattr(self, "tracer", NULL_TRACER)
        with tr.span("batch_query", n_queries=b, k=int(k)):
            plans, plan_overhead = self.make_plan_batch(preds, k)
            plan_share = plan_overhead / max(b, 1)
            exp_rows, exp_preds, decisions, ests, routes, row_map = (
                expand_for_execution(preds, plans))
            # no DNF rows: the expansion is the identity and execution below
            # is bit-identical to the historical whole-predicate batch path
            identity = len(exp_preds) == b and all(len(m) == 1 for m in row_map)
            xq = queries if identity else queries[exp_rows]
            t0 = time.perf_counter()
            with tr.span("execute", n_queries=b, k=int(k),
                         live=bool(live is not None and live.dirty)):
                kc0 = _kernel_snapshot() if tr.enabled else {}
                if live is not None and live.dirty:
                    d, ids, rounds = _live_execute_grouped(
                        self.pre_exec, self.ipre_exec, self.post_exec,
                        xq, exp_preds, k, decisions, ests, live,
                        routes=routes, backend_set=self.backend_set, tracer=tr,
                    )
                else:
                    d, ids, rounds = _execute_grouped(
                        self.pre_exec, self.ipre_exec, self.post_exec,
                        xq, exp_preds, k, decisions, ests,
                        routes=routes, backend_set=self.backend_set, tracer=tr,
                    )
                if not identity:
                    d, ids, rounds = collapse_clause_results(
                        d, ids, rounds, row_map, k)
                if tr.enabled:
                    _annotate_kernel_delta(tr, kc0)
            share = (time.perf_counter() - t0) / max(b, 1) + plan_share
            return package_results(d, ids, rounds, plans, share, plan_share)

    # ------------------------------------------------------------------
    def ground_truth(self, q: np.ndarray, pred: AnyPredicate, k: int = 10) -> np.ndarray:
        q = np.atleast_2d(q)
        mask = pred.eval(self.cat, self.num)
        live = getattr(self, "live", None)
        if live is not None and live.dirty:
            # exact truth over the LIVE rows: tombstones compose out of the
            # base mask, the segment scans exactly, parts merge with the
            # same handle-order tie-break the serving path uses
            from ..dist.collectives import merge_topk

            alive = live.alive_mask()
            mask = mask & alive[: live.base_n]
            b = q.shape[0]
            if mask.any():
                bd, bi = l2_topk(q, self.vectors, k, mask)
                bd, bi = np.asarray(bd), np.asarray(bi)
            else:
                bd = np.full((b, k), np.inf, np.float32)
                bi = np.full((b, k), -1, np.int32)
            sm = (pred.eval(live.seg_cat(), live.seg_num())
                  & alive[live.base_n:]) if live.seg_n else np.zeros(0, bool)
            if sm.any():
                kk = min(k, live.seg_n)
                sd, si = l2_topk(q, live.seg_vectors(), kk, sm)
                sd, si = np.asarray(sd), np.asarray(si)
                si = np.where(si >= 0, si + live.base_n, -1).astype(np.int32)
                _, bi = merge_topk([bd, sd], [bi, si], k)
            return bi
        _, ti = l2_topk(q, self.vectors, k, mask)
        return np.asarray(ti)
