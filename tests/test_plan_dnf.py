"""Per-disjunct DNF planning (the ExecutionPlan API).

The load-bearing guarantees:

* **exact-tier bit-identity** — when every clause of a DNF plan lands on
  an exact strategy (PRE/IPRE), the per-disjunct union is bit-identical
  to the whole-predicate compiled-bitmap path, flat AND sharded AND on a
  dirty live corpus;
* **cross-clause dedup** — a row matching two disjuncts appears once, at
  its best distance (composite-key merge, so ties break like the
  whole-predicate scan);
* **plan structure** — conjunctions plan as single-clause ``merge=none``
  plans (the legacy shape), ``Or`` plans per-disjunct with duplicate
  clauses collapsed, and logically-equal ``Or``s share one cache entry;
* **API surface** — ``SelEstimate`` carries per-clause estimates,
  ``QueryLabel`` is no longer a 4-tuple, ``explain`` renders the plan
  tree, and the feedback loop logs clause-level rows for DNF traffic.
"""
import numpy as np
import pytest

from repro.core import (
    EngineConfig,
    ExecutionPlan,
    FilteredANNEngine,
    INDEXED_PRE,
    LabelEq,
    Or,
    PRE_FILTER,
    Predicate,
    RangePred,
    SelEstimate,
)
from repro.core.selectivity import SelectivityEstimator  # noqa: F401 (API)
from repro.core.trainer import gen_queries
from repro.data import make_dataset
from repro.runtime import (
    FeedbackConfig,
    OnlineFeedback,
    OnlineRuntime,
    RuntimeRequest,
    SchedulerConfig,
    poisson_trace,
)
from repro.serve import ShardedANNEngine

K = 10
EXACT = (PRE_FILTER, INDEXED_PRE)


@pytest.fixture(scope="module")
def ds():
    return make_dataset("arxiv", scale="4000", seed=0)


@pytest.fixture(scope="module")
def eng(ds):
    """Built but UNFITTED: the untrained-planner fallback is deterministic
    (est < 0.05 -> PRE/IPRE), so low-selectivity clauses provably land on
    exact strategies — what the bit-identity tests need."""
    return FilteredANNEngine(
        ds.vectors, ds.cat, ds.num, EngineConfig(n_lists=32, seed=0)
    ).build()


@pytest.fixture(scope="module")
def fitted(ds):
    """A second, trained engine; the fit workload includes DNF queries so
    the per-clause labelling/decomposition path is exercised."""
    e = FilteredANNEngine(
        ds.vectors, ds.cat, ds.num, EngineConfig(n_lists=32, seed=0)
    ).build()
    tq, tp, _ = gen_queries(
        ds.vectors, ds.cat, ds.num, 24, kinds=ds.filter_kinds, seed=1
    )
    preds = list(tp) + [Or((tp[0], tp[1])), Or((tp[2], tp[3], tp[2]))]
    qs = list(tq) + [tq[0], tq[2]]
    e.fit(qs, preds, k=K)
    return e


def _low_sel_conjunctions(ds, want=3, lo=0.001, hi=0.04):
    """Label-pair conjunctions with exact (bitmap-covered) selectivity in
    (lo, hi] — under the fallback planner these always plan exact."""
    out = []
    for a in np.unique(ds.cat[:, 0]):
        for b in np.unique(ds.cat[:, 1]):
            p = Predicate(labels=(LabelEq(0, int(a)), LabelEq(1, int(b))))
            if lo < p.selectivity(ds.cat, ds.num) <= hi:
                out.append(p)
                if len(out) == want:
                    return out
    raise RuntimeError("fixture corpus has no low-selectivity label pairs")


# ----------------------------------------------------------------------
# plan structure
# ----------------------------------------------------------------------
def test_conjunction_plans_single_clause(eng, ds):
    p = _low_sel_conjunctions(ds, want=1)[0]
    plan, _ = eng.make_plan(p, K)
    assert isinstance(plan, ExecutionPlan)
    assert plan.merge == "none" and not plan.is_dnf and plan.n_clauses == 1
    assert plan.strategy in ("pre", "post", "ipre")
    assert plan.decision == plan.clauses[0].decision


def test_or_plans_per_disjunct(eng, ds):
    a, b, c = _low_sel_conjunctions(ds, want=3)
    plan, _ = eng.make_plan(Or((a, b, c)), K)
    assert plan.is_dnf and plan.merge == "union" and plan.n_clauses == 3
    assert plan.strategy == "dnf" and plan.backend == "dnf"
    for cl in plan.clauses:
        assert cl.decision in EXACT and cl.sel_exact
    # duplicate disjuncts collapse to one clause; a single-disjunct Or is
    # still a union plan (executes as one clause row)
    dup, _ = eng.make_plan(Or((a, b, a)), K)
    assert dup.n_clauses == 2
    solo, _ = eng.make_plan(Or((a,)), K)
    assert solo.is_dnf and solo.n_clauses == 1
    empty, _ = eng.make_plan(Or(()), K)
    assert empty.is_dnf and empty.n_clauses == 0


def test_permuted_or_shares_cache_entry(eng, ds):
    a, b, c = _low_sel_conjunctions(ds, want=3)
    eng.plan_cache.clear()
    p1, _ = eng.make_plan(Or((a, b, c)), K)
    h0 = eng.plan_cache.stats()["hits"]
    p2, _ = eng.make_plan(Or((c, a, b)), K)   # same canonical key
    assert eng.plan_cache.stats()["hits"] == h0 + 1
    assert p1 is p2
    # execution still aligns terms to clause plans by key, not position
    q = ds.vectors[0]
    r1 = eng.query(q, Or((a, b, c)), K)
    r2 = eng.query(q, Or((c, a, b)), K)
    np.testing.assert_array_equal(r1.result.ids, r2.result.ids)


# ----------------------------------------------------------------------
# exact-tier bit-identity: flat, sharded, live
# ----------------------------------------------------------------------
def test_per_disjunct_bit_identical_flat(eng, ds):
    clauses = _low_sel_conjunctions(ds, want=3)
    dnf = Or(tuple(clauses))
    plan, _ = eng.make_plan(dnf, K)
    assert all(cl.decision in EXACT for cl in plan.clauses)
    rng = np.random.default_rng(7)
    for _ in range(6):
        q = ds.vectors[rng.integers(ds.vectors.shape[0])]
        out = eng.query(q, dnf, K)
        ref = eng.pre_exec.search(q[None], dnf, K)   # whole-predicate bitmap
        np.testing.assert_array_equal(out.result.ids, ref.ids)
        np.testing.assert_array_equal(out.result.dists, ref.dists)
        np.testing.assert_array_equal(out.result.ids, eng.ground_truth(q, dnf, K))


def test_cross_clause_dedup(eng, ds):
    """Overlapping disjuncts: one clause strictly contains the other, so
    every hit of the narrow clause also matches the wide one — each id must
    surface exactly once, and the union must equal the whole-predicate scan."""
    wide = _low_sel_conjunctions(ds, want=1, lo=0.01, hi=0.04)[0]
    x1 = ds.num[:, 1]
    narrow = Predicate(
        labels=wide.labels,
        ranges=(RangePred(1, ((float(np.quantile(x1, 0.1)),
                               float(np.quantile(x1, 0.9))),)),),
    )
    dnf = Or((wide, narrow))
    plan, _ = eng.make_plan(dnf, K)
    assert plan.n_clauses == 2
    assert all(cl.decision in EXACT for cl in plan.clauses)
    rng = np.random.default_rng(11)
    for _ in range(6):
        q = ds.vectors[rng.integers(ds.vectors.shape[0])]
        out = eng.query(q, dnf, K)
        row = out.result.ids[0]
        valid = row[row >= 0]
        assert len(set(valid.tolist())) == len(valid), "duplicate id surfaced"
        ref = eng.pre_exec.search(q[None], dnf, K)
        np.testing.assert_array_equal(out.result.ids, ref.ids)
        np.testing.assert_array_equal(out.result.dists, ref.dists)
    # a literal duplicate clause is the degenerate overlap: Or((p, p)) == p
    p = wide
    r_dup = eng.query(ds.vectors[3], Or((p, p)), K)
    r_solo = eng.query(ds.vectors[3], p, K)
    np.testing.assert_array_equal(r_dup.result.ids, r_solo.result.ids)
    np.testing.assert_array_equal(r_dup.result.dists, r_solo.result.dists)


def test_per_disjunct_bit_identical_sharded(eng, ds):
    clauses = _low_sel_conjunctions(ds, want=3)
    dnf = Or(tuple(clauses))
    sharded = ShardedANNEngine(eng, n_shards=3)
    rng = np.random.default_rng(13)
    qs = ds.vectors[rng.integers(ds.vectors.shape[0], size=4)]
    for q in qs:
        flat = eng.query(q, dnf, K)
        shd = sharded.query(q, dnf, K)
        np.testing.assert_array_equal(shd.result.ids, flat.result.ids)
        # shards scan with matmuls of other shapes than the flat scan, so
        # the float32 sums may round differently in the last bits
        np.testing.assert_allclose(shd.result.dists, flat.result.dists, rtol=1e-5)
    # sharded batch path agrees row-for-row with per-query sharded calls
    mixed = [dnf, clauses[0], dnf, clauses[1]]
    batch = sharded.batch_query(qs, mixed, K)
    for i, r in enumerate(batch):
        solo = sharded.query(qs[i], mixed[i], K)
        np.testing.assert_array_equal(r.result.ids, solo.result.ids)


def test_per_disjunct_bit_identical_live(ds):
    """Dirty live corpus: upserts land in the append segment, deletes
    tombstone base rows — the per-disjunct union must still equal the exact
    live ground truth (label bitmaps stay exact through mutation)."""
    e = FilteredANNEngine(
        ds.vectors, ds.cat, ds.num, EngineConfig(n_lists=32, seed=0)
    ).build()
    clauses = _low_sel_conjunctions(ds, want=2)
    dnf = Or(tuple(clauses))
    rng = np.random.default_rng(17)
    rows = rng.choice(ds.vectors.shape[0], 40, replace=False)
    e.upsert(ds.vectors[rows], ds.cat[rows], ds.num[rows])
    e.delete(np.arange(25))
    assert e.live.dirty
    for i in range(4):
        q = ds.vectors[rng.integers(ds.vectors.shape[0])]
        out = e.query(q, dnf, K)
        np.testing.assert_array_equal(out.result.ids, e.ground_truth(q, dnf, K))


# ----------------------------------------------------------------------
# batch path: identity fast path + mixed-batch equivalence
# ----------------------------------------------------------------------
def test_batch_mixed_dnf_matches_per_query(eng, ds):
    clauses = _low_sel_conjunctions(ds, want=3)
    dnf = Or(tuple(clauses))
    preds = [clauses[0], dnf, clauses[1], Or((clauses[1], clauses[2])), clauses[2]]
    rng = np.random.default_rng(19)
    qs = ds.vectors[rng.integers(ds.vectors.shape[0], size=len(preds))]
    batch = eng.batch_query(qs, preds, K)
    assert len(batch) == len(preds)
    for i, r in enumerate(batch):
        solo = eng.query(qs[i], preds[i], K)
        np.testing.assert_array_equal(r.result.ids, solo.result.ids)
        np.testing.assert_array_equal(r.result.dists, solo.result.dists)
        assert r.plan.strategy == solo.plan.strategy
    assert batch[1].plan.is_dnf and not batch[0].plan.is_dnf
    # pure-conjunction batches take the identity fast path and stay
    # bit-identical to per-query serving (the PR 2 discipline)
    conj_batch = eng.batch_query(qs[:3], clauses, K)
    for i, r in enumerate(conj_batch):
        solo = eng.query(qs[i], clauses[i], K)
        np.testing.assert_array_equal(r.result.ids, solo.result.ids)


# ----------------------------------------------------------------------
# API surface: SelEstimate, QueryLabel, explain
# ----------------------------------------------------------------------
def test_sel_estimate_api(eng, ds):
    a, b, c = _low_sel_conjunctions(ds, want=3)
    se = eng.estimator.estimate(a)
    assert isinstance(se, SelEstimate)
    assert 0.0 <= se.sel <= 1.0 and se.is_exact and se.per_clause is None
    assert float(se) == se.sel
    # Or: per_clause aligns with pred.terms (duplicates included)
    orse = eng.estimator.estimate(Or((a, b, a, c)))
    assert len(orse.per_clause) == 4
    assert orse.per_clause[0].sel == orse.per_clause[2].sel == se.sel
    assert orse.sel == pytest.approx(Or((a, b, c)).selectivity(ds.cat, ds.num))
    # batch agrees with scalar, deprecated aliases agree with both
    ses = eng.estimator.estimate_batch([a, Or((a, b)), c])
    assert all(isinstance(s, SelEstimate) for s in ses)
    assert ses[0].sel == se.sel
    legacy_s, legacy_e = eng.estimator.estimate_ex(a)
    assert (legacy_s, legacy_e) == (se.sel, se.is_exact)
    bs, be = eng.estimator.estimate_batch_ex([a, c])
    assert bs[0] == se.sel and bool(be[0]) == se.is_exact


def test_query_label_no_longer_a_tuple(fitted, ds):
    p = _low_sel_conjunctions(ds, want=1)[0]
    lab = fitted.label_query(ds.vectors[0], p, K)
    with pytest.raises(TypeError):
        iter(lab)                      # the legacy 4-tuple shim is gone
    assert lab.clauses is None
    # DNF labels carry one per-clause race per UNIQUE disjunct
    a, b = _low_sel_conjunctions(ds, want=2)
    dlab = fitted.label_query(ds.vectors[0], Or((a, b, a)), K)
    assert dlab.clauses is not None and len(dlab.clauses) == 2
    assert all(cl.clauses is None for cl in dlab.clauses)


def test_explain_renders_plan_tree(fitted, ds):
    a, b = _low_sel_conjunctions(ds, want=2)
    text = fitted.explain(Or((a, b)), K)
    assert text.startswith("ExecutionPlan merge=union clauses=2")
    assert "clause[0]" in text and "clause[1]" in text
    assert "└─" in text
    conj = fitted.explain(a, K)
    assert conj.startswith("ExecutionPlan merge=none clauses=1")


# ----------------------------------------------------------------------
# runtime integration: telemetry "dnf" dimension + clause-level feedback
# ----------------------------------------------------------------------
def test_runtime_counts_dnf_plans(eng, ds):
    clauses = _low_sel_conjunctions(ds, want=2)
    dnf = Or(tuple(clauses))
    qs, _, _ = gen_queries(ds.vectors, ds.cat, ds.num, 8,
                           kinds=ds.filter_kinds, seed=23)
    trace = poisson_trace(qs, [dnf, clauses[0]], 40, 3000.0, k=K, seed=5)
    rep = OnlineRuntime(eng, SchedulerConfig(max_batch=8)).run_trace(trace)
    counts = rep.telemetry.counters()["plan_counts"]
    assert counts["dnf"] > 0
    assert sum(counts.values()) == 40


def test_feedback_logs_one_row_per_unique_clause(fitted, ds):
    a, b = _low_sel_conjunctions(ds, want=2)
    dnf = Or((a, b, a))
    fb = OnlineFeedback(fitted, FeedbackConfig(sample_rate=1.0, seed=0))
    q = ds.vectors[0]
    res = fitted.query(q, dnf, K)
    req = RuntimeRequest(rid=0, t_arrival=0.0, query=q, pred=dnf, k=K,
                         tier="standard", deadline=1.0)
    assert fb.observe(req, res)
    assert len(fb.log) == 2            # one per UNIQUE disjunct
    plan = res.plan
    by_key = {c.clause_key: c.decision for c in plan.clauses}
    logged = {e.decision for e in fb.log}
    assert logged <= set(by_key.values())
    # a conjunction request still logs exactly one whole-predicate row
    res2 = fitted.query(q, a, K)
    req2 = RuntimeRequest(rid=1, t_arrival=0.0, query=q, pred=a, k=K,
                          tier="standard", deadline=1.0)
    assert fb.observe(req2, res2)
    assert len(fb.log) == 3
