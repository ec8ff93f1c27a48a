"""Batched plan->execute pipeline: exactness vs the per-query path.

The batched path groups queries by planner decision and shares planning,
mask evaluation, kernel dispatches and IVF scans across the batch — but it
must return IDENTICAL ids and decisions to N independent ``query()`` calls,
on both the flat and the sharded engine.

The module fixture swaps in a deterministic selectivity-threshold planner
(engine API unchanged) so the workload provably covers BOTH decision groups
at test scale — the learned planner is free to (correctly) pick one strategy
everywhere on a small corpus, which would leave one executor group untested.
The batched MLP dispatch itself is covered row-vs-batch in test_planner.
"""
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core import (
    CorePlanner,
    EngineConfig,
    FilteredANNEngine,
    POST_FILTER,
    PRE_FILTER,
    Predicate,
    RangePred,
)
from repro.core.executors import PostFilterExec
from repro.core.trainer import gen_queries
from repro.data import make_dataset
from repro.serve import ShardedANNEngine


class _ThresholdPlanner(CorePlanner):
    """Deterministic stand-in: post-filter above 5% estimated selectivity.
    Row-wise on the (B, F) feature matrix, like the real MLP."""

    def __init__(self):
        super().__init__()
        self.params = {"stub": True}          # truthy: engine takes the decide() path

    def decide(self, features):
        f = np.atleast_2d(np.asarray(features, np.float32))
        return (f[:, 3] > 0.05).astype(np.int32)   # column 3 = est. selectivity


@pytest.fixture(scope="module")
def system():
    ds = make_dataset("sift", scale="8000", seed=0)
    eng = FilteredANNEngine(
        ds.vectors, ds.cat, ds.num, EngineConfig(n_lists=64, seed=0)
    ).build()
    # train the GBM refinement (so estimate_batch's pooled-GBM route is
    # exercised) without the heavyweight dual-strategy planner fit
    _, preds, sels = gen_queries(
        ds.vectors, ds.cat, ds.num, 30, kinds=("label", "mixed"), seed=3
    )
    eng.estimator.fit(preds, sels)
    eng.planner = _ThresholdPlanner()
    # mixed workload spanning predicate kinds AND the selectivity range so
    # both decisions (and both executors) appear in the batch
    q, p, _ = gen_queries(
        ds.vectors, ds.cat, ds.num, 24, kinds=("label", "range", "mixed"),
        sel_range=(0.01, 0.5), seed=7,
    )
    return ds, eng, q, p


def _assert_equivalent(batched, singles):
    assert len(batched) == len(singles)
    for i, (bq, sq) in enumerate(zip(batched, singles)):
        assert bq.decision == sq.decision, f"row {i}: decision mismatch"
        assert np.array_equal(bq.result.ids, sq.result.ids), f"row {i}: ids differ"
        np.testing.assert_allclose(
            bq.result.dists, sq.result.dists, err_msg=f"row {i}"
        )
        assert bq.est_selectivity == pytest.approx(sq.est_selectivity, abs=1e-12)
        assert bq.result.n_expansions == sq.result.n_expansions


def test_flat_batch_matches_per_query(system):
    _, eng, q, p = system
    batched = eng.batch_query(q, p, k=10)
    singles = [eng.query(q[i], p[i], k=10) for i in range(len(p))]
    _assert_equivalent(batched, singles)


def test_sharded_batch_matches_per_query(system):
    _, eng, q, p = system
    sharded = ShardedANNEngine(eng, n_shards=4)
    batched = sharded.batch_query(q, p, k=10)
    singles = [sharded.query(q[i], p[i], k=10) for i in range(len(p))]
    _assert_equivalent(batched, singles)


def test_batch_exercises_both_decisions(system):
    """The fixture must actually cover both executor groups, or the
    equivalence assertions above are vacuous for one of them."""
    _, eng, q, p = system
    decisions = {r.decision for r in eng.batch_query(q, p, k=10)}
    assert decisions == {PRE_FILTER, POST_FILTER}


def test_plan_batch_matches_plan(system):
    _, eng, q, p = system
    ests, decisions, _ = eng.plan_batch(p, k=10)
    for i, pred in enumerate(p):
        est_i, dec_i, _ = eng.plan(pred, k=10)
        assert ests[i] == pytest.approx(est_i, abs=1e-12)
        assert decisions[i] == dec_i


def test_batch_results_satisfy_predicates(system):
    ds, eng, q, p = system
    for i, r in enumerate(eng.batch_query(q, p, k=10)):
        ids = r.result.ids[r.result.ids >= 0]
        assert ids.size > 0
        assert p[i].eval(ds.cat[ids], ds.num[ids]).all()


def test_post_filter_budget_scales_with_selectivity(system):
    """Bugfix: the initial candidate request must be ~alpha0*k/selectivity,
    not a flat alpha0*k — at low selectivity the flat budget loses most
    candidates to the filter and pays doubling rounds the sized budget
    avoids."""
    ds, eng, _, _ = system
    qs, ps, sels = gen_queries(
        ds.vectors, ds.cat, ds.num, 5, kinds=("range",),
        sel_range=(0.005, 0.02), seed=11,
    )
    for i in range(len(ps)):
        sized = eng.post_exec.search(qs[i : i + 1], ps[i], k=10,
                                     est_selectivity=float(sels[i]))
        flat = eng.post_exec.search(qs[i : i + 1], ps[i], k=10)
        assert sized.n_expansions < flat.n_expansions
        assert (sized.ids >= 0).sum() == 10
    # and the sizing formula itself: budget rises as selectivity falls
    w_low, _ = eng.post_exec.initial_params(10, 0.01)
    w_high, _ = eng.post_exec.initial_params(10, 0.5)
    assert w_low > w_high


@pytest.mark.parametrize("n_lists,nprobe", [(64, 8), (316, 32), (1000, 64), (4, 4)])
def test_post_filter_first_probe_floor_follows_list_count(n_lists, nprobe):
    """The first probe covers at least MIN_PROBE_FRAC of the lists: nprobe0
    alone would probe a shrinking share of a growing index."""
    index = SimpleNamespace(n=n_lists * n_lists, n_lists=n_lists)
    px = PostFilterExec(index, None, None, nprobe0=8)
    assert px.initial_params(10)[1] == nprobe
    # a low selectivity still widens the probe past the floor
    assert px.initial_params(10, 1e-6)[1] == n_lists


def test_pre_filter_corpus_lives_on_the_scan_device(system):
    _, eng, _, _ = system
    sv = eng.pre_exec.scan_vectors
    assert isinstance(sv, jax.Array)
    assert sv.devices() == {jax.devices()[0]}
    np.testing.assert_array_equal(np.asarray(sv), eng.pre_exec.vectors)


def test_build_warms_every_pre_filter_scan_shape():
    """After build(), a single-query pre-filter search compiles nothing, on
    either scan branch: a compile inside fit()'s timed race would make the
    exact plan lose every label it should win."""
    ds = make_dataset("sift", scale="2000", seed=1)
    eng = FilteredANNEngine(
        ds.vectors, ds.cat, ds.num, EngineConfig(n_lists=16, seed=0)
    ).build()
    compiles = []

    def listener(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        n = ds.vectors.shape[0]
        for n_pass in (3, 10, 100, n // 4, n // 4 + 1, n):
            mask = np.zeros(n, bool)
            mask[:n_pass] = True
            for ex in (eng.pre_exec, eng.ipre_exec):
                ex.search_masked(ds.vectors[:1], mask, 10)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert compiles == []


def test_batch_query_single_row_and_empty_predicate(system):
    _, eng, q, p = system
    # B=1 degenerates to the per-query result
    r = eng.batch_query(q[:1], p[:1], k=10)
    assert len(r) == 1
    assert np.array_equal(r[0].result.ids, eng.query(q[0], p[0], k=10).result.ids)
    # a predicate matching nothing returns all-padding, no crash
    nothing = Predicate(ranges=(RangePred(0, ((1e9, 2e9),)),))
    out = eng.batch_query(q[:3], [nothing, p[0], nothing], k=5)
    assert (out[0].result.ids == -1).all() and (out[2].result.ids == -1).all()
    assert (out[1].result.ids >= 0).any()
