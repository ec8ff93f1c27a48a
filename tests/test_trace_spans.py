"""Spans of the serving path on the profiler's clock.

* one ``batch_query`` root per call, with ``plan`` and ``execute`` under it;
* exact groups carry ``mask``, ``scan_prep`` and ``scan_wait``, and
  ``h2d_bytes`` is the bytes of the host arrays the scan hands to jitted
  functions;
* post groups carry one ``ivf_round`` per (want, nprobe) dispatch, whose
  counters match a recount;
* an enabled tracer writes each span into a ``jax.profiler`` trace as a
  ``repro:<name>`` annotation, nested as the spans are;
* ``NULL_TRACER`` opens no annotation and computes no counter.
"""
import numpy as np
import pytest

from repro.core import EngineConfig, FilteredANNEngine
from repro.core import executors
from repro.core.trainer import gen_queries
from repro.data import make_dataset
from repro.obs import NULL_TRACER, Tracer
from repro.obs import trace as trace_mod

K = 10


@pytest.fixture(scope="module")
def system():
    ds = make_dataset("arxiv", scale="4000", seed=0)
    eng = FilteredANNEngine(
        ds.vectors, ds.cat, ds.num, EngineConfig(n_lists=32, seed=0)
    ).build()
    qs, preds, _ = gen_queries(
        ds.vectors, ds.cat, ds.num, 16, kinds=ds.filter_kinds,
        sel_range=(0.01, 0.4), seed=2,
    )
    return ds, eng, np.asarray(qs, np.float32), list(preds)


def _traced(eng, fn):
    tracer = Tracer()
    eng.set_tracer(tracer)
    try:
        fn()
    finally:
        eng.set_tracer(None)
    return tracer


def _groups(tracer, exact: bool):
    return [s for s in tracer.spans() if s.name == "group"
            and (s.attrs["decision"] in ("pre", "ipre")) == exact]


def test_batch_query_is_one_root_per_call(system):
    _, eng, qs, preds = system
    tracer = _traced(eng, lambda: [eng.batch_query(qs[:n], preds[:n], K) for n in (16, 5)])
    assert [r.name for r in tracer.roots] == ["batch_query", "batch_query"]
    assert [r.attrs["n_queries"] for r in tracer.roots] == [16, 5]
    for r in tracer.roots:
        assert [c.name for c in r.children] == ["plan", "execute"]
        assert r.start_s <= r.children[0].start_s
        assert r.wall_s >= sum(c.wall_s for c in r.children)


def test_exact_groups_carry_mask_and_scan_spans(system):
    _, eng, qs, preds = system
    tracer = _traced(eng, lambda: eng.batch_query(qs, preds, K))
    groups = _groups(tracer, exact=True)
    assert groups
    for g in groups:
        assert [c.name for c in g.children] == ["mask", "scan_prep", "scan_wait"]
        prep = g.children[1]
        assert prep.attrs["n_pass"] == g.attrs["n_candidates"]
        assert prep.attrs["h2d_bytes"] > 0
        mask, wait = g.children[0], g.children[2]
        assert mask.start_s + mask.wall_s <= prep.start_s <= wait.start_s


@pytest.mark.parametrize("sel,full", [(0.02, False), (0.6, True)])
def test_h2d_bytes_is_what_the_scan_hands_to_jit(system, monkeypatch, sel, full):
    """Every NumPy array the call passes to a jitted function, summed."""
    ds, eng, qs, _ = system
    handed = []

    def recording(fn):
        def wrapped(*args):
            handed.extend(a.nbytes for a in args if isinstance(a, np.ndarray))
            return fn(*args)
        return wrapped

    monkeypatch.setattr(executors, "_take_rows", recording(executors._take_rows))
    monkeypatch.setattr(executors, "fused_masked_topk",
                        recording(executors.fused_masked_topk))
    n = ds.vectors.shape[0]
    mask = np.random.default_rng(3).random(n) < sel
    tracer = Tracer()
    res = eng.pre_exec.search_masked(qs[:3], mask, K, tracer=tracer)
    prep, wait = list(tracer.spans())
    assert (prep.name, wait.name) == ("scan_prep", "scan_wait")
    assert prep.attrs["full_scan"] is full
    assert prep.attrs["n_pass"] == int(mask.sum())
    assert prep.attrs["h2d_bytes"] == sum(handed)
    untraced = eng.pre_exec.search_masked(qs[:3], mask, K)
    assert np.array_equal(res.ids, untraced.ids)


def test_post_groups_carry_ivf_rounds_that_recount(system, monkeypatch):
    ds, eng, qs, preds = system
    index = eng.post_exec.index
    calls = []
    search_scored = index.search_scored

    def recording(q, k, nprobe=8, mask=None):
        out = search_scored(q, k, nprobe=nprobe, mask=mask)
        calls.append((q.copy(), k, nprobe, out[1].copy()))
        return out

    monkeypatch.setattr(index, "search_scored", recording)
    tracer = _traced(eng, lambda: eng.batch_query(qs, preds, K))
    groups = _groups(tracer, exact=False)
    assert groups
    rounds = [c for g in groups for c in g.children]
    assert rounds and all(c.name == "ivf_round" for c in rounds)
    assert len(rounds) == len(calls)
    pred_of = {qs[i].tobytes(): preds[i] for i in range(len(preds))}
    for sp, (q, want, nprobe, ids) in zip(rounds, calls):
        assert (sp.attrs["n_rows"], sp.attrs["want"], sp.attrs["nprobe"]) \
            == (q.shape[0], want, nprobe)
        d2 = ((q[:, None, :].astype(np.float64)
               - index.centroids[None].astype(np.float64)) ** 2).sum(-1)
        probes = np.argsort(d2, axis=1)[:, :nprobe]
        assert sp.attrs["n_scored"] == int(index.list_counts[probes].sum())
        assert sp.attrs["n_retrieved"] == int((ids >= 0).sum())
        n_pass = 0
        for row, got in zip(q, ids):
            got = got[got >= 0]
            n_pass += int(pred_of[row.tobytes()].eval(ds.cat[got], ds.num[got]).sum())
        assert sp.attrs["n_pass"] == n_pass
        assert 0 <= n_pass <= sp.attrs["n_retrieved"] <= sp.attrs["n_scored"]


def test_profiler_trace_holds_nested_repro_annotations(system, tmp_path):
    import jax
    from jax.profiler import ProfileData

    _, eng, qs, preds = system
    eng.batch_query(qs, preds, K)               # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        tracer = _traced(eng, lambda: eng.batch_query(qs, preds, K))
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    ann = [(e.name[len("repro:"):], e.start_ns, e.start_ns + e.duration_ns)
           for p in ProfileData.from_file(str(path)).planes for line in p.lines
           for e in line.events if e.name.startswith("repro:")]
    spans = list(tracer.spans())
    names = sorted(s.name for s in spans)
    assert sorted(a[0] for a in ann) == names
    assert {"batch_query", "plan", "execute", "group", "mask", "scan_prep",
            "scan_wait", "ivf_round"} <= set(names)

    def parent_of(a):
        """The shortest annotation that encloses ``a``."""
        outer = [b for b in ann if b is not a and b[1] <= a[1] and a[2] <= b[2]]
        return min(outer, key=lambda b: b[2] - b[1])[0] if outer else None

    want = {}
    for s in spans:
        for c in s.children:
            want.setdefault(c.name, set()).add(s.name)
    for a in ann:
        p = parent_of(a)
        if a[0] == "batch_query":
            assert p is None
        else:
            assert p in want[a[0]], (a, p)


class _CountingMask(np.ndarray):
    """A candidate mask that counts its popcounts."""

    sums = 0

    def sum(self, *a, **kw):
        _CountingMask.sums += 1
        return super().sum(*a, **kw)


def _instrument(eng, monkeypatch) -> list:
    """Count the annotations the tracer opens (the list returned) and the
    popcounts of every candidate mask (``_CountingMask.sums``)."""
    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace_mod, "TraceAnnotation", Annotation)
    for ex in (eng.pre_exec, eng.ipre_exec):
        orig = ex.candidate_mask
        monkeypatch.setattr(ex, "candidate_mask",
                            lambda p, orig=orig: orig(p).view(_CountingMask))
    return opened


def test_null_tracer_opens_nothing_and_counts_nothing(system, monkeypatch):
    _, eng, qs, preds = system
    opened = _instrument(eng, monkeypatch)

    def run(tracer):
        opened.clear()
        _CountingMask.sums = 0
        eng.set_tracer(tracer)
        try:
            eng.batch_query(qs, preds, K)
        finally:
            eng.set_tracer(None)
        return list(opened), _CountingMask.sums

    opened_off, sums_off = run(NULL_TRACER)
    tracer = Tracer()
    opened_on, sums_on = run(tracer)
    n_exact = len(_groups(tracer, exact=True))
    assert n_exact
    assert opened_off == []
    assert sorted(opened_on) == sorted("repro:" + s.name for s in tracer.spans())
    # untraced: the scan's own popcount alone; traced: the group's
    # candidate count besides
    assert sums_off == n_exact
    assert sums_on == 2 * n_exact
    with NULL_TRACER.span("batch_query") as a, NULL_TRACER.span("mask") as b:
        assert a is b
