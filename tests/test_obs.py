"""repro.obs: tracing, metrics registry, recall probe, regression gate.

The load-bearing guarantees:

* **span-tree replay determinism** — the deterministic ledger of a traced
  run (span structure + attrs, never wall clock) is bit-identical across
  replays of the same trace + seed + engine cache state;
* **one registry, two views** — the legacy ``Telemetry`` counter shapes
  and the Prometheus/JSON exports read the SAME ``MetricsRegistry`` store,
  so they cannot disagree; a fleet shares one registry with tenant labels;
* **probe determinism** — per-rid seeded sampling is order-independent,
  and per-class online recall matches an injected oracle exactly;
* **the bench gate gates** — ``check_regression`` fails on out-of-band
  metrics and passes in-band ones.
"""
import json

import numpy as np
import pytest

from repro.core import EngineConfig, FilteredANNEngine
from repro.core.trainer import gen_queries
from repro.data import make_dataset
from repro.kernels.ops import vmem_working_set
from repro.obs import (
    MetricsRegistry,
    NULL_TRACER,
    RecallProbe,
    Tracer,
    publish_kernel_budget,
    publish_kernel_dispatch,
    publish_stats,
    span_summary,
)
from repro.runtime import OnlineRuntime, SchedulerConfig, poisson_trace
from repro.runtime.telemetry import Telemetry

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
    return ds, eng, qs, list(preds)


def _trace(qs, preds, n=80, rate=3000.0, seed=5):
    return poisson_trace(qs, preds, n, rate, k=K, seed=seed)


def _traced_run(eng, trace, probe=None):
    """One traced replay from a cold cache state (span cache-delta attrs
    depend on cache contents, so determinism checks must reset them)."""
    eng.plan_cache.clear()
    eng.pred_cache.clear()
    tracer = Tracer()
    rt = OnlineRuntime(eng, SchedulerConfig(max_batch=16, max_wait=0.004),
                       tracer=tracer, probe=probe)
    report = rt.run_trace(trace)
    eng.set_tracer(None)
    return tracer, report


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------
def test_registry_counters_gauges_series():
    reg = MetricsRegistry()
    reg.inc("req_total", 0)                       # pre-create at zero
    reg.inc("req_total")
    reg.inc("req_total", 2)
    assert reg.value("req_total") == 3
    reg.inc("plan_total", plan="pre")
    reg.inc("plan_total", plan="post", tenant="a")
    # label kwarg order must not fork series identity
    reg.inc("plan_total", tenant="a", plan="post")
    assert reg.value("plan_total", plan="post", tenant="a") == 2
    assert reg.series("plan_total", match={"tenant": "a"}) == [
        ({"plan": "post", "tenant": "a"}, 2)
    ]
    with pytest.raises(ValueError):
        reg.inc("req_total", -1)                  # counters never decrease
    with pytest.raises(ValueError):
        reg.set_gauge("req_total", 5)             # kind mismatch
    reg.set_gauge("depth", 7.5)
    assert reg.value("depth") == 7.5


def test_registry_prometheus_golden():
    """Byte-exact exposition: sorted metrics, sorted label sets, cumulative
    histogram buckets."""
    reg = MetricsRegistry()
    reg.inc("repro_requests_total", 3, help="served requests")
    reg.inc("repro_plan_total", 2, plan="ipre")
    reg.inc("repro_plan_total", 1, plan="post")
    reg.observe("repro_lat_seconds", 0.002, buckets=(1e-3, 1e-2), tier="std")
    reg.observe("repro_lat_seconds", 0.2, buckets=(1e-3, 1e-2), tier="std")
    assert reg.prometheus_text() == (
        "# TYPE repro_lat_seconds histogram\n"
        'repro_lat_seconds_bucket{tier="std",le="0.001"} 0\n'
        'repro_lat_seconds_bucket{tier="std",le="0.01"} 1\n'
        'repro_lat_seconds_bucket{tier="std",le="+Inf"} 2\n'
        'repro_lat_seconds_sum{tier="std"} 0.202\n'
        'repro_lat_seconds_count{tier="std"} 2\n'
        "# TYPE repro_plan_total counter\n"
        'repro_plan_total{plan="ipre"} 2\n'
        'repro_plan_total{plan="post"} 1\n'
        "# HELP repro_requests_total served requests\n"
        "# TYPE repro_requests_total counter\n"
        "repro_requests_total 3\n"
    )


def test_registry_snapshot_deterministic():
    def build():
        reg = MetricsRegistry()
        reg.inc("b_total", 2, x="1")
        reg.inc("a_total")
        reg.observe("h_seconds", 0.03)
        return reg
    assert build().snapshot() == build().snapshot()
    assert list(build().snapshot()) == ["a_total", "b_total", "h_seconds"]


def test_publish_stats_flattens_numeric_leaves():
    reg = MetricsRegistry()
    publish_stats(reg, {"pred_cache": {"hits": 4, "ratio": 0.5},
                        "name": "skipped", "ok": True}, prefix="repro_engine")
    assert reg.value("repro_engine_pred_cache_hits") == 4
    assert reg.value("repro_engine_pred_cache_ratio") == 0.5
    assert reg.value("repro_engine_ok") == 1
    assert reg.series("repro_engine_name") == []


def test_publish_kernel_budget_gauges():
    reg = MetricsRegistry()
    publish_kernel_budget(reg)
    for d in (128, 256, 512):
        k = f"masked_l2_d{d}"
        assert reg.value("repro_kernel_vmem_bytes", kernel=k) == (
            vmem_working_set(d)["total"])
        assert reg.series("repro_kernel_vmem_fits_16mib") == []


# ----------------------------------------------------------------------
# telemetry on the registry: legacy shapes == registry store
# ----------------------------------------------------------------------
def test_telemetry_legacy_view_reads_registry(system):
    _, eng, qs, preds = system
    trace = _trace(qs, preds, n=60)
    rt = OnlineRuntime(eng, SchedulerConfig(max_batch=16, max_wait=0.004))
    report = rt.run_trace(trace)
    tel = report.telemetry
    c = tel.counters()
    assert c["n_completed"] == 60 == tel.n_completed
    assert c["n_completed"] == tel.registry.value("repro_requests_total")
    assert sum(c["plan_counts"].values()) == 60
    assert set(c["plan_counts"]) == {"pre", "post", "ipre", "dnf"}   # pre-created
    assert sum(c["batch_sizes"].values()) == c["n_batches"]
    met = {lbl["tier"]: v for lbl, v in
           tel.registry.series("repro_deadline_total", match={"outcome": "met"})}
    assert {t: int(v) for t, v in met.items() if v} \
        == {t: v for t, v in c["deadline_met"].items() if v}
    # histogram observed every completion
    text = tel.registry.prometheus_text()
    assert "repro_latency_virtual_seconds_count" in text


def test_fleet_registry_shared_with_tenant_labels():
    from repro.fleet.telemetry import FleetTelemetry

    ft = FleetTelemetry()
    ta, tb = ft.tenant("a"), ft.tenant("b")
    assert ta.registry is ft.registry is tb.registry
    ta._inc("repro_requests_total", 5)
    tb._inc("repro_requests_total", 2)
    assert ta.n_completed == 5 and tb.n_completed == 2     # label isolation
    ft.record_reject("b")
    assert ft.rejects == {"b": 1}
    assert 'repro_requests_total{tenant="a"} 5' in ft.registry.prometheus_text()


# ----------------------------------------------------------------------
# tracing: span trees, determinism, summary
# ----------------------------------------------------------------------
def test_null_tracer_is_inert():
    with NULL_TRACER.span("anything", x=1):
        NULL_TRACER.annotate(y=2)
    assert not NULL_TRACER.enabled
    assert list(NULL_TRACER.spans()) == []


def test_span_tree_structure(system):
    _, eng, qs, preds = system
    tracer, _ = _traced_run(eng, _trace(qs, preds, n=40))
    names = {s.name for s in tracer.spans()}
    assert {"batch", "plan", "execute", "group"} <= names
    roots = tracer.roots
    assert all(s.name == "batch" for s in roots)
    plan = next(s for s in tracer.spans() if s.name == "plan")
    assert {"plan_cache_hits", "plan_cache_misses"} <= set(plan.attrs)
    comp = next(s for s in tracer.spans() if s.name == "predicate_compile")
    assert comp.attrs["bitmap_words"] > 0
    groups = [s for s in tracer.spans() if s.name == "group"]
    assert groups
    for g in groups:
        assert {"decision", "backend", "knob", "n_rows"} <= set(g.attrs)
    assert any("n_candidates" in g.attrs for g in groups)
    execs = [s for s in tracer.spans() if s.name == "execute"]
    assert any(any(k.startswith("kernel_") for k in e.attrs) for e in execs), \
        "execute spans must carry kernel dispatch deltas"


def test_span_tree_replay_bit_identical(system):
    """The tentpole guarantee: deterministic ledger identical across
    replays, wall clock excluded (and actually measured)."""
    _, eng, qs, preds = system
    trace = _trace(qs, preds, n=60)
    ta, _ = _traced_run(eng, trace)
    tb, _ = _traced_run(eng, trace)
    assert ta.deterministic_tree() == tb.deterministic_tree()
    assert sum(s.wall_s for s in ta.spans()) > 0.0


def test_span_summary_ranks_self_time(system):
    _, eng, qs, preds = system
    tracer, _ = _traced_run(eng, _trace(qs, preds, n=40))
    rows = span_summary(tracer)
    stages = [r["stage"] for r in rows]
    assert {"batch", "batch_query", "plan", "execute", "group", "mask",
            "scan_prep", "scan_wait"} <= set(stages)
    assert not any(s.startswith("kernel:") for s in stages)
    assert all(r["count"] >= 1 for r in rows)
    assert all(r["self_s"] <= r["wall_s"] + 1e-12 for r in rows)
    assert [r["self_s"] for r in rows] \
        == sorted((r["self_s"] for r in rows), reverse=True)


def test_trace_jsonl_export(system, tmp_path):
    _, eng, qs, preds = system
    tracer, _ = _traced_run(eng, _trace(qs, preds, n=24))
    path = tmp_path / "spans.jsonl"
    tracer.write_jsonl(path)
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(rows) == sum(1 for _ in tracer.spans())
    ids = {r["span_id"] for r in rows}
    assert all(r["parent_id"] in ids or r["parent_id"] == -1 for r in rows)
    assert all("wall" in r and "attrs" in r for r in rows)


def test_engine_stats_kernel_and_cache_ratios(system):
    from repro.kernels import ops

    _, eng, qs, preds = system
    eng.plan_cache.clear()
    eng.pred_cache.clear()
    ops.reset_dispatch_stats()
    eng.batch_query(np.stack(qs[:8]), preds[:8], K)
    counts = ops.dispatch_counts()
    assert counts.get("fused_masked_topk", 0) + counts.get("ivf_search", 0) > 0
    s = eng.stats()
    assert set(s["cache_hit_ratio"]) == {"pred_cache", "mask_tier", "plan_cache"}
    assert s["kernel_dispatch"] == counts
    reg = MetricsRegistry()
    publish_kernel_dispatch(reg)
    for name, n in counts.items():
        assert reg.value("repro_kernel_dispatch_total", kernel=name) == n


# ----------------------------------------------------------------------
# recall probe
# ----------------------------------------------------------------------
def test_probe_sampling_deterministic_and_order_free():
    p = RecallProbe(rate=0.3, seed=11)
    picks = {rid: p.should_sample(rid) for rid in range(200)}
    assert picks == {rid: p.should_sample(rid) for rid in reversed(range(200))}
    n = sum(picks.values())
    assert 0 < n < 200                       # actually samples a fraction
    assert RecallProbe(rate=1.0).should_sample(5)
    assert not RecallProbe(rate=0.0).should_sample(5)


def test_probe_recall_vs_injected_oracle(system):
    """Class recall must equal the analytic value for a known oracle: the
    truth_fn disagrees with the served ids on a known fraction of slots."""
    _, eng, qs, preds = system
    trace = _trace(qs, preds, n=40, seed=9)
    # oracle = actually-served ids with the last id replaced -> recall 0.9
    rt = OnlineRuntime(eng, SchedulerConfig(max_batch=16, max_wait=0.004))
    served_ids = rt.run_trace(trace).results
    # (query, pred) -> that request's served ids; duplicates collapse
    # safely because identical (query, pred, k) always serve identical ids
    by_key = {
        (r.query.tobytes(), id(r.pred)): served_ids[r.rid].result.ids[0]
        for r in trace.requests
    }

    def truth_fn(q, pred, k):
        t = by_key[(np.asarray(q[0], np.float32).tobytes(), id(pred))].copy()
        t[0] = 10**7             # planted miss: top-1 swapped for a fake id
        return t[None, :]

    probe = RecallProbe(backend=eng, rate=1.0, seed=0, truth_fn=truth_fn)
    report = OnlineRuntime(
        eng, SchedulerConfig(max_batch=16, max_wait=0.004), probe=probe,
    ).run_trace(trace)
    assert probe.n_seen == probe.n_sampled == 40
    est = probe.estimates()
    served_classes = {RecallProbe.class_key(r) for r in report.results.values()}
    assert set(est) == served_classes         # every served class estimated
    # expected recall per class: each request recovers all but the planted
    # miss of its n_valid true neighbours -> mean of (n_valid - 1)/n_valid
    want: dict = {}
    for res in report.results.values():
        n_valid = int((res.result.ids[0] >= 0).sum())
        want.setdefault(RecallProbe.class_key(res), []).append(
            (n_valid - 1) / n_valid)
    for key, row in est.items():
        assert row["recall"] == round(float(np.mean(want[key])), 6)
        assert row["recall"] < 1.0            # the planted miss registered
    assert probe.below(0.99) == {k: row["recall"] for k, row in est.items()}
    assert probe.below(0.5) == {}


def test_probe_replay_deterministic(system):
    _, eng, qs, preds = system
    trace = _trace(qs, preds, n=60)

    def run():
        probe = RecallProbe(rate=0.5, seed=3)
        OnlineRuntime(eng, SchedulerConfig(max_batch=16, max_wait=0.004),
                      probe=probe).run_trace(trace)
        return probe.counters()
    a, b = run(), run()
    assert a == b
    assert 0 < a["n_sampled"] < a["n_seen"] == 60


def test_probe_publish_gauges():
    probe = RecallProbe(rate=1.0, seed=0, truth_fn=lambda q, p, k: None)
    probe.n_seen, probe.n_sampled = 10, 10
    probe._sum["post/ivf:adapt"] = 9.0
    probe._count["post/ivf:adapt"] = 10
    reg = MetricsRegistry()
    probe.publish(reg, tenant="a")
    assert reg.value("repro_probe_recall", cls="post/ivf:adapt", tenant="a") == 0.9
    assert reg.value("repro_probe_seen_total", tenant="a") == 10


# ----------------------------------------------------------------------
# bench regression gate
# ----------------------------------------------------------------------
def test_check_regression_gate(tmp_path):
    from benchmarks.check_regression import main as gate

    tol = tmp_path / "tolerances.json"
    tol.write_text(json.dumps({
        "demo": {"recall": {"min": 0.9}, "counts.n": {"equals": 4},
                 "mem": {"max": 100}},
    }))
    good = tmp_path / "BENCH_demo_n5000.json"
    good.write_text(json.dumps({"recall": 0.95, "counts": {"n": 4}, "mem": 80}))
    assert gate([str(good), "--tolerances", str(tol)]) == 0
    bad = tmp_path / "BENCH_demo_n9000.json"
    bad.write_text(json.dumps({"recall": 0.85, "counts": {"n": 4}}))  # 2 bad
    assert gate([str(bad), "--tolerances", str(tol)]) == 1
    unknown = tmp_path / "BENCH_other_n5000.json"
    unknown.write_text("{}")
    assert gate([str(unknown), "--tolerances", str(tol)]) == 1


def test_committed_tolerances_cover_ci_benches():
    from benchmarks.check_regression import TOLERANCES

    bands = json.loads(TOLERANCES.read_text())
    assert {"backend", "mutation", "fleet", "runtime"} <= set(bands)
    for name, spec in bands.items():
        for path, band in spec.items():
            assert band and set(band) <= {"min", "max", "equals"}, (name, path)
