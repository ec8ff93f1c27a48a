"""CPU tests of the chip benchmark's own code (``bench/harness``).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They cover the trace reduction on a recorded fixture, the roofline work,
the loader and its data-driven discovery, the seeded generators, the
refusal to run without a TPU, the control (the reference in bfloat16 has
to fail the comparison) and a run with its timed path broken (``correct``
has to come out false).  No test describes a TPU topology.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

from harness import datagen, devtrace, readers, reference, roofline  # noqa: E402
from harness.pool import Conj, Disj, make_pool, pool_shape  # noqa: E402
from harness.schedule import make_schedule, zipf_counts  # noqa: E402
from harness.spec import SpecError, load_spec  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_events.json"
ARXIV = "arxiv1m.mixed.steady"


# ---------------------------------------------------------------- trace
def _fixture_events():
    with open(FIXTURE) as f:
        raw = json.load(f)
    return [devtrace.Event(**e) for e in raw["events"]], raw["expect"]


def test_trace_reduction_on_recorded_fixture():
    events, expect = _fixture_events()
    red = devtrace.reduce_events(events)
    assert red.n_devices == expect["n_devices"]
    assert red.busy_s == pytest.approx(expect["busy_s"], rel=1e-9)
    for name, s in expect["module_s"].items():
        assert red.module_s[name] == pytest.approx(s, rel=1e-9)
    # busy is a union: never more than the sum of the op events, never
    # more than the span from the first to the last op
    ops = [e for e in events if e.line == "XLA Ops" and devtrace.DEVICE_PLANE.match(e.plane)]
    assert red.busy_s <= sum(e.dur_ns for e in ops) * 1e-9 + 1e-12
    assert red.busy_s <= (max(e.end_ns for e in ops) - min(e.start_ns for e in ops)) * 1e-9
    assert len(red.idle_gaps) <= 10 and len(red.device_ops) <= 10
    assert all(name.startswith(("bench:", "host:")) for name, _ in red.idle_gaps)


def test_busy_union_idle_share_and_gap_names():
    p = "/device:TPU:0"
    ev = [devtrace.Event(p, "XLA Modules", "jit_masked_l2_topk(77)", 0, 400),
          devtrace.Event(p, "XLA Ops", "fusion.1", 0, 300),
          devtrace.Event(p, "XLA Ops", "custom-call", 200, 200),      # overlaps
          devtrace.Event(p, "XLA Modules", "jit__take_rows", 1000, 100),
          devtrace.Event(p, "XLA Ops", "gather", 1000, 100),
          devtrace.Event("/host:CPU", "python", "bench:wait", 400, 600),
          devtrace.Event("/host:CPU", "python", "unrelated", 0, 5000)]
    red = devtrace.reduce_events(ev)
    assert red.busy_s == pytest.approx(500e-9)
    assert red.module_s == {"masked_l2_topk": pytest.approx(400e-9),
                            "_take_rows": pytest.approx(100e-9)}
    assert red.idle_gaps == [("bench:wait", pytest.approx(600e-9))]
    assert devtrace.module_name("jit_l2_topk.3") == "l2_topk"


# ---------------------------------------------------------------- roofline
class _View:
    def __init__(self, spans, module_s, dim=384, k=10):
        self.spans, self.dim, self.k = spans, dim, k
        self.peaks = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
        self.reduced = devtrace.Reduced(1.0, 1, module_s, [], [])


@pytest.mark.parametrize("b,n,d", [(8, 1000, 384), (64, 262144, 384), (1, 1, 128),
                                   (64, 1_000_000, 128)])
def test_roofline_share_at_most_one_when_time_at_least_bound(b, n, d):
    fl, nb = roofline.scan_work(b, n, d, 10)
    assert fl == 2.0 * b * n * d
    assert nb == n * d * 4 + b * d * 4 + b * 10 * 8
    least, which = roofline.least_seconds(fl, nb, {"bf16_tflops": 197.0, "hbm_gbps": 819.0})
    assert which == "memory"            # a masked scan of f32 rows is bandwidth-bound
    for slack in (1.0, 1.5, 10.0):
        span = {"decision": "ipre", "n_rows": b, "n_candidates": n, "wall_s": 0.0}
        share = readers.scan_roofline(_View([span], {"masked_l2_topk": least * slack}, dim=d))
        assert share <= 100.0 + 1e-9
        assert share == pytest.approx(100.0 / slack)


def test_roofline_reads_nothing_without_exact_groups():
    v = _View([{"decision": "post", "n_rows": 8, "n_candidates": None, "wall_s": 0.01}],
              {"masked_l2_topk": 1.0})
    assert readers.scan_roofline(v) is None


def test_peaks_table_refuses_unknown_device():
    assert roofline.load_peaks(BENCH, "TPU v5 lite")["hbm_gbps"] == 819.0
    with pytest.raises(KeyError):
        roofline.load_peaks(BENCH, "TPU v9 imaginary")


# ---------------------------------------------------------------- loader
def _copy_tree(tmp: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp


def _small_root(tmp: Path, rows: int, pool_n: int = 64, n_or: int = 8) -> Path:
    """A copy of the benchmark whose configuration and traffic files are cut
    to a size the CPU runs in seconds; the code is the committed code."""
    root = _copy_tree(tmp)
    cfg_path = root / "bench/configs/arxiv1m.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["rows"] = rows
    cfg["fit"] = dict(cfg["fit"], n_broad=1)
    cfg_path.write_text(json.dumps(cfg))
    tr_path = root / "bench/traffic/mixed.steady.json"
    traffic = json.loads(tr_path.read_text())
    traffic["pool"] = dict(traffic["pool"], n=pool_n, n_or=n_or)
    tr_path.write_text(json.dumps(traffic))
    return root


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_loader_reads_every_cell_and_reader():
    spec = load_spec(ROOT)
    for name in spec.workloads:
        cell = spec.cell(name)
        assert cell.rate_qps > 0 and cell.end_to_end and cell.per_layer
        assert {m.name for m in cell.end_to_end} >= {"setup_s", "recall_at_10"}
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.reader(m.name))


@pytest.mark.parametrize("field,value", [
    ("name", "p50 ms"), ("name", "µs_metric"), ("name", "a/b"), ("unit", "q per s"),
    ("unit", "µs"), ("better", "faster"), ("bound", 0.5), ("source", "program_span")])
def test_loader_rejects_bad_end_to_end_fields(tmp_path, field, value):
    root = _copy_tree(tmp_path)
    raw = json.loads((root / "BENCHMARK.json").read_text())
    raw["end_to_end"][0][field] = value
    (root / "BENCHMARK.json").write_text(json.dumps(raw))
    with pytest.raises(SpecError):
        load_spec(root)


def test_new_config_traffic_cell_and_metric_need_no_edit(tmp_path):
    root = _copy_tree(tmp_path)
    before = _digest(root / "bench")
    raw = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/arxiv1m.json").read_text())
    cfg.update(name="glove200", rows=1_180_000, dim=200)
    (root / "bench/configs/glove200.json").write_text(json.dumps(cfg))
    traffic = json.loads((root / "bench/traffic/mixed.steady.json").read_text())
    traffic["pool"]["n"] = 16
    (root / "bench/traffic/cold.steady.json").write_text(json.dumps(traffic))
    (root / "bench/cells/glove200.cold.steady.json").write_text('{"rate_qps": 123}')
    (root / "bench/metrics/mask_ms.py").write_text("def read(v):\n    return 4.5\n")
    raw["configs"].append({"name": "glove200", "source": "https://nlp.stanford.edu/projects/glove/",
                           "file": "bench/configs/glove200.json", "reduced": [], "why": "x"})
    raw["workloads"].append({"name": "glove200.cold.steady", "config": "glove200",
                             "traffic": "cold.steady", "chips": 1, "why": "x"})
    raw["per_layer"].append({"name": "mask_ms", "unit": "ms", "better": "lower",
                             "source": "program_span", "layer": "predicate compile",
                             "moves": "p50_ms", "workloads": ["glove200.cold.steady"]})
    for m in raw["end_to_end"]:
        if m.get("workloads") and "arxiv1m.mixed.steady" in m["workloads"]:
            m["workloads"].append("glove200.cold.steady")
    (root / "BENCHMARK.json").write_text(json.dumps(raw))

    spec = load_spec(root)
    cell = spec.cell("glove200.cold.steady")
    assert cell.config["dim"] == 200 and cell.traffic["pool"]["n"] == 16
    assert cell.rate_qps == 123.0
    assert [m.name for m in cell.per_layer] == ["mask_ms"]
    assert spec.reader("mask_ms")(None) == 4.5
    after = _digest(root / "bench")
    assert all(after[f] == h for f, h in before.items())      # nothing edited


# ---------------------------------------------------------------- generators
def _small_corpus(seed, rows=3000):
    cfg = dict(load_spec(ROOT).configs["arxiv1m"], rows=rows, dim=32)
    return datagen.make_corpus_host(cfg, seed)


def test_seeded_corpus_pool_and_schedule_repeat_exactly():
    spec = load_spec(ROOT)
    traffic = spec.cell(ARXIV).traffic
    runs = []
    for _ in range(2):
        x, cat, num, qs = _small_corpus(2**33 + 7)
        pool = make_pool(cat, num, traffic["pool"], datagen.host_rng(2**33 + 7, "pool"))
        sched = make_schedule(len(pool), x, qs, 300.0, 2.0, 1.2,
                              datagen.host_rng(2**33 + 7, "schedule"))
        runs.append((x, cat, num, pool, sched))
    (x0, c0, n0, p0, s0), (x1, c1, n1, p1, s1) = runs
    assert np.array_equal(x0, x1) and np.array_equal(c0, c1) and np.array_equal(n0, n1)
    assert p0 == p1
    assert np.array_equal(s0.due, s1.due) and np.array_equal(s0.pred, s1.pred)
    assert np.array_equal(s0.queries, s1.queries)
    assert len(s0) == 600 and np.all(np.diff(s0.due) >= 0) and s0.due[-1] < 2.0
    assert np.array_equal(np.bincount(s0.pred, minlength=72), zipf_counts(600, 72, 1.2))
    assert len(p0) == 72 and all(isinstance(p, Disj) for p in p0[64:])
    assert all(p.eval(c0, n0).any() for p in p0)


def test_other_data_seed_moves_anchors_not_the_pool_shape():
    spec = load_spec(ROOT)
    pool_spec = spec.cell(ARXIV).traffic["pool"]
    assert pool_shape(pool_spec) == pool_shape(pool_spec)
    x, cat, num, _ = _small_corpus(5)
    a = make_pool(cat, num, pool_spec, datagen.host_rng(5, "pool"))
    b = make_pool(cat, num, pool_spec, datagen.host_rng(6, "pool"))
    assert a != b
    kinds = [("labels" if p.labels else "") + ("ranges" if p.ranges else "") for p in a[:64]]
    kinds_b = [("labels" if p.labels else "") + ("ranges" if p.ranges else "") for p in b[:64]]
    assert kinds == kinds_b


def test_program_translation_keeps_the_semantics():
    from harness.pool import to_program

    x, cat, num, _ = _small_corpus(9)
    pool = make_pool(cat, num, load_spec(ROOT).cell(ARXIV).traffic["pool"],
                     datagen.host_rng(9, "pool"))
    extra = Conj(labels=((0, int(cat[0, 0])),), ranges=((1, ((0.0, 5.0), (9.0, 20.0))),))
    for p in pool + [extra]:
        assert np.array_equal(p.eval(cat, num), to_program(p).eval(cat, num))


# ---------------------------------------------------------------- no chip
def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload", ARXIV,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


# ---------------------------------------------------------------- control
def test_control_in_bfloat16_fails_and_reference_passes(tmp_path):
    from control import control_readings

    cell = load_spec(_small_root(tmp_path, rows=4000)).cell(ARXIV)
    limits = cell.config["limits"]
    for seed in (11, 2**32 + 3):
        ctl, ref = control_readings(cell, seed, 8.0)   # 512 sampled, as a run
        assert not all(ok for *_, ok in reference.checks(ctl, limits))
        assert ctl.exact_gap > limits["exact_gap"]
        assert all(ok for *_, ok in reference.checks(ref, limits))
        assert ref.exact_gap == 0.0 and ref.wrong == 0


# ---------------------------------------------------------------- faults
def _half_batch(call):
    """Answers only the first half of each batch; the rest never come."""
    def broken(self, q, preds, k):
        h = max(1, len(preds) // 2)
        return call(self, q[:h], preds[:h], k)[: h if len(preds) > 1 else 0]
    return broken


def _altered_answer(call):
    """Alters one id of every answer where it is produced."""
    def broken(self, q, preds, k):
        out = call(self, q, preds, k)
        for pr in out:
            ids = pr.result.ids
            ids[0, 0] = (ids[0, 0] + 1) if ids[0, 0] >= 0 else 0
        return out
    return broken


@pytest.mark.parametrize("fault,expect", [(None, True), (_half_batch, False),
                                          (_altered_answer, False)])
def test_run_with_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault, expect):
    """A whole run past the look for a chip, on a small corpus, with the
    engine's served entry broken underneath the benchmark."""
    import jax
    from harness import cell as cell_mod
    from repro.core import FilteredANNEngine

    d0 = jax.devices()[0]
    monkeypatch.setattr(cell_mod, "check_device", lambda chips: {
        "platform": d0.platform, "kind": d0.device_kind, "count": 1})
    if fault is not None:
        monkeypatch.setattr(FilteredANNEngine, "batch_query",
                            fault(FilteredANNEngine.batch_query))
    spec = load_spec(_small_root(tmp_path, rows=3000, pool_n=6, n_or=2))
    out = cell_mod.run_cell(spec, ARXIV, 2**31 + 17, 1.0, False, time.perf_counter())
    assert out["correct"] is expect, out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m.name for m in spec.cell(ARXIV).end_to_end}
