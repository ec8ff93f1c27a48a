"""One run of one cell: set-up, the measured window, the device trace, and
the comparison with the reference.

``run_cell`` is what ``bench/run.py`` calls.  ``bench/control.py`` draws
the same deployment, window and check sample (``deployment``,
``draw_window``) and compares through the same ``reference_answers`` and
``compare``.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from . import batcher, datagen, devtrace, reference
from .pool import Conj, draw, make_pool, pool_shape, to_program
from .roofline import load_peaks
from .schedule import Schedule, make_schedule, zipf_probs
from .spec import Cell, Spec

CHECK_SAMPLE = 512          # requests of the window compared with the reference
QUERY_BUCKETS = (8, 16, 32, 64)


class NoDevice(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


class CompileMeter:
    """Backend compiles seen by JAX's monitoring events (a persistent-cache
    hit counts too: it is a program the window had not warmed)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def check_device(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoDevice(f"needs {chips} TPU chip(s); JAX finds {len(devs)} "
                       f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def peak_bytes() -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


@dataclasses.dataclass
class RunView:
    """What a per-layer metric's reader may read."""

    cell: Cell
    sched: Schedule
    served: batcher.Served
    spans: List[dict]                 # the program's `group` spans in the window
    reduced: Optional[devtrace.Reduced]
    window_s: float
    compiles: int
    dim: int
    k: int
    peaks: dict
    readings: reference.Readings   # the sampled answers against the reference
    setup_s: float

    def window_batches(self) -> List[batcher.Batch]:
        return [b for b in self.served.batches if b.start < self.sched.seconds]


@dataclasses.dataclass
class Deployment:
    """What a configuration's ``data_seed`` draws, whatever the run's seed:
    the corpus and the traffic mix's predicate pool."""

    cfg: dict
    traffic: dict
    k: int
    x: Optional[np.ndarray]   # the host corpus; None once freed
    cat: np.ndarray
    num: np.ndarray
    qscale: float
    pool: list                # the benchmark's predicates, most popular first

    def schedule(self, rate: float, seconds: float, seed: int, stream: str) -> Schedule:
        return make_schedule(len(self.pool), self.x, self.qscale, rate, seconds,
                             self.traffic["zipf"], datagen.host_rng(seed, stream))


def deployment(cell: Cell) -> Deployment:
    cfg, traffic = cell.config, cell.traffic
    ds = int(cfg["data_seed"])
    x, cat, num, qscale = datagen.make_corpus_host(cfg, ds)
    pool = make_pool(cat, num, traffic["pool"], datagen.host_rng(ds, "pool"))
    return Deployment(cfg, traffic, int(traffic["k"]), x, cat, num, qscale, pool)


def draw_window(dep: Deployment, cell: Cell, seed: int, seconds: float):
    """The window's schedule and the sample of its requests that is compared
    with the reference, both from the run's ``seed``."""
    sched = dep.schedule(cell.rate_qps, seconds, seed, "schedule")
    sample = np.sort(datagen.host_rng(seed, "sample").choice(
        len(sched), size=min(CHECK_SAMPLE, len(sched)), replace=False))
    return sched, sample


def _fit_set(dep: Deployment):
    """The planner's training set: every conjunction of the traffic's pool
    once (a deployment fits on the predicates its users send),
    then ``n_broad`` broad range filters, where a full masked scan loses to
    the post-filter; each with a query vector of its own."""
    fit = dep.cfg["fit"]
    rng = datagen.host_rng(int(dep.cfg["data_seed"]), "fit")
    broad = pool_shape({"shape_seed": fit["shape_seed"], "n": fit["n_broad"],
                        "kinds": ["range"], "sel": fit["broad_sel"]})
    preds = [p for p in dep.pool if isinstance(p, Conj)]
    preds += draw(dep.cat, dep.num, broad, rng, 0.2)
    return datagen.query_vectors(dep.x, dep.qscale, len(preds), rng), preds


def warm(eng, prog_pool: list, queries: np.ndarray, k: int) -> None:
    """Plan the whole pool (the plan and predicate caches fill), then run
    every pool predicate at each query bucket a batch can form, so each
    scan shape the window uses compiles here."""
    plans, _ = eng.make_plan_batch(prog_pool, k)
    for p, plan in zip(prog_pool, plans):
        exact = any(c.decision != 1 for c in plan.clauses)   # 1: post-filter
        for qb in QUERY_BUCKETS if exact else QUERY_BUCKETS[:1]:
            eng.batch_query(queries[:qb], [p] * qb, k)


def _trace_start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


@dataclasses.dataclass
class Prepared:
    """A cell after set-up: the engine built, fitted and warmed."""

    cell: Cell
    dep: Deployment
    prog_pool: list        # the pool as the program's IR
    eng: object
    meter: CompileMeter

    def schedule(self, rate: float, seconds: float, seed: int, stream: str) -> Schedule:
        return self.dep.schedule(rate, seconds, seed, stream)

    def serve(self, sched: Schedule, **kw) -> batcher.Served:
        traffic = self.dep.traffic
        return batcher.serve(
            self.eng.batch_query, sched.queries, [self.prog_pool[i] for i in sched.pred],
            sched.due, self.dep.k, close=sched.seconds, max_batch=int(traffic["max_batch"]),
            max_wait=float(traffic["max_wait_ms"]) * 1e-3, **kw)


def prepare(cell: Cell, seed: int) -> Prepared:
    """Set-up: the deployment (corpus, predicate pool, the engine's build
    and fit), all drawn from the configuration's ``data_seed``, then the
    warm-up: every scan shape of the pool, and a pre-window of the cell's
    own traffic on another stream of the run's ``seed``."""
    meter = CompileMeter()
    t = time.perf_counter()
    dep = deployment(cell)
    ds = int(dep.cfg["data_seed"])
    fit_q, fit_p = _fit_set(dep)
    log(f"setup data+pool: {time.perf_counter() - t:.3f} s "
        f"({dep.x.shape[0]} x {dep.x.shape[1]}, pool {len(dep.pool)})")

    from repro.core import EngineConfig, FilteredANNEngine

    t = time.perf_counter()
    eng = FilteredANNEngine(dep.x, dep.cat.copy(), dep.num.copy(),
                            EngineConfig(seed=datagen.small_seed(ds, "engine"))).build()
    log(f"setup build: {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    eng.fit(fit_q, [to_program(p) for p in fit_p], k=dep.k)
    log(f"setup fit: {time.perf_counter() - t:.3f} s ({len(fit_p)} races)")
    t = time.perf_counter()
    prog_pool = [to_program(p) for p in dep.pool]
    warm(eng, prog_pool, datagen.query_vectors(
        dep.x, dep.qscale, QUERY_BUCKETS[-1], datagen.host_rng(ds, "warm")), dep.k)
    prep = Prepared(cell, dep, prog_pool, eng, meter)
    log_plan_mix(prep)
    prep.serve(prep.schedule(cell.rate_qps, dep.traffic["prewindow_s"], seed, "prewindow"))
    log(f"setup warm-up: {time.perf_counter() - t:.3f} s")
    return prep


def log_plan_mix(prep: Prepared) -> None:
    """Which pool predicates the fitted planner sends to the post-filter, and
    the share of requests they draw: the plan mix a run's work follows."""
    dep = prep.dep
    plans, _ = prep.eng.make_plan_batch(prep.prog_pool, dep.k)
    post = [i for i, pl in enumerate(plans) if any(c.decision == 1 for c in pl.clauses)]
    share = zipf_probs(len(prep.prog_pool), dep.traffic["zipf"])[post].sum()
    sel = {i: float(dep.pool[i].eval(dep.cat, dep.num).mean()) for i in post}
    log(f"plan mix: {len(post)} of {len(plans)} pool predicates post "
        f"({100 * share:.1f} % of requests); rank:selectivity "
        + " ".join(f"{i}:{v:.4f}" for i, v in sel.items()))


def run_cell(spec: Spec, name: str, seed: int, seconds: float, trace: bool,
             t_process: float) -> dict:
    """One run; returns the result object that ``bench/run.py`` prints."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.obs import Tracer

    cell = spec.cell(name)
    device = check_device(cell.chips)
    prep = prepare(cell, seed)
    dep, eng, meter = prep.dep, prep.eng, prep.meter
    sched, sample = draw_window(dep, cell, seed, seconds)
    dep.x = None
    setup_s = time.perf_counter() - t_process

    # ---- the measured window ---------------------------------------------
    tracer = Tracer() if trace else None
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        eng.set_tracer(tracer)
        _trace_start(log_dir)
    n0 = meter.n
    t_w = time.perf_counter()
    served = prep.serve(sched, annotate=TraceAnnotation if trace else None)
    window_s = time.perf_counter() - t_w
    compiles = meter.n - n0
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        eng.set_tracer(None)
        path = devtrace.find_xspace(log_dir)
        reduced = devtrace.reduce_events(devtrace.events_from_xspace(path)) if path else None
        shutil.rmtree(log_dir, ignore_errors=True)
    device["memory_peak_bytes"] = peak_bytes()
    spans = ([{"decision": s.attrs.get("decision"), "n_rows": s.attrs.get("n_rows", 0),
               "n_candidates": s.attrs.get("n_candidates"), "wall_s": s.wall_s}
              for s in tracer.spans() if s.name == "group"] if trace else [])
    log(f"window: {len(served.batches)} batches, {compiles} compiles, "
        f"{served.errors} failed rows, {window_s:.3f} s with drain")
    slow = sorted(served.batches, key=lambda b: b.start - b.end)[:5]
    log("slowest batches (ms: pool ranks): " + "; ".join(
        f"{1e3 * (b.end - b.start):.1f}: {sorted(set(sched.pred[b.rows].tolist()))}"
        for b in slow))

    # ---- free the program, then the reference ----------------------------
    del eng, prep
    gc.collect()
    t = time.perf_counter()
    ref = reference_answers(dep, sched, sample)
    readings = compare(ref, served.ids[sample], ~np.isnan(served.done[sample]),
                       served.exact[sample])
    log(f"reference: {time.perf_counter() - t:.3f} s over {sample.size} requests "
        f"({readings.n_exact} exact-plan, {readings.n_post} approximate)")

    peaks = load_peaks(spec.bench_dir, device["kind"]) if reduced is not None else {}
    view = RunView(cell, sched, served, spans, reduced, window_s, compiles,
                   int(dep.cfg["dim"]), dep.k, peaks, readings, setup_s)
    return assemble(spec, cell, view, readings, device, trace)


@dataclasses.dataclass
class Reference:
    """The reference's answers to the sampled requests, and what comparing
    with them needs."""

    x_dev: object          # the corpus on the device, regenerated from the data seed
    masks: dict            # pool index -> (N,) bool
    pred_of: np.ndarray    # (S,) pool index of each sampled request
    q: np.ndarray          # (S, d) their query vectors
    ids: np.ndarray        # (S, k)
    d: np.ndarray          # (S, k)


def reference_answers(dep: Deployment, sched: Schedule, sample: np.ndarray) -> Reference:
    t = time.perf_counter()
    x_dev = datagen.make_corpus(dep.cfg, int(dep.cfg["data_seed"]))[0]
    pred_of = sched.pred[sample]
    masks = reference.masks_for(dep.pool, dep.cat, dep.num, pred_of)
    q = sched.queries[sample]
    t_masks = time.perf_counter()
    ids, d = reference.topk(x_dev, masks, pred_of, q, dep.k)
    log(f"reference: corpus+masks {t_masks - t:.3f} s, "
        f"top-k {time.perf_counter() - t_masks:.3f} s")
    return Reference(x_dev, masks, pred_of, q, ids, d)


def compare(ref: Reference, ids: np.ndarray, answered: np.ndarray,
            exact: np.ndarray) -> reference.Readings:
    """Answers ``ids`` to the sampled requests against the reference's."""
    d = reference.exact_distances(ref.x_dev, ids, ref.q)
    return reference.score(ids, d, answered, exact, ref.masks, ref.pred_of, ref.ids, ref.d)


def assemble(spec: Spec, cell: Cell, view: RunView, readings: reference.Readings,
             device: dict, trace: bool) -> dict:
    """The result line: with ``trace`` the cell's per-layer metrics, else its
    end-to-end ones, each from its reader ``bench/metrics/<name>.py``."""
    metrics: Dict[str, dict] = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.reader(m.name)(view)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    if trace and view.reduced is not None:
        device["busy_s"] = view.reduced.busy_s
        device["window_s"] = view.window_s
    checks = reference.checks(readings, cell.config["limits"])
    out = {"correct": bool(all(ok for *_, ok in checks)) and view.served.errors == 0,
           "attempted": len(view.sched), "failed": view.served.errors, "metrics": metrics,
           "device": device}
    if trace and view.reduced is not None:
        out["breakdown"] = {"device_ops": [[n, s] for n, s in view.reduced.device_ops],
                            "idle_gaps": [[n, s] for n, s in view.reduced.idle_gaps]}
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim, _ in checks}
    return out
