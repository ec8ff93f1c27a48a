"""Bring-up check: the planned filtered-ANN serving path on one TPU.

Drives the main path once through the entry points a user calls, at a
deployment size: an arXiv-shaped corpus (1,000,000 x 384, mixed label +
range metadata) -> ``FilteredANNEngine.build()`` -> ``fit()`` -> a Poisson
trace of 256 k=10 requests replayed through ``OnlineRuntime``.  Every served
answer is checked against an independent NumPy float64 masked brute force.

    python chip_smoke.py                 # one TPU chip; the last line is the verdict
    python chip_smoke.py --rows 4000     # rehearse every phase at a small size
                                         # (off a TPU it still fails, at the end)
    python chip_smoke.py --four-chips    # four chips: ShardedANNEngine(4 shards)
                                         # vs the one-chip engine, same trace

The last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failed phase or check exits non-zero without it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import EngineConfig, FilteredANNEngine, Or  # noqa: E402
from repro.core.plan import STRATEGY_NAMES  # noqa: E402
from repro.core.trainer import gen_queries  # noqa: E402
from repro.data import make_dataset  # noqa: E402
from repro.data.vectors import DATASETS  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.serve import use_compile_cache  # noqa: E402
from repro.runtime import OnlineRuntime, SchedulerConfig, poisson_trace  # noqa: E402
from repro.serve import ShardedANNEngine  # noqa: E402

K = 10
N_REQUESTS = 256
N_FIT = 40
N_FIT_BROAD = 8        # of N_FIT: broad range filters (see build_engine)
N_POOL = 24
POOL_SEL = (0.01, 0.4)  # target selectivities of the serving pool
N_OR = 4
RECALL_BAR = 0.90      # the paper's recall bar for approximate (post) plans
TIE_RTOL = 1e-4        # a served id this close to the reference's k-th is a tie
CPU_MAX_ROWS = 100_000  # off a TPU, only a rehearsal size runs at all
EXACT = ("pre", "ipre")


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is counted as a compile of ~0 s)."""

    def __init__(self):
        self.seconds, self.n, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@contextlib.contextmanager
def stage(name: str, meter: CompileMeter):
    t0, c0, n0 = time.perf_counter(), meter.seconds, meter.n
    yield
    print(f"stage {name}: {time.perf_counter() - t0!r} s "
          f"(backend compile {meter.seconds - c0!r} s in {meter.n - n0} compiles)",
          flush=True)


def reference_topk(vectors, mask, queries, k):
    """float64 masked brute force: (ids (g, k) -1 padded, dists (g, k) inf
    padded), ascending distance, lower id first on ties."""
    idx = np.flatnonzero(mask)
    g = queries.shape[0]
    q = queries.astype(np.float64)
    q2 = (q * q).sum(1)[:, None]
    cand_d = np.zeros((g, 0))
    cand_i = np.zeros((g, 0), np.int64)
    for s in range(0, idx.size, 1 << 18):
        ic = idx[s:s + (1 << 18)]
        x = vectors[ic].astype(np.float64)
        d = np.maximum(q2 + (x * x).sum(1)[None, :] - 2.0 * q @ x.T, 0.0)
        cand_d = np.concatenate([cand_d, d], axis=1)
        cand_i = np.concatenate([cand_i, np.broadcast_to(ic, d.shape)], axis=1)
        if cand_d.shape[1] > 4 * k:
            keep = np.argpartition(cand_d, 4 * k, axis=1)[:, :4 * k]
            cand_d = np.take_along_axis(cand_d, keep, 1)
            cand_i = np.take_along_axis(cand_i, keep, 1)
    ids = np.full((g, k), -1, np.int64)
    dist = np.full((g, k), np.inf)
    for r in range(g):
        # exact distances of the survivors, then (distance, id) order
        dd = ((vectors[cand_i[r]].astype(np.float64) - q[r]) ** 2).sum(1)
        order = np.lexsort((cand_i[r], dd))[:k]
        ids[r, :order.size] = cand_i[r][order]
        dist[r, :order.size] = dd[order]
    return ids, dist


def check_row(vectors, mask, q, served, ref_ids, ref_d, rtol=TIE_RTOL):
    """(mismatches, hits, n_ref) of one served row against the reference.
    A served id counts as a hit when it passes the predicate and lies within
    ``rtol`` of the reference's k-th distance (so exact ties are hits)."""
    ref_valid = ref_ids[ref_ids >= 0]
    got = served[served >= 0]
    kth = ref_d[len(ref_valid) - 1] if len(ref_valid) else -np.inf
    hits = mism = 0
    for s in got:
        d = ((vectors[s].astype(np.float64) - q.astype(np.float64)) ** 2).sum()
        if mask[s] and d <= kth * (1 + rtol):
            hits += 1
        else:
            mism += 1
    mism += len(got) - len(set(got.tolist()))           # duplicates
    mism += max(0, len(ref_valid) - len(got))            # missing answers
    return mism, min(hits, len(ref_valid)), len(ref_valid)


def plan_label(plan) -> str:
    names = [STRATEGY_NAMES[c.decision] for c in plan.clauses]
    return "dnf" if plan.is_dnf else names[0]


def build_engine(args, meter):
    name = "arxiv"
    paper_n, d, _ = DATASETS[name]
    with stage("data", meter):
        ds = make_dataset(name, scale=str(args.rows), seed=args.seed)
    print(f"dataset: {name} rows={ds.vectors.shape[0]} d={ds.vectors.shape[1]} "
          f"(published {paper_n} x {d})")
    corpus_gb = paper_n * d * 4 / 1e9
    print(f"reduced: rows {paper_n:,} -> {args.rows:,}: the pre-filter and indexed "
          f"pre-filter executors each keep the corpus on the device and every full "
          f"masked scan pads a copy of it, 3 x {corpus_gb:.2f} GB of the 16 GB HBM "
          f"at {paper_n:,} rows; and the float64 reference scan grows with N",
          flush=True)
    with stage("build", meter):
        eng = FilteredANNEngine(ds.vectors, ds.cat, ds.num,
                                EngineConfig(seed=args.seed)).build()
    # the planner trains on the mix it serves: predicates drawn like the
    # serving pool's, plus broad range filters (25-80 % pass, where a full
    # masked scan loses to the post-filter), which the pool's generator
    # reaches only now and then (label and mixed predicates undershoot)
    tq, tp, _ = gen_queries(ds.vectors, ds.cat, ds.num, N_FIT - N_FIT_BROAD,
                            kinds=ds.filter_kinds, sel_range=POOL_SEL,
                            seed=args.seed + 1)
    bq, bp, _ = gen_queries(ds.vectors, ds.cat, ds.num, N_FIT_BROAD,
                            kinds=("range",), sel_range=(0.25, 0.8),
                            seed=args.seed + 5)
    tq, tp = np.concatenate([tq, bq]), list(tp) + list(bp)
    with stage("fit", meter):
        eng.fit(tq, tp, k=K)
    fit_plans, _ = eng.make_plan_batch(tp, k=K)
    learned = [plan_label(p) for p in fit_plans]
    print("planner on its training predicates:",
          {x: learned.count(x) for x in sorted(set(learned))})
    qs, preds, _ = gen_queries(ds.vectors, ds.cat, ds.num, N_POOL,
                               kinds=ds.filter_kinds, sel_range=POOL_SEL,
                               seed=args.seed + 2)
    preds = list(preds) + [Or((preds[2 * i], preds[2 * i + 1])) for i in range(N_OR)]
    qs = np.concatenate([qs, qs[:N_OR]])
    trace = poisson_trace(qs, preds, N_REQUESTS, rate=2000.0, k=K, seed=args.seed + 3)
    # the trace fixes the predicate mix; each request gets a query vector of
    # its own, so recall averages over 256 queries, not the pool's 28
    qv, _, _ = gen_queries(ds.vectors, ds.cat, ds.num, N_REQUESTS,
                           kinds=ds.filter_kinds, seed=args.seed + 4)
    for r in trace:
        r.query = qv[r.rid]
    return ds, eng, trace


def pallas_in_served_path(n: int, d: int) -> bool:
    """The served scan shape lowers to a Mosaic kernel (``tpu_custom_call``)."""
    f32 = jnp.float32
    compiled = ops.masked_l2_topk.lower(
        jax.ShapeDtypeStruct((8, d), f32), jax.ShapeDtypeStruct((n, d), f32),
        jax.ShapeDtypeStruct((n,), jnp.bool_), k=K).compile()
    return "tpu_custom_call" in compiled.as_text()


def run_one_chip(args, meter, failures):
    ds, eng, trace = build_engine(args, meter)
    ops.reset_dispatch_stats()
    with stage("serve", meter):
        rep = OnlineRuntime(eng, SchedulerConfig(max_batch=64)).run_trace(trace)
    served_dispatch = ops.dispatch_counts()
    print(f"served: {len(rep.results)} requests in {len(rep.batches)} batches")

    plans, clause_counts = {}, {}
    for r in trace:
        plan = rep.results[r.rid].plan
        plans[r.rid] = plan
        for c in plan.clauses:
            name = STRATEGY_NAMES[c.decision]
            clause_counts[name] = clause_counts.get(name, 0) + 1
    labels = [plan_label(plans[r.rid]) for r in trace]
    print("plan counts (requests):", {x: labels.count(x) for x in sorted(set(labels))})
    print("plan counts (clauses):", dict(sorted(clause_counts.items())))

    with stage("reference", meter):
        by_pred: dict = {}
        for r in trace:
            by_pred.setdefault(r.pred, []).append(r)
        per_label: dict = {}
        exact_mismatch = 0
        for pred, reqs in by_pred.items():
            mask = pred.eval(ds.cat, ds.num)
            q = np.stack([r.query for r in reqs])
            ref_i, ref_d = reference_topk(ds.vectors, mask, q, K)
            for j, r in enumerate(reqs):
                plan = plans[r.rid]
                mism, hits, n_ref = check_row(ds.vectors, mask, q[j], rep.ids(r.rid),
                                              ref_i[j], ref_d[j])
                exact = all(STRATEGY_NAMES[c.decision] in EXACT for c in plan.clauses)
                if exact:
                    exact_mismatch += mism
                h, n = per_label.get(plan_label(plan), (0, 0))
                per_label[plan_label(plan)] = (h + hits, n + n_ref)
    recall = {x: h / max(n, 1) for x, (h, n) in sorted(per_label.items())}
    print("recall@10 per plan:", recall)
    print(f"exact-plan mismatches vs float64 reference: {exact_mismatch}")
    print("dispatch_counts (serve phase):", served_dispatch)

    if exact_mismatch:
        failures.append(f"{exact_mismatch} exact-plan ids disagree with the reference")
    if "post" not in recall:
        failures.append("no request was served by the post-filter plan")
    elif recall["post"] < RECALL_BAR:
        failures.append(f"post recall@10 {recall['post']!r} < {RECALL_BAR}")
    if not clause_counts.get("ipre"):
        failures.append(f"no request was served by the indexed pre-filter plan "
                        f"(clauses per plan: {clause_counts})")
    if not served_dispatch.get("fused_masked_topk"):
        failures.append(f"the served path dispatched no fused masked top-k "
                        f"(dispatches: {served_dispatch})")
    if jax.default_backend() == "tpu":
        n, d = ds.vectors.shape
        has_kernel = pallas_in_served_path(n, d)
        print(f"pallas kernel in served scan (tpu_custom_call): {has_kernel}")
        if not has_kernel:
            failures.append("the served scan did not lower to the Pallas kernel")


def run_four_chips(args, meter, failures):
    if len(jax.devices()) != 4:
        failures.append(f"--four-chips needs 4 devices, found {len(jax.devices())}")
        return
    ds, eng, trace = build_engine(args, meter)
    with stage("shard", meter):
        sharded = ShardedANNEngine(eng, n_shards=4)
    with stage("serve one-chip", meter):
        rep1 = OnlineRuntime(eng, SchedulerConfig(max_batch=64)).run_trace(trace)
    with stage("serve 4 shards", meter):
        rep4 = OnlineRuntime(sharded, SchedulerConfig(max_batch=64)).run_trace(trace)
    n_exact = differ = 0
    for r in trace:
        plan = rep1.results[r.rid].plan
        if all(STRATEGY_NAMES[c.decision] in EXACT for c in plan.clauses):
            n_exact += 1
            differ += int(not np.array_equal(rep1.ids(r.rid), rep4.ids(r.rid)))
    print(f"exact-plan requests: {n_exact}; ids differing 4 shards vs one chip: {differ}")
    q = np.zeros((8, ds.vectors.shape[1]), np.float32)
    devices = []
    for sh in sharded.shards:
        n_local = sh.pre_exec.vectors.shape[0]
        _, ids = ops.fused_masked_topk(q, sh.pre_exec.scan_vectors,
                                       np.ones(n_local, bool), K)
        dev = next(iter(ids.devices()))
        devices.append(dev)
        print(f"shard {sh.shard_id}: {n_local} rows, scan output on {dev}")
    if differ or not n_exact:
        failures.append(f"{differ} of {n_exact} exact-plan requests differ across shardings")
    if len(set(devices)) != 4:
        failures.append(f"shard outputs on {len(set(devices))} distinct devices, not 4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard path and the one-chip engine it is "
                         "compared with")
    args = ap.parse_args(argv)

    dev0 = jax.devices()[0]
    platform = dev0.platform
    if platform != "tpu" and args.rows > CPU_MAX_ROWS:
        print(f"FAIL: no TPU (platform {platform}); off a TPU only --rows "
              f"<= {CPU_MAX_ROWS} rehearses the phases", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache(ROOT)
    print(f"compile cache: {cache_dir}")
    meter = CompileMeter()
    failures: list = []
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips(args, meter, failures)
    else:
        run_one_chip(args, meter, failures)
    stats = dev0.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"total: {time.perf_counter() - t0!r} s; backend compile {meter.seconds!r} s "
          f"in {meter.n} compiles; persistent cache hits {meter.hits}, "
          f"misses {meter.misses}")
    if platform != "tpu":
        failures.insert(0, f"not a TPU: platform {platform}")
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev0.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
