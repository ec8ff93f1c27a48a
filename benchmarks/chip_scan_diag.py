"""Readings behind three choices of the exact scan and the post-filter, at
``chip_smoke.py``'s shape (the arXiv shape, 1,000,000 x 384, mixed label +
range metadata):

1. precision: an XLA masked scan at DEFAULT and at HIGHEST matmul precision
   against ``chip_smoke``'s float64 reference, over 64 (query, predicate)
   pairs at k=10, counted by id equality and by ``chip_smoke.check_row`` at
   several tie tolerances (on a TPU, DEFAULT runs f32 matmuls in bf16);
2. scan time: median ms per call of the served Pallas scan
   (``kernels.ops.masked_l2_topk``), the same with the corpus already padded
   to the kernel's row tile, and the XLA ``l2_topk``, at B=8 and B=64, with
   the corpus resident on the device;
3. post-filter recall@10 over the same 64 pairs against the first probe
   count: 8 / 16 / 32 / 64 lists, and the executor's own floor.

    python -m benchmarks.chip_scan_diag                 # repo root, one TPU
    JAX_PLATFORMS=cpu python -m benchmarks.chip_scan_diag --rows 4000

Off a TPU only a rehearsal size runs, and the Pallas scan runs in interpret
mode, so its times say nothing about the chip.
"""
from __future__ import annotations

import argparse
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import chip_smoke  # repo root; puts src/ on the path
from repro.core.executors import PostFilterExec
from repro.core.trainer import gen_queries
from repro.data import make_dataset
from repro.index.flat import l2_topk
from repro.index.ivf import IVFIndex
from repro.kernels import ops
from repro.kernels.masked_l2 import TN

K = 10
N_PAIRS = 64
TOLS = (1e-4, 1e-5, 1e-6, 0.0)
PROBES = (8, 16, 32, 64)


@functools.partial(jax.jit, static_argnames=("k",))
def l2_topk_default(queries, corpus, k, mask):
    """``l2_topk`` with its matmul at DEFAULT precision."""
    q2 = jnp.sum(queries * queries, axis=1, keepdims=True)
    x2 = jnp.sum(corpus * corpus, axis=1)
    d2 = jnp.maximum(q2 + x2[None, :] - 2.0 * queries @ corpus.T, 0.0)
    d2 = jnp.where(mask[None, :], d2, jnp.inf)
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, jnp.where(jnp.isinf(neg), -1, idx)


def median_ms(fn, n_calls: int) -> float:
    jax.block_until_ready(fn())                      # compile and warm
    ts = []
    for _ in range(n_calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e3


def precision(x, xd, masks, qs, refs):
    pad = np.zeros((8, x.shape[1]), np.float32)
    for name, scan in (("DEFAULT", l2_topk_default), ("HIGHEST", l2_topk)):
        missed = 0
        by_tol = dict.fromkeys(TOLS, 0)
        for q, m, (ri, rd) in zip(qs, masks, refs):
            pad[0] = q
            _, ids = scan(pad, xd, K, jnp.asarray(m))
            served = np.asarray(ids)[0]
            missed += len(set(ri[ri >= 0].tolist()) - set(served.tolist()))
            for tol in TOLS:
                by_tol[tol] += chip_smoke.check_row(x, m, q, served, ri, rd, rtol=tol)[0]
        print(f"precision {name}: {missed} reference ids missed of "
              f"{sum(int((ri >= 0).sum()) for ri, _ in refs)}; check_row mismatches "
              + ", ".join(f"rtol={t!r}: {v}" for t, v in by_tol.items()), flush=True)


def scan_times(x, xd, mask, qs):
    n = x.shape[0]
    md = jnp.asarray(mask)
    xpad = jnp.pad(xd, ((0, (-n) % TN), (0, 0)))
    mpad = jnp.pad(md, (0, (-n) % TN))
    for b, n_calls in ((8, 32), (64, 8)):
        qb = jnp.asarray(qs[:b])
        runs = {
            "pallas": lambda: ops.masked_l2_topk(qb, xd, md, K),
            "pallas_prepadded": lambda: ops.masked_l2_topk(qb, xpad, mpad, K),
            "xla_l2_topk": lambda: l2_topk(qb, xd, K, md),
        }
        ids = {name: np.asarray(fn()[1]) for name, fn in runs.items()}
        for name, fn in runs.items():
            differ = int((ids[name] != ids["xla_l2_topk"]).sum())
            print(f"scan B={b} {name}: median {median_ms(fn, n_calls)!r} ms over "
                  f"{n_calls} calls (N={n}); ids differing from xla_l2_topk: {differ}",
                  flush=True)


def post_recall(x, cat, num, preds, masks, qs, refs, seed):
    t0 = time.perf_counter()
    ivf = IVFIndex(x, None, seed=seed).build()
    print(f"ivf build {time.perf_counter() - t0!r} s, lists {ivf.n_lists}", flush=True)
    execs = []
    for p in PROBES:
        px = PostFilterExec(ivf, cat, num, nprobe0=p)
        px.MIN_PROBE_FRAC = 0.0                   # probe exactly p lists first
        execs.append((f"nprobe0={p}", px))
    px = PostFilterExec(ivf, cat, num)
    execs.append((f"default (first probe {px.initial_params(K)[1]})", px))
    for name, px in execs:
        rec, ms = [], []
        for q, pred, m, (ri, rd) in zip(qs, preds, masks, refs):
            t0 = time.perf_counter()
            _, ids, _ = px.search_rows(q[None], [pred], K, [float(m.mean())])
            ms.append((time.perf_counter() - t0) * 1e3)
            _, hits, n_ref = chip_smoke.check_row(x, m, q, ids[0], ri, rd)
            rec.append(hits / max(n_ref, 1))
        rec = np.array(rec)
        print(f"post {name}: recall@10 {float(rec.mean())!r}, min {float(rec.min())!r}, "
              f"share<{chip_smoke.RECALL_BAR} "
              f"{float((rec < chip_smoke.RECALL_BAR).mean())!r}, "
              f"median {float(np.median(ms))!r} ms/query", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and args.rows > chip_smoke.CPU_MAX_ROWS:
        print(f"no TPU (platform {dev.platform}); off a TPU only --rows <= "
              f"{chip_smoke.CPU_MAX_ROWS} runs", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    ds = make_dataset("arxiv", scale=str(args.rows), seed=args.seed)
    x = ds.vectors
    qs, preds, _ = gen_queries(x, ds.cat, ds.num, N_PAIRS, kinds=ds.filter_kinds,
                               sel_range=(0.01, 0.4), seed=args.seed + 2)
    masks = [p.eval(ds.cat, ds.num) for p in preds]
    t0 = time.perf_counter()
    refs = [tuple(a[0] for a in chip_smoke.reference_topk(x, m, q[None], K))
            for q, m in zip(qs, masks)]
    print(f"rows {x.shape[0]} d {x.shape[1]}; float64 reference "
          f"{time.perf_counter() - t0!r} s", flush=True)
    xd = jax.device_put(x, dev)
    precision(x, xd, masks, qs, refs)
    scan_times(x, xd, masks[int(np.argmax([m.mean() for m in masks]))], qs)
    post_recall(x, ds.cat, ds.num, preds, masks, qs, refs, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
