"""Kernel micro-benchmarks: fused masked-L2 vs. reference (CPU interpret mode
measures correctness-path speed only; the BlockSpec structure targets TPU).

Also reports the analytic VMEM block footprint per tile.  That is a lower
bound: whether the kernel fits the chip is what the ahead-of-time compile in
``tests/test_tpu_compile.py`` says.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import vmem_working_set
from repro.index.flat import l2_topk


def bench_xla_scan(n=65536, d=128, b=64, k=10, iters=3):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (b, d)).astype(np.float32))
    x = jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32))
    mask = jnp.asarray(rng.random(n) < 0.5)
    l2_topk(q, x, k, mask)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        l2_topk(q, x, k, mask)[0].block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    return dt


def run():
    rows = []
    for d in (128, 256, 512):
        ws = vmem_working_set(d)
        rows.append({
            "kernel": f"masked_l2_d{d}",
            "vmem_bytes": ws["total"],
        })
    dt = bench_xla_scan()
    rows.append({"kernel": "masked_l2_xla_base_us", "vmem_bytes": round(dt * 1e6, 1)})
    return rows


def main():
    print("kernel,vmem_bytes_or_us")
    for r in run():
        print(f"{r['kernel']},{r['vmem_bytes']}")


if __name__ == "__main__":
    main()
