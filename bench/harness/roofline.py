"""Peaks of each device, and the least work of the served kernels.

The masked top-k scan's work is the least that any implementation of it must
do, whatever the program runs: read every row that passes the predicate
once, plus the queries and the answers, and compute each passing row's dot
product with each query.  Padding rows, pow2 buckets, and full-corpus scans
of a selective predicate are waste, not work, so they lower the share.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Tuple

F32 = 4
ID = 4


def load_peaks(bench_dir: Path, device_kind: str) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``; an unknown device
    is an error, never a default."""
    with open(Path(bench_dir) / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def scan_work(n_queries: int, n_candidates: int, dim: int, k: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one masked top-k group: ``n_queries`` queries over
    the ``n_candidates`` rows that pass, returning ``k`` (distance, id) pairs
    per query."""
    flops = 2.0 * n_queries * n_candidates * dim
    nbytes = float(n_candidates * dim * F32 + n_queries * dim * F32
                   + n_queries * k * (F32 + ID))
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flop = flops / (peaks["bf16_tflops"] * 1e12)
    t_mem = nbytes / (peaks["hbm_gbps"] * 1e9)
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
