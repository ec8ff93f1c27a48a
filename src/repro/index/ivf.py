"""IVF-Flat index — the global index behind the post-filtering executor.

The paper's post-filtering uses "a global ANN index built at initialization";
on TPU the idiomatic global index is IVF (probe-list scans are dense matmuls;
graph indexes serialise the MXU — DESIGN.md §2).  Two search paths share one
semantics:

* ``search``     — numpy/JAX hybrid, contiguous sorted lists, fast on CPU;
  used by benchmarks.
* ``search_jax`` — fully jit-able padded-list path (vmap over queries), the
  TPU-target form used in the distributed engine and the dry-run.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .kmeans import kmeans

__all__ = ["IVFIndex"]


class IVFIndex:
    def __init__(self, vectors: np.ndarray, n_lists: Optional[int] = None, seed: int = 0):
        self.vectors_np = np.asarray(vectors, np.float32)
        self.n, self.dim = vectors.shape
        # clamp to the corpus size: kmeans cannot seed more centroids than
        # points (tiny corpora/shards otherwise crash the build)
        self.n_lists = min(n_lists or max(16, int(np.sqrt(self.n))), self.n)
        self.seed = seed
        self.built = False

    # ------------------------------------------------------------------
    def build(self, iters: int = 8) -> "IVFIndex":
        c, a = kmeans(self.vectors_np, self.n_lists, iters=iters, seed=self.seed)
        self.centroids = c                                   # (L, d)
        order = np.argsort(a, kind="stable")
        self.sorted_ids = order.astype(np.int32)             # (N,)
        self.sorted_vecs = self.vectors_np[order]            # (N, d) contiguous per list
        counts = np.bincount(a, minlength=self.n_lists)
        self.list_counts = counts.astype(np.int64)           # (L,)
        self.offsets = np.zeros(self.n_lists + 1, np.int64)
        np.cumsum(counts, out=self.offsets[1:])
        # per-row squared norms of the sorted layout: the batched search
        # computes distances in dot form (q2 + x2 - 2qx, one BLAS call per
        # row) instead of per-row difference loops
        self.sorted_sq = np.einsum(
            "nd,nd->n", self.sorted_vecs, self.sorted_vecs
        ).astype(np.float32)
        # padded layout for the jit path
        self.max_list = int(counts.max())
        padded = np.full((self.n_lists, self.max_list), -1, np.int32)
        for l in range(self.n_lists):
            seg = self.sorted_ids[self.offsets[l] : self.offsets[l + 1]]
            padded[l, : seg.size] = seg
        self.padded_ids = padded
        self._centroids_j = jnp.asarray(c)
        self._vecs_j = jnp.asarray(self.vectors_np)
        self._padded_j = jnp.asarray(padded)
        self.built = True
        return self

    # ------------------------------------------------------------------
    # CPU benchmark path: contiguous gathered blocks
    # ------------------------------------------------------------------
    def search(
        self,
        queries: np.ndarray,
        k: int,
        nprobe: int = 8,
        mask: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (dists (B,k), ids (B,k)); see :meth:`search_scored`."""
        return self.search_scored(queries, k, nprobe=nprobe, mask=mask)[:2]

    def search_scored(
        self,
        queries: np.ndarray,
        k: int,
        nprobe: int = 8,
        mask: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Returns (dists (B,k), ids (B,k), n_scored); unfilled slots have
        id -1/inf, and ``n_scored`` counts the corpus vectors scored (the
        probed lists' sizes summed over the rows).
        ``mask`` (N,) restricts results to passing points (applied DURING the
        scan — this is what post-filtering calls with mask=None and what the
        engine's fused path uses directly).

        Vectorised across rows: ragged probe segments expand into one
        right-padded (B, C) candidate matrix; query-candidate dot products
        come from one GEMM per probed LIST (each list is a fixed contiguous
        slice of the sorted layout — no per-row candidate gather), shared by
        all rows probing that list; then dot-form distance assembly against
        precomputed ``sorted_sq`` and one batched argpartition.

        Per-row results are IDENTICAL whether a row is searched alone or
        inside any batch — the invariant the batched serving path's
        exactness guarantee rests on.  The per-list GEMM keeps this despite
        BLAS: the left operand is the same memory every time, and the query
        block is padded to a multiple of 8 columns, where sgemm's per-column
        reduction is independent of column position and count (the N=1
        sgemv path, which IS numerically different, is never taken).
        """
        assert self.built
        q = np.asarray(queries, np.float32)
        b = q.shape[0]
        nprobe = min(nprobe, self.n_lists)
        # bound the (B, C) candidate workspace (~33 bytes/lane across the
        # index/valid/dots/distance/key arrays): row results are
        # composition-independent (see below), so chunking the batch is
        # exact, and the per-row transient stays O(nprobe * max_list)
        worst_c = nprobe * self.max_list
        if b > 1 and b * worst_c > 8_000_000:
            chunk = max(1, 8_000_000 // max(worst_c, 1))
            parts = [
                self.search_scored(q[s : s + chunk], k, nprobe=nprobe, mask=mask)
                for s in range(0, b, chunk)
            ]
            return (
                np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]),
                sum(p[2] for p in parts),
            )
        # dispatch ledger for the obs layer: one record per actual scan
        # (chunked calls record in the leaves, not the splitting parent)
        from ..kernels.ops import record_dispatch

        # query -> centroid distances.  Same fixed-shape GEMM discipline as
        # the list scans below — every call is (L, d) @ (d, 8) regardless of
        # batch size, so probe selection is batch-invariant too.
        dots_c = np.empty((b, self.n_lists), np.float32)
        qcols_c = np.zeros((q.shape[1], 8), np.float32)
        for s in range(0, b, 8):
            e = min(b, s + 8)
            qcols_c[:] = 0.0
            qcols_c[:, : e - s] = q[s:e].T
            dots_c[s:e] = (self.centroids @ qcols_c).T[: e - s]
        qc = (
            (q * q).sum(1, keepdims=True)
            + (self.centroids * self.centroids).sum(1)[None, :]
            - 2.0 * dots_c
        )
        probes = np.argpartition(qc, nprobe - 1, axis=1)[:, :nprobe]    # (B, nprobe)
        out_d = np.full((b, k), np.inf, np.float32)
        out_i = np.full((b, k), -1, np.int32)
        counts = self.list_counts[probes]                               # (B, nprobe)
        totals = counts.sum(1)                                          # (B,)
        c = int(totals.max()) if b else 0
        if c == 0:
            record_dispatch("ivf_search")
            return out_d, out_i, 0
        # ragged probe segments -> right-padded (B, C) sorted-row indices,
        # preserving per-row segment order (flat repeat/cumsum construction,
        # O(total candidates) memory)
        counts_flat = counts.ravel()
        t = int(counts_flat.sum())
        seg_rep = np.repeat(np.arange(counts_flat.size), counts_flat)
        pos_in_seg = np.arange(t) - np.repeat(
            np.cumsum(counts_flat) - counts_flat, counts_flat
        )
        cand_flat = self.offsets[probes].ravel()[seg_rep] + pos_in_seg
        row_of = np.repeat(np.arange(b), totals)
        pos_in_row = np.arange(t) - np.repeat(np.cumsum(totals) - totals, totals)
        rows_idx = np.zeros((b, c), np.int64)
        valid = np.zeros((b, c), bool)
        rows_idx[row_of, pos_in_row] = cand_flat
        valid[row_of, pos_in_row] = True
        ids = self.sorted_ids[rows_idx]                                 # (B, C)
        if mask is not None:
            valid &= mask[ids]
        # one GEMM per probed list, shared by every row probing it
        seg_start = np.cumsum(counts, axis=1) - counts                  # (B, nprobe)
        by_list: dict = {}
        for r in range(b):
            for s in range(nprobe):
                by_list.setdefault(int(probes[r, s]), []).append((r, s))
        dots = np.empty((b, c), np.float32)
        qcols = np.zeros((q.shape[1], 8), np.float32)
        for l, pairs in by_list.items():
            lo, hi = self.offsets[l], self.offsets[l + 1]
            if hi <= lo:
                continue
            a_l = self.sorted_vecs[lo:hi]                               # fixed view
            # every GEMM is exactly (len_l, d) @ (d, 8): a fixed shape per
            # list regardless of how many rows probe it, because sgemm
            # results are column-stable within one shape but NOT across
            # different column counts
            for c0 in range(0, len(pairs), 8):
                grp = pairs[c0 : c0 + 8]
                qcols[:] = 0.0
                qcols[:, : len(grp)] = q[[r for r, _ in grp]].T
                d_l = a_l @ qcols                                       # (len_l, 8)
                for j, (r, s) in enumerate(grp):
                    p0 = seg_start[r, s]
                    dots[r, p0 : p0 + (hi - lo)] = d_l[:, j]
        q2 = (q * q).sum(1)
        # padded lanes of `dots` are uninitialised (masked out below) — they
        # may hold garbage that overflows in the arithmetic; that's expected
        with np.errstate(over="ignore", invalid="ignore"):
            d2 = self.sorted_sq[rows_idx] + q2[:, None] - 2.0 * dots
        d2 = np.where(valid, np.maximum(d2, 0.0), np.inf)
        # canonical top-k: compose (distance bits, candidate position) into
        # one int64 key.  Non-negative f32 bit patterns sort like the floats,
        # so equal distances break ties by position — making BOTH the
        # boundary pick and the within-tie order independent of the row's
        # padded width (which varies with batch composition; distances tie
        # often on integer-valued corpora)
        key = (d2.view(np.int32).astype(np.int64) << 32) | np.arange(
            c, dtype=np.int64
        )[None, :]
        kk = min(k, c)
        sel = np.argpartition(key, kk - 1, axis=1)[:, :kk]
        order = np.argsort(np.take_along_axis(key, sel, axis=1), axis=1)
        sel = np.take_along_axis(sel, order, axis=1)
        sd = np.take_along_axis(d2, sel, axis=1)
        si = np.take_along_axis(ids, sel, axis=1)
        fin = np.isfinite(sd)
        out_d[:, :kk] = np.where(fin, sd, np.inf)
        out_i[:, :kk] = np.where(fin, si, -1)
        record_dispatch("ivf_search")
        return out_d, out_i, t

    # ------------------------------------------------------------------
    # TPU-target path: fixed shapes, jit + vmap
    # ------------------------------------------------------------------
    @partial(jax.jit, static_argnames=("self", "k", "nprobe"))
    def search_jax(
        self,
        queries: jax.Array,
        k: int,
        nprobe: int = 8,
        mask: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        assert self.built
        nprobe = min(nprobe, self.n_lists)
        c = self._centroids_j
        x = self._vecs_j
        q2 = jnp.sum(queries**2, axis=1, keepdims=True)
        qc = q2 + jnp.sum(c**2, 1)[None, :] - 2.0 * queries @ c.T
        _, probes = jax.lax.top_k(-qc, nprobe)              # (B, nprobe)

        def per_query(qv, pl):
            ids = self._padded_j[pl].reshape(-1)            # (nprobe*max_list,)
            valid = ids >= 0
            cand = x[jnp.maximum(ids, 0)]                   # (C, d)
            d2 = jnp.sum((cand - qv[None, :]) ** 2, axis=1)
            if mask is not None:
                valid = valid & mask[jnp.maximum(ids, 0)]
            d2 = jnp.where(valid, d2, jnp.inf)
            neg, pos = jax.lax.top_k(-d2, k)
            return -neg, jnp.where(jnp.isinf(-neg), -1, ids[pos])

        return jax.vmap(per_query)(queries, probes)
