"""Exact (brute-force) KNN with optional predicate masking.

This is the pre-filtering executor's search engine (paper §4.1 implements
pre-filtering with brute-force KNN) and the ground-truth oracle for recall
measurement.  On TPU the masked dense scan is the idiomatic form; the fused
Pallas kernel in :mod:`repro.kernels` implements the same contract and is
validated against :func:`l2_topk`.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["l2_topk", "chunked_masked_topk", "FlatIndex"]


@partial(jax.jit, static_argnames=("k",))
def l2_topk(
    queries: jax.Array,
    corpus: jax.Array,
    k: int,
    mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k by squared L2 distance.

    queries: (B, d), corpus: (N, d), mask: optional (N,) bool — True = passes
    the predicate.  Returns (dists (B,k), idx (B,k)); masked-out entries get
    +inf distance and index -1.
    """
    q2 = jnp.sum(queries * queries, axis=1, keepdims=True)   # (B, 1)
    x2 = jnp.sum(corpus * corpus, axis=1)                    # (N,)
    # HIGHEST: on a TPU the default f32 matmul runs in bf16 passes, which
    # reorders near neighbours; this is an exact path
    qx = jnp.matmul(queries, corpus.T, precision=jax.lax.Precision.HIGHEST)
    d2 = q2 + x2[None, :] - 2.0 * qx                         # (B, N)
    d2 = jnp.maximum(d2, 0.0)
    if mask is not None:
        d2 = jnp.where(mask[None, :], d2, jnp.inf)
    neg, idx = jax.lax.top_k(-d2, k)
    dists = -neg
    idx = jnp.where(jnp.isinf(dists), -1, idx)
    return dists, idx


@partial(jax.jit, static_argnames=("k", "chunk"))
def chunked_masked_topk(
    queries: jax.Array,
    corpus: jax.Array,
    k: int,
    mask: Optional[jax.Array] = None,
    chunk: int = 65536,
) -> Tuple[jax.Array, jax.Array]:
    """Streaming variant: scans the corpus in chunks with a running top-k,
    never materialising the (B, N) distance matrix.  This is the XLA
    realisation of the Pallas kernel's loop structure, usable at corpus
    sizes where (B, N) would not fit."""
    n, d = corpus.shape
    b = queries.shape[0]
    pad = (-n) % chunk
    if pad:
        corpus = jnp.pad(corpus, ((0, pad), (0, 0)))
        mask_full = jnp.pad(
            mask if mask is not None else jnp.ones(n, bool), (0, pad), constant_values=False
        )
    else:
        mask_full = mask if mask is not None else jnp.ones(n, bool)
    n_chunks = corpus.shape[0] // chunk
    xs = corpus.reshape(n_chunks, chunk, d)
    ms = mask_full.reshape(n_chunks, chunk)
    q2 = jnp.sum(queries * queries, axis=1, keepdims=True)

    def step(carry, inp):
        best_d, best_i = carry                                # (B,k), (B,k)
        x, m, start = inp
        x2 = jnp.sum(x * x, axis=1)
        qx = jnp.matmul(queries, x.T, precision=jax.lax.Precision.HIGHEST)
        d2 = jnp.maximum(q2 + x2[None, :] - 2.0 * qx, 0.0)
        d2 = jnp.where(m[None, :], d2, jnp.inf)
        ids = start + jnp.arange(chunk)
        cat_d = jnp.concatenate([best_d, d2], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, (b, chunk))], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, k)
        return (-neg, jnp.take_along_axis(cat_i, pos, axis=1)), None

    starts = jnp.arange(n_chunks) * chunk
    init = (jnp.full((b, k), jnp.inf), jnp.full((b, k), -1, jnp.int32))
    (best_d, best_i), _ = jax.lax.scan(step, init, (xs, ms, starts))
    best_i = jnp.where(jnp.isinf(best_d), -1, best_i)
    return best_d, best_i


class FlatIndex:
    """Thin object wrapper so executors share one index interface."""

    def __init__(self, vectors: np.ndarray):
        self.vectors = jnp.asarray(vectors, jnp.float32)
        self.n, self.dim = vectors.shape

    def build(self) -> "FlatIndex":
        return self  # nothing to build

    def search(self, queries, k: int, mask=None):
        q = jnp.asarray(queries, jnp.float32)
        if self.n * q.shape[0] <= 64_000_000:
            return l2_topk(q, self.vectors, k, None if mask is None else jnp.asarray(mask))
        return chunked_masked_topk(
            q, self.vectors, k, None if mask is None else jnp.asarray(mask)
        )
