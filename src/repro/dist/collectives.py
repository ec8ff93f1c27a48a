"""Compressed cross-shard collectives (int8 allreduce + top-k merge).

Gradient allreduce dominates step time once the mesh spans hosts, and the
payload is the full parameter footprint per step.  ``compressed_psum``
cuts the wire bytes ~4x by quantising each shard to int8 with one fp32
scale per shard before the collective:

    scale_i = max|x_i| / 127          (per shard i)
    q_i     = round(x_i / scale_i)    in [-127, 127], int8
    mean    = (1/n) * sum_i q_i * scale_i

The wire format is the int8 payload plus one scalar per shard (a ring
all-gather of int8 moves the same bytes as reduce-scatter + all-gather at
int8; a raw psum cannot sum values carrying different scales).  Here the
reduction is expressed as a ``psum`` of the locally *dequantised* payload
— identical arithmetic, and it lets shard_map's replication checker infer
the replicated output; a production kernel would move the int8 bytes.
The result is the *mean* over the axis (the gradient convention), not the
sum.

Plain quantisation biases training: the per-step rounding error
``e_i = x_i - q_i*scale_i`` (|e_i| <= scale_i/2) is lost each round.
``psum_with_error_feedback`` carries it instead (Seide et al. 2014;
Karimireddy et al. 2019 "EF-SGD"):

    c_t   = g_t + e_{t-1}        # add residual before quantising
    out_t = mean_i(Q(c_t))       # compressed reduce of the compensated grad
    e_t   = c_t - Q(c_t)         # local residual, carried to t+1

Telescoping: sum_t Q(c_t) = sum_t g_t + e_0 - e_T, so the accumulated
update converges to the exact mean at O(scale/T) — quantisation error is
deferred, never dropped, which is the property the optimizer needs.

All entry points are ``jax.shard_map``-compatible: call them from inside
a shard-mapped function with the mesh axis name.  ``merge_topk`` is the
host-side counterpart used by the sharded ANN query path: each corpus
shard returns its local top-k and the reduction is a concat + re-top-k
(exact, associative — merging shard-local top-k's loses nothing because
any global top-k element is in its own shard's top-k).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["compressed_psum", "psum_with_error_feedback", "merge_topk",
           "merge_topk_unique"]


def _quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-shard int8 quantisation: (q, scale), x ~= q * scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _reduced_mean(q: jax.Array, scale: jax.Array, axis_name: str) -> jax.Array:
    deq = q.astype(jnp.float32) * scale              # shard's int8 contribution
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    return jax.lax.psum(deq, axis_name) / n


def compressed_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """int8-quantised allreduce-mean over ``axis_name``.

    Error is bounded by the largest shard's quantisation step:
    |out - mean| <= max_i(scale_i) / 2.  Use inside ``jax.shard_map``.
    """
    q, scale = _quantize_int8(x)
    return _reduced_mean(q, scale, axis_name)


def psum_with_error_feedback(
    g: jax.Array, err: jax.Array, axis_name: str
) -> Tuple[jax.Array, jax.Array]:
    """Compressed allreduce-mean with a carried quantisation residual.

    Returns ``(mean, new_err)``; feed ``new_err`` back on the next call so
    repeated reductions converge to the exact mean (see module docstring).
    The residual keeps a leading singleton shard axis so it round-trips
    through ``shard_map`` with ``out_specs=P(axis)`` unchanged.
    """
    comp = g + err
    q, scale = _quantize_int8(comp)
    new_err = comp - q.astype(jnp.float32) * scale   # includes clip error
    return _reduced_mean(q, scale, axis_name), new_err[None]


def merge_topk(
    dists: np.ndarray, ids: np.ndarray, k: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Merge per-shard top-k results into a global top-k.

    ``dists``/``ids``: (n_shards, B, k_i) with -1 ids / +inf dists padding
    invalid slots (ids are already global).  Returns (B, k) sorted by
    ascending distance, -1/inf padded — the same contract as
    ``index.flat.l2_topk``.
    """
    d = np.concatenate(list(dists), axis=1).astype(np.float32)   # (B, sum k_i)
    i = np.concatenate(list(ids), axis=1)
    if d.shape[1] < k:                       # fewer candidates than k: pad
        b, pad = d.shape[0], k - d.shape[1]
        d = np.concatenate([d, np.full((b, pad), np.inf, np.float32)], axis=1)
        i = np.concatenate([i, np.full((b, pad), -1, i.dtype)], axis=1)
    d = np.where(i < 0, np.inf, d)
    # canonical (distance-bits, column) composite key, like IVFIndex.search:
    # squared-L2 distances are non-negative, so the f32 bit pattern sorts
    # like the float and equal distances break ties by column (= shard
    # order) — deterministic at the k boundary even on tie-heavy corpora,
    # while argpartition keeps the merge o(C log C) as n_shards*k grows
    key = (
        np.ascontiguousarray(d).view(np.int32).astype(np.int64) << 32
    ) | np.arange(d.shape[1], dtype=np.int64)[None, :]
    if d.shape[1] > k:
        part = np.argpartition(key, k - 1, axis=1)[:, :k]
        inner = np.argsort(np.take_along_axis(key, part, axis=1), axis=1)
        order = np.take_along_axis(part, inner, axis=1)
    else:
        order = np.argsort(key, axis=1)[:, :k]
    rows = np.arange(d.shape[0])[:, None]
    out_d, out_i = d[rows, order], i[rows, order]
    out_i = np.where(np.isinf(out_d), -1, out_i).astype(np.int32)
    return out_d, out_i


_PAD_ID = np.int64(np.iinfo(np.int32).max)   # sorts after every real id


def merge_topk_unique(
    dists: np.ndarray, ids: np.ndarray, k: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Merge candidate lists into a global top-k with de-duplication.

    Same (n_lists, B, k_i) -> (B, k) contract as :func:`merge_topk`, with two
    differences that make it the per-disjunct DNF merge:

    * the composite key is ``(distance-bits, global id)`` — equal distances
      break ties by the *lowest id*, matching ``jax.lax.top_k``'s
      lowest-index-first rule on a whole-corpus scan, so an exact-tier
      per-clause union reproduces the whole-predicate scan bit-for-bit;
    * an id appearing in several lists (a row matching two disjuncts) is
      kept exactly once, at its best (lowest-key) occurrence — approximate
      tiers may score the same id differently per clause.
    """
    d = np.concatenate(list(dists), axis=1).astype(np.float32)   # (B, sum k_i)
    i = np.asarray(np.concatenate(list(ids), axis=1))
    if d.shape[1] < k:
        b, pad = d.shape[0], k - d.shape[1]
        d = np.concatenate([d, np.full((b, pad), np.inf, np.float32)], axis=1)
        i = np.concatenate([i, np.full((b, pad), -1, i.dtype)], axis=1)
    d = np.where(i < 0, np.inf, d)
    iid = np.where(i < 0, _PAD_ID, i.astype(np.int64))
    key = (
        np.ascontiguousarray(d).view(np.int32).astype(np.int64) << 32
    ) | iid
    # de-dup: sort each row by (id, key), mark every non-first occurrence of
    # an id, and neutralise those slots before the top-k selection
    order = np.lexsort((key, iid))                       # (B, C) along axis -1
    rows = np.arange(d.shape[0])[:, None]
    s_iid = iid[rows, order]
    dup_sorted = np.zeros_like(s_iid, dtype=bool)
    dup_sorted[:, 1:] = (s_iid[:, 1:] == s_iid[:, :-1]) & (s_iid[:, 1:] != _PAD_ID)
    dup = np.zeros_like(dup_sorted)
    dup[rows, order] = dup_sorted
    d = np.where(dup, np.inf, d)
    i = np.where(dup, -1, i)
    key = np.where(dup, np.iinfo(np.int64).max, key)
    if d.shape[1] > k:
        part = np.argpartition(key, k - 1, axis=1)[:, :k]
        inner = np.argsort(np.take_along_axis(key, part, axis=1), axis=1)
        sel = np.take_along_axis(part, inner, axis=1)
    else:
        sel = np.argsort(key, axis=1)[:, :k]
    out_d, out_i = d[rows, sel], i[rows, sel]
    out_i = np.where(np.isinf(out_d), -1, out_i).astype(np.int32)
    return out_d, out_i
