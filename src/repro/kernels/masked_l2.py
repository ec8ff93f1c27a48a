"""Fused masked-L2-distance + streaming top-k Pallas kernel.

The hot loop of pre-filtering (the masked brute-force scan).  The naive
formulation materialises the (B, N) distance matrix in HBM — at B=256,
N=1M that is 1 TB of traffic.  This kernel never leaves VMEM: for each
query tile it streams corpus tiles HBM->VMEM, computes the distance block
on the MXU, folds the predicate mask in as +BIG, and maintains a running
top-k in VMEM scratch; only (B, KPAD) leaves the core.

Grid: (num_query_tiles, num_corpus_tiles) — corpus is the minor axis, so the
scratch accumulator persists across the corpus sweep of one query tile.

The merge keeps the k best so far in an unordered VMEM buffer and, per
corpus tile, inserts the tile's entries that beat a row's worst buffer entry,
one per row per round, for only as many rounds as some row still has such an
entry; the buffer is sorted once, when the sweep ends.  It is built from
``min``, ``max``, ``where`` and ``iota`` only: Mosaic (the TPU Pallas
compiler) has no lowering for ``lax.top_k`` or ``take_along_axis`` inside a
kernel.  The order is ``kernels.ref.masked_l2_topk_ref``'s: ascending
distance, ties broken by the lower corpus id.

Block shapes (TPU v5e): query tile (TQ, d), corpus tile (TN, d), mask tile
(1, TN) lane-dense, d padded to a multiple of 128 for MXU alignment; the
top-k buffer is KPAD lanes wide.  TQ=32 because serving groups are small
(query batches are padded only to a power of two, floor 8): a 32-row tile
wastes at most 24 rows at B=8, while B=64 pays two corpus sweeps.  The
kernel compiles for v5e at d <= 512 (``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["masked_l2_topk_kernel", "TQ", "TN", "KPAD"]

TQ = 32      # query tile (sublane-aligned)
TN = 512     # corpus tile
KPAD = 128   # top-k buffer width (lane-aligned)
BIG = 3.4e38  # python float: jnp constants would be captured consts in pallas
NO_ID = 2**31 - 1  # larger than any corpus id: neutral element of the id-min


def _worst(bd, bi, live):
    """Per row, the buffer's worst entry in (distance, id) order: its
    distance and lane.  Empty slots (BIG, -1) are the worst of all."""
    w = jnp.max(jnp.where(live, bd, -1.0), axis=1, keepdims=True)      # (TQ, 1)
    at_w = live & (bd == w)
    wid = jnp.max(jnp.where(at_w, bi, -2), axis=1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, bd.shape, 1)
    slot = jnp.min(jnp.where(at_w & (bi == wid), lane, KPAD), axis=1, keepdims=True)
    return w, slot


def _merge_tile(bd, bi, td, ti, k: int):
    """Fold a distance tile (td, ti) into the running top-k (bd, bi).

    The buffer holds the k best so far, unordered, in lanes [0, k).  Each
    round takes every row's best remaining tile entry (lowest id among equal
    distances) and, where it beats the row's worst buffer entry, puts it in
    that entry's slot.  Rounds run only while some row still has a tile entry
    below its worst — late in a corpus sweep most tiles need none.  A tile
    entry never displaces an equal buffer distance: buffer ids are lower
    (earlier tiles, or earlier rounds of this one), which is the reference's
    lowest-id tie-break.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, bd.shape, 1)
    live = lane < k

    def cond(carry):
        bd, bi, td = carry
        w, _ = _worst(bd, bi, live)
        return jnp.any(jnp.min(td, axis=1, keepdims=True) < w)

    def body(carry):
        bd, bi, td = carry
        m = jnp.min(td, axis=1, keepdims=True)                           # (TQ, 1)
        sel = jnp.min(jnp.where(td == m, ti, NO_ID), axis=1, keepdims=True)
        w, slot = _worst(bd, bi, live)
        put = (m < w) & (lane == slot)
        bd = jnp.where(put, m, bd)
        bi = jnp.where(put, sel, bi)
        td = jnp.where((m < w) & (ti == sel), BIG, td)
        return bd, bi, td

    bd, bi, _ = jax.lax.while_loop(cond, body, (bd, bi, td))
    return bd, bi


def _sorted_k(bd, bi, k: int):
    """The buffer's k entries in ascending (distance, id) order in lanes
    [0, k); BIG / -1 elsewhere."""
    lane = jax.lax.broadcasted_iota(jnp.int32, bd.shape, 1)
    bd = jnp.where(lane < k, bd, BIG)

    def body(r, carry):
        bd, nd, ni = carry
        m = jnp.min(bd, axis=1, keepdims=True)
        sel = jnp.min(jnp.where(bd == m, bi, NO_ID), axis=1, keepdims=True)
        nd = jnp.where(lane == r, m, nd)
        ni = jnp.where(lane == r, sel, ni)
        # retire the taken entry (ids are unique among finite distances)
        bd = jnp.where((bd == m) & (bi == sel), BIG, bd)
        return bd, nd, ni

    nd = jnp.full(bd.shape, BIG, jnp.float32)
    ni = jnp.full(bi.shape, -1, jnp.int32)
    _, nd, ni = jax.lax.fori_loop(0, k, body, (bd, nd, ni))
    return nd, ni


def _kernel(q_ref, x_ref, m_ref, od_ref, oi_ref, bd_ref, bi_ref, *,
            n_tiles: int, k: int):
    """q_ref: (TQ, d) — x_ref: (TN, d) — m_ref: (1, TN) mask as f32 {0,1}
    od/oi: (TQ, KPAD) outputs — bd/bi: (TQ, KPAD) VMEM scratch."""
    j = pl.program_id(1)

    # reset the running top-k at the start of each corpus sweep
    @pl.when(j == 0)
    def _init():
        bd_ref[...] = jnp.full((TQ, KPAD), BIG, jnp.float32)
        bi_ref[...] = jnp.full((TQ, KPAD), -1, jnp.int32)

    q = q_ref[...]
    x = x_ref[...]

    # squared L2 via the MXU: ||q||^2 + ||x||^2 - 2 q.x
    q2 = jnp.sum(q * q, axis=1, keepdims=True)                    # (TQ, 1)
    ones = jnp.ones((1, x.shape[1]), jnp.float32)
    x2 = jax.lax.dot_general(                                     # (1, TN)
        ones, x * x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    qx = jax.lax.dot_general(                                     # (TQ, TN)
        q, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    d2 = jnp.maximum(q2 + x2 - 2.0 * qx, 0.0)
    d2 = jnp.where(m_ref[...] > 0, d2, BIG)                       # fold predicate in

    ids = j * TN + jax.lax.broadcasted_iota(jnp.int32, (TQ, TN), 1)
    bd_ref[...], bi_ref[...] = _merge_tile(bd_ref[...], bi_ref[...], d2, ids, k)

    # flush on the last corpus tile
    @pl.when(j == n_tiles - 1)
    def _flush():
        d, i = _sorted_k(bd_ref[...], bi_ref[...], k)
        od_ref[...] = d
        oi_ref[...] = jnp.where(d >= BIG, -1, i)


def masked_l2_topk_kernel(
    queries: jax.Array,  # (B, d) f32, B % TQ == 0
    corpus: jax.Array,   # (N, d) f32, N % TN == 0, d % 128 == 0
    mask: jax.Array,     # (1, N) f32 {0,1}
    k: int,
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Raw pallas_call; use :mod:`repro.kernels.ops` for the padded wrapper.
    Returns (B, KPAD) buffers whose first ``k`` columns are the answer."""
    b, d = queries.shape
    n = corpus.shape[0]
    assert b % TQ == 0 and n % TN == 0, (b, n)
    assert 1 <= k <= KPAD, k
    grid = (b // TQ, n // TN)
    kernel = functools.partial(_kernel, n_tiles=grid[1], k=k)
    out_d, out_i = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TQ, d), lambda i, j: (i, 0)),      # query tile (stays)
            pl.BlockSpec((TN, d), lambda i, j: (j, 0)),      # corpus tile (streams)
            pl.BlockSpec((1, TN), lambda i, j: (0, j)),      # mask tile
        ],
        out_specs=[
            pl.BlockSpec((TQ, KPAD), lambda i, j: (i, 0)),
            pl.BlockSpec((TQ, KPAD), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, KPAD), jnp.float32),
            jax.ShapeDtypeStruct((b, KPAD), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((TQ, KPAD), jnp.float32),
            pltpu.VMEM((TQ, KPAD), jnp.int32),
        ],
        interpret=interpret,
    )(queries, corpus, mask)
    return out_d, out_i
